"""DataMatrix: the framework's in-memory dataset abstraction.

Replaces the reference's ``xgb.DMatrix`` (a handle into libxgboost's C++
memory). Here the dataset is plain numpy on the host — dense float32 with NaN
as the missing marker — and moves to TPU HBM only after binning (see
``binning.py``), as a compact uint8/uint16 bin-index matrix sharded over the
mesh. Sparse inputs (libsvm/recordio CSR) stay CSR: an absent entry is
*missing* (XGBoost's default split direction), never 0. A training session
that takes the bundled layout (``data/bundling.py``) reads the CSR itself;
whoever needs floats reads ``float_block`` a block of rows at a time, and
``features`` densifies the whole matrix (NaN fill) only when asked for.
"""

import numpy as np
import scipy.sparse as sp

from ..toolkit import exceptions as exc


QUANTITATIVE, CATEGORICAL = "q", "c"
# xgboost's spellings of a column's type: ``c`` a category's code, the rest a number
_NUMERIC_TYPES = (QUANTITATIVE, "float", "int", "i")


def normalize_feature_types(feature_types, num_col):
    """``feature_types`` as a list of ``q`` / ``c`` a column (xgboost's
    spelling; ``float``, ``int`` and ``i`` read as ``q``), None where none
    was given."""
    if feature_types is None:
        return None
    types = [str(t) for t in feature_types]
    if len(types) != num_col:
        raise exc.UserError(
            "feature_types names {} columns but the data has {}".format(len(types), num_col)
        )
    unknown = sorted({t for t in types if t != CATEGORICAL and t not in _NUMERIC_TYPES})
    if unknown:
        raise exc.UserError(
            "feature_types takes 'q' (a number) or 'c' (a category's code), got {}".format(
                unknown
            )
        )
    return [CATEGORICAL if t == CATEGORICAL else QUANTITATIVE for t in types]


class DataMatrix:
    """Features + labels + optional per-row weights and ranking groups."""

    def __init__(self, features, labels=None, weights=None, groups=None, feature_names=None,
                 feature_types=None):
        if sp.issparse(features):
            self.csr = features.tocsr().astype(np.float32, copy=False)
            self._dense = None
        else:
            self.csr = None
            self._dense = np.asarray(features, dtype=np.float32)
        if len(self.shape) != 2:
            raise exc.AlgorithmError(
                "DataMatrix features must be 2-D, got shape {}".format(self.shape)
            )
        self.labels = None if labels is None else np.asarray(labels, dtype=np.float32).reshape(-1)
        self.weights = None if weights is None else np.asarray(weights, dtype=np.float32).reshape(-1)
        self.groups = None if groups is None else np.asarray(groups, dtype=np.int32).reshape(-1)
        self.feature_names = list(feature_names) if feature_names is not None else None
        self.feature_types = normalize_feature_types(feature_types, self.num_col)

        if self.labels is not None and len(self.labels) != self.num_row:
            raise exc.UserError(
                "Label count {} does not match row count {}".format(len(self.labels), self.num_row)
            )
        if self.weights is not None and len(self.weights) != self.num_row:
            raise exc.UserError(
                "Weight count {} does not match row count {}".format(
                    len(self.weights), self.num_row
                )
            )
        if self.groups is not None and int(self.groups.sum()) != self.num_row:
            raise exc.UserError(
                "Group sizes sum to {} but the data has {} rows".format(
                    int(self.groups.sum()), self.num_row
                )
            )

    @property
    def is_sparse(self):
        return self.csr is not None

    @property
    def has_categorical(self):
        """Whether a column is given as categories (``feature_types`` ``c``)."""
        return self.feature_types is not None and CATEGORICAL in self.feature_types

    @property
    def shape(self):
        return self.csr.shape if self.csr is not None else self._dense.shape

    @property
    def features(self):
        """Dense float32 [n, d], NaN = missing. A sparse matrix is densified
        whole on first use and kept: for the paths that read every float."""
        if self._dense is None:
            self._dense = _densify_with_nan(self.csr)
        return self._dense

    def float_block(self, start, end):
        """Rows ``[start, end)`` as dense float32, NaN = missing; of a sparse
        matrix only that block is ever dense."""
        if self._dense is not None:
            return self._dense[start:end]
        return _densify_with_nan(self.csr[start:end])

    @property
    def num_row(self):
        return self.shape[0]

    @property
    def num_col(self):
        return self.shape[1]

    def get_label(self):
        return self.labels if self.labels is not None else np.empty(0, dtype=np.float32)

    def get_weight(self):
        if self.weights is None:
            return np.ones(self.num_row, dtype=np.float32)
        return self.weights

    def slice(self, row_indices):
        """Row subset (used by k-fold CV), preserving labels/weights."""
        row_indices = np.asarray(row_indices)
        return DataMatrix(
            self.csr[row_indices] if self.is_sparse else self._dense[row_indices],
            labels=None if self.labels is None else self.labels[row_indices],
            weights=None if self.weights is None else self.weights[row_indices],
            feature_names=self.feature_names,
            feature_types=self.feature_types,
        )

    def pad_features(self, num_col):
        """Widen with all-missing columns (serving: model trained on more cols)."""
        if num_col <= self.num_col:
            return self
        if self.is_sparse:  # the new columns hold no entry: all missing
            wide = sp.csr_matrix(
                (self.csr.data, self.csr.indices, self.csr.indptr),
                shape=(self.num_row, num_col),
            )
        else:
            pad = np.full((self.num_row, num_col - self.num_col), np.nan, dtype=np.float32)
            wide = np.concatenate([self._dense, pad], axis=1)
        return DataMatrix(
            wide,
            labels=self.labels,
            weights=self.weights,
            groups=self.groups,
            feature_names=self.feature_names,
            feature_types=(
                None if self.feature_types is None
                else self.feature_types + [QUANTITATIVE] * (num_col - self.num_col)
            ),
        )

    def concat(self, other):
        """Row-wise concatenation (CV train+validation merge)."""
        d = max(self.num_col, other.num_col)
        a, b = self.pad_features(d), other.pad_features(d)

        def _cat(x, y):
            if x is None and y is None:
                return None
            if x is None:
                x = np.zeros(a.num_row, dtype=y.dtype)
            if y is None:
                y = np.zeros(b.num_row, dtype=x.dtype)
            return np.concatenate([x, y])

        if a.is_sparse and b.is_sparse:
            rows = sp.vstack([a.csr, b.csr], format="csr")
        else:
            rows = np.concatenate([a.features, b.features], axis=0)
        return DataMatrix(
            rows,
            labels=_cat(a.labels, b.labels),
            weights=_cat(a.weights, b.weights),
            feature_names=self.feature_names,
            feature_types=a.feature_types,
        )


def _densify_with_nan(csr):
    """CSR -> dense float32 where absent entries become NaN (missing)."""
    out = np.full(csr.shape, np.nan, dtype=np.float32)
    rows = np.repeat(np.arange(csr.shape[0]), np.diff(csr.indptr))
    out[rows, csr.indices] = csr.data
    return out
