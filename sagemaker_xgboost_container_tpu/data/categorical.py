"""The host half of categorical training: where a categorical column's codes
sit in the bin matrix.

A column given as categories (``DataMatrix(feature_types=[..., "c", ...])``,
xgboost's spelling) is not sketched and has no cuts: **its bin is its code**.
The level histogram kernel reads bin columns of ``max_bin`` data positions and
one missing slot, and a column may hold thousands of categories, so a
categorical column of ``C`` categories takes ``ceil(C / (max_bin - 1))`` bin
columns, its *chunks*: category ``c`` sits at position ``c % (max_bin - 1)``
of chunk ``c // (max_bin - 1)`` and in the missing slot of the column's other
chunks. A row whose value is NaN sits in the missing slot of every chunk; a
row whose value is a number but no category of the column (negative, or at or
above ``C``: an evaluation row's unseen category) sits at position
``max_bin - 1`` of the first chunk, which holds no category: such a row is
*present and in no set*, as ``ops/predict.py`` serves it (xgboost's
``common::Decision``: an invalid category goes left, NaN follows
``default_left``). A numeric column keeps its one bin column and its cuts.

So the histogram of a level gives, per node, every category's own gradient
sums at a static place, and the layout (``CatLayout``) **follows the
cardinalities alone**: which rows hold which category never moves a table, so
the round program of one table shape is one program on every seed.

The floats go through the session's own sketch and bin-apply unchanged:
``expand`` writes the matrix as ``[rows, bin columns]`` floats (a chunk's
column holds the position, NaN elsewhere) and ``cuts`` gives every chunk the
half-integer cuts under which ``bin == position``; only the numeric columns
are sketched. ``ops/categorical.py`` holds the device half: the partition
scan over the level histogram and the set test in row routing.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..toolkit import exceptions as exc
from .matrix import CATEGORICAL

ROW_BLOCK = 1 << 16
THREADS = 8
# a float32 holds every integer below it exactly: xgboost's own bound on a code
MAX_CODE = 1 << 24


def cardinalities(features, feature_types):
    """Categories of each column of the training rows (0 for a numeric one):
    one more than its largest code, as xgboost counts them. A code that is
    negative, no whole number or too large for a float32 to hold exactly is
    the user's error: a training row's category cannot be invalid."""
    counts = [0] * len(feature_types)
    for f, kind in enumerate(feature_types):
        if kind != CATEGORICAL:
            continue
        column = features[:, f]
        held = column[~np.isnan(column)]
        if held.size == 0:
            continue
        low, high = float(held.min()), float(held.max())
        if low < 0 or high >= MAX_CODE or np.any(held != np.floor(held)):
            raise exc.UserError(
                "Column {} is given as categories (feature_types 'c') but holds a value "
                "that is no category code: codes are whole numbers from 0 to {} "
                "(found values from {} to {}).".format(f, MAX_CODE - 1, low, high)
            )
        counts[f] = int(high) + 1
    return counts


class CatLayout:
    """Which bin columns hold which original column, from the columns' types
    and cardinalities alone. Per bin column (numpy, static): ``col_feature``
    (the original column), ``col_first`` (the code at position 0; 0 for a
    numeric column), ``col_count`` (positions that hold a value: a chunk's
    categories, ``max_bin`` for a numeric column) and ``col_is_cat``."""

    def __init__(self, feature_types, counts, max_bin):
        self.feature_types = list(feature_types)
        self.cardinalities = [int(c) for c in counts]
        self.max_bin = int(max_bin)
        self.positions = self.max_bin - 1  # categories a chunk holds
        feature, first, count, is_cat = [], [], [], []
        self.first_column = []  # original column -> its first bin column
        for f, kind in enumerate(self.feature_types):
            self.first_column.append(len(feature))
            if kind != CATEGORICAL:
                feature.append(f), first.append(0), count.append(self.max_bin)
                is_cat.append(False)
                continue
            # (a column of no category at all keeps one empty chunk)
            for lo in range(0, max(self.cardinalities[f], 1), self.positions):
                feature.append(f), first.append(lo), is_cat.append(True)
                count.append(min(self.positions, self.cardinalities[f] - lo))
        self.col_feature = np.asarray(feature, np.int32)
        self.col_first = np.asarray(first, np.int32)
        self.col_count = np.asarray(count, np.int32)
        self.col_is_cat = np.asarray(is_cat, bool)

    @classmethod
    def of(cls, dmatrix, max_bin):
        return cls(
            dmatrix.feature_types, cardinalities(dmatrix.features, dmatrix.feature_types), max_bin
        )

    @property
    def num_bin_columns(self):
        return len(self.col_feature)

    @property
    def num_col(self):
        return len(self.feature_types)

    @property
    def numeric_columns(self):
        """Bin columns that hold a numeric column: what the sketch reads."""
        return np.flatnonzero(~self.col_is_cat)

    @property
    def max_cardinality(self):
        return max(self.cardinalities, default=0)

    @property
    def set_words(self):
        """32-bit words of a node's category set: the widest column's."""
        return max(1, -(-self.max_cardinality // 32))

    def chunks(self, f):
        """The bin columns of original column ``f``."""
        return np.flatnonzero(self.col_feature == f)

    def expand(self, features):
        """``features`` ``[rows, columns]`` as float32 ``[rows, bin columns]``:
        a numeric column as it is, a chunk's column the position of the row's
        category where the chunk holds it, the first chunk ``max_bin - 1``
        where the value is no category of the column, NaN elsewhere."""
        features = np.asarray(features, np.float32)
        n = features.shape[0]
        out = np.empty((n, self.num_bin_columns), np.float32)
        invalid_at = np.float32(self.positions)

        def block(lo):
            src, dst = features[lo:lo + ROW_BLOCK], out[lo:lo + ROW_BLOCK]
            for c in range(self.num_bin_columns):
                v = src[:, self.col_feature[c]]
                if not self.col_is_cat[c]:
                    dst[:, c] = v
                    continue
                # (a NaN compares false everywhere and stays NaN)
                first, count = self.col_first[c], self.col_count[c]
                at = np.floor(v) - np.float32(first)
                held = (at >= 0) & (at < count)
                if first == 0:
                    no_category = (v < 0) | (v >= self.cardinalities[self.col_feature[c]])
                    at = np.where(no_category, invalid_at, at)
                    held |= no_category
                dst[:, c] = np.where(held, at, np.float32(np.nan))

        with ThreadPoolExecutor(max_workers=THREADS) as pool:
            list(pool.map(block, range(0, n, ROW_BLOCK)))
        return out

    def cuts(self, numeric_cuts):
        """Cuts of every bin column: ``numeric_cuts`` (a list, one entry per
        numeric column in bin-column order) where they belong, and for a chunk
        the half-integers under which a position bins to itself (the first
        chunk's reach ``max_bin - 1``, where a value that is no category sits)."""
        numeric_cuts = iter(numeric_cuts)
        out = []
        for c in range(self.num_bin_columns):
            if not self.col_is_cat[c]:
                out.append(np.asarray(next(numeric_cuts), np.float32))
                continue
            top = self.positions if self.col_first[c] == 0 else self.col_count[c] - 1
            out.append(np.arange(max(top, 0), dtype=np.float32) + np.float32(0.5))
        return out

    def numeric_cut_counts(self, cut_counts):
        """``cut_counts`` (cuts a bin column) with a chunk's set to 0: no
        threshold split is legal on a categorical column."""
        return np.where(self.col_is_cat, 0, cut_counts).astype(np.int32)

    def reach(self, cut_counts):
        """The highest bin a training row of each bin column can sit in: a
        numeric column's count of cuts, a chunk's last category."""
        return np.where(
            self.col_is_cat, np.maximum(self.col_count - 1, 0), cut_counts
        ).astype(np.int32)

    def feature_cuts(self, cuts):
        """Cuts by *original* column, for the trees' thresholds: a numeric
        column's own, None for a categorical one."""
        return [
            None if kind == CATEGORICAL else cuts[self.first_column[f]]
            for f, kind in enumerate(self.feature_types)
        ]

    def same_types(self, dmatrix):
        return dmatrix.feature_types == self.feature_types


def words_to_categories(words):
    """The codes whose bits a node's set words (int32 ``[words]``) hold."""
    bits = np.unpackbits(np.ascontiguousarray(words, "<i4").view(np.uint8), bitorder="little")
    return np.flatnonzero(bits).astype(np.int64)
