"""Exclusive feature bundling: a wide sparse matrix as a narrow dense bin
matrix, exactly.

A one-hot encoded table holds thousands of columns of which a row fills a
few dozen. Columns that no row fills together (the values of one categorical,
and whatever else happens to exclude each other) can share ONE dense bin
column, a *bundle*: each member keeps its own range of the bundle's bin
positions, a row sits at the position of the member it holds (offset + the
member's own bin of its value) and in the missing slot where it holds none.
The bundled matrix is what every other layer of the trainer already takes: a
dense ``[rows, bundles]`` matrix of bin indices with ``max_bin`` as the
missing slot. ``ops/bundle.py`` is the device half: the split scan that
judges original columns over positions and the range test that routes rows.

**Conflict-free only.** Two columns share a bundle only if no row of any
matrix of the session (training and evaluation sets) fills both, so the
bundled matrix holds every present cell and the trees are the densified
path's: an absent cell is missing, follows the split's ``default_left`` and
enters no sketch. (LightGBM's bundling tolerates a share of conflicts and is
approximate; this one is not.) ``BundledBins.conflict_rows`` counts, from the
finished matrices, the rows that lost a cell: 0.

**Cuts** come from a column's *present* training values alone, as the
densified path's do (NaN never enters a sketch). Columns present in at least
``DENSE_COLUMN_MIN_FILL`` of the training rows that hold more than one value
(the numeric ones) are bundles of one: their values go as one dense float
block through the same sketch and bin-apply every dense matrix takes (on the
chip where the session's sketch runs there). The rest are cut on the host, by
``binning._select_cuts``.

**Positions.** A member takes one position for each of its bins that any row
holds, up to the highest one: a one-hot column (one value, one cut above it)
takes one. A split may sit at a member's position ``b`` where the column has
a cut ``b``; positions above a member's highest filled bin would give the
same sums as that bin's and are left out, which loses no split.

Nothing here makes a dense float matrix of the input: the work runs over the
CSC form, a column at a time.
"""

import numpy as np

from ..ops.bundle import BundleTables
from ..telemetry.spans import span
from . import binning

# A column of several values filled in at least this share of the training
# rows conflicts with nearly everything: it is a bundle of one, sketched and
# binned as a dense column (NaN where absent).
DENSE_COLUMN_MIN_FILL = 0.5

# The input's shape rule (``takes_bundled_layout``): a sparse training matrix
# at most this full is bundled. At a quarter full a row's present cells alone
# need a quarter of the width; above it bundling has little left to share.
BUNDLE_MAX_FILL = 0.25

# Rows of a column tried against a bundle before the whole column is: two
# popular columns that conflict do so within their first rows, so a failed
# try costs this many reads and only a likely fit reads the column whole.
CONFLICT_PROBE_ROWS = 64


def takes_bundled_layout(matrices):
    """The rule of the input's shape: every matrix of the session is a sparse
    ``DataMatrix`` of one width and the training matrix (the first) is at most
    ``BUNDLE_MAX_FILL`` full."""
    from .matrix import DataMatrix

    if not all(isinstance(m, DataMatrix) and m.is_sparse for m in matrices):
        return False
    train = matrices[0]
    cells = train.num_row * train.num_col
    if not cells or any(m.num_col != train.num_col for m in matrices):
        return False
    return train.csr.nnz <= BUNDLE_MAX_FILL * cells


class BundlePlan:
    """Which original columns share which bin column, and each member's range.

    ``bundle_of`` / ``offset_of`` / ``bins_of``: int32 ``[columns]``, a
    column's bundle (-1: no training row fills it, so it has no cut and is in
    no bundle), first position and number of positions. ``members``: a list a
    bundle of its columns in position order. ``cut_points``: the original
    columns' cuts, ``max_bin`` the missing slot."""

    def __init__(self, num_col, max_bin, cut_points, members, bins_of, dense_columns):
        self.num_col = int(num_col)
        self.max_bin = int(max_bin)
        self.cut_points = cut_points
        self.members = [list(m) for m in members]
        self.dense_columns = list(dense_columns)
        self.bundle_of = np.full(num_col, -1, np.int32)
        self.offset_of = np.zeros(num_col, np.int32)
        self.bins_of = np.asarray(bins_of, np.int32)
        shape = (len(self.members), self.max_bin)
        lo = np.zeros(shape, np.int32)
        hi = np.zeros(shape, np.int32)
        legal = np.zeros(shape, bool)
        column = np.full(shape, -1, np.int32)
        for b, cols in enumerate(self.members):
            at = 0
            for f in cols:
                width = int(self.bins_of[f])
                self.bundle_of[f], self.offset_of[f] = b, at
                lo[b, at : at + width] = at
                hi[b, at : at + width] = at + width
                column[b, at : at + width] = f
                legal[b, at : at + min(width, len(cut_points[f]))] = True
                at += width
            if at > self.max_bin:
                raise ValueError("bundle {} holds {} positions".format(b, at))
        self.tables = BundleTables(lo, hi, legal, column)

    @property
    def num_bundles(self):
        return len(self.members)

    @property
    def bins_used(self):
        return int(self.bins_of[self.bundle_of >= 0].sum())

    def position_of(self, column, local_bin):
        """(bundle, position) of ``local_bin`` of original ``column``."""
        return int(self.bundle_of[column]), int(self.offset_of[column]) + int(local_bin)

    def original_splits(self, padded):
        """A bundled build's padded tree arrays with ``feature`` and ``bin``
        as the original column and that column's own bin (the index of its
        cut), which is what ``compact_padded_tree`` turns into thresholds."""
        bundle = np.asarray(padded["feature"])
        position = np.asarray(padded["bin"])
        column = self.tables.column[bundle, position]
        out = dict(padded)
        out["feature"] = np.maximum(column, 0).astype(np.int32)
        out["bin"] = (position - self.tables.lo[bundle, position]).astype(np.int32)
        return out


class BundledBins:
    """What ``bundle_matrices`` hands the session: the plan, the bundled bin
    matrix of every input matrix (``[rows, bundles]``, a device array where
    the dense columns were binned on a device), the training matrix's present
    cells and the rows of all matrices that lost a cell to a conflict."""

    def __init__(self, plan, bins, cells_present, conflict_rows):
        self.plan = plan
        self.bins = bins
        self.cells_present = int(cells_present)
        self.conflict_rows = int(conflict_rows)


def _present(csr):
    """``csr`` without stored NaN: a stored NaN is a missing cell."""
    import scipy.sparse as sp

    keep = ~np.isnan(csr.data)
    if keep.all():
        return csr
    rows = np.repeat(np.arange(csr.shape[0]), np.diff(csr.indptr))[keep]
    return sp.csr_matrix((csr.data[keep], (rows, csr.indices[keep])), shape=csr.shape)


def _column(csc, f):
    lo, hi = csc.indptr[f], csc.indptr[f + 1]
    return csc.indices[lo:hi], csc.data[lo:hi]


def _dense_block(csc, columns):
    """The ``columns`` of ``csc`` as a float32 ``[rows, len(columns)]`` view
    (NaN where absent), stored a column at a time."""
    block = np.full((len(columns), csc.shape[0]), np.nan, np.float32)
    for j, f in enumerate(columns):
        rows, values = _column(csc, f)
        block[j, rows] = values
    return block.T


def _sparse_cuts(csc, columns, weights, max_cuts):
    """Cuts of sparse ``columns`` from their present training values, by the
    host sketch's own selection (``binning._select_cuts``). A column of one
    value, every one-hot column, gets its one cut without a sort."""
    cuts = {}
    for f in columns:
        rows, values = _column(csc, f)
        low = values.min()
        if low == values.max():
            cuts[f] = binning._select_cuts(values[:1], None, max_cuts)
            continue
        order = np.argsort(values, kind="stable")
        w = np.ones(len(rows), np.float32) if weights is None else weights[rows[order]]
        cuts[f] = binning._select_cuts(values[order], w, max_cuts)
    return cuts


class _OpenBundle:
    """A bundle while the plan is made: its members, the positions taken and,
    a matrix each, the position every row holds (``missing`` where none)."""

    def __init__(self, row_counts, missing, dtype):
        self.members = []
        self.used = 0
        self.held = [np.full(n, missing, dtype) for n in row_counts]

    def fits(self, entries, missing, probe):
        """No row of any matrix that holds this column holds a member."""
        for held, (rows, _bins) in zip(self.held, entries):
            if len(rows) and (held[rows[:probe]] != missing).any():
                return False
        return not probe or self.fits(entries, missing, None)

    def add(self, column, entries, width):
        for held, (rows, bins) in zip(self.held, entries):
            held[rows] = (self.used + bins).astype(held.dtype)
        self.members.append(column)
        self.used += width


def bundle_matrices(csrs, weights, max_bin, sketch_dense, apply_dense, names):
    """Plan the bundles of a session's matrices and build their bundled bins.

    csrs: scipy CSR matrices of one width, the training matrix first (cuts
    come from it alone; the plan excludes over all of them).
    weights: the training rows' sketch weights, or None.
    sketch_dense(block) -> cuts: the session's sketch of a dense float block
    (``[rows, dense columns]``, NaN = absent) of the training matrix.
    apply_dense(block, cuts, name) -> bins ``[rows, dense columns]`` (numpy or
    a device array): the session's bin-apply.
    names: a name a matrix, for the ``setup.bin_apply`` spans.
    Spans: ``setup.bundle_plan`` (the CSC forms, the dense columns' float
    block, the conflict search and the plan), ``setup.sketch`` (cuts),
    ``setup.bin_apply`` (the bundled bins).
    """
    if max_bin is None:
        raise ValueError("the bundled layout needs a bin budget (tree_method=hist)")
    max_cuts = max_bin - 1
    dtype = binning.bin_dtype(max_bin)
    d = csrs[0].shape[1]
    with span("setup.bundle_plan", attributes={"part": "csc", "columns": d}):
        csrs = [_present(m) for m in csrs]
        cscs = [m.tocsc() for m in csrs]
        train = cscs[0]
        filled = np.diff(train.indptr)
        is_dense = filled >= max(DENSE_COLUMN_MIN_FILL * train.shape[0], 1)
        for f in np.flatnonzero(is_dense):
            # one value in most rows is a popular one-hot column: it needs no
            # sketch and its group's other values can share its bundle
            values = _column(train, f)[1]
            is_dense[f] = values.min() != values.max()
        dense_columns = [int(f) for f in np.flatnonzero(is_dense)]
        sparse_columns = np.flatnonzero((filled > 0) & ~is_dense)
        # popular columns first (LightGBM's order): they open the bundles
        sparse_columns = sparse_columns[np.argsort(-filled[sparse_columns], kind="stable")]

    cut_points = [np.empty(0, np.float32)] * d
    with span(
        "setup.bundle_plan", attributes={"part": "dense_block", "columns": len(dense_columns)}
    ):
        dense_blocks = [_dense_block(csc, dense_columns) for csc in cscs]
    if dense_columns:
        for f, cuts in zip(dense_columns, sketch_dense(dense_blocks[0])):
            cut_points[f] = cuts
    with span(
        "setup.sketch",
        attributes={"rows": train.shape[0], "columns": len(sparse_columns), "impl": "host_sparse"},
    ):
        for f, cuts in _sparse_cuts(train, sparse_columns, weights, max_cuts).items():
            cut_points[f] = cuts

    bins_of = np.zeros(d, np.int32)
    for f in dense_columns:
        bins_of[f] = len(cut_points[f]) + 1
    row_counts = [csc.shape[0] for csc in cscs]
    with span(
        "setup.bundle_plan",
        attributes={"part": "conflict_search", "columns": len(sparse_columns)},
    ):
        bundles = []
        for f in sparse_columns:
            entries = []
            for csc in cscs:
                rows, values = _column(csc, f)
                entries.append((rows, np.searchsorted(cut_points[f], values, side="right")))
            width = 1 + max(int(bins.max(initial=0)) for _rows, bins in entries)
            bins_of[f] = width
            home = next(
                (
                    b for b in bundles
                    if b.used + width <= max_bin and b.fits(entries, max_bin, CONFLICT_PROBE_ROWS)
                ),
                None,
            )
            if home is None:
                home = _OpenBundle(row_counts, max_bin, dtype)
                bundles.append(home)
            home.add(int(f), entries, width)
        plan = BundlePlan(
            d, max_bin, cut_points,
            [[f] for f in dense_columns] + [b.members for b in bundles],
            bins_of, dense_columns,
        )

    out = []
    conflict_rows = 0
    planned = plan.bundle_of >= 0
    for i, (csr, name) in enumerate(zip(csrs, names)):
        parts = []
        if dense_columns:
            parts.append(
                apply_dense(dense_blocks[i], [cut_points[f] for f in dense_columns], name)
            )
        attributes = {
            "rows": row_counts[i], "columns": len(bundles), "set": name or "",
            "impl": "bundle_scatter",
        }
        with span("setup.bin_apply", attributes=attributes):
            if bundles:
                parts.append(_bundle_columns([b.held[i] for b in bundles], parts))
            bins = parts[0] if len(parts) == 1 else _concat_columns(parts)
            # every present cell of a planned column must be a filled cell
            cells = int(np.diff(cscs[i].indptr)[planned].sum())
            conflict_rows += _lost_rows(bins, max_bin, cells, csr, planned)
        if i == 0:
            cells_present = cells
        out.append(bins)
    return BundledBins(plan, out, cells_present, conflict_rows)


def _bundle_columns(held, parts):
    """The bundles' row vectors as ``[rows, bundles]``, beside ``parts`` (on
    the device where they are: the transpose runs there)."""
    stacked = np.stack(held)  # [bundles, rows]: each row vector whole
    if parts and not isinstance(parts[0], np.ndarray):
        import jax.numpy as jnp

        return jnp.asarray(stacked).T
    return np.ascontiguousarray(stacked.T)


def _concat_columns(parts):
    if isinstance(parts[0], np.ndarray):
        return np.concatenate(parts, axis=1)
    import jax.numpy as jnp

    return jnp.concatenate(parts, axis=1)


def _lost_rows(bins, missing, cells, csr, planned):
    """Rows of a bundled matrix that hold fewer cells than their input row
    holds of planned columns: 0 where the filled cells are all ``cells``."""
    filled = (bins != missing).sum(axis=1)
    if int(filled.sum()) == cells:
        return 0
    rows = np.repeat(np.arange(csr.shape[0]), np.diff(csr.indptr))[planned[csr.indices]]
    return int((np.bincount(rows, minlength=csr.shape[0]) != np.asarray(filled)).sum())
