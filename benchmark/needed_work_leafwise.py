"""Needed work of a loss-guided round's histograms, beside ``needed_work.py``.

A depth-wise round reads every row once a level, ``max_depth`` levels, so its
reader multiplies one level's work by the depth. A loss-guided tree has no
levels: what its histograms *need* is one read of a row, as
``needed_work.level_histogram`` counts a row, at every depth at which the
row's node splits. Summed over the rows that is the sum over the tree's
internal nodes of the rows that reach them, and for a complete tree of depth
d it is d times the rows, the depth-wise count: the two rooflines read the
same work whatever implements it. The program reads every row at every one
of its ``max_leaves - 1`` steps, which is what the share is there to show.

The rows are counted by the benchmark's own routing of the judged trees over
the raw floats (``gbt_reference.route``); nothing is taken from the program.
"""

from benchmark import needed_work
from benchmark.reference import gbt_reference


def histogram_rows(tree, x):
    """Row reads the histograms of ``tree`` need over the rows ``x``: the sum
    over the rows of the depth of their leaf."""
    depth = gbt_reference.node_depths(tree)
    parts = gbt_reference._over_row_blocks(
        lambda lo, hi: int(depth.take(gbt_reference.route(tree, x[lo:hi])[-1]).sum()), len(x)
    )
    return int(sum(parts))


def tree_histograms(trees, x, features, num_bins):
    """{"bytes", "ops"} the histograms of ``trees`` need, each tree counted
    by ``histogram_rows`` and a row read as one level reads it."""
    rows = sum(histogram_rows(tree, x) for tree in trees)
    return needed_work.level_histogram(rows, features, num_bins)
