"""Needed work of a level histogram over a *sparse* matrix, beside
``needed_work.py``.

``needed_work.level_histogram`` counts rows x columns bin reads a level: for
a matrix 0.74 % full that is 135 times what any layout has to read, and a
roofline share over it reads above 100 % (a fault in the count, not a fast
kernel). What a level of the gradient histogram *needs* of a sparse matrix is
one read of every **present** cell's bin at its stored width, and of each
row's f32 gradient and hessian and i32 node id for each tree of the round; one
add per present cell, tree and statistic. An absent cell needs no read: its
column's missing sums are the node's totals less the present ones. Whatever
layout the program chose (bundled columns, rows of column and bin pairs),
this is what the level needs; the present cells are counted by the benchmark
from the generated CSR, never taken from the program.
"""


def level_histogram(rows, present_cells, num_bins, trees=1):
    """{"bytes", "ops"} of one level over ``rows`` rows holding
    ``present_cells`` values in all, for the ``trees`` one round grows side
    by side."""
    bin_bytes = 1 if num_bins <= 256 else 2
    return {
        "bytes": present_cells * bin_bytes + trees * rows * (4 + 4 + 4),
        "ops": present_cells * 2 * trees,
    }
