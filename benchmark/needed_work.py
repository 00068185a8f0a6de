"""Bytes and operations the *algorithm* needs for a kernel call, from shapes.

Needed work, not issued work: the one-hot-matmul histogram issues about
32,768 times the arithmetic counted here, which is exactly what its
roofline share is there to show. A share over 100 % would mean this file
counts too much.
"""


def level_histogram(rows, features, num_bins, trees=1):
    """One level of the gradient histogram over ``rows`` x ``features``, for
    the ``trees`` that one round grows side by side (one a class).

    Reads each row's bin index once at its stored width (u8 up to 256 bins,
    else u16), whatever the number of trees, and for each tree the row's f32
    gradient and hessian and its i32 node id; does one add per row, feature,
    tree and statistic (gradient, hessian). Output histograms are small
    beside the reads and are left out. However the program splits a level
    into kernel calls, this is what the level needs.
    """
    bin_bytes = 1 if num_bins <= 256 else 2
    return {
        "bytes": rows * features * bin_bytes + trees * rows * (4 + 4 + 4),
        "ops": rows * features * 2 * trees,
    }


def least_seconds(work, peaks):
    """(seconds, bound): the larger of bytes over bandwidth and operations
    over peak, and which of the two it is. f32 adds are held to the bf16
    matrix peak, the only arithmetic peak published: generous to the kernel."""
    by_memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    by_compute = work["ops"] / peaks["flops_bf16"]
    if by_memory >= by_compute:
        return by_memory, "memory"
    return by_compute, "compute"
