"""Plain reference for a served forest: numpy traversal, float64 leaf sums.

Takes the trees as the benchmark's generator made them (plain arrays), never
the model file or anything else the program wrote. ``dtype`` computes the
same answer with thresholds and leaf values held in a lower precision: the
control that the check has to fail.
"""

import numpy as np


def _to_dtype(a, dtype):
    if dtype == "bfloat16":  # numpy has none: round float32 to 8 bits of mantissa
        bits = np.asarray(a, np.float32).view(np.uint32).astype(np.uint64)
        rounded = ((bits + 0x7FFF + ((bits >> 16) & 1)) >> 16) << 16
        return rounded.astype(np.uint32).view(np.float32).astype(np.float64)
    return np.asarray(a, dtype).astype(np.float64)


def margins(trees, rows, dtype="float64"):
    """[n] float64: the sum over trees of the leaf each row reaches. Goes
    right when ``v >= threshold``."""
    x = np.asarray(rows, np.float32).astype(np.float64)
    idx = np.arange(len(x))
    total = np.zeros(len(x))
    for tree in trees:
        thr = _to_dtype(tree["threshold"], dtype)
        val = _to_dtype(tree["value"], dtype)
        node = np.zeros(len(x), np.int64)
        while True:
            internal = tree["left"][node] >= 0
            if not internal.any():
                break
            right = x[idx, tree["feature"][node]] >= thr[node]
            nxt = np.where(right, tree["right"][node], tree["left"][node])
            node = np.where(internal, nxt, node)
        total += val[node]
    return total


def predict(trees, rows, base_score=0.5, dtype="float64"):
    """binary:logistic probabilities, float64."""
    m = margins(trees, rows, dtype) + np.log(base_score / (1.0 - base_score))
    return 1.0 / (1.0 + np.exp(-m))
