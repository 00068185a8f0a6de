"""A forest and request rows from the seed, with ties designed out.

Every threshold is an odd multiple of 1/2048 and every feature value a
multiple of 1/1024 inside the thresholds' range, written with enough digits
to parse back to the same float32. So no row can sit on a threshold and no
parser can round one across it: the served answer and the reference's can
differ only by the precision of the leaf sum.

Trees are complete, in xgboost's breadth-first node order (children of node
i are 2i+1 and 2i+2), as plain arrays; the kind hands them to the program
through its public ``Tree`` / ``Forest.append_round`` / ``save_model``.
"""

import numpy as np

GRID = 1024          # feature values are k / GRID
HALF_RANGE = 2       # thresholds and values lie in (-2, 2)
LEAF_SCALE = 0.05


def make_trees(spec, num_feature, seed):
    depth = int(spec["max_depth"])
    n_internal, n_nodes = (1 << depth) - 1, (1 << (depth + 1)) - 1
    rng = np.random.default_rng([int(seed) % (1 << 63), 0x466F72657374])
    ids = np.arange(n_nodes)
    trees = []
    for _ in range(int(spec["num_trees"])):
        feature = np.zeros(n_nodes, np.int64)
        threshold = np.zeros(n_nodes, np.float32)
        value = np.zeros(n_nodes, np.float32)
        feature[:n_internal] = rng.integers(0, num_feature, n_internal)
        odd = 2 * rng.integers(-HALF_RANGE * GRID, HALF_RANGE * GRID, n_internal) + 1
        threshold[:n_internal] = odd / np.float32(2 * GRID)
        value[n_internal:] = rng.normal(0.0, LEAF_SCALE, n_nodes - n_internal)
        trees.append(
            {
                "feature": feature,
                "threshold": threshold,
                "default_left": np.zeros(n_nodes, bool),
                "left": np.where(ids < n_internal, 2 * ids + 1, -1),
                "right": np.where(ids < n_internal, 2 * ids + 2, -1),
                "value": value,
            }
        )
    return trees


def make_rows(rng, n, num_feature):
    k = rng.integers(-HALF_RANGE * GRID, HALF_RANGE * GRID, size=(n, num_feature))
    return (k / np.float32(GRID)).astype(np.float32)


def encode_csv(rows):
    """text/csv body; ``repr`` of the float is exact for multiples of 1/1024."""
    return "\n".join(",".join(repr(float(v)) for v in row) for row in rows).encode()
