"""The loss-guided build's split steps as ONE rolled loop (PR 42).

* The forest is pinned bit for bit at every shape PR 42's issue names
  (``tests/lossguide_cases.py``): the unrolled loop's digests up to PR 43,
  which moved the sums' last bits where a tree splits more than once.
* The program does not grow with ``max_leaves``.
* The program's tree is the plain float64 grower's
  (``benchmark/reference/leafwise_reference.py::grow``).
* Compact node ids follow the padded slots: breadth-first for a heap, the
  order of expansion for a loss-guided tree.
* The gauges that say what a round builds, and what it built.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.models.forest import compact_padded_tree
from sagemaker_xgboost_container_tpu.ops import lossguide as lossguide_mod
from sagemaker_xgboost_container_tpu.ops.lossguide import build_tree_lossguide
from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree
from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

from tests import lossguide_cases

# sha256[:16] of the padded tree arrays and row_out. PR 42 read all 53 off its
# parent commit (4707354: the Python loop ``for t in range(max_leaves - 1)``)
# and PR 43's passes first gave the same 53 (the flat builder sums a node's
# rows in row order whatever the node slots beside it). Then a pass dealt a
# large leaf's rows round several slots (``_leaf_slots``: a leaf over an
# eighth of the root's hessian sum, so every root's child), whose sums are
# added up afterwards: another order of additions, other last bits. The 43
# cases that split more than once were read anew off that program (the ten
# ``l2`` ones are the parent's still); what holds them to the mathematics is
# the plain grower (below and in ``tests/test_lossguide_passes.py``). Nine of
# the 53 were the mesh cases again under a second collective lowering, equal
# by construction; they went with it in PR 45 and the 44 that stay are as
# they were.
PARENT_DIGESTS = {
    "l2.sub.plain": "c5991d5ab201c881", "l2.sub.bynode": "c5991d5ab201c881",
    "l2.sub.sets": "c5991d5ab201c881", "l2.sub.mcw": "c5991d5ab201c881",
    "l2.sub.depth3": "c5991d5ab201c881", "l2.nosub.plain": "c5991d5ab201c881",
    "l2.nosub.bynode": "c5991d5ab201c881", "l2.nosub.sets": "c5991d5ab201c881",
    "l2.nosub.mcw": "c5991d5ab201c881", "l2.nosub.depth3": "c5991d5ab201c881",
    "l8.sub.plain": "673b5032fd4adde2", "l8.sub.bynode": "8074bf421aa8a4ad",
    "l8.sub.bylevel": "5dd80eccd3e4beaa", "l8.sub.sets": "e12e7c35e2820947",
    "l8.sub.mcw": "994e0798e7b59c7a", "l8.sub.depth3": "3b219e8a35e0d208",
    "l8.sub.gamma": "a4e89fc2e1a30eee", "l8.nosub.plain": "abdbad2aca44cb18",
    "l8.nosub.bynode": "c0be7293512bef17", "l8.nosub.bylevel": "3462b671240e958b",
    "l8.nosub.sets": "9aa54f5be8013d87", "l8.nosub.mcw": "fd7275719dc69b25",
    "l8.nosub.depth3": "b87385fb22972b96", "l8.nosub.gamma": "ab8324a6d77b28a5",
    "l31.sub.plain": "79d371dec5a1797d", "l31.sub.bynode": "2ed1e41c59dd2712",
    "l31.sub.sets": "c618baca4ff06f0b", "l31.sub.mcw": "36cf8db4c14131f4",
    "l31.sub.depth3": "7d2db9cf7ab0be4f", "l31.nosub.plain": "03b73b4bdc5f0632",
    "l31.nosub.bynode": "6b9b93ac9858e2c3", "l31.nosub.sets": "f7ed1b2623dbc3df",
    "l31.nosub.mcw": "543330921d34eaf5", "l31.nosub.depth3": "90f1bd9485fc5944",
    "l8.sub.kernel": "222f5c984a70442d",
    "data4.psum.sub.plain": "0052e36868abc57d", "data4.psum.sub.bynode": "5a07cad657fe96e6",
    "data4.psum.sub.sets": "eacf476b11f65e01", "data4.psum.nosub.plain": "c222f41ec387ed76",
    "data4.psum.nosub.bynode": "575f966b9e6b822b", "data4.psum.nosub.sets": "7451bba2019f803b",
    "data2xfeature2.psum.plain": "5de9d21f6363ed9a",
    "data2xfeature2.psum.bynode": "137dc296c006e3dc",
    "data2xfeature2.psum.sets": "ba31962fcd94f857",
}
CASES = lossguide_cases.cases()


def test_every_case_has_its_digest():
    assert set(CASES) == set(PARENT_DIGESTS)


@pytest.mark.parametrize("name", sorted(PARENT_DIGESTS))
def test_rolled_build_is_the_unrolled_builds_forest_bit_for_bit(name):
    tree, row_out = lossguide_cases.run_case(*CASES[name])
    assert lossguide_cases.digest(tree, row_out) == PARENT_DIGESTS[name]
    # PR 43: the same forest from fewer passes over the rows, in every case
    passes, filled, used = (int(v) for v in tree["hist_passes"])
    kids = 1 if CASES[name][1] else 2  # node slots a leaf takes in a pass
    steps = int((~tree["is_leaf"]).sum())
    assert passes <= steps and kids * steps <= used <= filled <= lossguide_mod.PASS_SLOTS * passes


# ------------------------------------------------------- the program's size
def _build_jaxpr(max_leaves, counter=None, subtract=True):
    from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod

    rows = jax.ShapeDtypeStruct((64,), jnp.float32)
    cap = hist_mod.SUBTRACT_CACHE_MAX_BYTES
    hist_mod.SUBTRACT_CACHE_MAX_BYTES = cap if subtract else 0
    real = lossguide_mod.level_histogram

    def counted(*args, **kwargs):
        counter.append(args[4])  # the nodes built in the call
        return real(*args, **kwargs)

    if counter is not None:
        lossguide_mod.level_histogram = counted
    try:
        return jax.make_jaxpr(
            lambda b, g, h, c: build_tree_lossguide(
                b, g, h, c, max_leaves=max_leaves, num_bins=9, colsample_bynode=0.5,
                rng=jax.random.PRNGKey(0),
            )
        )(
            jax.ShapeDtypeStruct((64, 4), jnp.uint8), rows, rows,
            jax.ShapeDtypeStruct((4,), jnp.int32),
        )
    finally:
        hist_mod.SUBTRACT_CACHE_MAX_BYTES = cap
        lossguide_mod.level_histogram = real


def _count_equations(jaxpr):
    total = 0
    for eqn in jaxpr.eqns:
        total += 1
        for value in eqn.params.values():
            # one nested jaxpr (a loop's body) or several (a cond's branches)
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    total += _count_equations(inner)
    return total


@pytest.mark.parametrize("subtract", [True, False], ids=["subtraction", "both_children"])
def test_build_program_does_not_grow_with_max_leaves(subtract):
    """The equation count, every nested body included, at 16 and 255 leaves;
    and one ``level_histogram`` call site for the root and one for the step
    body's pass (PR 43: ``PASS_SLOTS`` = 8 node slots, eight leaves' left
    children with sibling subtraction, four leaves' two children without)."""
    calls_16, calls_255 = [], []
    small = _build_jaxpr(16, calls_16, subtract)
    large = _build_jaxpr(255, calls_255, subtract)
    assert _count_equations(small.jaxpr) == _count_equations(large.jaxpr)
    assert len(small.jaxpr.eqns) == len(large.jaxpr.eqns)
    assert calls_16 == calls_255 == [1, 8]


def test_benchmark_probe_reads_the_rolled_loop():
    from benchmark.kinds import train_window_leafwise

    assert train_window_leafwise.build_equations(16) == train_window_leafwise.build_equations(32)
    train_window_leafwise.require_rolled_steps()  # does not leave


# ------------------------------------------------ against the plain grower
def _grower_inputs(seed, n=900, d=6, num_bins=13):
    """Seeded rows whose candidate gains do not tie: continuous gradients,
    hessians of their own, a few missing."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, num_bins - 1, size=(n, d)).astype(np.uint8)
    bins[rng.rand(n, d) < 0.04] = num_bins - 1
    signal = (bins[:, 1] > 5) * 1.0 - 0.5 * (bins[:, 4] > 8) + 0.07 * bins[:, 2]
    grad = (rng.randn(n) * 0.7 + signal - signal.mean()).astype(np.float32)
    hess = (0.1 + rng.rand(n)).astype(np.float32)
    return bins, grad, hess, np.full(d, num_bins - 1, np.int32), num_bins


@pytest.mark.parametrize(
    "extra",
    [{}, {"min_child_weight": 25.0}, {"max_depth": 3}, {"max_depth": 4, "min_child_weight": 8.0}],
    ids=["plain", "min_child_weight", "max_depth_3", "both"],
)
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_program_tree_is_the_plain_growers(seed, extra):
    from benchmark.reference import leafwise_reference

    bins, grad, hess, num_cuts, num_bins = _grower_inputs(seed)
    max_leaves = 12
    tree, _row_out = jax.jit(
        lambda b, g, h, c: build_tree_lossguide(
            b, g, h, c, max_leaves=max_leaves, num_bins=num_bins, reg_lambda=1.0, eta=0.1,
            **extra
        )
    )(bins, grad, hess, num_cuts)
    tree = {k: np.asarray(v) for k, v in tree.items()}
    want = leafwise_reference.grow(
        bins, num_cuts, grad, hess, max_leaves, lam=1.0, eta=0.1,
        min_child_weight=extra.get("min_child_weight", 1.0),
        max_depth=extra.get("max_depth", 0),
    )
    n = len(want["left"])
    internal = want["left"] >= 0
    # the padded slots ARE the order of expansion: step t made 2t+1, 2t+2
    assert np.array_equal(~tree["is_leaf"][:n], internal)
    assert tree["is_leaf"][n:].all()
    assert np.array_equal(tree["left"][:n][internal], want["left"][internal])
    assert np.array_equal(tree["right"][:n][internal], want["right"][internal])
    assert np.array_equal(tree["feature"][:n][internal], want["feature"][internal])
    assert np.array_equal(tree["bin"][:n][internal], want["bin"][internal])
    assert np.array_equal(tree["default_left"][:n][internal], want["default_left"][internal])
    np.testing.assert_allclose(tree["leaf_value"][:n][~internal], want["value"][~internal],
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(tree["gain"][:n][internal], want["gain"][internal], rtol=1e-4)
    np.testing.assert_allclose(tree["sum_hess"][:n], want["sum_hess"], rtol=1e-5)
    if "max_depth" in extra:
        from benchmark.reference import gbt_reference

        assert gbt_reference.node_depths(want).max() <= extra["max_depth"]


# ------------------------------------------------------ compact node ids
def _bfs_compact_ids(padded):
    """The numbering ``compact_padded_tree`` handed out before PR 42."""
    order = [0]
    for node in order:
        if not padded["is_leaf"][node]:
            order += [int(padded["left"][node]), int(padded["right"][node])]
    return order


# the five cells' shapes at small size: (features, bins, depth, missing share)
CELL_SHAPES = {
    "higgs-d8": (28, 257, 8, 0.0), "mslr-ndcg": (136, 257, 8, 0.0),
    "criteo-tb-d8": (39, 257, 8, 0.14), "criteo-tb-d8-host4": (39, 257, 8, 0.14),
    "mnist8m-mc10": (784, 257, 5, 0.0),
}


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_depthwise_forest_is_numbered_as_before(cell):
    """A heap's slots are breadth-first order, so increasing-slot numbering
    is the breadth-first numbering, pruned subtrees and all: the same bytes."""
    d, num_bins, depth, missing = CELL_SHAPES[cell]
    rng = np.random.RandomState(len(cell))
    n = 700
    bins = rng.randint(0, num_bins - 1, size=(n, d)).astype(np.uint16)
    bins[rng.rand(n, d) < missing] = num_bins - 1
    grad = rng.randn(n).astype(np.float32)
    hess = (0.05 + rng.rand(n)).astype(np.float32)
    tree, _ = jax.jit(
        lambda b, g, h, c: build_tree(
            b, g, h, c, depth, num_bins, min_child_weight=4.0, gamma=0.05
        )
    )(bins, grad, hess, np.full(d, num_bins - 1, np.int32))
    padded = {k: np.asarray(v) for k, v in tree.items()}
    order = _bfs_compact_ids(padded)
    assert len(order) < len(padded["is_leaf"])  # something was pruned
    assert order == sorted(order)
    cuts = [np.arange(num_bins - 1, dtype=np.float32) for _ in range(d)]
    compact = compact_padded_tree(padded, cuts)
    want_left = [order.index(int(padded["left"][s])) if not padded["is_leaf"][s] else -1
                 for s in order]
    assert compact.left.tolist() == want_left
    assert np.array_equal(compact.sum_hess, padded["sum_hess"][order])
    assert np.array_equal(compact.value, padded["leaf_value"][order])


def test_lossguide_forest_is_numbered_in_expansion_order_and_round_trips(tmp_path):
    from benchmark.reference import gbt_reference, leafwise_reference

    from sagemaker_xgboost_container_tpu.models.forest import Forest

    rng = np.random.RandomState(9)
    X = rng.randn(1500, 7).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] > 0).astype(np.float32)
    forest = train(
        {"objective": "binary:logistic", "grow_policy": "lossguide", "max_depth": 0,
         "max_leaves": 20, "eta": 0.3, "max_bin": 32},
        DataMatrix(X, labels=y), num_boost_round=3,
    )
    for tree in forest.trees:
        internal = np.flatnonzero(tree.left >= 0)
        # children after parents, and each split's children the next two ids
        assert (tree.left[internal] > internal).all()
        steps = np.argsort(tree.left[internal])
        assert tree.left[internal][steps].tolist() == list(range(1, 2 * len(internal), 2))
        assert (tree.right[internal] == tree.left[internal] + 1).all()
        plain = {"left": tree.left.astype(np.int64), "right": tree.right.astype(np.int64),
                 "gain": tree.gain}
        assert leafwise_reference.best_first_violations(plain) == 0
        assert tree.depth() == gbt_reference.node_depths(plain).max()
        # the breadth-first numbering of before reads as out of order
        assert len(internal) == 19
    path = str(tmp_path / "model.json")
    forest.save_model(path)
    again = Forest.load_model(path)
    assert np.array_equal(
        np.asarray(forest.predict(X), np.float32), np.asarray(again.predict(X), np.float32)
    )
    for a, b in zip(forest.trees, again.trees):
        assert np.array_equal(a.left, b.left) and np.array_equal(a.right, b.right)


def test_breadth_first_ids_read_as_best_first_violations():
    """What the judge's exact check is for: hand it a best-first tree
    renumbered breadth-first (the compact ids of before PR 42)."""
    from benchmark.reference import leafwise_reference

    bins, grad, hess, num_cuts, num_bins = _grower_inputs(4)
    tree = leafwise_reference.grow(bins, num_cuts, grad, hess, 16, lam=1.0, eta=0.1)
    assert leafwise_reference.best_first_violations(tree) == 0
    order = [0]
    for node in order:
        if tree["left"][node] >= 0:
            order += [int(tree["left"][node]), int(tree["right"][node])]
    assert order != sorted(order)  # not a heap: the two numberings differ
    new_id = {old: new for new, old in enumerate(order)}
    renumbered = {
        "left": np.array([new_id.get(int(tree["left"][o]), -1) for o in order]),
        "right": np.array([new_id.get(int(tree["right"][o]), -1) for o in order]),
        "gain": tree["gain"][order],
    }
    assert leafwise_reference.best_first_violations(renumbered) > 0


# ----------------------------------------------------------------- gauges
def _gauge(name):
    for metric, _kind, _help, family in REGISTRY.collect():
        if metric == name:
            return [s.value for s in family]
    return None


@pytest.mark.parametrize(
    "params, steps, trees",
    [
        ({"objective": "binary:logistic", "grow_policy": "lossguide", "max_depth": 0,
          "max_leaves": 9}, 8, 1),
        ({"objective": "binary:logistic", "max_depth": 3}, 0, 1),
        ({"objective": "multi:softmax", "num_class": 10, "max_depth": 2}, 0, 10),
        ({"objective": "multi:softmax", "num_class": 3, "grow_policy": "lossguide",
          "max_depth": 0, "max_leaves": 5}, 12, 3),
    ],
    ids=["loss_guided", "depth_wise", "ten_class", "loss_guided_three_class"],
)
def test_round_shape_gauges(params, steps, trees):
    rng = np.random.RandomState(2)
    X = rng.randn(400, 5).astype(np.float32)
    classes = int(params.get("num_class", 2))
    y = (np.abs(X[:, 0] * 3).astype(int) % classes).astype(np.float32)
    leaves_before = (_gauge("tree_leaves_total") or [0.0])[0]
    forest = train(dict(params, max_bin=16), DataMatrix(X, labels=y), num_boost_round=2)
    assert _gauge("round_split_steps") == [float(steps)]
    assert _gauge("round_class_trees") == [float(trees)]
    grown = sum(int((t.left < 0).sum()) for t in forest.trees)
    assert _gauge("tree_leaves_total")[0] - leaves_before == grown
    assert _gauge("tree_depth_max") == [float(max(t.depth() for t in forest.trees))]


@pytest.mark.parametrize(
    "params, watchlist, replayed",
    [
        ({"grow_policy": "lossguide", "max_depth": 0, "max_leaves": 9}, ("validation",), 8),
        ({"grow_policy": "lossguide", "max_depth": 0, "max_leaves": 9}, ("train", "validation"), 8),
        ({"grow_policy": "lossguide", "max_depth": 0, "max_leaves": 9}, ("validation", "test"), 16),
        ({"grow_policy": "lossguide", "max_depth": 0, "max_leaves": 9}, (), 0),
        ({"grow_policy": "lossguide", "max_depth": 0, "max_leaves": 5, "num_class": 3,
          "objective": "multi:softmax"}, ("validation",), 12),
        ({"max_depth": 3}, ("train", "validation"), 0),
    ],
    ids=["one_set", "the_training_rows_share_leaf_margin", "two_sets", "no_watchlist",
         "three_class", "depth_wise"],
)
def test_eval_replay_steps_gauge(params, watchlist, replayed):
    """``round_eval_replay_steps``: the split steps a round replays over
    evaluation rows, ``round_split_steps`` x the evaluation sets that do not
    share the training rows; 0 where rows walk a heap level by level."""
    rng = np.random.RandomState(4)
    X = rng.randn(500, 5).astype(np.float32)
    classes = int(params.get("num_class", 2))
    y = (np.abs(X[:, 0] * 3).astype(int) % classes).astype(np.float32)
    dtrain = DataMatrix(X[:300], labels=y[:300])
    sets = {
        "train": dtrain,
        "validation": DataMatrix(X[300:400], labels=y[300:400]),
        "test": DataMatrix(X[400:], labels=y[400:]),
    }
    train(
        dict({"objective": "binary:logistic"}, **params, max_bin=16), dtrain, num_boost_round=1,
        evals=[(sets[name], name) for name in watchlist], verbose_eval=False,
    )
    assert _gauge("round_eval_replay_steps") == [float(replayed)]


def test_front_door_takes_the_settings_as_strings():
    """``grow_policy=lossguide``, ``max_depth=0``, ``max_leaves=255`` as the
    strings of a ``hyperparameters.json`` pass the validation and arrive at
    ``TrainConfig`` as what the build needs."""
    from sagemaker_xgboost_container_tpu.algorithm import hyperparameters as hpv
    from sagemaker_xgboost_container_tpu.algorithm import metrics as metrics_mod
    from sagemaker_xgboost_container_tpu.models.booster import TrainConfig

    schema = hpv.initialize(metrics_mod.initialize())
    validated = schema.validate({
        "num_round": "500", "objective": "binary:logistic", "tree_method": "hist",
        "grow_policy": "lossguide", "max_depth": "0", "max_leaves": "255", "eta": "0.1",
        "min_child_weight": "100", "lambda": "1.0", "max_bin": "256", "eval_metric": "logloss",
    })
    config = TrainConfig(validated)
    assert (config.grow_policy, config.max_depth, config.max_leaves) == ("lossguide", 0, 255)
    assert config.eval_traversal == "replay" and config.predict_depth == 254
    assert config.min_child_weight == 100.0
