"""Row routing's bin fetch: ``row_bin_lookup``'s two lowerings give the same
integers, the tree builder and the binned traversal give the same trees and
margins under either, and the chooser reads a backend and a width and nothing
else. Everything runs on the CPU with the lowering forced: through ``impl=`` /
``route_impl=`` where the function takes it; where it takes ``knobs``, through
a session snapshot whose ``backend`` says ``tpu`` (the chip's program: every
chooser reads that one field) with the width rule cut to zero for the gather.

The evaluation walk: ``predict_binned_levels`` (depth-wise trees, level by
level over the level's own node tables), ``predict_binned`` (the pointer
traversal) and the build's own ``row_out`` agree bit for bit under every
lowering of the bin fetch and of the node-table lookup, and a session takes
the walk its ``grow_policy`` names (a loss-guided one the step replay,
``predict_binned_steps``: ``tests/test_eval_step_replay.py``).
"""

import functools
import itertools
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from sagemaker_xgboost_container_tpu.data.binning import (
    apply_cut_points,
    compute_cut_points,
)
from sagemaker_xgboost_container_tpu.ops import tree_build
from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs
from sagemaker_xgboost_container_tpu.ops.tree_build import (
    NODE_TABLE_SELECT_MAX_WIDTH,
    ROUTE_DENSE_MAX_WIDTH,
    build_tree,
    choose_eval_traversal,
    choose_route_impl,
    choose_table_impl,
    node_table_lookup,
    pack_split_word,
    pack_tree,
    predict_binned,
    predict_binned_levels,
    row_bin_lookup,
    split_word_bin_bits,
    tree_from_packed,
    unpack_split_word,
)

# the node-table lowering each backend's chooser picks at a level's width
BACKEND_OF_TABLE = {"tpu": "select", "cpu": "gather"}


@pytest.mark.parametrize("d", [1, 7, 28, 130])
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
def test_row_bin_lookup_dense_equals_gather(dtype, d):
    num_bins = 256 if dtype == np.uint8 else 257
    n = 1000 + 37  # not a multiple of the 128 lanes
    rng = np.random.RandomState(d)
    bins = rng.randint(0, num_bins, size=(n, d)).astype(dtype)
    bins[rng.rand(n, d) < 0.1] = num_bins - 1  # the missing bin
    feat = rng.randint(0, d, size=n).astype(np.int32)
    feat[:4] = [0, d - 1, 0, d - 1]
    bins[0, 0] = bins[1, d - 1] = num_bins - 1
    want = bins[np.arange(n), feat].astype(np.int32)
    for impl in ("gather", "dense"):
        got = row_bin_lookup(jnp.asarray(bins), jnp.asarray(feat), impl=impl)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=impl)


def test_row_bin_lookup_rejects_unknown_lowering():
    with pytest.raises(ValueError, match="onehot"):
        row_bin_lookup(jnp.zeros((4, 2), jnp.uint8), jnp.zeros(4, jnp.int32), impl="onehot")


def _nan_problem(n=3000, d=8, max_bin=32, seed=5):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, d).astype(np.float32)
    x[rng.rand(n, d) < 0.1] = np.nan
    y = (np.nan_to_num(x[:, 0]) + np.sin(6 * np.nan_to_num(x[:, 2])) > 1).astype(np.float32)
    cuts = compute_cut_points(x, None, max_bin)
    bins = apply_cut_points(x, cuts, max_bin).astype(np.uint8)
    num_cuts = np.asarray([len(c) for c in cuts], np.int32)
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)
    assert (bins == max_bin).any()  # NaNs sit in the missing bin
    return bins, grad, hess, num_cuts, max_bin + 1


def _build_plain(problem, knobs, depth=5, **growth):
    bins, grad, hess, num_cuts, num_bins = problem

    @jax.jit
    def build(b, g, h, nc):
        tree, row_out = build_tree(
            b, g, h, nc, max_depth=depth, num_bins=num_bins, eta=0.3, knobs=knobs,
            **growth
        )
        return pack_tree(tree), row_out

    packed, row_out = build(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(num_cuts)
    )
    return np.asarray(packed), np.asarray(row_out)


def _build_feature_sharded(problem, knobs, depth=5, **growth):
    bins, grad, hess, num_cuts, num_bins = problem
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), axis_names=("data", "feature"))

    def build(b, g, h, nc):
        tree, row_out = build_tree(
            b, g, h, nc, max_depth=depth, num_bins=num_bins, eta=0.3, knobs=knobs,
            axis_name="data", feature_axis_name="feature", **growth
        )
        return pack_tree(tree), row_out

    mapped = jax.jit(
        jax.shard_map(
            build,
            mesh=mesh,
            in_specs=(P("data", "feature"), P("data"), P("data"), P("feature")),
            out_specs=(P(), P("data")),
            check_vma=False,
        )
    )
    packed, row_out = mapped(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(num_cuts)
    )
    return np.asarray(packed), np.asarray(row_out)


def _build_class_stack(problem, knobs, depth=5, **growth):
    """Three one-vs-rest trees side by side, as the booster's class ``vmap``
    builds them: every node table gains a leading axis."""
    bins, _grad, hess, num_cuts, num_bins = problem
    classes = np.digitize(bins[:, 0], [8, 20])
    # seeded noise on the gradients: something to split on down to the last level
    noise = np.random.RandomState(3).randn(3, bins.shape[0]) * 0.3
    grads = np.stack([0.3 - (classes == c) for c in range(3)]) + noise

    @jax.jit
    def build(b, g, h, nc):
        def one(gc):
            tree, row_out = build_tree(
                b, gc, h, nc, max_depth=depth, num_bins=num_bins, eta=0.3, knobs=knobs,
                **growth
            )
            return pack_tree(tree), row_out

        return jax.vmap(one)(g)

    packed, row_out = build(
        jnp.asarray(bins), jnp.asarray(grads, jnp.float32), jnp.asarray(hess),
        jnp.asarray(num_cuts),
    )
    return np.asarray(packed), np.asarray(row_out)


# the rule each axis cuts to give the other lowering, the chooser that reads it
# (with the width the assertion asks about), its two answers, and the cut: zero,
# or for the word one bit less than `_nan_problem`'s fields need (4 + 6 + 2), so
# that the four fields are read one by one
LOWERING_AXES = {
    "route": (
        "ROUTE_DENSE_MAX_WIDTH", lambda k: choose_route_impl(k.backend, 2),
        "dense", "gather", 0,
    ),
    "table": (
        "NODE_TABLE_SELECT_MAX_WIDTH", lambda k: choose_table_impl(k.backend, 256),
        "select", "gather", 0,
    ),
    "word": ("SPLIT_WORD_BITS", lambda k: split_word_bin_bits(8, 33), 6, None, 11),
}
DEEP = {"depth": 8}  # levels 7 and 8: the 128- and 256-entry tables
# a hessian floor of 12 rows: branches stop at levels 1-6, four nodes split at level 7
EARLY_LEAVES = {"depth": 8, "min_child_weight": 3.0}


@pytest.mark.multichip
@pytest.mark.parametrize(
    "axis,build,growth",
    [
        pytest.param("route", _build_plain, {}, id="plain"),
        pytest.param("route", _build_feature_sharded, {}, id="feature_sharded"),
        pytest.param("table", _build_plain, DEEP, id="table-plain"),
        pytest.param("table", _build_feature_sharded, DEEP, id="table-feature_sharded"),
        pytest.param("table", _build_class_stack, DEEP, id="table-class_stack"),
        pytest.param("table", _build_plain, EARLY_LEAVES, id="table-early_leaves"),
        pytest.param("word", _build_plain, DEEP, id="word-plain"),
        pytest.param("word", _build_feature_sharded, DEEP, id="word-feature_sharded"),
        pytest.param("word", _build_class_stack, EARLY_LEAVES, id="word-class_stack"),
    ],
)
def test_build_tree_identical_under_each_lowering(monkeypatch, axis, build, growth):
    """Tree arrays and ``row_out`` bit for bit; on the feature axis the width
    the route chooser sees is the shard's own two columns. Both sides are the
    chip's program; the other side has the axis's rule cut to zero (the word's
    to one bit short). The deep cases read the 128- and 256-entry levels."""
    deep = growth.get("depth") == 8
    problem = _nan_problem(n=9000) if deep else _nan_problem()
    knobs = resolve_hist_knobs()._replace(backend="tpu")
    rule, chosen, ours, other, cut = LOWERING_AXES[axis]
    results = {}
    for impl, limit in ((ours, getattr(tree_build, rule)), (other, cut)):
        monkeypatch.setattr(tree_build, rule, limit)
        assert chosen(knobs) == impl
        results[impl] = build(problem, knobs, **growth)
    packed, row_out = results[other]
    split = packed[..., 3, :] < 0.5  # is_leaf row: a tree with real splits
    assert split.sum() > 10
    if deep:
        reached = packed[..., 7, :] > 0  # sum_hess
        assert (split & reached)[..., 127:255].any()  # level 7 splits, so level 8 is read
        if "min_child_weight" in growth:
            # rows that finish above level 7, beside branches that go on
            assert (~split & reached)[..., 1:127].any()
    np.testing.assert_array_equal(results[ours][0], packed)
    np.testing.assert_array_equal(results[ours][1], row_out)


@pytest.mark.parametrize(
    "feature_ids,num_bins,want",
    [
        (28, 257, 9),    # higgs-d8: 5 + 9 + 2 bits
        (136, 257, 9),   # mslr-ndcg: 8 + 9 + 2
        (39, 257, 9),    # criteo-tb-d8: 6 + 9 + 2
        ((1 << 19) - 1, 1023, 10),     # 19 + 10 + 2: the widest that fits
        (1 << 19, 1023, None),         # 20 + 10 + 2
        ((1 << 19) - 1, 1024, None),   # 19 + 11 + 2
    ],
)
def test_split_word_fits_31_bits_or_is_not_packed(feature_ids, num_bins, want):
    assert tree_build.SPLIT_WORD_BITS == 31
    assert split_word_bin_bits(feature_ids, num_bins) == want


@pytest.mark.parametrize(
    "feature_ids,num_bins", [(1, 2), (28, 257), (4095, 65535), ((1 << 19) - 1, 1023)]
)
def test_split_word_round_trips_the_extreme_values(feature_ids, num_bins):
    bin_bits = split_word_bin_bits(feature_ids, num_bins)
    feature, split_bin, default_left, becomes_leaf = (
        np.asarray(v)
        for v in zip(
            *itertools.product(
                (0, feature_ids - 1), (0, num_bins - 1), (False, True), (False, True)
            )
        )
    )
    word = pack_split_word(
        jnp.asarray(feature, jnp.int32), jnp.asarray(split_bin, jnp.int32),
        jnp.asarray(default_left), jnp.asarray(becomes_leaf), bin_bits,
    )
    assert word.dtype == jnp.int32 and (np.asarray(word) >= 0).all()
    got = unpack_split_word(word, bin_bits)
    for g, w in zip(got, (feature, split_bin, default_left, becomes_leaf)):
        assert g.dtype == (jnp.bool_ if w.dtype == bool else jnp.int32)
        np.testing.assert_array_equal(np.asarray(g), w)


def test_predict_binned_identical_under_each_lowering():
    problem = _nan_problem()
    bins, num_bins = problem[0], problem[-1]
    packed, row_out = _build_plain(problem, None)
    tree = tree_from_packed(jnp.asarray(packed))
    unseen = _nan_problem(n=1111, seed=9)[0]
    for rows in (unseen, bins):
        got = {
            impl: np.asarray(
                predict_binned(tree, jnp.asarray(rows), 5, num_bins, route_impl=impl)
            )
            for impl in ("gather", "dense")
        }
        np.testing.assert_array_equal(got["dense"], got["gather"])
    # the traversal lands every train row on the leaf the build routed it to
    np.testing.assert_array_equal(got["dense"], row_out)
    assert np.unique(got["dense"]).size > 4


# what stops a branch: nothing, a hessian floor that leafs some branches at
# levels 1-3, a gamma no split clears (the tree is its root), and three class
# trees side by side under vmap
GROWTH = {
    "full": {},
    "early_leaves": {"min_child_weight": 45.0},
    "single_leaf": {"gamma": 1e9},
    "class_stack": {},
}


@functools.lru_cache(maxsize=None)
def _grown(depth, growth):
    """(tree dict or stack of three, row_out, train bins, unseen bins, num_bins)
    for one depth and way of growing, built once for every lowering."""
    bins, grad, hess, num_cuts, num_bins = _nan_problem()
    unseen = _nan_problem(n=1111, seed=9)[0]

    @jax.jit
    def build(g):
        return build_tree(
            jnp.asarray(bins), g, jnp.asarray(hess), jnp.asarray(num_cuts),
            max_depth=depth, num_bins=num_bins, eta=0.3, **GROWTH[growth]
        )

    if growth != "class_stack":
        tree, row_out = build(jnp.asarray(grad))
        return tree, np.asarray(row_out), bins, unseen, num_bins
    # one-vs-rest gradients of three classes cut from the first column's bins
    classes = np.digitize(bins[:, 0], [8, 20])
    built = [build(jnp.asarray((0.3 - (classes == c)).astype(np.float32))) for c in range(3)]
    stack = {k: jnp.stack([t[k] for t, _ in built]) for k in built[0][0]}
    return stack, np.stack([np.asarray(r) for _, r in built]), bins, unseen, num_bins


def _bits(x):
    return np.asarray(x, np.float32).view(np.int32)


@pytest.mark.parametrize("table_backend", ["cpu", "tpu"])
@pytest.mark.parametrize("route_impl", ["gather", "dense"])
@pytest.mark.parametrize(
    "depth,growth",
    [(d, g) for d in (1, 5, 8) for g in ("full", "early_leaves", "single_leaf")]
    + [(5, "class_stack")],
)
def test_level_walk_equals_pointer_traversal_and_build(depth, growth, route_impl, table_backend):
    """Train rows land on the leaf the build routed them to, unseen rows on
    the pointer traversal's, to the bit (the float's 32 bits, so a signed
    zero counts)."""
    tree, row_out, bins, unseen, num_bins = _grown(depth, growth)
    assert choose_table_impl(table_backend, 2**depth) == BACKEND_OF_TABLE[table_backend]

    def level(t, rows):
        return predict_binned_levels(
            t, rows, depth, num_bins, route_impl=route_impl, table_backend=table_backend
        )

    def pointer(t, rows):
        return predict_binned(t, rows, depth, num_bins, route_impl=route_impl)

    if growth == "class_stack":
        level, pointer = (
            lambda t, rows, f=f: jax.vmap(lambda one: f(one, rows))(t) for f in (level, pointer)
        )
    for rows in (unseen, bins):
        got = _bits(level(tree, jnp.asarray(rows)))
        np.testing.assert_array_equal(got, _bits(pointer(tree, jnp.asarray(rows))))
    np.testing.assert_array_equal(got, _bits(row_out))
    # node axis last: a stack of class trees reads as one tree does
    split = ~np.asarray(tree["is_leaf"])
    reached = np.asarray(tree["sum_hess"]) > 0
    if growth == "single_leaf":
        assert not split[0] and np.unique(got).size == 1
    elif depth > 1:
        assert np.unique(got).size > 4
        if growth != "class_stack":
            # both default directions among the splits rows pass through
            assert set(np.asarray(tree["default_left"])[split & reached]) == {False, True}
    if growth == "early_leaves" and depth > 1:
        # a leaf rows reach at levels 1-3, and a split beside it
        assert (~split & reached)[1:15].any() and (split & reached)[1:15].any()


@pytest.mark.parametrize("width", [1, 2, 8, 128, 511])
@pytest.mark.parametrize("dtype", ["int32", "bool", "float32"])
def test_node_table_lookup_select_equals_gather(dtype, width):
    rng = np.random.RandomState(width)
    n = 1000 + 37
    table = {
        "int32": rng.randint(0, 300, size=width).astype(np.int32),
        "bool": rng.rand(width) < 0.5,
        # signed zeros and a non-finite entry must come back as they are
        "float32": np.resize(
            np.asarray([-0.0, 0.0, -1.5, np.inf, 3e-41], np.float32), width
        ),
    }[dtype]
    idx = rng.randint(0, width, size=n).astype(np.int32)
    idx[:2] = [0, width - 1]
    got = {
        impl: np.asarray(node_table_lookup(jnp.asarray(table), jnp.asarray(idx), impl=impl))
        for impl in ("gather", "select")
    }
    assert got["select"].dtype == table.dtype
    view = np.int32 if dtype == "float32" else table.dtype
    np.testing.assert_array_equal(got["gather"].view(view), table[idx].view(view))
    np.testing.assert_array_equal(got["select"].view(view), table[idx].view(view))


def test_node_table_lookup_rejects_unknown_lowering():
    with pytest.raises(ValueError, match="onehot"):
        node_table_lookup(jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32), impl="onehot")


class NoEnviron:
    def __getattr__(self, name):
        raise AssertionError("the chooser read the environment")

    __getitem__ = __contains__ = __getattr__


@pytest.mark.parametrize(
    "backend,width,want",
    [
        ("tpu", 1, "select"),
        ("tpu", 128, "select"),
        ("tpu", NODE_TABLE_SELECT_MAX_WIDTH, "select"),
        ("tpu", NODE_TABLE_SELECT_MAX_WIDTH + 1, "gather"),
        ("cpu", 1, "gather"),
        ("cpu", 128, "gather"),
        ("gpu", 128, "gather"),
    ],
)
def test_table_chooser_reads_backend_and_width_only(monkeypatch, backend, width, want):
    with monkeypatch.context() as during_the_call:
        during_the_call.setattr(os, "environ", NoEnviron())
        got = choose_table_impl(backend, width)
    assert got == want


def test_session_walks_as_its_grow_policy_says(monkeypatch):
    """A loss-guided session replays the tree's split steps, a depth-wise one
    takes the level walk; the choice reads the policy and nothing else, and
    no session traces the pointer traversal."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import booster, train

    with monkeypatch.context() as during_the_call:
        during_the_call.setattr(os, "environ", NoEnviron())
        assert choose_eval_traversal("lossguide") == "replay"
        assert choose_eval_traversal("depthwise") == "level"
    lossguide = {"grow_policy": "lossguide", "max_leaves": 8, "max_depth": 0}
    assert booster.TrainConfig(lossguide).eval_traversal == "replay"
    assert booster.TrainConfig({"max_depth": 3}).eval_traversal == "level"

    taken = []
    assert not hasattr(booster, "predict_binned")
    for name in ("predict_binned_steps", "predict_binned_levels"):
        def spy(*args, _name=name, _fn=getattr(booster, name), **kwargs):
            taken.append(_name)
            return _fn(*args, **kwargs)

        monkeypatch.setattr(booster, name, spy)
    rng = np.random.RandomState(3)
    X = rng.rand(500, 4).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 1).astype(np.float32)
    dtrain, dval = DataMatrix(X[:400], labels=y[:400]), DataMatrix(X[400:], labels=y[400:])
    for params, want in (
        (lossguide, "predict_binned_steps"), ({"max_depth": 3}, "predict_binned_levels")
    ):
        del taken[:]
        train(
            dict(params, objective="binary:logistic"), dtrain, num_boost_round=2,
            evals=[(dval, "validation")], verbose_eval=False,
        )
        assert set(taken) == {want}


@pytest.mark.parametrize(
    "growth, walk",
    [
        ({"max_depth": 5}, "predict_binned_levels"),
        ({"grow_policy": "lossguide", "max_depth": 0, "max_leaves": 24}, "predict_binned_steps"),
    ],
    ids=["depthwise_level_walk", "lossguide_step_replay"],
)
def test_train_logs_the_pointer_traversals_validation_metric(monkeypatch, growth, walk):
    """End to end: ``train()`` with a validation set logs, every round, the
    metric that the same forest gives those rows under the pointer traversal,
    to the bit, whichever walk the session's growth policy names."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import booster, train

    rng = np.random.RandomState(5)
    X = rng.rand(3000, 7).astype(np.float32)
    X[rng.rand(3000, 7) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 2]) > 1).astype(np.float32)
    dtrain = DataMatrix(X[:2400], labels=y[:2400])
    dval = DataMatrix(X[2400:], labels=y[2400:])
    params = dict(
        growth, objective="binary:logistic", gamma=0.5, _rounds_per_dispatch=2
    )

    def run():
        log = {}

        class Rec:
            def after_iteration(self, model, epoch, evals_log):
                log["logloss"] = list(evals_log["validation"]["logloss"])
                return False

        forest = train(
            params, dtrain, num_boost_round=4, evals=[(dval, "validation")],
            callbacks=[Rec()], verbose_eval=False,
        )
        return forest, log["logloss"]

    forest, logged = run()
    assert len(logged) == 4
    walked = []

    def pointer(t, b, *depth, **_lowerings):
        # the level walk is called (t, b, depth, num_bins), the replay (t, b, num_bins)
        *depth, num_bins = depth
        walked.append("pointer")
        steps = depth[0] if depth else (t["left"].shape[-1] - 1) // 2
        return predict_binned(t, b, steps, num_bins)

    monkeypatch.setattr(booster, walk, pointer)
    forest_p, logged_p = run()
    assert walked
    assert [float(v).hex() for v in logged] == [float(v).hex() for v in logged_p]
    for a, b in zip(forest.trees, forest_p.trees):
        np.testing.assert_array_equal(a.feature, b.feature)
        np.testing.assert_array_equal(a.value, b.value)
    # and the host's float traversal of the returned forest agrees with it
    p = np.clip(forest.predict(X[2400:]), 1e-7, 1 - 1e-7)
    direct = float(-np.mean(y[2400:] * np.log(p) + (1 - y[2400:]) * np.log(1 - p)))
    assert abs(direct - logged[-1]) < 1e-5


@pytest.mark.parametrize(
    "backend,width,want",
    [
        ("tpu", 1, "dense"),
        ("tpu", 28, "dense"),
        ("tpu", ROUTE_DENSE_MAX_WIDTH, "dense"),
        ("tpu", ROUTE_DENSE_MAX_WIDTH + 1, "gather"),
        ("tpu", 1 << 20, "gather"),
        ("cpu", 1, "gather"),
        ("cpu", 28, "gather"),
        ("cpu", ROUTE_DENSE_MAX_WIDTH + 1, "gather"),
        ("gpu", 28, "gather"),
    ],
)
def test_chooser_reads_backend_and_width_only(monkeypatch, backend, width, want):
    with monkeypatch.context() as during_the_call:
        during_the_call.setattr(os, "environ", NoEnviron())
        got = choose_route_impl(backend, width)
    assert got == want


def test_session_snapshot_holds_the_backend():
    knobs = resolve_hist_knobs()
    assert knobs.backend == jax.default_backend() == "cpu"


@pytest.mark.parametrize(
    "backend,width,grow_policy,max_depth,want",
    [
        ("tpu", 28, "depthwise", 8, ("dense", "level", "select")),
        ("cpu", 28, "depthwise", 8, ("gather", "level", "gather")),
        ("tpu", 28, "lossguide", 0, ("dense", "replay", None)),
        # a server: no binned rows, no trees built
        ("tpu", None, None, None, (None, None, None)),
        # the widest level a select still reads, and one level past it
        ("tpu", 28, "depthwise", 12, ("dense", "level", "select")),
        ("tpu", 28, "depthwise", 13, ("dense", "level", "gather")),
    ],
)
def test_device_runtime_line_names_the_resolved_lowering(
    monkeypatch, caplog, backend, width, grow_policy, max_depth, want
):
    from sagemaker_xgboost_container_tpu.utils import device_runtime

    monkeypatch.setattr(device_runtime, "enable_compile_cache", lambda: None)
    knobs = resolve_hist_knobs()._replace(backend=backend)
    with caplog.at_level(logging.INFO, logger=device_runtime.__name__):
        fields = device_runtime.start_device_runtime(
            "train", knobs=knobs, route_width=width, grow_policy=grow_policy,
            max_depth=max_depth,
        )
    line = [r.getMessage() for r in caplog.records if "device runtime: " in r.getMessage()][-1]
    logged = json.loads(line.split("device runtime: ", 1)[1])
    for said in (fields, logged):
        assert (
            said["route_impl"], said["eval_traversal"], said["build_table_impl"]
        ) == want
        assert said["route_width"] == width


@pytest.mark.parametrize(
    "role, trees, want",
    [("train", 10, 10), ("train", 1, 1), ("serve", None, None)],
    ids=["ten_class_trainer", "one_tree_trainer", "server"],
)
def test_device_runtime_line_says_the_trees_a_round(monkeypatch, caplog, role, trees, want):
    """`trees_per_round`: classes x `num_parallel_tree` as `train_job` reads
    them from the job's hyper-parameters; a server grows none."""
    from sagemaker_xgboost_container_tpu.utils import device_runtime

    monkeypatch.setattr(device_runtime, "enable_compile_cache", lambda: None)
    extra = {} if trees is None else {"trees_per_round": trees}
    with caplog.at_level(logging.INFO, logger=device_runtime.__name__):
        fields = device_runtime.start_device_runtime(role, **extra)
    line = [r.getMessage() for r in caplog.records if "device runtime: " in r.getMessage()][-1]
    logged = json.loads(line.split("device runtime: ", 1)[1])
    assert fields["trees_per_round"] == logged["trees_per_round"] == want
