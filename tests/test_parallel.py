"""Multi-chip + ranking + parallel-tree tests on the virtual 8-device mesh.

conftest.py forces JAX_PLATFORMS=cpu with xla_force_host_platform_device_count=8,
so these exercise real SPMD partitioning + psum without TPU hardware — the
TPU analog of the reference's N-local-process Rabit tests
(test/unit/test_distributed.py:25-31).
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.models.eval_metrics import evaluate as eval_metric
from sagemaker_xgboost_container_tpu.parallel.distributed import Cluster


def _friedman(n=2000, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 5).astype(np.float32)
    y = (
        10 * np.sin(np.pi * X[:, 0] * X[:, 1])
        + 20 * (X[:, 2] - 0.5) ** 2
        + 10 * X[:, 3]
        + 5 * X[:, 4]
    ).astype(np.float32)
    return X, y


@pytest.fixture(scope="module")
def mesh8():
    devices = np.array(jax.devices()[:8])
    assert devices.size == 8, "conftest must provide 8 virtual devices"
    return Mesh(devices, axis_names=("data",))


@pytest.mark.multichip
def test_mesh_training_matches_single_device(mesh8, sketch_as_mesh):
    sketch_as_mesh(8)  # both sides under the mesh session's cuts
    X, y = _friedman(1024)
    dtrain = DataMatrix(X, labels=y)
    params = {"max_depth": 4, "eta": 0.3, "seed": 3}
    single = train(params, dtrain, num_boost_round=5)
    sharded = train(params, dtrain, num_boost_round=5, mesh=mesh8)
    # same greedy algorithm over the same (psum-combined) histograms ->
    # identical trees up to float-sum ordering
    p1, p2 = single.predict(X), sharded.predict(X)
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-4)


@pytest.mark.multichip
def test_mesh_training_unpadded_rowcount(mesh8):
    # 1003 rows does not divide 8: exercises zero-weight padding
    X, y = _friedman(1003)
    dtrain = DataMatrix(X, labels=y)
    forest = train({"max_depth": 4, "eta": 0.3}, dtrain, num_boost_round=15, mesh=mesh8)
    rmse = eval_metric("rmse", forest.predict(X), y)
    base = float(np.sqrt(np.mean((y - y.mean()) ** 2)))
    assert rmse < 0.3 * base


@pytest.mark.multichip
def test_mesh_binary_with_eval_set(mesh8):
    rng = np.random.RandomState(1)
    X = rng.randn(1600, 4).astype(np.float32)
    y = ((X[:, 0] + X[:, 1]) > 0).astype(np.float32)
    dtrain = DataMatrix(X[:1200], labels=y[:1200])
    dval = DataMatrix(X[1200:], labels=y[1200:])
    log = {}

    class Recorder:
        def after_iteration(self, model, epoch, evals_log):
            log.update({k: dict(v) for k, v in evals_log.items()})
            return False

    train(
        {"objective": "binary:logistic", "max_depth": 4},
        dtrain,
        num_boost_round=10,
        evals=[(dtrain, "train"), (dval, "validation")],
        callbacks=[Recorder()],
        mesh=mesh8,
    )
    assert log["validation"]["logloss"][-1] < log["validation"]["logloss"][0]


def test_ranking_pairwise_learns():
    rng = np.random.RandomState(2)
    n_groups, group_size = 60, 12
    X = rng.randn(n_groups * group_size, 4).astype(np.float32)
    relevance = (X[:, 0] > 0.5).astype(np.float32) + (X[:, 1] > 0).astype(np.float32)
    groups = np.full(n_groups, group_size, np.int32)
    dtrain = DataMatrix(X, labels=relevance, groups=groups)
    forest = train(
        {"objective": "rank:pairwise", "max_depth": 4, "eta": 0.3},
        dtrain,
        num_boost_round=20,
    )
    preds = forest.predict(X)
    ndcg = eval_metric("ndcg", preds, relevance, groups=groups)
    random_ndcg = eval_metric("ndcg", rng.randn(len(preds)), relevance, groups=groups)
    assert ndcg > 0.95 and ndcg > random_ndcg + 0.05


def test_ranking_ndcg_weighting():
    rng = np.random.RandomState(3)
    n_groups, group_size = 40, 10
    X = rng.randn(n_groups * group_size, 3).astype(np.float32)
    relevance = np.clip(np.round(X[:, 0] * 1.5 + 1.5), 0, 4).astype(np.float32)
    groups = np.full(n_groups, group_size, np.int32)
    dtrain = DataMatrix(X, labels=relevance, groups=groups)
    forest = train(
        {"objective": "rank:ndcg", "max_depth": 3, "eta": 0.3},
        dtrain,
        num_boost_round=15,
        evals=[(dtrain, "train")],
    )
    ndcg = eval_metric("ndcg", forest.predict(X), relevance, groups=groups)
    assert ndcg > 0.9


def test_num_parallel_tree_random_forest_round():
    X, y = _friedman(800)
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {
            "max_depth": 5,
            "num_parallel_tree": 8,
            "subsample": 0.8,
            "colsample_bytree": 0.8,
            "eta": 1.0,
        },
        dtrain,
        num_boost_round=1,
    )
    assert len(forest.trees) == 8
    assert forest.num_boosted_rounds == 1
    rmse = eval_metric("rmse", forest.predict(X), y)
    base = float(np.sqrt(np.mean((y - y.mean()) ** 2)))
    assert rmse < 0.5 * base
    # boosted-forest mode stays stable over multiple rounds too
    boosted = train(
        {"max_depth": 4, "num_parallel_tree": 4, "subsample": 0.8, "eta": 0.5},
        dtrain,
        num_boost_round=5,
    )
    assert eval_metric("rmse", boosted.predict(X), y) < 0.4 * base


def test_num_parallel_tree_multiclass():
    """Lifted r2 parity hole: num_parallel_tree x multi-class. Layout contract: P trees per class per round, committed
    class-major with tree_info carrying the class id (xgboost gbtree
    layout); the bagged round must learn."""
    rng = np.random.RandomState(3)
    n, C, PT = 900, 3, 4
    X = rng.randn(n, 5).astype(np.float32)
    y = ((X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)).astype(
        np.float32
    )
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {
            "objective": "multi:softprob",
            "num_class": C,
            "max_depth": 4,
            "num_parallel_tree": PT,
            "subsample": 0.8,
            "eta": 0.7,
        },
        dtrain,
        num_boost_round=3,
    )
    assert len(forest.trees) == 3 * C * PT
    assert forest.num_boosted_rounds == 3
    # class-major within a round: [c0 x PT, c1 x PT, c2 x PT]
    round0_info = forest.tree_info[: C * PT]
    assert round0_info == [c for c in range(C) for _ in range(PT)]
    acc = float(np.mean(np.argmax(np.asarray(forest.predict(X)), axis=1) == y))
    assert acc > 0.85, acc
    # eval-margin path (device metrics / watchlist) survives the P x C stack
    forest2 = train(
        {
            "objective": "multi:softmax",
            "num_class": C,
            "max_depth": 3,
            "num_parallel_tree": 2,
            "eval_metric": "merror",
        },
        dtrain,
        num_boost_round=2,
        evals=[(dtrain, "train")],
    )
    assert float(np.mean(np.asarray(forest2.predict(X)) == y)) > 0.7


def test_lossguide_colsample_bylevel():
    """Lifted r2 parity hole: lossguide x colsample_bylevel. The per-depth Bernoulli mask must actually constrain
    split choices (aggressive setting changes trees), training must still
    learn, and the same seed must reproduce identical trees."""
    X, y = _friedman(900)
    dtrain = DataMatrix(X, labels=y)
    base_params = {
        "grow_policy": "lossguide",
        "max_leaves": 16,
        "max_depth": 0,
        "seed": 11,
        "eta": 0.3,
    }
    full = train(dict(base_params), dtrain, num_boost_round=4)
    narrow = train(
        dict(base_params, colsample_bylevel=0.25), dtrain, num_boost_round=4
    )
    f_full = np.concatenate([t.feature[~t.is_leaf] for t in full.trees])
    f_narrow = np.concatenate([t.feature[~t.is_leaf] for t in narrow.trees])
    assert f_full.shape != f_narrow.shape or not np.array_equal(
        f_full, f_narrow
    ), "colsample_bylevel had no effect on lossguide trees"

    again = train(
        dict(base_params, colsample_bylevel=0.25), dtrain, num_boost_round=4
    )
    for ta, tb in zip(narrow.trees, again.trees):
        np.testing.assert_array_equal(ta.feature, tb.feature)
        np.testing.assert_allclose(ta.value, tb.value, atol=1e-6)

    learns = train(
        dict(base_params, colsample_bylevel=0.6), dtrain, num_boost_round=20
    )
    rmse = eval_metric("rmse", learns.predict(X), y)
    base = float(np.sqrt(np.mean((y - y.mean()) ** 2)))
    assert rmse < 0.35 * base


def _paths_within_sets(tree, sets):
    """Walk root->leaf; every path's split features must fit one set."""
    stack = [(0, frozenset())]
    while stack:
        node, used = stack.pop()
        if tree.left[node] < 0:
            if used and not any(used <= s for s in sets):
                return False
            continue
        used2 = used | {int(tree.feature[node])}
        stack.append((int(tree.left[node]), used2))
        stack.append((int(tree.right[node]), used2))
    return True


@pytest.mark.multichip
def test_lossguide_2d_mesh_matches_single_device(sketch_as_mesh):
    """r3 parity lift (ADVICE medium): lossguide growth on a
    (data x feature) mesh — candidate-store combine across column shards +
    owner/psum row routing — must reproduce the single-device trees, with
    and without colsample draws."""
    sketch_as_mesh(4)  # both sides under the mesh session's cuts
    from jax.sharding import Mesh as JMesh

    X, y = _friedman(768)  # d = 5 pads to 6 across 2 feature shards
    dtrain = DataMatrix(X, labels=y)
    params = {
        "grow_policy": "lossguide",
        "max_leaves": 12,
        "max_depth": 0,
        "eta": 0.3,
        "seed": 7,
    }
    single = train(dict(params), dtrain, num_boost_round=5)
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh2d = JMesh(devices, axis_names=("data", "feature"))
    sharded = train(dict(params), dtrain, num_boost_round=5, mesh=mesh2d)
    np.testing.assert_allclose(
        single.predict(X), sharded.predict(X), rtol=1e-4, atol=1e-4
    )
    # colsample draws ride the replicated global rng stream: identical trees
    p2 = dict(params, colsample_bylevel=0.6, colsample_bynode=0.8, seed=9)
    single2 = train(dict(p2), dtrain, num_boost_round=4)
    sharded2 = train(dict(p2), dtrain, num_boost_round=4, mesh=mesh2d)
    np.testing.assert_allclose(
        single2.predict(X), sharded2.predict(X), rtol=1e-4, atol=1e-4
    )


def test_interaction_constraints_lossguide():
    """r3 parity lift: interaction_constraints x lossguide —
    per-leaf alive constraint sets thread through best-first growth; no
    root->leaf path may mix features across sets, and the model still
    learns the learnable part of the signal."""
    rng = np.random.RandomState(11)
    X = rng.rand(1500, 4).astype(np.float32)
    y = (X[:, 0] * X[:, 2] * 10).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {
            "grow_policy": "lossguide",
            "max_leaves": 12,
            "max_depth": 0,
            "interaction_constraints": [[0, 1], [2, 3]],
        },
        dtrain,
        num_boost_round=8,
    )
    sets = [{0, 1}, {2, 3}]
    assert all(_paths_within_sets(t, sets) for t in forest.trees)
    # splits must actually have happened (constraints didn't kill growth)
    assert any((~t.is_leaf).any() for t in forest.trees)


@pytest.mark.multichip
def test_interaction_constraints_lossguide_2d_mesh(sketch_as_mesh):
    """Constraint masks are sliced per column shard: the sharded lossguide
    build must agree with single-device under interaction_constraints."""
    sketch_as_mesh(4)  # both sides under the mesh session's cuts
    from jax.sharding import Mesh as JMesh

    rng = np.random.RandomState(17)
    X = rng.rand(1024, 5).astype(np.float32)
    y = (X[:, 0] * X[:, 2] * 10 + X[:, 4]).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    params = {
        "grow_policy": "lossguide",
        "max_leaves": 10,
        "max_depth": 0,
        "interaction_constraints": [[0, 1], [2, 3], [4]],
        "seed": 3,
    }
    single = train(dict(params), dtrain, num_boost_round=4)
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh2d = JMesh(devices, axis_names=("data", "feature"))
    sharded = train(dict(params), dtrain, num_boost_round=4, mesh=mesh2d)
    np.testing.assert_allclose(
        single.predict(X), sharded.predict(X), rtol=1e-4, atol=1e-4
    )
    sets = [{0, 1}, {2, 3}, {4}]
    assert all(_paths_within_sets(t, sets) for t in sharded.trees)


def test_colsample_bylevel_still_learns():
    X, y = _friedman(800)
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {"max_depth": 4, "colsample_bylevel": 0.6, "seed": 5},
        dtrain,
        num_boost_round=20,
    )
    rmse = eval_metric("rmse", forest.predict(X), y)
    base = float(np.sqrt(np.mean((y - y.mean()) ** 2)))
    assert rmse < 0.3 * base


def test_max_depth_zero_rejected():
    from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc

    X, y = _friedman(100)
    with pytest.raises(exc.UserError, match="max_depth"):
        train({"max_depth": 0}, DataMatrix(X, labels=y), num_boost_round=1)


# ---------------------------------------------------------------------------
# Cluster lifecycle (the reference's multi-process localhost trick)
# ---------------------------------------------------------------------------


def test_cluster_synchronize_multiprocess():
    import multiprocessing as mp

    hosts = ["127.0.0.1", "localhost"]

    from tests.util_ports import free_port
    from tests.util_cluster import sync_worker as worker

    port = free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [ctx.Process(target=worker, args=(h, q, port)) for h in hosts]
    for p in procs:
        p.start()
    results = dict(q.get(timeout=60) for _ in hosts)
    for p in procs:
        p.join(timeout=60)
    assert results["127.0.0.1"] == results["localhost"]
    flags = {m["host"]: m["include_in_training"] for m in results["localhost"]}
    assert flags == {"127.0.0.1": True, "localhost": False}


def test_interaction_constraints_enforced():
    rng = np.random.RandomState(11)
    X = rng.rand(1500, 4).astype(np.float32)
    # signal mixes features 0 and 2 multiplicatively; constraints forbid
    # {0,1} x {2,3} interaction, so no path may use both 0 and 2
    y = (X[:, 0] * X[:, 2] * 10).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {
            "max_depth": 4,
            "tree_method": "hist",
            "interaction_constraints": [[0, 1], [2, 3]],
        },
        dtrain,
        num_boost_round=8,
    )

    def paths_ok(tree):
        # walk root->leaf collecting split features; each path must stay
        # within one constraint set
        sets = [{0, 1}, {2, 3}]
        stack = [(0, frozenset())]
        while stack:
            node, used = stack.pop()
            if tree.left[node] < 0:
                if used and not any(used <= s for s in sets):
                    return False
                continue
            used2 = used | {int(tree.feature[node])}
            stack.append((int(tree.left[node]), used2))
            stack.append((int(tree.right[node]), used2))
        return True

    assert all(paths_ok(t) for t in forest.trees)


@pytest.mark.multichip
def test_two_process_jax_distributed_training():
    """Two OS processes x two virtual CPU devices = a 4-device 'pod': each
    process holds half the rows, the psum inside the round step combines
    histograms globally, and both processes produce identical trees."""
    import multiprocessing as mp

    from tests.util_multiprocess import distributed_train_worker
    from tests.util_ports import free_port

    port = free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=distributed_train_worker, args=(r, 2, port, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    results = {}
    for _ in range(2):
        rank, preds = q.get(timeout=300)
        results[rank] = preds
    for p in procs:
        p.join(timeout=60)
        assert p.exitcode == 0
    np.testing.assert_allclose(results[0], results[1], rtol=1e-5, atol=1e-6)
    # the model actually learned from the COMBINED data
    assert np.std(results[0]) > 0.1


def test_ranking_group_chunking_equivalence():
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops.ranking import (
        build_group_layout,
        lambdarank_grad_hess,
        with_slot_columns,
    )

    rng = np.random.RandomState(7)
    n_groups, m = 20, 6
    margins = jnp.asarray(rng.randn(n_groups * m).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, 3, n_groups * m).astype(np.float32))
    weights = jnp.asarray(np.ones(n_groups * m, np.float32))
    idx = with_slot_columns(build_group_layout(np.full(n_groups, m)), labels, weights)
    width = idx.indices[0].shape[1]
    # a budget of four groups' pair tensors a step against one step for all
    g1, h1 = lambdarank_grad_hess(
        margins, idx, "ndcg", pair_slots_per_step=4 * width * width
    )
    g2, h2 = lambdarank_grad_hess(margins, idx, "ndcg")
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-5, atol=1e-6)


@pytest.mark.multichip
def test_2d_mesh_feature_axis_tree_build():
    """(data x feature) 2D mesh: column-sharded histogram build + split
    combination produces the identical tree as a single device (the
    reference's dsplit=col, done as SPMD)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from sagemaker_xgboost_container_tpu.data.binning import (
        apply_cut_points,
        compute_cut_points,
    )
    from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree, pack_tree

    from jax import shard_map

    rng = np.random.RandomState(0)
    n, d, max_bin = 512, 8, 32
    X = rng.rand(n, d).astype(np.float32)
    y = (3 * X[:, 5] + np.sin(6 * X[:, 2]) + X[:, 0] * X[:, 1]).astype(np.float32)
    grad = (y - y.mean()).astype(np.float32)
    hess = np.ones(n, np.float32)
    cuts = compute_cut_points(X, None, max_bin)
    bins = apply_cut_points(X, cuts, max_bin).astype(np.int32)
    num_cuts = np.asarray([len(c) for c in cuts], np.int32)
    B = max_bin + 1

    kwargs = dict(max_depth=3, num_bins=B, reg_lambda=1.0, eta=0.3)

    ref_tree, ref_out = build_tree(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(num_cuts), **kwargs
    )

    devices = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devices, axis_names=("data", "feature"))

    def build(b, g, h, nc):
        tree, row_out = build_tree(
            b, g, h, nc, axis_name="data", feature_axis_name="feature", **kwargs
        )
        return pack_tree(tree), row_out

    mapped = shard_map(
        build,
        mesh=mesh,
        in_specs=(P("data", "feature"), P("data"), P("data"), P("feature")),
        out_specs=(P(), P("data")),
        check_vma=False,
    )
    packed, row_out = mapped(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(num_cuts)
    )
    from sagemaker_xgboost_container_tpu.ops.tree_build import unpack_tree

    got = unpack_tree(np.asarray(packed))
    want = {k: np.asarray(v) for k, v in ref_tree.items()}
    np.testing.assert_array_equal(got["feature"], want["feature"])
    np.testing.assert_array_equal(got["bin"], want["bin"])
    np.testing.assert_array_equal(got["is_leaf"], want["is_leaf"])
    np.testing.assert_allclose(got["leaf_value"], want["leaf_value"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(row_out), np.asarray(ref_out), rtol=1e-5, atol=1e-6)


@pytest.mark.multichip
def test_train_api_2d_mesh(sketch_as_mesh):
    """train() on a (data x feature) 2D mesh matches single-device output,
    including column padding when d doesn't divide the feature shards."""
    sketch_as_mesh(4)  # both sides under the mesh session's cuts
    from jax.sharding import Mesh as JMesh

    X, y = _friedman(512)  # d = 5, feature shards = 2 -> pads to 6
    dtrain = DataMatrix(X, labels=y)
    params = {"max_depth": 4, "eta": 0.3, "seed": 11}
    single = train(params, dtrain, num_boost_round=5)
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh2d = JMesh(devices, axis_names=("data", "feature"))
    sharded = train(params, dtrain, num_boost_round=5, mesh=mesh2d)
    np.testing.assert_allclose(
        single.predict(X), sharded.predict(X), rtol=1e-4, atol=1e-4
    )


@pytest.mark.multichip
def test_mesh_k_batching_no_evals(mesh8, sketch_as_mesh):
    """mesh + _rounds_per_dispatch>1 without eval sets (spec-structure path)."""
    sketch_as_mesh(8)  # both sides under the mesh session's cuts
    X, y = _friedman(1024)
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {"max_depth": 3, "eta": 0.3, "seed": 12, "_rounds_per_dispatch": 3},
        dtrain,
        num_boost_round=6,
        mesh=mesh8,
    )
    assert forest.num_boosted_rounds == 6
    single = train(
        {"max_depth": 3, "eta": 0.3, "seed": 12}, dtrain, num_boost_round=6
    )
    np.testing.assert_allclose(
        forest.predict(X), single.predict(X), rtol=1e-4, atol=1e-4
    )


def test_colsample_bynode_still_learns():
    X, y = _friedman(900)
    dtrain = DataMatrix(X, labels=y)
    for extra in ({}, {"grow_policy": "lossguide", "max_leaves": 16, "max_depth": 0}):
        params = {"max_depth": 4, "colsample_bynode": 0.6, "seed": 13}
        params.update(extra)
        forest = train(params, dtrain, num_boost_round=20)
        base = float(np.sqrt(np.mean((y - y.mean()) ** 2)))
        rmse = eval_metric("rmse", forest.predict(X), y)
        assert rmse < 0.35 * base, (extra, rmse, base)


@pytest.mark.multichip
def test_mesh_k_batching_metrics_match_k1(mesh8):
    """On a mesh, K=10 device-metric lines must equal the
    K=1 host-evaluated lines (psum-able partial stats make batched metrics
    globally exact — reference semantics distributed.py:219)."""
    rng = np.random.RandomState(5)
    X = rng.randn(1600, 5).astype(np.float32)
    y = ((X[:, 0] * X[:, 1] + X[:, 2]) > 0).astype(np.float32)
    dtrain = DataMatrix(X[:1200], labels=y[:1200])
    dval = DataMatrix(X[1200:], labels=y[1200:])

    def run(extra):
        log = {}

        class Recorder:
            def after_iteration(self, model, epoch, evals_log):
                log.update(
                    {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
                )
                return False

        params = {
            "objective": "binary:logistic",
            "max_depth": 4,
            "seed": 9,
            "eval_metric": ["logloss", "auc", "error"],
        }
        params.update(extra)
        train(
            params,
            dtrain,
            num_boost_round=10,
            evals=[(dtrain, "train"), (dval, "validation")],
            callbacks=[Recorder()],
            mesh=mesh8,
        )
        return log

    k1 = run({})
    k10 = run({"_rounds_per_dispatch": 10})
    for ds in ("train", "validation"):
        for metric in ("logloss", "error"):
            # decomposable metrics are globally exact under psum: the K=10
            # device lines equal the K=1 host-evaluated lines
            np.testing.assert_allclose(
                k10[ds][metric], k1[ds][metric], rtol=2e-4, atol=2e-5,
                err_msg=f"{ds}/{metric}",
            )
        # AUC on a mesh follows xgboost's distributed semantics (pair-
        # weighted average of per-shard AUCs — device_metrics.py docstring):
        # identical on every host, but a slightly different estimator than
        # the single-machine global AUC, noticeably so on tiny shards
        # (validation here is 50 rows/shard)
        np.testing.assert_allclose(
            k10[ds]["auc"], k1[ds]["auc"], atol=2e-2, err_msg=f"{ds}/auc"
        )


@pytest.mark.multichip
def test_mesh_k_batching_matches_single_device_rmse(mesh8, sketch_as_mesh):
    """K-batched mesh run vs plain single-device run: same trees, same
    device-metric values (rmse decomposes exactly across shards)."""
    sketch_as_mesh(8)  # both sides under the mesh session's cuts
    X, y = _friedman(1280)
    dtrain = DataMatrix(X, labels=y)

    def run(mesh, extra):
        log = {}

        class Recorder:
            def after_iteration(self, model, epoch, evals_log):
                log.update(
                    {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
                )
                return False

        params = {"max_depth": 4, "eta": 0.3, "seed": 3}
        params.update(extra)
        forest = train(
            params, dtrain, num_boost_round=6,
            evals=[(dtrain, "train")], callbacks=[Recorder()], mesh=mesh,
        )
        return forest, log

    _, single_log = run(None, {})
    forest, mesh_log = run(mesh8, {"_rounds_per_dispatch": 6})
    np.testing.assert_allclose(
        mesh_log["train"]["rmse"], single_log["train"]["rmse"], rtol=2e-4, atol=2e-5
    )


@pytest.mark.multichip
def test_host_loss_aborts_survivors():
    """Mid-train host loss: there is no rejoin
    analog of the reference tracker's `recover` path — when a host dies the
    surviving host must FAIL within ~heartbeat_timeout (never hang in the
    histogram psum, never finish on partial data). Recovery is restart +
    checkpoint resume, covered by test_resume_from_checkpoint."""
    import multiprocessing as mp
    import queue as queue_mod
    import time

    from tests.util_multiprocess import host_loss_worker
    from tests.util_ports import free_port

    port = free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=host_loss_worker, args=(r, 2, port, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    try:
        events = []
        deadline = time.monotonic() + 300
        # started x2, then rank 1's "died"
        while len(events) < 3 and time.monotonic() < deadline:
            try:
                events.append(q.get(timeout=5))
            except queue_mod.Empty:
                continue
        assert ("died", 1, 2) in events, events
        # the survivor must terminate on its own (heartbeat 10s + margin)
        procs[0].join(timeout=180)
        assert procs[0].exitcode is not None, "survivor hung after host loss"
        assert procs[0].exitcode != 0, "survivor must fail, not succeed"
        while True:
            try:
                events.append(q.get_nowait())
            except queue_mod.Empty:
                break
        assert not any(e[0] == "completed" for e in events), events
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
            p.join(timeout=30)


def test_two_process_global_metrics_exact():
    """Metric lines in a 2-process pod: identical on every host AND equal to
    the single-device run over the combined data (reference bar:
    distributed.py:219 allreduces metrics under the communicator)."""
    import multiprocessing as mp

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from tests.util_multiprocess import distributed_metrics_worker
    from tests.util_ports import free_port

    port = free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=distributed_metrics_worker, args=(r, 2, port, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    got = {}
    for _ in range(2):
        rank, dev_log, host_log, check = q.get(timeout=300)
        got[rank] = (dev_log, host_log, check)
    for p in procs:
        p.join(timeout=60)
        assert p.exitcode == 0

    # both hosts: identical lines, both paths
    for key in ("train", "validation"):
        for metric in ("logloss", "error"):
            np.testing.assert_allclose(
                got[0][0][key][metric], got[1][0][key][metric], rtol=1e-6,
                err_msg=f"device {key}/{metric}",
            )
    for metric in ("logloss", "error", "myacc"):
        np.testing.assert_allclose(
            got[0][1]["train"][metric], got[1][1]["train"][metric], rtol=1e-6,
            err_msg=f"host {metric}",
        )

    # the last device line must equal the metric recomputed host-side from
    # the final model over the FULL (combined) datasets — global exactness,
    # not per-host values
    check = got[0][2]
    np.testing.assert_allclose(
        got[0][1]["train"]["logloss"][-1], check["host3_logloss"],
        rtol=2e-4, atol=2e-5, err_msg="mixed-watchlist logloss exactness",
    )
    for key in ("train", "validation"):
        np.testing.assert_allclose(
            got[0][0][key]["logloss"][-1], check[key + "_logloss"],
            rtol=2e-4, atol=2e-5, err_msg=f"global {key}/logloss",
        )
        np.testing.assert_allclose(
            got[0][0][key]["error"][-1], check[key + "_error"],
            rtol=2e-4, atol=2e-5, err_msg=f"global {key}/error",
        )


def test_two_process_cox_watchlist_exact():
    """r3 parity lift: survival:cox + watchlist in a 2-process
    pod — previously a UserError. cox-nloglik lines must be identical on
    both hosts and equal to the global metric of the final model over the
    combined rows, on both the device-scan and host-evaluate paths."""
    import multiprocessing as mp

    from tests.util_multiprocess import cox_metrics_worker
    from tests.util_ports import free_port

    port = free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=cox_metrics_worker, args=(r, 2, port, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    got = {}
    for _ in range(2):
        rank, dev_log, host_log, check = q.get(timeout=300)
        got[rank] = (dev_log, host_log, check)
    for p in procs:
        p.join(timeout=60)
        assert p.exitcode == 0

    for key in ("train", "validation"):
        np.testing.assert_allclose(
            got[0][0][key]["cox-nloglik"], got[1][0][key]["cox-nloglik"],
            rtol=1e-6, err_msg=f"device {key} lines must agree across hosts",
        )
        np.testing.assert_allclose(
            got[0][1][key]["cox-nloglik"], got[1][1][key]["cox-nloglik"],
            rtol=1e-6, err_msg=f"host {key} lines must agree across hosts",
        )
    check = got[0][2]
    np.testing.assert_allclose(
        got[0][0]["train"]["cox-nloglik"][-1], check["train_cox"],
        rtol=5e-4, atol=1e-5, err_msg="device-path global exactness",
    )
    np.testing.assert_allclose(
        got[0][0]["validation"]["cox-nloglik"][-1], check["val_cox"],
        rtol=5e-4, atol=1e-5, err_msg="device-path eval-set exactness (uneven)",
    )
    np.testing.assert_allclose(
        got[0][1]["train"]["cox-nloglik"][-1], check["host3_cox"],
        rtol=5e-4, atol=1e-5, err_msg="host-path global exactness",
    )
    np.testing.assert_allclose(
        got[0][1]["validation"]["cox-nloglik"][-1], check["host3_val_cox"],
        rtol=5e-4, atol=1e-5, err_msg="host-path eval-set exactness (uneven)",
    )


def test_two_process_gblinear_training():
    """r4 parity lift: booster=gblinear trains across processes (psum'd
    coordinate-descent statistics, uneven 301/299 shards) — previously a
    UserError. Both hosts must produce identical predictions and identical
    watchlist lines, matching a single-device oracle on the combined data."""
    import multiprocessing as mp

    from tests.util_multiprocess import gblinear_worker
    from tests.util_ports import free_port

    port = free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=gblinear_worker, args=(r, 2, port, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    got = {}
    for _ in range(2):
        rank, preds, rmse_lines = q.get(timeout=300)
        got[rank] = (preds, rmse_lines)
    for p in procs:
        p.join(timeout=60)
        assert p.exitcode == 0

    np.testing.assert_allclose(got[0][0], got[1][0], rtol=1e-6)
    np.testing.assert_allclose(got[0][1], got[1][1], rtol=1e-6)

    # single-device oracle over the combined rows (identical data/seed)
    rng = np.random.RandomState(7)
    n = 600
    X = rng.randn(n, 5).astype(np.float32)
    beta = np.asarray([1.0, -2.0, 0.5, 0.0, 3.0], np.float32)
    y = (X @ beta + 0.1 * rng.randn(n)).astype(np.float32)
    oracle = train(
        {"booster": "gblinear", "eta": 0.5, "reg_lambda": 0.1},
        DataMatrix(X, labels=y),
        num_boost_round=20,
    )
    np.testing.assert_allclose(
        got[0][0], np.asarray(oracle.predict(X[:32])), rtol=2e-3, atol=2e-3
    )
    # the rmse lines must descend (training is actually learning)
    assert got[0][1][-1] < got[0][1][0]


def test_two_process_dart_training():
    """r4 parity lift: booster=dart trains across processes (shared-seed
    dropout, GSPMD histogram combines, uneven 401/399 shards) — previously
    a UserError. Hosts must agree on predictions and watchlist lines."""
    import multiprocessing as mp

    from tests.util_multiprocess import dart_worker
    from tests.util_ports import free_port

    port = free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=dart_worker, args=(r, 2, port, q)) for r in range(2)
    ]
    for p in procs:
        p.start()
    got = {}
    for _ in range(2):
        rank, preds, rmse_lines = q.get(timeout=300)
        got[rank] = (preds, rmse_lines)
    for p in procs:
        p.join(timeout=60)
        assert p.exitcode == 0

    np.testing.assert_allclose(got[0][0], got[1][0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[0][1], got[1][1], rtol=1e-6)
    # dropout-regularized training still learns
    assert got[0][1][-1] < got[0][1][0]


def test_two_process_update_refresh():
    """r4 parity lift: process_type=update across processes (per-node stats
    allgather-summed, uneven 251/249 shards) — previously a UserError. Both
    hosts must refresh to identical trees, equal to a single-device update
    over the combined rows."""
    import multiprocessing as mp

    from tests.util_multiprocess import update_worker
    from tests.util_ports import free_port

    port = free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=update_worker, args=(r, 2, port, q)) for r in range(2)
    ]
    for p in procs:
        p.start()
    got = {}
    for _ in range(2):
        rank, preds = q.get(timeout=300)
        got[rank] = preds
    for p in procs:
        p.join(timeout=60)
        assert p.exitcode == 0

    np.testing.assert_allclose(got[0], got[1], rtol=1e-6)

    # single-device oracle over the combined update rows
    rng = np.random.RandomState(9)
    n = 600
    X = rng.rand(n, 4).astype(np.float32)
    y = (3 * X[:, 0] + np.sin(5 * X[:, 1])).astype(np.float32)
    base = train(
        {"max_depth": 4, "eta": 0.3, "seed": 1, "gamma": 0.0},
        DataMatrix(X, labels=y),
        num_boost_round=4,
    )
    X2 = rng.rand(500, 4).astype(np.float32)
    y2 = (3 * X2[:, 0] + np.sin(5 * X2[:, 1])).astype(np.float32)
    oracle = train(
        {
            "max_depth": 4,
            "eta": 0.3,
            "process_type": "update",
            "updater": "refresh,prune",
            "gamma": 0.1,
        },
        DataMatrix(X2, labels=y2),
        num_boost_round=4,
        xgb_model=base,
    )
    np.testing.assert_allclose(
        got[0], np.asarray(oracle.predict(X2[:32])), rtol=1e-4, atol=1e-5
    )


@pytest.mark.multichip
def test_ranking_on_mesh_matches_single_device(mesh8, sketch_as_mesh):
    """rank:ndcg trains on a data mesh — rows sharded BY
    GROUP (groups whole per shard), LambdaMART gradients shard-local, psum'd
    histograms. Must match the single-device trees (reference bar: ranking
    trains under Rabit, hyperparameter_validation.py:283-309)."""
    rng = np.random.RandomState(21)
    n_groups = 64
    sizes = rng.randint(5, 40, n_groups).astype(np.int32)  # uneven groups
    sketch_as_mesh(8, groups=sizes)  # both sides under the mesh session's cuts
    n = int(sizes.sum())
    X = rng.randn(n, 4).astype(np.float32)
    relevance = np.clip(np.round(X[:, 0] * 1.5 + 1.5), 0, 4).astype(np.float32)
    dtrain = DataMatrix(X, labels=relevance, groups=sizes)

    params = {"objective": "rank:ndcg", "max_depth": 3, "eta": 0.3, "seed": 4}
    single = train(params, dtrain, num_boost_round=8)
    sharded = train(params, dtrain, num_boost_round=8, mesh=mesh8)

    p1, p2 = single.predict(X), sharded.predict(X)
    np.testing.assert_allclose(p1, p2, rtol=1e-3, atol=1e-3)

    ndcg = eval_metric("ndcg", p2, relevance, groups=sizes)
    assert ndcg > 0.9

    # eval-set metric lines work through the host path on a mesh too
    log = {}

    class Rec:
        def after_iteration(self, model, epoch, evals_log):
            log.update({k: dict(v) for k, v in evals_log.items()})
            return False

    train(
        {"objective": "rank:pairwise", "max_depth": 3, "eta": 0.3, "seed": 4},
        dtrain, num_boost_round=4,
        evals=[(dtrain, "train")], callbacks=[Rec()], mesh=mesh8,
    )
    assert "train" in log and len(next(iter(log["train"].values()))) == 4


@pytest.mark.multichip
def test_ranking_on_2d_mesh_matches_single_device(sketch_as_mesh):
    """r3 parity lift: rank:ndcg on a (data x feature) mesh —
    the group-partitioned row layout composes with column sharding; trees
    must match single-device."""
    from jax.sharding import Mesh as JMesh

    rng = np.random.RandomState(23)
    n_groups = 48
    sizes = rng.randint(5, 40, n_groups).astype(np.int32)
    sketch_as_mesh(4, groups=sizes)  # both sides under the mesh session's cuts
    n = int(sizes.sum())
    X = rng.randn(n, 5).astype(np.float32)  # d=5 pads to 6 over 2 shards
    relevance = np.clip(np.round(X[:, 0] * 1.5 + 1.5), 0, 4).astype(np.float32)
    dtrain = DataMatrix(X, labels=relevance, groups=sizes)

    params = {"objective": "rank:ndcg", "max_depth": 3, "eta": 0.3, "seed": 4}
    single = train(dict(params), dtrain, num_boost_round=6)
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh2d = JMesh(devices, axis_names=("data", "feature"))
    sharded = train(dict(params), dtrain, num_boost_round=6, mesh=mesh2d)

    p1, p2 = single.predict(X), sharded.predict(X)
    np.testing.assert_allclose(p1, p2, rtol=1e-3, atol=1e-3)
    ndcg = eval_metric("ndcg", p2, relevance, groups=sizes)
    assert ndcg > 0.85, ndcg


@pytest.mark.multichip
def test_mesh_colsample_matches_single_device(mesh8, sketch_as_mesh):
    """colsample feature draws must be replicated across data shards (the
    row-subsample rng is shard-folded, the feature rng must NOT be): with
    subsample=1, a colsample_bylevel/bynode mesh run equals single-device."""
    sketch_as_mesh(8)  # both sides under the mesh session's cuts
    X, y = _friedman(1024, seed=13)
    dtrain = DataMatrix(X, labels=y)
    for extra in ({"colsample_bylevel": 0.6}, {"colsample_bynode": 0.6}):
        params = {"max_depth": 4, "eta": 0.3, "seed": 7}
        params.update(extra)
        single = train(params, dtrain, num_boost_round=4)
        sharded = train(params, dtrain, num_boost_round=4, mesh=mesh8)
        np.testing.assert_allclose(
            single.predict(X), sharded.predict(X), rtol=1e-4, atol=1e-4,
            err_msg=str(extra),
        )


@pytest.mark.multichip
def test_2d_mesh_colsample_monotone_interaction(sketch_as_mesh):
    """The (data x feature) mesh supports colsample /
    monotone / interaction constraints — draws are made over GLOBAL columns
    with the replicated rng, each shard slicing its own segment, so the 2-D
    run equals single-device."""
    sketch_as_mesh(4)  # both sides under the mesh session's cuts
    from jax.sharding import Mesh as JMesh

    X, y = _friedman(512, seed=23)
    dtrain = DataMatrix(X, labels=y)
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh2d = JMesh(devices, axis_names=("data", "feature"))

    for extra in (
        {"colsample_bytree": 0.6},
        {"colsample_bylevel": 0.6},
        {"colsample_bynode": 0.6},
        {"monotone_constraints": [1, 0, 0, 1, 0]},
        {"interaction_constraints": [[0, 1], [2, 3, 4]]},
    ):
        params = {"max_depth": 4, "eta": 0.3, "seed": 11}
        params.update(extra)
        single = train(params, dtrain, num_boost_round=4)
        sharded = train(params, dtrain, num_boost_round=4, mesh=mesh2d)
        np.testing.assert_allclose(
            single.predict(X), sharded.predict(X), rtol=1e-4, atol=1e-4,
            err_msg=str(extra),
        )


@pytest.mark.multichip
def test_2d_mesh_k_batched_metrics():
    """K-batched device metrics on a 2-D mesh: stats psum over 'data' only,
    replicated across 'feature' — lines equal the K=1 run."""
    from jax.sharding import Mesh as JMesh

    X, y = _friedman(512, seed=29)
    dtrain = DataMatrix(X, labels=y)
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh2d = JMesh(devices, axis_names=("data", "feature"))

    def run(extra):
        log = {}

        class Rec:
            def after_iteration(self, model, epoch, evals_log):
                log.update(
                    {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
                )
                return False

        params = {"max_depth": 3, "eta": 0.3, "seed": 2}
        params.update(extra)
        train(params, dtrain, num_boost_round=6,
              evals=[(dtrain, "train")], callbacks=[Rec()], mesh=mesh2d)
        return log

    k1 = run({})
    k6 = run({"_rounds_per_dispatch": 6})
    np.testing.assert_allclose(
        k6["train"]["rmse"], k1["train"]["rmse"], rtol=2e-4, atol=2e-5
    )


@pytest.mark.multichip
def test_two_process_2d_mesh_training():
    """2-process x (2 data x 2 feature) pod: column-sharded split finding
    with colsample/monotone active; both hosts produce identical models and
    the model actually learns."""
    import multiprocessing as mp

    from tests.util_multiprocess import distributed_2d_mesh_worker
    from tests.util_ports import free_port

    port = free_port()
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    procs = [
        ctx.Process(target=distributed_2d_mesh_worker, args=(r, 2, port, q))
        for r in range(2)
    ]
    for p in procs:
        p.start()
    results = {}
    for _ in range(2):
        rank, preds = q.get(timeout=300)
        results[rank] = preds
    for p in procs:
        p.join(timeout=60)
        assert p.exitcode == 0
    np.testing.assert_allclose(results[0], results[1], rtol=1e-5, atol=1e-6)
    assert np.std(results[0]) > 0.1  # learned from combined data


@pytest.mark.multichip
def test_survival_cox_on_mesh_matches_single_device(mesh8, sketch_as_mesh):
    """survival:cox trains on a mesh — global risk sets
    via all_gather inside the jitted round (exact, not per-shard)."""
    sketch_as_mesh(8)  # both sides under the mesh session's cuts
    rng = np.random.RandomState(31)
    n = 1024
    X = rng.rand(n, 4).astype(np.float32)
    hazard = np.exp(0.8 * X[:, 0] - 0.5 * X[:, 1])
    times = rng.exponential(1.0 / hazard).astype(np.float32) + 0.01
    censored = rng.rand(n) < 0.3
    labels = np.where(censored, -times, times).astype(np.float32)
    dtrain = DataMatrix(X, labels=labels)

    params = {"objective": "survival:cox", "max_depth": 3, "eta": 0.3, "seed": 3}
    single = train(params, dtrain, num_boost_round=6)
    sharded = train(params, dtrain, num_boost_round=6, mesh=mesh8)
    np.testing.assert_allclose(
        single.predict(X, output_margin=True),
        sharded.predict(X, output_margin=True),
        rtol=1e-3, atol=1e-3,
    )
    # the model orders risk correctly: higher true hazard -> higher margin
    m = sharded.predict(X, output_margin=True)
    corr = np.corrcoef(m, np.log(hazard))[0, 1]
    assert corr > 0.6, corr


def _cox_data(n=1024, seed=31):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 4).astype(np.float32)
    hazard = np.exp(0.8 * X[:, 0] - 0.5 * X[:, 1])
    times = rng.exponential(1.0 / hazard).astype(np.float32) + 0.01
    censored = rng.rand(n) < 0.3
    labels = np.where(censored, -times, times).astype(np.float32)
    return X, labels


def test_cox_nloglik_device_metric_matches_host():
    """The device cox-nloglik (argsort + cumsum risk sets) must agree with
    the host eval_metrics formulation, including weight-0 padding rows."""
    from sagemaker_xgboost_container_tpu.models.device_metrics import (
        make_device_metric,
    )
    from sagemaker_xgboost_container_tpu.models.eval_metrics import cox_nloglik

    _, labels = _cox_data(400)
    rng = np.random.RandomState(5)
    margins = rng.randn(400).astype(np.float32) * 0.5
    weights = rng.rand(400).astype(np.float32) + 0.5

    dmf = make_device_metric("cox-nloglik", "survival:cox")
    assert dmf is not None and dmf.needs_global_rows
    import jax.numpy as jnp

    got = float(dmf(jnp.asarray(margins), jnp.asarray(labels), jnp.asarray(weights)))
    want = cox_nloglik(np.exp(margins.astype(np.float64)), labels, weights)
    np.testing.assert_allclose(got, want, rtol=2e-4)

    # padding rows (weight 0) must be inert — on the device metric AND the
    # host formula (0 * log(0) NaN hazard, r4 review finding)
    m_pad = np.concatenate([margins, np.ones(37, np.float32)])
    y_pad = np.concatenate([labels, np.zeros(37, np.float32)])
    w_pad = np.concatenate([weights, np.zeros(37, np.float32)])
    got_pad = float(dmf(jnp.asarray(m_pad), jnp.asarray(y_pad), jnp.asarray(w_pad)))
    np.testing.assert_allclose(got_pad, want, rtol=2e-4)
    host_pad = cox_nloglik(np.exp(m_pad.astype(np.float64)), y_pad, w_pad)
    assert np.isfinite(host_pad)
    np.testing.assert_allclose(host_pad, want, rtol=1e-6)


@pytest.mark.multichip
def test_cox_watchlist_on_mesh_k_batched(mesh8):
    """r3 parity lift: survival:cox eval metrics on a mesh with
    K-round batching — the non-decomposable cox-nloglik gathers global rows
    inside the jitted scan; every line must match the host oracle computed
    from the final model on the full dataset."""
    X, labels = _cox_data(900)
    dtrain = DataMatrix(X[:700], labels=labels[:700])
    dval = DataMatrix(X[700:], labels=labels[700:])
    log = {}

    class Recorder:
        def after_iteration(self, model, epoch, evals_log):
            log.update({k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()})
            return False

    params = {
        "objective": "survival:cox",
        "max_depth": 3,
        "eta": 0.3,
        "seed": 3,
        "_rounds_per_dispatch": 3,
    }
    forest = train(
        params,
        dtrain,
        num_boost_round=6,
        evals=[(dtrain, "train"), (dval, "validation")],
        callbacks=[Recorder()],
        mesh=mesh8,
    )
    from sagemaker_xgboost_container_tpu.models.eval_metrics import cox_nloglik

    for tag, (Xf, yf) in (
        ("train", (X[:700], labels[:700])),
        ("validation", (X[700:], labels[700:])),
    ):
        want = cox_nloglik(np.asarray(forest.predict(Xf), np.float64), yf)
        got = log[tag]["cox-nloglik"][-1]
        np.testing.assert_allclose(got, want, rtol=5e-4, atol=1e-5)


@pytest.mark.multichip
def test_gblinear_mesh_matches_single_device(mesh8):
    """gblinear on a data mesh: coordinate-descent sufficient statistics
    psum across shards, so weights match single-device (the reference
    trains gblinear under Rabit with allreduced gradient sums)."""
    rng = np.random.RandomState(0)
    X = rng.randn(1003, 6).astype(np.float32)  # not divisible by 8
    y = (X @ rng.randn(6).astype(np.float32) + 0.1 * rng.randn(1003)).astype(
        np.float32
    )
    params = {
        "booster": "gblinear", "objective": "reg:squarederror",
        "eta": 0.5, "lambda": 1.0, "alpha": 0.1,
    }
    single = train(params, DataMatrix(X, labels=y), num_boost_round=12)
    dist = train(params, DataMatrix(X, labels=y), num_boost_round=12, mesh=mesh8)
    np.testing.assert_allclose(single.weights, dist.weights, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(single.bias, dist.bias, rtol=2e-5, atol=2e-6)


@pytest.mark.multichip
def test_approx_resketch_mesh_matches_single_device(mesh8, sketch_as_mesh):
    """tree_method=approx (r5 per-dispatch re-sketch): the hessian-weighted
    cut refresh is computed from globally identical margins, so a data mesh
    trains the same trees as single-device."""
    sketch_as_mesh(8)  # both sides under the mesh session's cuts
    rng = np.random.RandomState(7)
    X = rng.rand(1003, 5).astype(np.float32)
    y = (np.sin(4 * X[:, 0]) + X[:, 1] * X[:, 2]).astype(np.float32)
    params = {
        "tree_method": "approx", "max_bin": 64, "max_depth": 3,
        "_rounds_per_dispatch": 1,
    }
    single = train(params, DataMatrix(X, labels=y), num_boost_round=5)
    dist = train(params, DataMatrix(X, labels=y), num_boost_round=5, mesh=mesh8)
    np.testing.assert_allclose(
        np.asarray(single.predict(X[:200])),
        np.asarray(dist.predict(X[:200])),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.multichip
def test_gblinear_cox_mesh_matches_single_device(mesh8):
    """r5 guard lift: gblinear × survival:cox on a data mesh. The linear
    round's grad/hess all_gathers the global rows for the risk-set cumsums
    (same recipe as the tree path's cox-on-mesh), so coordinate-descent
    updates match single-device. The reference trains this under Rabit."""
    X, labels = _cox_data(n=1003, seed=17)  # not divisible by 8
    params = {
        "booster": "gblinear", "objective": "survival:cox",
        "eta": 0.5, "lambda": 1.0, "alpha": 0.0, "seed": 3,
    }
    single = train(params, DataMatrix(X, labels=labels), num_boost_round=10)
    dist = train(
        params, DataMatrix(X, labels=labels), num_boost_round=10, mesh=mesh8
    )
    np.testing.assert_allclose(single.weights, dist.weights, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(single.bias, dist.bias, rtol=2e-4, atol=2e-5)
    # the linear model orders risk: higher true hazard -> higher margin
    m = dist.predict(X, output_margin=True)
    hazard = 0.8 * X[:, 0] - 0.5 * X[:, 1]
    assert np.corrcoef(m, hazard)[0, 1] > 0.6


@pytest.mark.multichip
def test_dart_mesh_matches_single_device(mesh8, sketch_as_mesh):
    """dart on a data mesh: the session shards rows; GSPMD partitions the
    dart builder's histogram ops, so dropout/rescale bookkeeping and trees
    match single-device."""
    sketch_as_mesh(8)  # both sides under the mesh session's cuts
    rng = np.random.RandomState(0)
    X = rng.rand(2005, 6).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0.8).astype(np.float32)
    params = {
        "booster": "dart", "objective": "binary:logistic", "max_depth": 4,
        "rate_drop": 0.3, "one_drop": 1, "seed": 7,
    }
    single = train(params, DataMatrix(X, labels=y), num_boost_round=8)
    dist = train(params, DataMatrix(X, labels=y), num_boost_round=8, mesh=mesh8)
    np.testing.assert_allclose(
        np.asarray(single.predict(X[:200])),
        np.asarray(dist.predict(X[:200])),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.multichip
def test_dart_multiclass_mesh_matches_single_device(mesh8, sketch_as_mesh):
    """r5 guard lift: dart × multi:softprob on a data mesh. The per-class
    vmap'd builder runs on row-sharded [n, C] gradients under GSPMD; the
    shared-seed round-unit dropout bookkeeping is host-side and identical,
    so predictions match single-device."""
    sketch_as_mesh(8)  # both sides under the mesh session's cuts
    rng = np.random.RandomState(3)
    X = rng.randn(1203, 5).astype(np.float32)  # not divisible by 8
    y = rng.randint(0, 3, size=1203).astype(np.float32)
    X[:, 1] += 2.5 * y
    params = {
        "booster": "dart", "objective": "multi:softprob", "num_class": 3,
        "max_depth": 3, "rate_drop": 0.3, "one_drop": 1, "seed": 13,
    }
    single = train(params, DataMatrix(X, labels=y), num_boost_round=6)
    dist = train(params, DataMatrix(X, labels=y), num_boost_round=6, mesh=mesh8)
    np.testing.assert_allclose(
        np.asarray(single.predict(X[:200])),
        np.asarray(dist.predict(X[:200])),
        rtol=1e-4, atol=1e-5,
    )


def test_mesh_with_pallas_hist_matches_single_device(sketch_as_mesh):
    """The production TPU configuration is the pallas histogram kernel
    INSIDE shard_map with the data-axis psum — the v5p pod path. It must
    compose (per-device kernel, XLA collective around it) and match the
    single-device flat reference."""
    sketch_as_mesh(8)  # both sides under the mesh session's cuts
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train
    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs
    from jax.sharding import Mesh

    rng = np.random.RandomState(5)
    X = rng.randn(4096, 6).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]) > 0).astype(np.float32)
    d = DataMatrix(X, labels=y)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3}
    mesh = Mesh(np.array(jax.devices()), axis_names=("data",))
    f_mesh = train(
        dict(params), d, num_boost_round=3, mesh=mesh,
        hist_knobs=resolve_hist_knobs()._replace(backend="tpu"),  # the chip's program
    )
    f_flat = train(dict(params), d, num_boost_round=3)
    np.testing.assert_allclose(
        np.asarray(f_mesh.predict(X)),
        np.asarray(f_flat.predict(X)),
        atol=2e-5,
    )


# ------------------------------------------ a chip's share of a `data` mesh
def _click_like(n, seed):
    """Missing-heavy click-log columns (45 %, 77 %, 22 %, none missing;
    spiked counts, a three-valued code) and a gradient near a 3 % click rate
    that leans on whether column 0 is there."""
    rng = np.random.RandomState(seed)
    counts = np.floor(2.0 * (rng.rand(n, 4) ** -0.8 - 1.0))
    X = np.concatenate([counts, rng.randint(0, 3, (n, 1)), rng.rand(n, 1) * 50], axis=1)
    rates = np.asarray([0.45, 0.77, 0.22, 0.0, 0.12, 0.0])
    X = np.where(rng.rand(n, 6) < rates, np.nan, X).astype(np.float32)
    p = 1.0 / (
        1.0 + np.exp(
            3.4 - 1.6 * np.isnan(X[:, 0]) - 1.2 * (np.nan_to_num(X[:, 2]) > 1)
            - 0.9 * (X[:, 4] == 2) + 0.8 * np.isnan(X[:, 1]) - 0.03 * X[:, 5]
        )
    )
    y = (rng.rand(n) < p).astype(np.float32)
    return X, (0.1 - y).astype(np.float32), np.full(n, 0.09, np.float32)


@pytest.mark.multichip
def test_four_row_shares_add_up_to_the_uncut_data_and_grow_its_tree(monkeypatch):
    """What `criteo-tb-d8` leaves out on one chip, at a small size: under the
    cuts four row shares agree on through their allgather, the root and
    level-1 histograms and node totals of the shares sum to those of the
    uncut data (to 1e-4 of the largest entry: the histogram's gradient operand
    is two bfloat16 halves, 2^-16 a term, and the shares sum in another order), and `build_tree` over a `data` mesh of 4 returns the
    one-device tree, default directions included."""
    import jax.numpy as jnp
    from jax import shard_map
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    from sagemaker_xgboost_container_tpu.data.binning import apply_cut_points
    from sagemaker_xgboost_container_tpu.models import booster
    from sagemaker_xgboost_container_tpu.ops.histogram import level_histogram, node_totals
    from sagemaker_xgboost_container_tpu.ops.split import find_best_splits
    from sagemaker_xgboost_container_tpu.ops.tree_build import (
        build_tree, pack_tree, unpack_tree,
    )

    from functools import partial

    from sagemaker_xgboost_container_tpu.data.binning import sketch_shards

    shares, max_bin = 4, 32
    n = shares * 1024
    X, grad, hess = _click_like(n, seed=5)
    parts = [slice(s * n // shares, (s + 1) * n // shares) for s in range(shares)]

    def merged_cuts(part):
        """One process's share sketched and merged with everybody's candidates."""
        return sketch_shards(
            [X[part]], [None], max_bin,
            merge=partial(booster._merge_cuts_across_processes, max_bin=max_bin),
        )

    # every process's candidates, as the allgather would bring them
    gathered = {}
    monkeypatch.setattr(
        multihost_utils, "process_allgather", lambda x: np.asarray(x)[None]
    )
    for part in parts:
        merged_cuts(part)  # warm the shapes
    local = []

    def record(x):
        local.append(np.asarray(x))
        return np.asarray(x)[None]

    monkeypatch.setattr(multihost_utils, "process_allgather", record)
    for part in parts:
        merged_cuts(part)
    mats, counts = local[0::2], local[1::2]
    monkeypatch.setattr(
        multihost_utils,
        "process_allgather",
        lambda x: np.stack(mats if np.asarray(x).ndim == 2 else counts),
    )
    merged = [merged_cuts(part) for part in parts]
    for other in merged[1:]:  # every share agrees on the cuts, to the bit
        assert all(a.tobytes() == b.tobytes() for a, b in zip(merged[0], other))
    cuts = merged[0]
    assert all(len(c) <= max_bin - 1 for c in cuts)

    bins = apply_cut_points(X, cuts, max_bin).astype(np.int32)
    assert abs((bins[:, 1] == max_bin).mean() - 0.77) < 0.03
    num_cuts = np.asarray([len(c) for c in cuts], np.int32)
    B = max_bin + 1

    def level(rows, node_local, width):
        G, H = level_histogram(
            jnp.asarray(bins[rows]), jnp.asarray(grad[rows]), jnp.asarray(hess[rows]),
            jnp.asarray(node_local[rows]), width, B,
        )
        g, h = node_totals(
            jnp.asarray(grad[rows]), jnp.asarray(hess[rows]), jnp.asarray(node_local[rows]), width
        )
        return [np.asarray(a, np.float64) for a in (G, H, g, h)]

    everything = slice(0, n)
    root = np.zeros(n, np.int32)
    whole = level(everything, root, 1)
    split = find_best_splits(
        jnp.asarray(whole[0], jnp.float32), jnp.asarray(whole[1], jnp.float32),
        jnp.asarray(num_cuts), reg_lambda=1.0, gamma=0.0, min_child_weight=1.0,
    )
    f, b, left = int(split["feature"][0]), int(split["bin"][0]), bool(split["default_left"][0])
    col = bins[:, f]
    level1 = np.where(col == max_bin, not left, col > b).astype(np.int32)
    for node_local, width in ((root, 1), (level1, 2)):
        want = level(everything, node_local, width)
        got = [sum(x) for x in zip(*(level(part, node_local, width) for part in parts))]
        for a, w in zip(got, want):
            np.testing.assert_allclose(a, w, rtol=0, atol=1e-4 * np.abs(w).max())
    assert want[1][:, :, max_bin].sum() > 0.2 * want[1].sum() / 6  # a live missing bin

    kwargs = dict(max_depth=3, num_bins=B, reg_lambda=1.0, eta=0.1)
    ref_tree, ref_out = jax.jit(lambda *a: build_tree(*a, **kwargs))(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(num_cuts)
    )
    mesh = Mesh(np.array(jax.devices()[:shares]), axis_names=("data",))

    def build(bn, g, h, nc):
        tree, row_out = build_tree(bn, g, h, nc, axis_name="data", **kwargs)
        return pack_tree(tree), row_out

    packed, row_out = jax.jit(shard_map(
        build, mesh=mesh, in_specs=(P("data", None), P("data"), P("data"), P()),
        out_specs=(P(), P("data")), check_vma=False,
    ))(jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(num_cuts))
    got = unpack_tree(np.asarray(packed))
    want = {k: np.asarray(v) for k, v in ref_tree.items()}
    for key in ("feature", "bin", "is_leaf", "default_left"):
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    assert want["default_left"][~want["is_leaf"]].any()
    assert (~want["default_left"][~want["is_leaf"]]).any()
    np.testing.assert_allclose(got["leaf_value"], want["leaf_value"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(row_out), np.asarray(ref_out), rtol=1e-5, atol=1e-6)


def test_sixteen_row_shares_add_up_to_the_uncut_histograms_class_by_class():
    """What `mnist8m-mc10` leaves out on one chip, at a small size: the
    deployment divides the rows over a `data` mesh of 16 and all-reduces the
    level histograms. Under one set of cuts, the root and level-1 histograms
    and node totals of the 16 shares, built for the ten classes side by side
    as the round builds them (`vmap` over the class axis of the softmax
    gradients), sum to the uncut matrix's, class by class (to 1e-4 of a
    class's largest entry: the shares sum in another order)."""
    import jax.numpy as jnp

    from benchmark.datagen import mnist8m_like
    from benchmark.reference import gbt_reference
    from sagemaker_xgboost_container_tpu.data.binning import (
        apply_cut_points, compute_cut_points,
    )
    from sagemaker_xgboost_container_tpu.ops.histogram import level_histogram, node_totals

    shares, classes, max_bin = 16, 10, 256
    n = shares * 192
    config = {"train_rows": n, "validation_rows": 8, "num_feature": 784,
              "params": {"num_class": classes}}
    x, y = mnist8m_like.make(config, 2**31 + 16)["train"]
    cuts = compute_cut_points(x, None, max_bin)
    bins = apply_cut_points(x, cuts, max_bin).astype(np.int32)
    # margins of a later round, so that the classes' gradients differ by row
    rng = np.random.RandomState(16)
    margin = 0.5 + 0.8 * rng.randn(n, classes) + 1.5 * (y[:, None] == np.arange(classes))
    g, h = (
        a.astype(np.float32)
        for a in gbt_reference.grad_hess("multi:softmax", margin, y.astype(np.float64))
    )
    lit = int(np.argmax((bins > 0).mean(axis=0) * (bins == 0).mean(axis=0)))
    level1 = (bins[:, lit] > 0).astype(np.int32)  # a pixel lit or dark
    assert 0.2 < level1.mean() < 0.8
    parts = [slice(s * n // shares, (s + 1) * n // shares) for s in range(shares)]

    def level(rows, node_local, width):
        def one(gc, hc):
            G, H = level_histogram(
                jnp.asarray(bins[rows]), gc, hc, jnp.asarray(node_local[rows]), width, max_bin + 1
            )
            return (G, H) + tuple(node_totals(gc, hc, jnp.asarray(node_local[rows]), width))

        out = jax.vmap(one)(jnp.asarray(g[rows].T), jnp.asarray(h[rows].T))
        return [np.asarray(a, np.float64) for a in out]

    for node_local, width in ((np.zeros(n, np.int32), 1), (level1, 2)):
        want = level(slice(0, n), node_local, width)
        got = [sum(a) for a in zip(*(level(part, node_local, width) for part in parts))]
        assert want[0].shape == (classes, width, 784, max_bin + 1)
        for a, w in zip(got, want):
            for c in range(classes):
                np.testing.assert_allclose(a[c], w[c], rtol=0, atol=1e-4 * np.abs(w[c]).max())
        # every class has a histogram of its own: the hessians differ by class
        assert len({round(float(want[1][c].sum()), 3) for c in range(classes)}) == classes


# ------------------------------------------------------ set-up shard by shard
def _mesh4():
    return Mesh(np.array(jax.devices()[:4]), axis_names=("data",))


def _mesh_session(X, y, mesh, params=None, evals=(), weights=None):
    from sagemaker_xgboost_container_tpu.models.booster import TrainConfig, _TrainingSession
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    cfg = TrainConfig(dict({"objective": "binary:logistic", "max_depth": 3, "max_bin": 32},
                           **(params or {})))
    forest = Forest(objective_name=cfg.objective, base_score=cfg.base_score,
                    num_feature=X.shape[1])
    return _TrainingSession(cfg, DataMatrix(X, labels=y, weights=weights), list(evals), forest, mesh=mesh)


def _shard_blocks(X, shards):
    """The mesh session's shards of a matrix: contiguous, ceil(n / shards)
    rows, the tail padded with rows that are missing in every column."""
    rows = -(-len(X) // shards)
    padded = np.full((rows * shards,) + X.shape[1:], np.nan, np.float32)
    padded[: len(X)] = X
    return [padded[s * rows : (s + 1) * rows] for s in range(shards)]


@pytest.mark.multichip
def test_mesh_session_cuts_are_the_merge_of_its_shards_sketches():
    """A one-process mesh job's cuts are the merge of its shards' sketches
    (as a multi-process job's always were), one set for every shard; one
    shard's merge is its own sketch, to the bit."""
    from sagemaker_xgboost_container_tpu.data import binning

    X, grad, _hess = _click_like(1003, seed=8)   # 1003 rows: the last share is padded
    y = (grad < 0).astype(np.float32)
    session = _mesh_session(X, y, _mesh4())
    per_shard = [
        binning._host_cut_points(block, np.ones(len(block), np.float32), 31)
        for block in _shard_blocks(X, 4)
    ]
    want = binning.merge_cut_candidates(per_shard, 32)
    assert [c.tobytes() for c in session.cuts] == [c.tobytes() for c in want]
    whole = binning._host_cut_points(X, np.ones(len(X), np.float32), 31)
    assert any(a.tobytes() != b.tobytes() for a, b in zip(want, whole))  # not the uncut sketch
    # one shard: the old cuts, to the bit, through the same path
    one = _mesh_session(X, y, None)
    assert [c.tobytes() for c in one.cuts] == [c.tobytes() for c in whole]
    assert [c.tobytes() for c in binning.merge_cut_candidates([whole], 32)] == [
        c.tobytes() for c in whole
    ]


@pytest.mark.multichip
def test_bins_binned_by_shard_are_the_whole_matrix_binned_under_the_same_cuts():
    from sagemaker_xgboost_container_tpu.data.binning import apply_cut_points

    X, grad, _hess = _click_like(1003, seed=9)
    y = (grad < 0).astype(np.float32)
    Xv = _click_like(301, seed=10)[0]
    session = _mesh_session(X, y, _mesh4(), evals=[(DataMatrix(Xv, labels=np.zeros(301, np.float32)), "v")])
    cuts = session.cuts
    placed = np.asarray(session.bins)
    assert placed.shape == (1004, X.shape[1]) and len(session.bins.addressable_shards) == 4
    want = apply_cut_points(X, cuts, 32)
    assert placed.dtype == want.dtype
    assert placed[:1003].tobytes() == want.tobytes() and (placed[1003:] == 32).all()
    # whoever asks for the bins on the host pulls them then
    assert session.train_binned.bins.tobytes() == want.tobytes()
    assert session.train_binned.num_row == 1003
    ev = np.asarray(session.eval_bins[0])
    assert ev[:301].tobytes() == apply_cut_points(Xv, cuts, 32).tobytes() and (ev[301:] == 32).all()
    assert session.eval_sets[0][2].bins.tobytes() == ev[:301].tobytes()
    # the gauges come from where the shards lie, padding taken off
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    gauges = {name: family for name, _k, _h, family in REGISTRY.collect()}
    assert gauges["train_cells_missing"][0].value == float((want == 32).sum())
    assert gauges["train_cells_total"][0].value == float(want.size)


@pytest.mark.multichip
def test_mesh_of_four_forest_is_the_one_device_forest_under_the_same_cuts(sketch_as_mesh):
    """Splits to the bit; leaf values to float32 summation order (the psum
    adds four partial sums where one device adds one)."""
    X, grad, _hess = _click_like(4 * 1024, seed=11)
    y = (grad < 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3, "max_bin": 32,
              "_rounds_per_dispatch": 2}
    on_mesh = train(dict(params), DataMatrix(X, labels=y), num_boost_round=4, mesh=_mesh4())
    sketch_as_mesh(4)
    alone = train(dict(params), DataMatrix(X, labels=y), num_boost_round=4)
    assert len(on_mesh.trees) == len(alone.trees) == 4
    for a, b in zip(on_mesh.trees, alone.trees):
        for key in ("feature", "threshold", "default_left", "left", "right"):
            assert np.asarray(getattr(a, key)).tobytes() == np.asarray(getattr(b, key)).tobytes(), key
        np.testing.assert_allclose(a.value, b.value, rtol=0, atol=2e-5)


def test_one_process_merge_is_what_four_processes_would_merge(monkeypatch):
    """Four local shards merged in one process give the cuts four processes
    of one shard each agree on through their allgather (the stand-in of
    `test_four_row_shares...`), and two processes of two shards each."""
    from jax.experimental import multihost_utils

    from sagemaker_xgboost_container_tpu.data import binning
    from sagemaker_xgboost_container_tpu.models import booster

    X, _grad, _hess = _click_like(4096, seed=12)
    max_bin = 32
    sets = [
        binning._host_cut_points(block, np.ones(len(block), np.float32), max_bin - 1)
        for block in _shard_blocks(X, 4)
    ]
    want = binning.merge_cut_candidates(sets, max_bin)

    def gathered(processes):
        """What each process hands the allgather, stacked as it returns them."""
        sent = []
        monkeypatch.setattr(
            multihost_utils, "process_allgather",
            lambda x: (sent.append(np.asarray(x)), np.asarray(x)[None])[1],
        )
        for local in processes:
            booster._merge_cuts_across_processes(local, max_bin)
        mats, counts = sent[0::2], sent[1::2]
        monkeypatch.setattr(
            multihost_utils, "process_allgather",
            lambda x: np.stack(mats if np.asarray(x).ndim == 2 else counts),
        )
        return [booster._merge_cuts_across_processes(local, max_bin) for local in processes]

    for processes in ([[s] for s in sets], [sets[:2], sets[2:]]):
        for merged in gathered(processes):
            assert [c.tobytes() for c in merged] == [c.tobytes() for c in want]


@pytest.mark.multichip
def test_weighted_sketch_goes_shard_by_shard_through_the_same_path():
    """The approx re-sketch: hessians weigh each shard's sketch where it lies
    and the shards' candidates are merged, as set-up's are."""
    from sagemaker_xgboost_container_tpu.data import binning

    X, grad, _hess = _click_like(1003, seed=13)
    y = (grad < 0).astype(np.float32)
    session = _mesh_session(X, y, _mesh4(), params={"tree_method": "approx", "max_bin": 16})
    assert session.approx_resketch and len(session._train_floats) == 4
    first = [np.asarray(c).copy() for c in session.cuts]
    session.run_rounds()   # re-sketches under the current hessians, then one round
    session.end_turnaround()
    # the hessians the re-sketch saw: base margin 0, so p (1 - p) = 0.25 a real row
    weights = np.zeros(1004, np.float32)
    weights[:1003] = 0.25
    want = binning.merge_cut_candidates(
        [
            binning._host_cut_points(block, weights[s * 251 : (s + 1) * 251], 15)
            for s, block in enumerate(_shard_blocks(X, 4))
        ],
        16,
    )
    assert [np.asarray(c).tobytes() for c in session.cuts] == [c.tobytes() for c in want]
    assert len(first) == len(want)


@pytest.mark.multichip
def test_setup_spans_cover_the_phase_once_and_each_shard_inside_it(monkeypatch):
    from sagemaker_xgboost_container_tpu.telemetry import spans

    ended = []
    real = spans.end_span
    monkeypatch.setattr(
        spans, "end_span",
        lambda open_span, emit=False: (ended.append(open_span), real(open_span, emit=emit))[1],
    )
    X, grad, _hess = _click_like(1003, seed=14)
    _mesh_session(X, (grad < 0).astype(np.float32), _mesh4())
    names = [s.name for s in ended]
    assert names.count("setup.sketch") == 1 and names.count("setup.bin_apply") == 1
    assert names.count("setup.sketch.shard") == 4 and names.count("setup.bin_apply.shard") == 4
    assert names.count("setup.sketch_merge") == 1
    # the merge ends inside the sketch's span, the shards before the merge
    assert names.index("setup.sketch_merge") < names.index("setup.sketch")
    assert max(i for i, n in enumerate(names) if n == "setup.sketch.shard") < names.index(
        "setup.sketch_merge"
    )
    # parts of a phase stay out of the round record's phases
    assert all(s.covering for s in ended if s.name.endswith(".shard") or s.name == "setup.sketch_merge")
