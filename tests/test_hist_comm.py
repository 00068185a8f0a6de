"""GRAFT_HIST_COMM equivalence suite: reduce-scatter histogram rounds.

The reduce_scatter lowering (ops/histogram.scatter_histograms) replaces the
full-histogram psum with ``lax.psum_scatter`` along the data axis: each
device aggregates and scans only its d/axis_size feature slice and the
per-shard winners merge through combine_splits_across_shards. On a 2-D
(data x feature) mesh the slicing composes with the feature axis: each
feature shard's local histograms scatter along the data axis, devices scan
doubly-sharded d_local/n_data_shards blocks, and winners merge
hierarchically (data-axis sub-slice merge, then the feature-axis merge).
The contract is BIT-IDENTICAL committed trees versus the psum lowering on
the same mesh — same argmax, same tie-breaking (max gain, lowest global
feature id), same node totals (broadcast_node_totals) — at roughly half
the collective wire bytes and 1/axis_size the split-scan FLOPs.

Runs on the conftest 8-virtual-device CPU mesh (real SPMD partitioning +
collectives without TPU hardware).
"""

import numpy as np
import pytest
import scipy.sparse as sp

import jax
from jax.sharding import Mesh

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
from sagemaker_xgboost_container_tpu.ops.histogram import (
    MERGE_COLLECTIVES_PER_SCAN,
    padded_feature_width,
    round_comm_plan,
)

_TREE_FIELDS = (
    "feature",
    "threshold",
    "default_left",
    "left",
    "right",
    "value",
    "base_weight",
    "gain",
    "sum_hess",
)


@pytest.fixture(scope="module")
def mesh8():
    devices = np.array(jax.devices()[:8])
    assert devices.size == 8, "conftest must provide 8 virtual devices"
    return Mesh(devices, axis_names=("data",))


def _data(n=1024, d=11, seed=0, missing=0.12):
    """Dense features with NaN missing cells (the sparsity-aware path)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    if missing:
        X[rng.rand(n, d) < missing] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1 % d]) > 0).astype(
        np.float32
    )
    return X, y


def _assert_forests_bitwise(f1, f2):
    assert len(f1.trees) == len(f2.trees) and f1.trees
    for t1, t2 in zip(f1.trees, f2.trees):
        for k in _TREE_FIELDS:
            a, b = getattr(t1, k), getattr(t2, k)
            assert np.array_equal(a, b), "tree field {!r} diverges".format(k)


_CACHE_CAP_AS_SHIPPED = hist_mod.SUBTRACT_CACHE_MAX_BYTES


def _train_both(monkeypatch, params, X, y, mesh, rounds=4):
    """Train under psum and reduce_scatter; assert packed trees AND
    predictions are bitwise identical; return the psum forest."""
    forests = []
    for comm in ("psum", "reduce_scatter"):
        monkeypatch.setenv("GRAFT_HIST_COMM", comm)
        forests.append(
            train(dict(params), DataMatrix(X, labels=y), num_boost_round=rounds,
                  mesh=mesh)
        )
    monkeypatch.delenv("GRAFT_HIST_COMM")
    f1, f2 = forests
    _assert_forests_bitwise(f1, f2)
    p1 = np.asarray(f1.predict(X), np.float32)
    p2 = np.asarray(f2.predict(X), np.float32)
    assert np.array_equal(p1.view(np.uint32), p2.view(np.uint32))
    return f1


@pytest.mark.multichip
def test_k_round_equivalence_matrix(monkeypatch, mesh8):
    """Fused-dispatch equivalence matrix: K∈{1,4} x {psum, reduce_scatter}
    x {hist, lossguide} x subtraction on/off — committed trees AND
    predictions must be u32-view identical to the K=1 psum reference of the
    same (builder, subtraction) cell. This is the bit-identity contract the
    fused round pipeline (K-round lax.scan + overlapped collectives +
    donated round state) must keep."""
    X, y = _data(n=512, d=9, seed=11)
    builder_params = {
        "hist": {"objective": "binary:logistic", "max_depth": 3, "seed": 4},
        "lossguide": {
            "objective": "binary:logistic",
            "grow_policy": "lossguide",
            "max_leaves": 6,
            "max_depth": 0,
            "seed": 4,
        },
    }
    for builder, params in builder_params.items():
        for cache_cap in (_CACHE_CAP_AS_SHIPPED, 0):  # subtraction on, off
            monkeypatch.setattr(hist_mod, "SUBTRACT_CACHE_MAX_BYTES", cache_cap)
            reference = None
            for comm in ("psum", "reduce_scatter"):
                monkeypatch.setenv("GRAFT_HIST_COMM", comm)
                for k_rounds in (1, 4):
                    f = train(
                        dict(params, _rounds_per_dispatch=k_rounds),
                        DataMatrix(X, labels=y),
                        num_boost_round=4,
                        mesh=mesh8,
                    )
                    assert f.num_boosted_rounds == 4
                    if reference is None:
                        reference = f
                        continue
                    cell = (builder, cache_cap, comm, k_rounds)
                    _assert_forests_bitwise(reference, f)
                    pr = np.asarray(reference.predict(X), np.float32)
                    pf = np.asarray(f.predict(X), np.float32)
                    assert np.array_equal(
                        pr.view(np.uint32), pf.view(np.uint32)
                    ), cell


@pytest.mark.multichip
def test_overlap_knob_bitwise_and_single_batch(monkeypatch, mesh8):
    """GRAFT_HIST_OVERLAP=0 (single fused per-level collective) commits the
    same bits as the default pipelined schedule, and the schedule helper
    degenerates to one whole-level batch when disabled."""
    from sagemaker_xgboost_container_tpu.ops.histogram import (
        overlap_node_batches,
    )

    assert overlap_node_batches(8, False) == [slice(0, 8)]
    assert overlap_node_batches(1, True) == [slice(0, 1)]
    assert overlap_node_batches(8, True) == [slice(0, 4), slice(4, 8)]

    X, y = _data(n=512, d=11, seed=12)
    params = {"objective": "binary:logistic", "max_depth": 4, "seed": 2}
    forests = []
    for ov in ("1", "0"):
        monkeypatch.setenv("GRAFT_HIST_OVERLAP", ov)
        monkeypatch.setenv("GRAFT_HIST_COMM", "reduce_scatter")
        forests.append(
            train(dict(params), DataMatrix(X, labels=y), num_boost_round=3,
                  mesh=mesh8)
        )
    _assert_forests_bitwise(*forests)


def test_scan_carry_donation_reuses_round_buffers():
    """Round-state donation: the fused dispatch donates the margin carry
    (and the eval-margin carry), so round N+1 writes into round N's
    buffers instead of allocating. Asserted via unsafe_buffer_pointer on
    backends whose runtime implements input-output aliasing; skipped where
    donation is advisory."""
    from sagemaker_xgboost_container_tpu.models.booster import (
        TrainConfig,
        _TrainingSession,
    )
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    rng = np.random.RandomState(3)
    X = rng.rand(600, 5).astype(np.float32)
    y = (X[:, 0] > 0.4).astype(np.float32)
    Xv = rng.rand(128, 5).astype(np.float32)
    yv = (Xv[:, 0] > 0.4).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    dval = DataMatrix(Xv, labels=yv)
    cfg = TrainConfig(
        {"objective": "binary:logistic", "max_depth": 3,
         "_rounds_per_dispatch": 3, "eval_metric": "logloss"}
    )
    forest = Forest(
        objective_name=cfg.objective, base_score=cfg.base_score, num_feature=5
    )
    session = _TrainingSession(
        cfg, dtrain, [(dval, "validation")], forest,
        metric_names=["logloss"],
    )
    assert session.use_scan_rounds and session.rounds_per_dispatch == 3
    session.run_rounds()  # compile + first allocation
    session.end_turnaround()
    try:
        margin_ptr = session.margins.unsafe_buffer_pointer()
        eval_ptr = session.eval_margins[0].unsafe_buffer_pointer()
    except (AttributeError, NotImplementedError):
        pytest.skip("backend exposes no unsafe_buffer_pointer")
    session.run_rounds()
    session.end_turnaround()
    if session.margins.unsafe_buffer_pointer() != margin_ptr:
        pytest.skip("backend does not alias donated round buffers")
    # train margins AND the scanned eval-margin carry both reuse their
    # donated buffers across dispatches
    assert session.margins.unsafe_buffer_pointer() == margin_ptr
    assert session.eval_margins[0].unsafe_buffer_pointer() == eval_ptr


@pytest.mark.multichip
def test_reduce_scatter_bitwise_depthwise(monkeypatch, mesh8):
    # d=11 does not divide 8: features pad to 16, 2 per shard, the last
    # shard scanning pure padding — which must never win a split
    X, y = _data(d=11, seed=1)
    _train_both(
        monkeypatch,
        {"objective": "binary:logistic", "max_depth": 4, "seed": 3},
        X, y, mesh8,
    )


@pytest.mark.multichip
def test_reduce_scatter_bitwise_lossguide(monkeypatch, mesh8):
    X, y = _data(d=9, seed=2)
    _train_both(
        monkeypatch,
        {
            "objective": "binary:logistic",
            "grow_policy": "lossguide",
            "max_leaves": 8,
            "max_depth": 0,
            "seed": 5,
        },
        X, y, mesh8,
    )


@pytest.mark.multichip
def test_reduce_scatter_bitwise_without_subtraction(monkeypatch, mesh8):
    # the default runs exercise the subtraction cache (parent - left on the
    # local slice); this pins the direct-histogram path for both growers
    X, y = _data(d=11, seed=3)
    # a cache over the cap: both growers build both children directly
    monkeypatch.setattr(hist_mod, "SUBTRACT_CACHE_MAX_BYTES", 0)
    _train_both(
        monkeypatch,
        {"objective": "binary:logistic", "max_depth": 4, "seed": 1},
        X, y, mesh8,
    )
    _train_both(
        monkeypatch,
        {
            "objective": "binary:logistic",
            "grow_policy": "lossguide",
            "max_leaves": 6,
            "max_depth": 0,
            "seed": 1,
        },
        X, y, mesh8, rounds=3,
    )


@pytest.mark.multichip
def test_reduce_scatter_bitwise_fewer_features_than_shards(monkeypatch, mesh8):
    # d=5 < 8 shards: shards 5..7 hold pure padding columns
    X, y = _data(d=5, seed=4)
    _train_both(
        monkeypatch,
        {"objective": "reg:squarederror", "max_depth": 3, "seed": 2},
        X, y, mesh8,
    )


@pytest.mark.multichip
def test_reduce_scatter_bitwise_sparse_input(monkeypatch, mesh8):
    # csr input densifies with NaN (libsvm serve/train path)
    rng = np.random.RandomState(7)
    dense = rng.randn(800, 7).astype(np.float32)
    dense[rng.rand(800, 7) < 0.6] = 0.0
    X = np.asarray(
        DataMatrix(sp.csr_matrix(dense)).features
    )  # zeros -> NaN densification
    y = (np.nan_to_num(X[:, 0]) > 0).astype(np.float32)
    _train_both(
        monkeypatch,
        {"objective": "binary:logistic", "max_depth": 3, "seed": 9},
        X, y, mesh8,
    )


@pytest.mark.multichip
def test_reduce_scatter_scan_runs_on_feature_slice(monkeypatch, mesh8):
    """The split scan provably runs on d/axis_size features per device:
    record the histogram widths find_best_splits traces under shard_map."""
    from sagemaker_xgboost_container_tpu.ops import tree_build

    seen = []
    orig = tree_build.find_best_splits

    def recorder(G, H, num_cuts, **kw):
        seen.append(int(G.shape[1]))
        return orig(G, H, num_cuts, **kw)

    monkeypatch.setattr(tree_build, "find_best_splits", recorder)
    d = 11
    d_slice = padded_feature_width(d, 8) // 8  # 16 // 8 = 2
    X, y = _data(d=d, seed=5)
    monkeypatch.setenv("GRAFT_HIST_COMM", "reduce_scatter")
    train(
        {"objective": "binary:logistic", "max_depth": 3},
        DataMatrix(X, labels=y),
        num_boost_round=1,
        mesh=mesh8,
    )
    assert seen and all(w == d_slice for w in seen), seen

    seen.clear()
    monkeypatch.setenv("GRAFT_HIST_COMM", "psum")
    train(
        {"objective": "binary:logistic", "max_depth": 3},
        DataMatrix(X, labels=y),
        num_boost_round=1,
        mesh=mesh8,
    )
    assert seen and all(w == d for w in seen), seen


def _mesh2d(shape):
    devices = np.array(jax.devices()[:8]).reshape(shape)
    return Mesh(devices, axis_names=("data", "feature"))


_BUILDER_PARAMS_2D = {
    "hist": {"objective": "binary:logistic", "max_depth": 3, "seed": 4},
    "lossguide": {
        "objective": "binary:logistic",
        "grow_policy": "lossguide",
        "max_leaves": 5,
        "max_depth": 0,
        "seed": 4,
    },
}


@pytest.mark.multichip
@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2)])
def test_2d_mesh_equivalence_matrix(monkeypatch, mesh_shape):
    """2-D (data x feature) composition of the reduce_scatter lowering:
    every cell of (builder x subtraction x K∈{1,4} x overlap on/off) must
    commit packed trees AND predictions u32-view identical to the psum
    lowering on the same mesh — the PR-4 bit-identity contract extended to
    the two-axis winner merge (data-axis sub-slice merge, then the
    feature-axis merge, global feature ids offset per shard)."""
    mesh = _mesh2d(mesh_shape)
    X, y = _data(n=256, d=9, seed=21)
    for builder, params in _BUILDER_PARAMS_2D.items():
        for cache_cap in (_CACHE_CAP_AS_SHIPPED, 0):  # subtraction on, off
            monkeypatch.setattr(hist_mod, "SUBTRACT_CACHE_MAX_BYTES", cache_cap)
            monkeypatch.setenv("GRAFT_HIST_OVERLAP", "1")
            monkeypatch.setenv("GRAFT_HIST_COMM", "psum")
            reference = train(
                dict(params), DataMatrix(X, labels=y), num_boost_round=4,
                mesh=mesh,
            )
            pr = np.asarray(reference.predict(X), np.float32)
            monkeypatch.setenv("GRAFT_HIST_COMM", "reduce_scatter")
            for k_rounds in (1, 4):
                for overlap in ("1", "0"):
                    monkeypatch.setenv("GRAFT_HIST_OVERLAP", overlap)
                    f = train(
                        dict(params, _rounds_per_dispatch=k_rounds),
                        DataMatrix(X, labels=y),
                        num_boost_round=4,
                        mesh=mesh,
                    )
                    cell = (mesh_shape, builder, cache_cap, k_rounds, overlap)
                    assert f.num_boosted_rounds == 4, cell
                    _assert_forests_bitwise(reference, f)
                    pf = np.asarray(f.predict(X), np.float32)
                    assert np.array_equal(
                        pr.view(np.uint32), pf.view(np.uint32)
                    ), cell


@pytest.mark.multichip
def test_2d_scan_runs_on_doubly_sharded_slice(monkeypatch):
    """The 2-D reduce_scatter scan provably covers exactly
    d_local/n_data_shards columns per device (vs the feature-shard-local
    d_local under psum): record the histogram widths find_best_splits
    traces under shard_map."""
    from sagemaker_xgboost_container_tpu.ops import tree_build

    seen = []
    orig = tree_build.find_best_splits

    def recorder(G, H, num_cuts, **kw):
        seen.append(int(G.shape[1]))
        return orig(G, H, num_cuts, **kw)

    monkeypatch.setattr(tree_build, "find_best_splits", recorder)
    d, n_data, n_feat = 11, 4, 2
    mesh = _mesh2d((n_data, n_feat))
    d_local = padded_feature_width(d, n_feat) // n_feat            # 6
    d_slice = padded_feature_width(d_local, n_data) // n_data      # 2
    X, y = _data(d=d, seed=25)
    monkeypatch.setenv("GRAFT_HIST_COMM", "reduce_scatter")
    train(
        {"objective": "binary:logistic", "max_depth": 3},
        DataMatrix(X, labels=y),
        num_boost_round=1,
        mesh=mesh,
    )
    assert seen and all(w == d_slice for w in seen), seen

    seen.clear()
    monkeypatch.setenv("GRAFT_HIST_COMM", "psum")
    train(
        {"objective": "binary:logistic", "max_depth": 3},
        DataMatrix(X, labels=y),
        num_boost_round=1,
        mesh=mesh,
    )
    assert seen and all(w == d_local for w in seen), seen


@pytest.mark.multichip
def test_comm_bytes_counter_and_round_fields(monkeypatch, mesh8):
    """hist_comm_bytes_total under reduce_scatter < 0.75x the psum bytes,
    and the training.round record carries the comm fields."""
    from sagemaker_xgboost_container_tpu.telemetry import (
        REGISTRY,
        get_round_fields,
    )

    X, y = _data(d=11, seed=8)
    params = {"objective": "binary:logistic", "max_depth": 4}
    observed = {}
    for comm in ("psum", "reduce_scatter"):
        REGISTRY.reset()
        monkeypatch.setenv("GRAFT_HIST_COMM", comm)
        monkeypatch.setenv("GRAFT_HIST_COMM_CALIBRATE", "0")
        train(dict(params), DataMatrix(X, labels=y), num_boost_round=3,
              mesh=mesh8)
        counter = REGISTRY.counter(
            "hist_comm_bytes_total", labels={"impl": comm}
        )
        observed[comm] = counter.value
        fields = get_round_fields()
        assert fields.get("hist_comm") == comm
        assert fields.get("hist_comm_bytes", 0) > 0
    assert observed["psum"] > 0 and observed["reduce_scatter"] > 0
    ratio = observed["reduce_scatter"] / observed["psum"]
    assert ratio < 0.75, "reduce_scatter moved {:.2f}x the psum bytes".format(
        ratio
    )


def test_round_comm_plan_formula():
    """Host-side sanity of the bytes-per-round formula (docs/DESIGN.md
    Communication): ring allreduce = 2(p-1)/p x payload, reduce-scatter =
    (p-1)/p x padded payload."""
    d, B, p = 28, 257, 8
    _, ps = round_comm_plan("depthwise", 6, 0, d, B, p, "psum", False)
    _, rs = round_comm_plan("depthwise", 6, 0, d, B, p, "reduce_scatter", False)
    d_pad = padded_feature_width(d, p)  # 32
    expected_ratio = d_pad / (2.0 * d)  # padded payload, half the ring factor
    assert ps > 0 and rs > 0
    assert abs(rs / ps - expected_ratio) < 0.02
    # subtraction halves the per-level histogram widths -> fewer bytes
    _, ps_sub = round_comm_plan("depthwise", 6, 0, d, B, p, "psum", True)
    assert ps_sub < ps
    # single shard: no collectives
    entries, zero = round_comm_plan("depthwise", 6, 0, d, B, 1, "psum", False)
    assert entries == [] and zero == 0


def test_round_comm_plan_2d_formula():
    """Plan formula for the 2-D lowering: fed the feature-shard-LOCAL width
    (what each data shard histograms on a data x feature mesh), the
    reduce_scatter plan's data-axis hist wire bytes must stay < 0.75x the
    psum plan's — the PR-4 bound, now on 2-D — and the plan must carry the
    winner-merge psum entries of the hierarchical two-axis merge."""
    d_local, B, p_data = 6, 257, 4   # e.g. d=11 on a (4 x 2) mesh
    e_ps, ps = round_comm_plan(
        "depthwise", 5, 0, d_local, B, p_data, "psum", False
    )
    e_rs, rs = round_comm_plan(
        "depthwise", 5, 0, d_local, B, p_data, "reduce_scatter", False
    )
    hist_ps = sum(e["bytes"] for e in e_ps if e["kind"] == "hist")
    hist_rs = sum(e["bytes"] for e in e_rs if e["kind"] == "hist")
    assert hist_ps > 0 and hist_rs > 0
    assert hist_rs < 0.75 * hist_ps
    assert rs < 0.75 * ps  # the bound holds with merge entries included
    d_pad = padded_feature_width(d_local, p_data)  # 8
    assert abs(hist_rs / hist_ps - d_pad / (2.0 * d_local)) < 0.02
    # hist payloads are the pre-scatter padded-local width; the per-device
    # scattered scan slice is d_pad / p_data columns
    assert all(
        e["shape"][1] == d_pad for e in e_rs if e["kind"] == "hist"
    )
    assert d_pad % p_data == 0 and d_pad // p_data == 2
    # winner-merge entries: reduce_scatter only, one [W] psum-class entry
    # per gain-scan width, MERGE_COLLECTIVES_PER_SCAN collectives each
    merge = [e for e in e_rs if e["kind"] == "merge"]
    assert merge and all(len(e["shape"]) == 1 for e in merge)
    assert [e["shape"][0] for e in merge] == [1, 2, 4, 8, 16]
    ratio = (p_data - 1) / p_data
    assert merge[0]["bytes"] == MERGE_COLLECTIVES_PER_SCAN * 1 * 4 * 2 * ratio
    assert not [e for e in e_ps if e["kind"] == "merge"]
    # lossguide: root merge (W=1) + one both-children merge (W=2) per step
    e_lg, _ = round_comm_plan(
        "lossguide", 0, 6, d_local, B, p_data, "reduce_scatter", True
    )
    lg_merge = [e for e in e_lg if e["kind"] == "merge"]
    assert [(e["shape"][0], e["count"]) for e in lg_merge] == [(1, 1), (2, 5)]


def test_hist_comm_env_validation(monkeypatch):
    from sagemaker_xgboost_container_tpu.ops.histogram import hist_comm_impl

    monkeypatch.setenv("GRAFT_HIST_COMM", "ring")
    with pytest.raises(ValueError, match="reduce_scatter"):
        hist_comm_impl()
    monkeypatch.setenv("GRAFT_HIST_COMM", "reduce_scatter")
    assert hist_comm_impl() == "reduce_scatter"
    monkeypatch.delenv("GRAFT_HIST_COMM")
    assert hist_comm_impl() == "psum"
