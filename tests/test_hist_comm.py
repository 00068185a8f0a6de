"""The round program on a mesh: the level histograms' one collective.

The level histograms cross the `data` axis by a ``psum``
(``ops/histogram.py::apply_hist_collective``); on a 2-D (data x feature)
mesh the per-shard winners then merge along the `feature` axis
(``ops/split.py::combine_splits_across_shards``). What is held here:

* a fused dispatch of K = 4 rounds commits the forest K = 1 commits, bit
  for bit, for both builders, with and without sibling subtraction, on a
  `data` mesh of 8 and on both 2-D shapes: one case a cell, so a failure
  names its cell;
* the depth-wise build under ``shard_map`` returns the arrays it returned at
  the parent of PR 45 (nine digests, ``tests/lossguide_cases.py::
  depthwise_cases``; the loss-guided ones are ``tests/test_lossguide_rolled.py``'s):
  the depth-wise mesh build's only bitwise check since the second collective
  lowering, which it was compared with until then, went;
* the fused dispatch donates its round state;
* the bytes counter, the round record's fields and the plan's formula.

Runs on the conftest 8-virtual-device CPU mesh (real SPMD partitioning +
collectives without TPU hardware). About 70 s on one worker.
"""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
from sagemaker_xgboost_container_tpu.ops.histogram import round_comm_plan

from tests import lossguide_cases

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


_BUILDER_PARAMS = {
    "hist": {"objective": "binary:logistic", "max_depth": 3, "seed": 4},
    "lossguide": {
        "objective": "binary:logistic",
        "grow_policy": "lossguide",
        "max_leaves": 6,
        "max_depth": 0,
        "seed": 4,
    },
}


def _assert_fused_dispatch_is_round_by_round(monkeypatch, mesh, builder, subtract, X, y):
    """K = 4 rounds a dispatch commit the trees AND the predictions (u32
    view) that K = 1 commits on the same mesh: the contract the fused round
    pipeline (K-round lax.scan, batched collectives, donated round state)
    keeps."""
    monkeypatch.setattr(
        hist_mod, "SUBTRACT_CACHE_MAX_BYTES", _CACHE_CAP_AS_SHIPPED if subtract else 0
    )
    forests = [
        train(
            dict(_BUILDER_PARAMS[builder], _rounds_per_dispatch=k_rounds),
            DataMatrix(X, labels=y),
            num_boost_round=4,
            mesh=mesh,
        )
        for k_rounds in (1, 4)
    ]
    assert all(f.num_boosted_rounds == 4 for f in forests)
    _assert_forests_bitwise(*forests)
    one, fused = (np.asarray(f.predict(X), np.float32) for f in forests)
    assert np.array_equal(one.view(np.uint32), fused.view(np.uint32))


@pytest.mark.multichip
@pytest.mark.parametrize("subtract", [True, False], ids=["sub", "nosub"])
@pytest.mark.parametrize("builder", sorted(_BUILDER_PARAMS))
def test_k_round_equivalence_matrix(monkeypatch, mesh8, builder, subtract):
    X, y = _data(n=512, d=9, seed=11)
    _assert_fused_dispatch_is_round_by_round(monkeypatch, mesh8, builder, subtract, X, y)


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


def _mesh2d(shape):
    devices = np.array(jax.devices()[:8]).reshape(shape)
    return Mesh(devices, axis_names=("data", "feature"))


@pytest.mark.multichip
@pytest.mark.parametrize("subtract", [True, False], ids=["sub", "nosub"])
@pytest.mark.parametrize("builder", sorted(_BUILDER_PARAMS))
@pytest.mark.parametrize("mesh_shape", [(2, 4), (4, 2)], ids=["data2xfeature4", "data4xfeature2"])
def test_2d_mesh_equivalence_matrix(monkeypatch, mesh_shape, builder, subtract):
    """The same on a 2-D (data x feature) mesh, where the winners merge
    along the feature axis (global feature ids offset per shard)."""
    X, y = _data(n=256, d=9, seed=21)
    _assert_fused_dispatch_is_round_by_round(
        monkeypatch, _mesh2d(mesh_shape), builder, subtract, X, y
    )


# sha256[:16] of the padded tree arrays and row_out of ``build_tree`` (depth 3)
# under ``shard_map``, read off the parent of PR 45 (f68adf9) with no
# environment name set: ``python tests/lossguide_cases.py`` prints them
DEPTHWISE_MESH_DIGESTS = {
    "data4.sub.plain": "71d6ed3fee7bb980",
    "data4.sub.bynode": "9b335738d2f0d49e",
    "data4.sub.sets": "ce3f53e891473127",
    "data4.nosub.plain": "a3e845de2bcff36f",
    "data4.nosub.bynode": "3db5eee7ce25b4e8",
    "data4.nosub.sets": "d2646bf14ca102ef",
    "data2xfeature2.plain": "1f02f78519f99a09",
    "data2xfeature2.bynode": "15192c9940b6012a",
    "data2xfeature2.sets": "1d1dd5246dda58b9",
}


@pytest.mark.multichip
@pytest.mark.parametrize("name", sorted(DEPTHWISE_MESH_DIGESTS))
def test_depthwise_mesh_build_is_the_parents_forest_bit_for_bit(name):
    cases = lossguide_cases.depthwise_cases()
    assert set(cases) == set(DEPTHWISE_MESH_DIGESTS)
    tree, row_out = lossguide_cases.run_case(*cases[name], depthwise=True)
    assert lossguide_cases.digest(tree, row_out) == DEPTHWISE_MESH_DIGESTS[name]


@pytest.mark.multichip
def test_comm_bytes_counter_and_round_fields(monkeypatch, mesh8):
    """hist_comm_bytes_total counts the plan's bytes a round, and the
    training.round record carries the comm fields."""
    from sagemaker_xgboost_container_tpu.telemetry import (
        REGISTRY,
        get_round_fields,
    )

    X, y = _data(d=11, seed=8)
    REGISTRY.reset()
    monkeypatch.setenv("GRAFT_HIST_COMM_CALIBRATE", "0")
    train({"objective": "binary:logistic", "max_depth": 4}, DataMatrix(X, labels=y),
          num_boost_round=3, mesh=mesh8)
    fields = get_round_fields()
    assert fields.get("hist_comm") == "psum"
    assert fields.get("hist_comm_bytes", 0) > 0
    counter = REGISTRY.counter("hist_comm_bytes_total", labels={"impl": "psum"})
    assert counter.value == 3 * fields["hist_comm_bytes"]


def test_round_comm_plan_formula():
    """Host-side sanity of the bytes-per-round formula (docs/DESIGN.md
    Communication): ring allreduce = 2(p-1)/p x payload."""
    d, B, p = 28, 257, 8
    entries, ps = round_comm_plan("depthwise", 6, 0, d, B, p, False)
    hist = [e for e in entries if e["kind"] == "hist"]
    assert [e["shape"] for e in hist] == [(2**level, d, B) for level in range(6)]
    ratio = 2.0 * (p - 1) / p
    assert hist[3]["bytes"] == 2 * 8 * d * B * 4 * ratio
    assert [(e["shape"], e["count"]) for e in entries if e["kind"] == "totals"] == [((64,), 1)]
    assert ps == int(sum(e["bytes"] for e in entries))
    # subtraction halves the per-level histogram widths -> fewer bytes
    _, ps_sub = round_comm_plan("depthwise", 6, 0, d, B, p, True)
    assert 0 < ps_sub < ps
    # single shard: no collectives
    assert round_comm_plan("depthwise", 6, 0, d, B, 1, False) == ([], 0)
    # fed the feature-shard-LOCAL width (what each data shard histograms on
    # a data x feature mesh), the payloads carry that width
    d_local, p_data = 6, 4   # e.g. d=11 on a (4 x 2) mesh
    e_2d, _ = round_comm_plan("depthwise", 5, 0, d_local, B, p_data, False)
    assert {e["kind"] for e in e_2d} == {"hist", "totals"}
    assert all(e["shape"][1] == d_local for e in e_2d if e["kind"] == "hist")
    # lossguide: the root's call, then at most a pass a split step; no totals
    e_lg, _ = round_comm_plan("lossguide", 0, 6, d_local, B, p_data, True, pass_slots=8)
    assert [(e["kind"], e["shape"][0], e["count"]) for e in e_lg] == [
        ("hist", 1, 1), ("hist", 8, 5)
    ]
