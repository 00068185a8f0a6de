"""Device-window plane (telemetry/device.py, SM_DEVICE_TELEMETRY).

Covers the unset-gate guard (no records, no threads, bit-identical trees vs
an armed run — AOT lowering must not consume the RNG stream), the
``training.compiled`` record shape on a tiny mesh train (with the stage
table's summary), the stage table from HLO text, the HBM watermark
cadence (SM_HBM_SAMPLE_EVERY) and wire shape, the shared cached sampler the
heartbeat plane delegates to, the OOM forensics drill (injected
RESOURCE_EXHAUSTED -> hbm-forensics-rank0.json + exit 86), the /status
memory section + memory-skew naming, and the on-demand /debug/profile
endpoint (bounded capture when armed, 404 when not).
"""

import json
import os
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.constants import EXIT_DEVICE_OOM
from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.models.booster import (
    TrainConfig,
    _TrainingSession,
)
from sagemaker_xgboost_container_tpu.models.forest import Forest
from sagemaker_xgboost_container_tpu.telemetry import device, fleet, tracing
from sagemaker_xgboost_container_tpu.training import watchdog
from sagemaker_xgboost_container_tpu.training.profiling import RoundTimer


def _records(out, metric):
    needle = '"metric": "{}"'.format(metric)
    return [json.loads(l) for l in out.splitlines() if needle in l]


@pytest.fixture
def device_env(monkeypatch):
    for knob in (
        device.DEVICE_TELEMETRY_ENV,
        device.HBM_SAMPLE_EVERY_ENV,
        "SM_PROFILER_TRACE_DIR",
        tracing.TRACE_EXPORT_DIR_ENV,
    ):
        monkeypatch.delenv(knob, raising=False)
    device._reset_for_tests()
    fleet._reset_for_tests()
    yield monkeypatch
    device._reset_for_tests()
    fleet._reset_for_tests()


def _tiny_data(n=256, d=5):
    rng = np.random.RandomState(7)
    X = rng.rand(n, d).astype(np.float32)
    y = (X @ rng.rand(d).astype(np.float32) > 0.5).astype(np.float32)
    return X, y


def _train_tiny(mesh=None, rounds=4, timer=False):
    X, y = _tiny_data()
    # the entrypoint layer installs RoundTimer (training/callbacks.py); tests
    # that assert the watermark path add it explicitly
    callbacks = [RoundTimer(log_every=0)] if timer else None
    return train(
        {"max_depth": 3, "objective": "binary:logistic"},
        DataMatrix(X, labels=y),
        num_boost_round=rounds,
        verbose_eval=False,
        mesh=mesh,
        callbacks=callbacks,
    )


# ------------------------------------------------------------- the gate off
def test_gate_off_no_records_no_threads(device_env, capsys):
    before = set(threading.enumerate())
    _train_tiny(timer=True)
    out = capsys.readouterr().out
    assert _records(out, "training.compiled") == []
    assert set(threading.enumerate()) == before
    assert device.sample_cadence() == 0
    assert device.watermark_wire() is None
    assert device.memory_status() is None
    # the stage table is lazy: registered, never computed on this path
    assert device._round_program is not None and device._stage_table is None


def test_gate_does_not_change_trees(device_env, tmp_path, capsys):
    """Arming the plane must be pure observation: the AOT lowering reads
    avals only, so the tree stream is bit-identical with and without it."""
    off = _train_tiny()
    device_env.setenv(device.DEVICE_TELEMETRY_ENV, "1")
    device._reset_for_tests()
    on = _train_tiny()
    capsys.readouterr()
    p_off, p_on = str(tmp_path / "off.json"), str(tmp_path / "on.json")
    off.save_model(p_off)
    on.save_model(p_on)
    with open(p_off, "rb") as f_off, open(p_on, "rb") as f_on:
        assert f_off.read() == f_on.read()


# ------------------------------------------------------- compiled-cost record
def test_compiled_record_on_tiny_mesh_train(device_env, capsys):
    import jax
    from jax.sharding import Mesh

    device_env.setenv(device.DEVICE_TELEMETRY_ENV, "1")
    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("data",))
    _train_tiny(mesh=mesh, timer=True)
    out = capsys.readouterr().out
    compiled = _records(out, "training.compiled")
    assert len(compiled) == 1
    rec = compiled[0]
    assert rec["kind"] == "train_round"
    assert rec["flops"] > 0
    assert rec["bytes_accessed"] > 0
    assert rec["flops_per_round"] > 0
    assert rec["hbm_peak_bytes"] >= 0
    assert rec["rounds_per_dispatch"] >= 1
    assert rec["mesh_shape"] == {"data": 2}
    assert rec["backend"] == "cpu"
    # the stage table's summary rode the same record: every stage of a
    # one-tree round, the collective's among them on a mesh
    for name in ("grad", "hist", "hist_allreduce", "split_scan", "route_rows"):
        assert rec["stages"][name]["instructions"] > 0, name
    # and the record survived for /status + forensics
    last = device.last_compiled()
    assert last is not None and last["flops"] == rec["flops"]


# --------------------------------------------------------------- stage table
_HLO = """HloModule jit_multi_round

%fused_computation.3 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %mul.9 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(multi_round)/while/body/closed_call/grad/mul"}
}

%body.1 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.2 = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.12 = f32[8]{0} fusion(%gte.2), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(multi_round)/while/body/closed_call/grad/mul" source_file="booster.py" source_line=9}
  %gather.4 = (u16[16,4]{1,0}, pred[4]{0}) gather(%fusion.12), metadata={op_name="jit(multi_round)/while/body/closed_call/hist/hist_allreduce/psum"}
  ROOT %copy.7 = f32[8]{0} copy(%fusion.12), metadata={op_name="reduce_window_sum"}
}
"""


def test_stage_of_op_name_takes_the_innermost_scope():
    assert device.stage_of_op_name("jit(f)/while/body/closed_call/route_rows/gather") == "route_rows"
    assert device.stage_of_op_name("jit(f)/while/body/hist/hist_allreduce/psum") == "hist_allreduce"
    assert device.stage_of_op_name("jit(f)/eval_apply/while/body/jit(take_along_axis)/gather") == "eval_apply"
    assert device.stage_of_op_name("reduce_window_sum") is None
    assert set(device.STAGES) >= {"grad", "hist", "node_totals", "split_scan", "pack"}


@pytest.mark.parametrize(
    "op_name, stage",
    [
        # a multi-class round traces its builder under the class vmap
        ("jit(multi_round)/while/body/closed_call/vmap(route_rows)/jit(clip)/min", "route_rows"),
        ("jit(multi_round)/while/body/closed_call/vmap(hist)/broadcast_in_dim", "hist"),
        ("jit(f)/while/body/closed_call/vmap(vmap(split_scan))/add", "split_scan"),
        # a vmapped function that is no stage leaves the scope outside it to decide
        ("jit(f)/while/body/closed_call/eval_apply/vmap(jit(_where))/select_n", "eval_apply"),
        ("jit(f)/while/body/closed_call/vmap()/sub", None),
        # only vmap is looked through: a jitted function named like a stage is none
        ("jit(f)/while/body/jit(hist)/add", None),
    ],
)
def test_stage_of_op_name_looks_through_a_vmapped_scope(op_name, stage):
    assert device.stage_of_op_name(op_name) == stage


def test_stages_from_hlo_text_names_fusions_and_counts_what_runs():
    table, summary = device.stages_from_hlo_text(_HLO)
    # a fusion takes the stage of its own op_name; its body is named too
    assert table == {"mul.9": "grad", "fusion.12": "grad", "gather.4": "hist_allreduce"}
    # the summary counts what runs as an operation of its own: the fusion's
    # body does not, and what was traced under no stage goes under ""
    assert summary["grad"] == {"instructions": 1, "result_bytes": 32}
    assert summary["hist_allreduce"] == {"instructions": 1, "result_bytes": 16 * 4 * 2 + 4}
    assert summary[""]["instructions"] == 3  # parameter, get-tuple-element, copy


def test_round_program_stages_is_lazy_and_cached(device_env):
    class Compiled:
        def as_text(self):
            return _HLO

    calls = []
    assert device.round_program_stages() == {}  # no session has registered
    device.register_round_program(lambda: calls.append(1) or Compiled())
    assert calls == []  # registering lowers nothing
    assert device.round_program_stages()["fusion.12"] == "grad"
    assert device.round_program_stages()["gather.4"] == "hist_allreduce"
    assert calls == [1]  # compiled once, when somebody asked
    device.register_round_program(lambda: calls.append(2) or Compiled())
    assert device._stage_table is None  # a new session drops the old table


def _loss_guided_round_program(mesh=None):
    """A traced loss-guided round on the CPU; returns (table, summary, HLO)."""
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(7)
    X = rng.rand(600, 5).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] > 0.9).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    device._reset_for_tests()
    train(
        {"objective": "binary:logistic", "grow_policy": "lossguide", "max_depth": 0,
         "max_leaves": 9, "eval_metric": "logloss", "_rounds_per_dispatch": 2},
        dtrain, num_boost_round=2, verbose_eval=False, mesh=mesh,
        evals=[(dtrain, "train"), (DataMatrix(X[:200], labels=y[:200]), "validation")],
    )
    compiled = device._round_program()
    table, summary = device.note_stage_table(compiled)
    return table, summary, compiled.as_text()


@pytest.mark.parametrize("shards", [1, 4], ids=["one_device", "data_mesh_of_4"])
def test_loss_guided_round_leaves_no_instruction_of_its_body_without_a_stage(shards):
    """PR 42: ``ops/lossguide.py`` had no stage scope at all, so a loss-guided
    round's device time would have read as unnamed. Now every instruction the
    build traces lies under a stage, ``step_pick`` where no other."""
    import jax
    from jax.sharding import Mesh

    mesh = None
    if shards > 1:
        mesh = Mesh(np.array(jax.devices()[:shards]), axis_names=("data",))
    table, summary, hlo = _loss_guided_round_program(mesh)
    want = {"grad", "hist", "split_scan", "step_pick", "route_rows", "leaf_margin",
            "eval_apply", "eval_metric", "pack"}
    if shards > 1:
        want |= {"hist_allreduce"}
    assert set(table.values()) == want  # and no node_totals: no such stage here
    assert summary["step_pick"]["instructions"] > 0
    # every instruction that carries the build's own frames is named
    unnamed = []
    for line in hlo.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and ("split_step" in m.group(1) or "/step_pick/" in m.group(1)):
            if device.stage_of_op_name(m.group(1)) is None:
                unnamed.append(m.group(1))
    assert not unnamed, unnamed[:5]
    # the step loop itself is step_pick's, what is inside it the inner stage's
    assert device.stage_of_op_name("jit(multi_round)/while/body/step_pick/while") == "step_pick"
    assert (
        device.stage_of_op_name("jit(f)/while/body/step_pick/while/body/closed_call/hist/pad")
        == "hist"
    )


# ------------------------------------------------------------ HBM watermarks
def test_watermark_cadence(device_env, monkeypatch):
    device_env.setenv(device.DEVICE_TELEMETRY_ENV, "1")
    device_env.setenv(device.HBM_SAMPLE_EVERY_ENV, "3")
    sampled = []
    monkeypatch.setattr(device, "sample_watermark", sampled.append)
    timer = RoundTimer(log_every=0, emit_structured=False)
    assert timer._hbm_every == 3
    timer.before_training(None)
    for epoch in range(9):
        timer.after_iteration(None, epoch, {})
    timer.after_training(None)
    assert sampled == [0, 3, 6]


def test_watermark_state_and_wire(device_env):
    device_env.setenv(device.DEVICE_TELEMETRY_ENV, "1")
    mark = device.sample_watermark(5)
    assert mark["round"] == 5
    assert mark["source"] in ("memory_stats", "live_arrays", "none")
    wire = device.watermark_wire()
    assert wire["round"] == 5
    assert wire["high_bytes"] >= wire["bytes_in_use"] >= 0
    status = device.memory_status()
    assert status["watermark"]["round"] == 5
    assert "current" in status


def test_sampler_is_shared_and_cached(device_env, monkeypatch):
    """Satellite: the heartbeat plane's device_live_bytes and the watermark
    walk must share ONE cached sample — at most one live-buffer walk per
    interval however many consumers fire."""
    from sagemaker_xgboost_container_tpu.telemetry import cluster

    walks = []
    real = device._sample_uncached
    monkeypatch.setattr(
        device, "_sample_uncached", lambda: (walks.append(1), real())[1]
    )
    device._reset_for_tests()
    first = device.sample_device_memory()
    cluster._device_live_bytes()
    device.sample_device_memory()
    assert len(walks) == 1
    assert cluster._device_live_bytes() == int(first["total_bytes_in_use"])
    # max_age_s=0 (forensics) forces a fresh walk through the cache
    device.sample_device_memory(max_age_s=0.0)
    assert len(walks) == 2


# ------------------------------------------------------------- OOM forensics
def _tiny_session():
    X, y = _tiny_data(64, 4)
    config = TrainConfig({"max_depth": 2, "objective": "reg:squarederror"})
    dtrain = DataMatrix(X, labels=y)
    forest = Forest(
        objective_name=config.objective,
        objective_params=None,
        base_score=config.base_score,
        num_feature=dtrain.num_col,
        num_class=config.num_class,
    )
    return _TrainingSession(config, dtrain, [], forest, mesh=None)


def test_oom_drill_dumps_forensics_and_exits_86(
    device_env, tmp_path, monkeypatch, capsys
):
    device_env.setenv(tracing.TRACE_EXPORT_DIR_ENV, str(tmp_path))
    codes = []
    monkeypatch.setattr(watchdog, "_exit", codes.append)
    watchdog._reset_abort_for_tests()
    session = _tiny_session()

    def _boom():
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            "9876543210 bytes."
        )

    monkeypatch.setattr(session, "_run_rounds_inner", _boom)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        session.run_rounds()
    assert codes == [EXIT_DEVICE_OOM]
    path = tmp_path / "hbm-forensics-rank0.json"
    assert path.exists()
    doc = json.loads(path.read_text())
    assert doc["reason"] == "device_oom"
    assert "RESOURCE_EXHAUSTED" in doc["error"]
    assert isinstance(doc["top_live_buffers"], list) and doc["top_live_buffers"]
    assert doc["memory"]["source"] in ("memory_stats", "live_arrays", "none")
    aborts = _records(capsys.readouterr().out, "training.abort")
    assert aborts and aborts[0]["reason"] == "device_oom"
    assert aborts[0]["forensics"] == str(path)
    watchdog._reset_abort_for_tests()


def test_non_oom_errors_propagate_without_abort(device_env, monkeypatch):
    codes = []
    monkeypatch.setattr(watchdog, "_exit", codes.append)
    watchdog._reset_abort_for_tests()
    session = _tiny_session()

    def _boom():
        raise ValueError("not a memory problem")

    monkeypatch.setattr(session, "_run_rounds_inner", _boom)
    with pytest.raises(ValueError):
        session.run_rounds()
    assert codes == []
    watchdog._reset_abort_for_tests()


def test_is_oom_error_matches_xla_text_only():
    assert device.is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert device.is_oom_error(RuntimeError("Resource exhausted: HBM"))
    assert device.is_oom_error(MemoryError("ran out of memory on device"))
    assert not device.is_oom_error(ValueError("shapes do not match"))


# ----------------------------------------------- /status memory + /debug/profile
def test_status_memory_section_and_skew(device_env):
    device_env.setenv(device.DEVICE_TELEMETRY_ENV, "1")
    device.sample_watermark(2)
    collector = fleet.FleetCollector(num_ranks=3, port=0)
    try:
        for rank, bytes_in_use in ((0, 100), (1, 120), (2, 1000)):
            assert collector.fold(
                {
                    "type": "spans",
                    "rank": rank,
                    "host": "algo-{}".format(rank + 1),
                    "spans": [],
                    "memory": {"round": 2, "bytes_in_use": bytes_in_use},
                }
            )
        snap = collector.memory_snapshot()
        assert set(snap["ranks"]) == {0, 1, 2}
        skew = snap["memory_skew"]
        assert skew["rank"] == 2 and skew["host"] == "algo-3"
        assert skew["ratio"] > 1.5
        server = fleet.StatusServer(0, collector=collector).start()
        try:
            with urllib.request.urlopen(
                "http://127.0.0.1:{}/status".format(server.port), timeout=10
            ) as resp:
                doc = json.loads(resp.read())
            memory = doc["memory"]
            assert memory["local"]["watermark"]["round"] == 2
            assert memory["memory_skew"]["rank"] == 2
        finally:
            server.stop()
    finally:
        collector.stop()


def test_debug_profile_capture_and_404(device_env, tmp_path):
    server = fleet.StatusServer(0).start()
    url = "http://127.0.0.1:{}/debug/profile?ms=10".format(server.port)
    try:
        # unarmed: indistinguishable from an unknown path
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(url, timeout=10)
        assert err.value.code == 404
        device_env.setenv("SM_PROFILER_TRACE_DIR", str(tmp_path))
        with urllib.request.urlopen(url, timeout=30) as resp:
            doc = json.loads(resp.read())
        assert doc["ms"] == 10
        assert doc["path"].startswith(str(tmp_path))
        assert os.path.isdir(doc["path"])
        # bad ms is a 400, not a crash
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                "http://127.0.0.1:{}/debug/profile?ms=soon".format(server.port),
                timeout=10,
            )
        assert err.value.code == 400
    finally:
        server.stop()
