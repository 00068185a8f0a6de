"""training/profiling.py unit tier (r5: shrink the covgate blind-spot list —
the module previously ran only under scripts/dissect.py on real
hardware, reporting 0% in-process coverage)."""

import json
import logging
import os
import time

import numpy as np

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.telemetry.cluster import (
    _on_jax_duration_event,
)
from sagemaker_xgboost_container_tpu.training.profiling import (
    TRACE_DIR_ENV, RoundTimer, xla_trace,
)


def test_round_timer_logs_and_summarizes(caplog):
    timer = RoundTimer(num_rows=1000, log_every=2)
    with caplog.at_level(logging.INFO, "sagemaker_xgboost_container_tpu"):
        timer.before_training(None)
        for epoch in range(4):
            assert timer.after_iteration(None, epoch, {}) is False
        timer.after_training(None)
    msgs = [r.message for r in caplog.records]
    per_round = [m for m in msgs if "ms/round" in m]
    assert len(per_round) == 2, msgs  # epochs 1 and 3 at log_every=2
    assert all("rows/sec" in m for m in per_round)
    assert any("trained 4 rounds in" in m for m in msgs)


def test_round_timer_as_training_callback(caplog):
    """RoundTimer rides the standard callback protocol end-to-end."""
    rng = np.random.RandomState(0)
    X = rng.rand(300, 4).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    with caplog.at_level(logging.INFO, "sagemaker_xgboost_container_tpu"):
        train(
            {"objective": "binary:logistic", "max_depth": 3},
            DataMatrix(X, labels=y),
            num_boost_round=3,
            callbacks=[RoundTimer(num_rows=300, log_every=1)],
        )
    assert sum("ms/round" in r.message for r in caplog.records) == 3


def _round_records(out):
    return [
        json.loads(line)
        for line in out.splitlines()
        if '"metric": "training.round"' in line
    ]


def test_round0_compile_reported_as_own_phase(capsys):
    """Regression (ISSUE 7 satellite): an XLA compile landing inside a
    round becomes a `compile` phases_ms key; build_eval no longer silently
    absorbs it."""
    timer = RoundTimer(log_every=0)
    timer.before_training(None)
    time.sleep(0.01)
    # a 5s fake compile through the real jax.monitoring listener: far
    # larger than the round's wall time, so an un-split build_eval would
    # have been inflated by 3 orders of magnitude
    _on_jax_duration_event("/jax/xla/backend_compile_duration", 5.0)
    timer.after_iteration(None, 0, {})
    time.sleep(0.005)
    timer.after_iteration(None, 1, {})
    timer.after_training(None)
    records = _round_records(capsys.readouterr().out)
    assert len(records) == 2
    round0 = records[0]
    assert 5000.0 <= round0["phases_ms"]["compile"] < 5500.0
    # the remainder is clamped to the real elapsed minus the compile — it
    # must NOT contain the compile time
    assert round0["phases_ms"]["build_eval"] < 1000.0
    # a round with no compile has no compile key at all
    assert "compile" not in records[1]["phases_ms"]


def _fenced_session(monkeypatch):
    """A tiny real session with SM_TRACE_DEVICE_SYNC=1 (every dispatch
    fenced); returns (session, fire) where fire() injects a fake 2s compile
    event through the real jax.monitoring listener."""
    from sagemaker_xgboost_container_tpu.models.booster import (
        TrainConfig,
        _TrainingSession,
    )
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    monkeypatch.setenv("SM_TRACE_DEVICE_SYNC", "1")
    rng = np.random.RandomState(0)
    X = rng.rand(200, 4).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    config = TrainConfig({"objective": "binary:logistic", "max_depth": 3})
    forest = Forest(
        objective_name=config.objective,
        objective_params=None,
        base_score=config.base_score,
        num_feature=4,
        num_class=config.num_class,
    )
    session = _TrainingSession(config, DataMatrix(X, labels=y), [], forest)

    def fire():
        _on_jax_duration_event("/jax/xla/backend_compile_duration", 2.0)

    return session, fire


def test_compile_inside_fenced_dispatch_not_double_counted(
    monkeypatch, capsys
):
    """A compile completing INSIDE the fenced dispatch is re-attributed at
    the source: the round's compile + host_dispatch must not both carry it."""
    session, fire = _fenced_session(monkeypatch)
    inner = session._round_fn

    def compiling_round(*args, **kwargs):
        out = inner(*args, **kwargs)
        fire()  # completes while the host_dispatch span is open
        return out

    session._round_fn = compiling_round
    timer = RoundTimer(log_every=0)
    timer.before_training(None)
    session.run_rounds()
    session.end_turnaround()
    timer.after_iteration(None, 0, {})
    timer.after_training(None)
    out = capsys.readouterr().out
    round0 = _round_records(out)[0]
    assert round0["phases_ms"]["compile"] >= 2000.0
    assert round0["phases_ms"]["host_dispatch"] < 2000.0
    attr = [
        json.loads(line)
        for line in out.splitlines()
        if '"metric": "training.attribution"' in line
    ][0]
    assert attr["host_ms"] < 2000.0 <= attr["compile_ms"]


def test_compile_outside_fence_keeps_host_dispatch(monkeypatch, capsys):
    """A compile on an UNFENCED code path must not erode the measured
    host_dispatch time (the mid-job recompile / sampled-fence case)."""
    session, fire = _fenced_session(monkeypatch)
    timer = RoundTimer(log_every=0)
    timer.before_training(None)
    session.run_rounds()
    session.end_turnaround()
    fire()  # completes after the fence closed — outside host_dispatch
    timer.after_iteration(None, 0, {})
    timer.after_training(None)
    round0 = _round_records(capsys.readouterr().out)[0]
    assert round0["phases_ms"]["compile"] >= 2000.0
    assert round0["phases_ms"]["host_dispatch"] > 0.0


def test_attribution_record_has_stable_shape(capsys):
    timer = RoundTimer(log_every=0)
    timer.before_training(None)
    timer.after_iteration(None, 0, {})
    timer.after_training(None)
    out = capsys.readouterr().out
    attr = [
        json.loads(line)
        for line in out.splitlines()
        if '"metric": "training.attribution"' in line
    ]
    assert len(attr) == 1
    rec = attr[0]
    assert rec["rounds"] == 1
    for key in ("compile_ms", "host_ms", "device_ms", "collective_ms"):
        assert rec[key] >= 0.0
        assert rec[key.replace("_ms", "_pct")] >= 0.0


def test_xla_trace_noop_without_env(monkeypatch):
    monkeypatch.delenv(TRACE_DIR_ENV, raising=False)
    with xla_trace():
        pass  # no profiler started, no artifacts


def test_xla_trace_writes_trace(tmp_path, monkeypatch, caplog):
    trace_dir = str(tmp_path / "trace")
    monkeypatch.setenv(TRACE_DIR_ENV, trace_dir)
    import jax.numpy as jnp

    with caplog.at_level(logging.INFO, "sagemaker_xgboost_container_tpu"):
        with xla_trace():
            (jnp.ones((32, 32)) @ jnp.ones((32, 32))).block_until_ready()
    assert any("profiler trace" in r.message for r in caplog.records)
    found = [
        os.path.join(dp, f)
        for dp, _dn, fns in os.walk(trace_dir)
        for f in fns
    ]
    assert found, "trace dir is empty"


def test_xla_trace_creates_missing_dir_and_emits_record(
    tmp_path, monkeypatch, capsys
):
    trace_dir = str(tmp_path / "deep" / "missing")
    monkeypatch.setenv(TRACE_DIR_ENV, trace_dir)
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with xla_trace():
        pass
    assert os.path.isdir(trace_dir)
    records = [
        json.loads(line)
        for line in capsys.readouterr().out.splitlines()
        if '"metric": "training.trace"' in line
    ]
    assert records and records[-1]["trace_dir"] == trace_dir


def test_xla_trace_start_failure_is_non_fatal(tmp_path, monkeypatch, caplog):
    monkeypatch.setenv(TRACE_DIR_ENV, str(tmp_path))
    import jax

    def boom(directory):
        raise RuntimeError("profiler already active")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    stopped = []
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: stopped.append(1))
    with caplog.at_level(logging.WARNING, "sagemaker_xgboost_container_tpu"):
        with xla_trace():
            pass  # must not raise
    assert any("could not start" in r.message for r in caplog.records)
    assert not stopped  # stop is never called for a trace that never started


def test_xla_trace_stop_failure_is_non_fatal(tmp_path, monkeypatch, caplog, capsys):
    monkeypatch.setenv(TRACE_DIR_ENV, str(tmp_path))
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)

    def boom():
        raise RuntimeError("collector died")

    monkeypatch.setattr(jax.profiler, "stop_trace", boom)
    with caplog.at_level(logging.WARNING, "sagemaker_xgboost_container_tpu"):
        with xla_trace():
            pass  # must not raise
    assert any("stop_trace failed" in r.message for r in caplog.records)
    # no training.trace record for a capture that failed to finalize
    assert '"metric": "training.trace"' not in capsys.readouterr().out
