"""Model-quality plane (telemetry/model.py, SM_MODEL_TELEMETRY).

Covers the unset-gate guard (no records, no gauges, bit-identical trees vs
an armed run — the on-device stat reductions are read-only), the
``training.learning`` record shape on an eval'd train, the byte-identical
EvaluationMonitor stdout contract with ``training.eval`` riding alongside,
the numeric-health guard drill (``train.gradient_poison`` fault ->
learning-forensics-rank0.json + exit 87 naming the first poisoned round),
the PSI math (decile grouping vs small windows), the served-drift
round-trip (trip + lifecycle DEGRADED + automatic recovery), the /status
learning/drift sections + schema_version, and the manifest learning +
drift_baseline stamps.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.constants import EXIT_NUMERIC_POISON
from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.ops import histogram
from sagemaker_xgboost_container_tpu.serving import lifecycle
from sagemaker_xgboost_container_tpu.telemetry import fleet, tracing
from sagemaker_xgboost_container_tpu.telemetry import model as model_telemetry
from sagemaker_xgboost_container_tpu.training import watchdog
from sagemaker_xgboost_container_tpu.training.callbacks import EvaluationMonitor
from sagemaker_xgboost_container_tpu.utils import faults, integrity


def _records(out, metric):
    needle = '"metric": "{}"'.format(metric)
    return [json.loads(l) for l in out.splitlines() if needle in l]


def _eval_lines(out):
    return [l for l in out.splitlines() if l.startswith("[")]


@pytest.fixture
def model_env(monkeypatch):
    for knob in (
        model_telemetry.MODEL_TELEMETRY_ENV,
        model_telemetry.DRIFT_PSI_MAX_ENV,
        model_telemetry.DRIFT_WINDOW_ENV,
        model_telemetry.DRIFT_MIN_ROWS_ENV,
        faults.FAULT_SPEC_ENV,
        tracing.TRACE_EXPORT_DIR_ENV,
    ):
        monkeypatch.delenv(knob, raising=False)
    faults.reset()
    model_telemetry._reset_for_tests()
    fleet._reset_for_tests()
    yield monkeypatch
    faults.reset()
    model_telemetry._reset_for_tests()
    fleet._reset_for_tests()


def _tiny_data(n=192, d=5, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, d).astype(np.float32)
    y = (X[:, 0] + 0.25 * X[:, 1] > 0.6).astype(np.float32)
    return X, y


def _train_tiny(rounds=4, k=1, evals=False, monitor=False, seed=3, hist_knobs=None):
    X, y = _tiny_data(seed=seed)
    Xv, yv = _tiny_data(n=64, seed=seed + 1)
    kwargs = {}
    if evals:
        kwargs["evals"] = [
            (DataMatrix(X, labels=y), "train"),
            (DataMatrix(Xv, labels=yv), "validation"),
        ]
    if monitor:
        kwargs["callbacks"] = [EvaluationMonitor()]
    return train(
        {
            "objective": "binary:logistic",
            "max_depth": 3,
            "max_bin": 32,
            "_rounds_per_dispatch": k,
        },
        DataMatrix(X, labels=y),
        num_boost_round=rounds,
        verbose_eval=False,
        hist_knobs=hist_knobs,
        **kwargs
    )


def _uniform_baseline(d=3):
    """Hand-shaped manifest baseline: quartile cuts, uniform mass, empty
    missing bucket (layout of baseline_from_binned: len(cuts) + 2)."""
    feature = {"cuts": [0.25, 0.5, 0.75], "fracs": [0.25, 0.25, 0.25, 0.25, 0.0]}
    return {"version": 1, "rows": 1000, "features": [dict(feature) for _ in range(d)]}


# ------------------------------------------------------------- the gate off
def test_gate_off_no_records_no_state(model_env, capsys):
    before = set(threading.enumerate())
    _train_tiny(evals=True, monitor=True)
    out = capsys.readouterr().out
    assert _records(out, "training.learning") == []
    assert _records(out, "training.eval") == []
    assert set(threading.enumerate()) == before
    assert not model_telemetry.enabled()
    assert model_telemetry.learning_status() is None
    assert model_telemetry.learning_summary() is None
    assert model_telemetry.drift_baseline() is None
    assert model_telemetry.drift_status() is None
    assert model_telemetry.maybe_install_drift(_uniform_baseline()) is None
    assert model_telemetry.active_drift() is None


@pytest.mark.parametrize("k", [1, 4])
@pytest.mark.parametrize("impl", ["flat", "pallas"])
def test_gate_does_not_change_trees(model_env, tmp_path, capsys, k, impl):
    """Arming the plane must be pure observation: the per-round stats are
    read-only reductions riding the same dispatch, so the tree stream is
    bit-identical with and without it — under both fused-dispatch shapes
    and both histogram builders."""
    backend = {"flat": "cpu", "pallas": "tpu"}[impl]
    assert histogram.choose_hist_impl(backend) == impl
    knobs = histogram.resolve_hist_knobs()._replace(backend=backend)
    off = _train_tiny(k=k, hist_knobs=knobs)
    model_env.setenv(model_telemetry.MODEL_TELEMETRY_ENV, "1")
    model_telemetry._reset_for_tests()
    on = _train_tiny(k=k, hist_knobs=knobs)
    out = capsys.readouterr().out
    assert len(_records(out, "training.learning")) == 4
    p_off, p_on = str(tmp_path / "off.json"), str(tmp_path / "on.json")
    off.save_model(p_off)
    on.save_model(p_on)
    with open(p_off, "rb") as f_off, open(p_on, "rb") as f_on:
        assert f_off.read() == f_on.read()


# ------------------------------------------------- learning records + curve
def test_learning_records_and_eval_curve(model_env, capsys):
    model_env.setenv(model_telemetry.MODEL_TELEMETRY_ENV, "1")
    _train_tiny(evals=True, monitor=True)
    out = capsys.readouterr().out
    learning = _records(out, "training.learning")
    assert [r["round"] for r in learning] == [0, 1, 2, 3]
    rec = learning[-1]
    for field in model_telemetry.DEVICE_STAT_FIELDS:
        assert field in rec, field
    for field in ("trees", "leaves", "max_depth", "leaf_value_absmax", "split_gain_max"):
        assert field in rec, field
    assert rec["grad_nonfinite"] == 0
    assert rec["margin_nonfinite"] == 0
    assert rec["leaves"] > 0 and rec["trees"] == 1
    # hess of binary:logistic is p(1-p) > 0: the sum must be positive
    assert rec["hess_sum"] > 0

    evals_rec = _records(out, "training.eval")
    assert {r["dataset"] for r in evals_rec} == {"train", "validation"}
    assert all(r["name"] == "logloss" for r in evals_rec)

    summary = model_telemetry.learning_summary()
    assert summary["dataset"] == "validation"
    assert summary["metric"] == "logloss"
    assert 0 <= summary["best_iteration"] <= 3
    assert "train-logloss" in summary["final"]
    assert "gap_last" in summary
    status = model_telemetry.learning_status()
    assert status["last_round"]["round"] == 3
    assert status["curve"]["best_iteration"] == summary["best_iteration"]


def test_eval_stdout_lines_byte_identical(model_env, capsys):
    """The SageMaker HPO scrape contract: arming the plane adds JSON lines
    but must not change a byte of the ``[N]<TAB>...`` metric lines."""
    _train_tiny(evals=True, monitor=True)
    off_lines = _eval_lines(capsys.readouterr().out)
    model_env.setenv(model_telemetry.MODEL_TELEMETRY_ENV, "1")
    model_telemetry._reset_for_tests()
    _train_tiny(evals=True, monitor=True)
    on_lines = _eval_lines(capsys.readouterr().out)
    assert off_lines and off_lines == on_lines


# --------------------------------------------------- numeric-health guard
def test_nan_drill_dumps_forensics_and_exits_87(model_env, tmp_path, monkeypatch, capsys):
    model_env.setenv(model_telemetry.MODEL_TELEMETRY_ENV, "1")
    model_env.setenv(tracing.TRACE_EXPORT_DIR_ENV, str(tmp_path))
    model_env.setenv(faults.FAULT_SPEC_ENV, "train.gradient_poison:nan@3")
    faults.configure_from_env()

    class _Exited(BaseException):
        pass

    codes = []

    def _exit(code):
        codes.append(code)
        raise _Exited()  # os._exit never returns; neither may the stand-in

    monkeypatch.setattr(watchdog, "_exit", _exit)
    watchdog._reset_abort_for_tests()
    try:
        with pytest.raises(_Exited):
            _train_tiny(rounds=6)
        out = capsys.readouterr().out
        assert codes == [EXIT_NUMERIC_POISON]
        aborts = _records(out, "training.abort")
        assert aborts and aborts[0]["reason"] == "numeric_poison"
        # the poison hit the 3rd dispatch: rounds 0-1 clean, round 2 poisoned
        assert aborts[0]["round"] == 2
        path = tmp_path / "learning-forensics-rank0.json"
        assert str(path) == aborts[0]["forensics"]
        doc = json.loads(path.read_text())
        assert doc["reason"] == "numeric_poison"
        assert doc["first_bad_round"] == 2
        history = {row["round"]: row for row in doc["stats_history"]}
        assert history[1]["grad_nonfinite"] == 0
        assert (
            history[2]["grad_nonfinite"] > 0 or history[2]["margin_nonfinite"] > 0
        )
    finally:
        watchdog._reset_abort_for_tests()


def test_first_poisoned_round_names_the_round():
    clean = {"grad_nonfinite": 0.0, "margin_nonfinite": 0.0, "grad_sum": 1.0}
    bad = {"grad_nonfinite": 4.0, "margin_nonfinite": 0.0, "grad_sum": 1.0}
    nonfinite_sum = {"grad_nonfinite": 0.0, "margin_nonfinite": 0.0, "grad_sum": float("nan")}
    assert model_telemetry.first_poisoned_round([clean, clean], 10) is None
    assert model_telemetry.first_poisoned_round([clean, bad, clean], 10) == 11
    assert model_telemetry.first_poisoned_round([nonfinite_sum], 7) == 7


# ------------------------------------------------------------------ PSI math
def test_psi_zero_on_matching_distribution():
    expected = [0.25, 0.25, 0.25, 0.25]
    assert model_telemetry.psi(expected, [250, 250, 250, 250]) == pytest.approx(0.0)


def test_psi_large_on_disjoint_mass():
    assert model_telemetry.psi([0.5, 0.5, 0.0], [0, 0, 100]) > 1.0


def test_psi_groups_fold_contiguously():
    expected = np.full(33, 1.0 / 33)
    groups = model_telemetry.psi_groups(expected)
    assert groups[0] == 0 and groups[-1] == int(groups.max())
    assert int(groups.max()) + 1 <= model_telemetry.PSI_GROUPS
    assert np.all(np.diff(groups) >= 0)  # contiguous, ordered


def test_small_window_psi_stays_below_threshold():
    """The small-sample guard the grouping exists for: a min_rows-sized
    window vs a 33-bin baseline must not read as drift when the traffic
    matches (E[PSI] of matching traffic ~ (groups-1)/rows — ungrouped, the
    ~33 near-empty fine bins would put it far past any usable threshold)."""
    rng = np.random.RandomState(5)
    cuts = [float(c) for c in np.linspace(0.03, 0.97, 32)]
    fracs = [1.0 / 33] * 33 + [0.0]
    baseline = {"version": 1, "rows": 10000, "features": [{"cuts": cuts, "fracs": fracs}]}
    window = model_telemetry.DriftWindow(baseline, psi_max=0.2)
    worst = window.observe(rng.rand(model_telemetry.DEFAULT_DRIFT_MIN_ROWS, 1))
    assert worst < 0.2
    assert not window.degraded


def test_bin_features_layout_and_missing():
    counts = model_telemetry.bin_features(
        np.array([[0.1, np.nan], [0.3, 5.0], [0.9, np.inf]]),
        [[0.25, 0.5, 0.75], [1.0]],
    )
    assert counts[0].tolist() == [1, 1, 0, 1, 0]  # bins 0..3 + missing
    assert counts[1].tolist() == [0, 1, 2]  # 5.0 above the cut; nan+inf missing


# -------------------------------------------------------- drift round-trip
def test_drift_trip_lifecycle_and_recovery(model_env, capsys):
    clock = [0.0]
    window = model_telemetry.DriftWindow(
        _uniform_baseline(),
        psi_max=0.2,
        window_s=60.0,
        min_rows=64,
        clock=lambda: clock[0],
    )
    rng = np.random.RandomState(11)
    lc = lifecycle.install(lifecycle.ServingLifecycle())
    try:
        lc.mark_ready()
        lifecycle.observe(window)
        for _ in range(4):
            window.observe(rng.rand(32, 3), predictions=rng.rand(32))
            clock[0] += 1.0
        assert not window.degraded
        assert lc.state == lifecycle.READY
        for _ in range(4):
            window.observe(3.0 + rng.rand(32, 3), predictions=rng.rand(32))
            clock[0] += 1.0
        assert window.degraded
        lifecycle.observe(window)
        assert lc.state == lifecycle.DEGRADED
        # automatic recovery: the shifted batches age out of the window
        clock[0] += 120.0
        assert not window.degraded
        lifecycle.observe(window)
        assert lc.state == lifecycle.READY
        # the recovered transition is recorded on the next fed request
        window.observe(rng.rand(32, 3))
    finally:
        lifecycle.uninstall()
    out = capsys.readouterr().out
    drift = _records(out, "serving.drift")
    assert [r["drifted"] for r in drift] == [True, False]
    assert drift[0]["psi"] > 0.2 and drift[0]["rows"] >= 64
    snap = window.snapshot()
    assert snap["rows"] == 32 and not snap["degraded"]
    assert len(snap["per_feature_psi"]) == 3


def test_drift_snapshot_prediction_histogram(model_env):
    window = model_telemetry.DriftWindow(
        _uniform_baseline(1), psi_max=10.0, min_rows=8, clock=lambda: 0.0
    )
    window.observe(np.random.RandomState(0).rand(16, 1), predictions=[0.1] * 16)
    snap = window.snapshot()
    # probability outputs pin the edges to [0, 1]; all mass in one bin
    assert max(snap["prediction"]["fracs"]) == pytest.approx(1.0)
    assert sum(snap["prediction"]["fracs"]) == pytest.approx(1.0)
    assert len(snap["prediction"]["edges"]) == model_telemetry.PRED_BINS + 1


def test_maybe_install_drift_gated_and_idempotent(model_env):
    baseline = _uniform_baseline()
    assert model_telemetry.maybe_install_drift(baseline) is None  # unarmed
    model_env.setenv(model_telemetry.MODEL_TELEMETRY_ENV, "1")
    assert model_telemetry.maybe_install_drift(None) is None
    first = model_telemetry.maybe_install_drift(baseline)
    assert first is not None
    assert model_telemetry.maybe_install_drift(_uniform_baseline(5)) is first
    assert model_telemetry.active_drift() is first
    assert model_telemetry.drift_status()["rows"] == 0


def test_drift_knobs_read_from_env(model_env):
    model_env.setenv(model_telemetry.DRIFT_PSI_MAX_ENV, "0.35")
    model_env.setenv(model_telemetry.DRIFT_WINDOW_ENV, "120")
    model_env.setenv(model_telemetry.DRIFT_MIN_ROWS_ENV, "17")
    window = model_telemetry.DriftWindow(_uniform_baseline())
    assert window.psi_max == pytest.approx(0.35)
    assert window.window_s == pytest.approx(120.0)
    assert window.min_rows == 17


# ------------------------------------------------- /status + manifest stamps
def test_status_learning_drift_and_schema_version(model_env):
    model_env.setenv(model_telemetry.MODEL_TELEMETRY_ENV, "1")
    model_telemetry.note_learning(2, {"grad_sum": 1.5, "grad_nonfinite": 0.0})
    model_telemetry.note_eval(2, "validation", "logloss", 0.4)
    model_telemetry.maybe_install_drift(_uniform_baseline())
    server = fleet.StatusServer(0).start()
    try:
        with urllib.request.urlopen(
            "http://127.0.0.1:{}/status".format(server.port), timeout=10
        ) as resp:
            doc = json.loads(resp.read())
    finally:
        server.stop()
    assert doc["schema_version"] == fleet.STATUS_SCHEMA_VERSION
    assert doc["learning"]["last_round"]["round"] == 2
    assert doc["learning"]["curve"]["best_iteration"] == 2
    assert doc["drift"]["psi_max"] == pytest.approx(0.2)
    assert doc["drift"]["rows"] == 0


def test_status_omits_model_sections_when_unarmed(model_env):
    server = fleet.StatusServer(0).start()
    try:
        with urllib.request.urlopen(
            "http://127.0.0.1:{}/status".format(server.port), timeout=10
        ) as resp:
            doc = json.loads(resp.read())
    finally:
        server.stop()
    assert doc["schema_version"] == fleet.STATUS_SCHEMA_VERSION
    assert "learning" not in doc and "drift" not in doc


def test_manifest_stamps_learning_and_baseline(model_env, tmp_path, capsys):
    model_env.setenv(model_telemetry.MODEL_TELEMETRY_ENV, "1")
    bst = _train_tiny(evals=True, monitor=True)
    capsys.readouterr()
    path = str(tmp_path / "xgboost-model")
    bst.save_model(path)
    baseline = model_telemetry.drift_baseline()
    assert baseline is not None and len(baseline["features"]) == 5
    for feature in baseline["features"]:
        assert len(feature["fracs"]) == len(feature["cuts"]) + 2
        assert sum(feature["fracs"]) == pytest.approx(1.0, abs=1e-3)
    integrity.write_manifest(
        path,
        learning=model_telemetry.learning_summary(),
        drift_baseline=baseline,
    )
    manifest = integrity.read_manifest(path)
    assert manifest["learning"]["metric"] == "logloss"
    assert manifest["drift_baseline"]["rows"] == 192
    # unarmed funnel: both accessors are None and the keys stay absent
    model_telemetry._reset_for_tests()
    doc = integrity.build_manifest(
        path, learning=model_telemetry.learning_summary(),
        drift_baseline=model_telemetry.drift_baseline(),
    )
    assert "learning" not in doc and "drift_baseline" not in doc
