"""Hierarchical tracing & attribution plane (telemetry/tracing.py).

Covers the tracer core (nesting, cross-thread propagation, ring bound, the
disabled fast path), Chrome-trace export validity, the end-to-end training
tree (round -> {collective, checkpoint, compile}), the flight-recorder dump
on a watchdog abort (exit 79), correlation-id -> trace-id propagation
across the serving batcher's worker thread, and device-sync attribution
(SM_TRACE_DEVICE_SYNC).
"""

import json
import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.serving.batcher import PredictBatcher
from sagemaker_xgboost_container_tpu.telemetry import tracing
from sagemaker_xgboost_container_tpu.telemetry.cluster import (
    _on_jax_duration_event,
)
from sagemaker_xgboost_container_tpu.telemetry.correlation import (
    set_request_id,
    clear_request_id,
)
from sagemaker_xgboost_container_tpu.telemetry.registry import MetricsRegistry
from sagemaker_xgboost_container_tpu.telemetry.spans import span
from sagemaker_xgboost_container_tpu.telemetry.wsgi import instrument_wsgi
from sagemaker_xgboost_container_tpu.training import watchdog
from sagemaker_xgboost_container_tpu.training.checkpointing import (
    SaveCheckpointCallBack,
)
from sagemaker_xgboost_container_tpu.training.callbacks import _TimedCallback
from sagemaker_xgboost_container_tpu.training.profiling import RoundTimer


@pytest.fixture
def tracing_on(monkeypatch):
    monkeypatch.setenv("SM_TRACE", "1")
    monkeypatch.delenv("SM_TRACE_EXPORT_DIR", raising=False)
    tracing._reset_for_tests()
    yield
    tracing._reset_for_tests()


@pytest.fixture
def tracing_off(monkeypatch):
    monkeypatch.delenv("SM_TRACE", raising=False)
    tracing._reset_for_tests()
    yield
    tracing._reset_for_tests()


def _records(out, metric):
    needle = '"metric": "{}"'.format(metric)
    return [json.loads(l) for l in out.splitlines() if needle in l]


# ------------------------------------------------------------- tracer core
class TestTracerCore:
    def test_nesting_and_context(self, tracing_on):
        with tracing.trace_span("parent", attributes={"k": 1}) as parent:
            assert tracing.current_context() == (
                parent.trace_id,
                parent.span_id,
            )
            with tracing.trace_span("child") as child:
                assert child.parent_id == parent.span_id
                assert child.trace_id == parent.trace_id
        assert tracing.current_context() is None
        by_name = {s.name: s for s in tracing.snapshot_spans()}
        assert by_name["child"].parent_id == by_name["parent"].span_id
        assert by_name["parent"].attributes["k"] == 1
        assert by_name["parent"].dur_us >= by_name["child"].dur_us

    def test_cross_thread_explicit_parent(self, tracing_on):
        with tracing.trace_span("root") as root:
            ctx = tracing.current_context()
        seen = {}

        def worker():
            with tracing.trace_span("hop", parent=ctx) as s:
                seen["trace"] = s.trace_id
                seen["parent"] = s.parent_id

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        t.join(5)
        assert seen["trace"] == root.trace_id
        assert seen["parent"] == root.span_id

    def test_record_span_is_retroactive(self, tracing_on):
        with tracing.trace_span("round"):
            tracing.record_span("xla.compile", duration_s=0.5)
        spans = {s.name: s for s in tracing.snapshot_spans()}
        compiled = spans["xla.compile"]
        assert compiled.parent_id == spans["round"].span_id
        assert compiled.dur_us == pytest.approx(5e5)

    def test_ring_buffer_bounded(self, tracing_on, monkeypatch):
        monkeypatch.setenv("SM_TRACE_BUFFER", "32")
        tracing._reset_for_tests()
        for i in range(100):
            tracing.record_span("s{}".format(i))
        spans = tracing.snapshot_spans()
        assert len(spans) == 32
        assert spans[-1].name == "s99"

    def test_open_spans_in_dump_snapshot(self, tracing_on):
        open_span = tracing.start_span("wedged")
        spans = tracing.snapshot_spans(include_open=True)
        flagged = [s for s in spans if s.attributes.get("in_flight")]
        assert [s.name for s in flagged] == ["wedged"]
        tracing.finish_span(open_span)


# -------------------------------------------------------- disabled fast path
class TestDisabledFastPath:
    def test_span_layer_never_touches_tracer(self, tracing_off, monkeypatch):
        assert tracing.enabled() is False

        def boom(*args, **kwargs):
            raise AssertionError("tracer touched with SM_TRACE unset")

        monkeypatch.setattr(tracing, "start_span", boom)
        before = threading.active_count()
        with span("phase_guard"):
            pass
        timer = RoundTimer(log_every=0, emit_structured=False)
        timer.before_training(None)
        timer.after_iteration(None, 0, {})
        timer.after_training(None)
        assert threading.active_count() == before  # tracing adds no threads

    def test_no_spans_recorded_when_disabled(self, tracing_off):
        with span("phase_guard2"):
            pass
        with tracing.trace_span("direct") as s:
            assert s is None
        assert tracing.record_span("x") is None
        assert tracing.snapshot_spans() == []

    def test_fast_path_overhead_is_small(self, tracing_off):
        # generous absolute guard: the disabled check must stay a cached
        # boolean, not an env read or lock per call
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            tracing.enabled()
        per_call = (time.perf_counter() - t0) / n
        assert per_call < 50e-6


# ------------------------------------------------------------ chrome export
class TestChromeExport:
    def test_export_is_valid_chrome_trace(self, tracing_on, tmp_path, capsys):
        with tracing.trace_span("outer"):
            with tracing.trace_span("inner"):
                time.sleep(0.002)
        path = tracing.export_traces(default_dir=str(tmp_path))
        assert path is not None
        with open(path) as f:
            doc = json.load(f)
        assert doc["otherData"]["rank"] == 0
        events = doc["traceEvents"]
        assert any(e["ph"] == "M" and e["name"] == "process_name" for e in events)
        complete = [e for e in events if e["ph"] == "X"]
        by_id = {e["args"]["span_id"]: e for e in complete}
        inner = next(e for e in complete if e["name"] == "inner")
        outer = by_id[inner["args"]["parent_id"]]
        assert outer["name"] == "outer"
        # containment: child window inside parent window (microseconds)
        assert inner["ts"] >= outer["ts"] - 1
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
        # export is announced as a structured record
        recs = _records(capsys.readouterr().out, "training.trace_export")
        assert recs and recs[-1]["path"] == path

    def test_export_respects_env_dir_and_rank(
        self, tracing_on, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("SM_TRACE_EXPORT_DIR", str(tmp_path / "sub"))
        tracing.set_rank(3)
        tracing.record_span("x")
        path = tracing.export_traces(default_dir="/nonexistent-ignored")
        assert path == str(tmp_path / "sub" / "trace-rank3.json")
        with open(path) as f:
            doc = json.load(f)
        assert doc["otherData"]["rank"] == 3

    def test_export_noop_when_disabled(self, tracing_off, tmp_path):
        assert tracing.export_traces(default_dir=str(tmp_path)) is None
        assert list(tmp_path.iterdir()) == []


# --------------------------------------------------------- training e2e tree
@pytest.mark.multichip
def test_training_trace_tree_nests_round_children(
    tracing_on, tmp_path, monkeypatch
):
    """A traced mesh training run exports a consistent parent/child tree:
    round spans own the collective dispatch, the checkpoint save (and its
    manifest), and the XLA compile events of that round."""
    monkeypatch.setenv("GRAFT_HIST_COMM_CALIBRATE", "0")
    devices = np.array(jax.devices()[:8])
    mesh = Mesh(devices, axis_names=("data",))
    rng = np.random.RandomState(0)
    X = rng.randn(512, 11).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)

    class _FakeCompile:
        # deterministic stand-in for a real backend_compile_duration event
        # (CPU backends may not emit them) — goes through the REAL listener
        def after_iteration(self, model, epoch, evals_log):
            if epoch == 0:
                _on_jax_duration_event("/jax/xla/backend_compile_duration", 0.01)
            return False

    ckpt_dir = tmp_path / "ckpt"
    callbacks = [
        _FakeCompile(),
        _TimedCallback(
            SaveCheckpointCallBack(str(ckpt_dir), num_round=3), "checkpoint"
        ),
        RoundTimer(log_every=0, emit_structured=False),
    ]
    train(
        {"objective": "binary:logistic", "max_depth": 3, "seed": 7},
        DataMatrix(X, labels=y),
        num_boost_round=3,
        callbacks=callbacks,
        mesh=mesh,
    )
    path = tracing.export_traces(default_dir=str(tmp_path))
    with open(path) as f:
        doc = json.load(f)
    complete = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    by_id = {e["args"]["span_id"]: e for e in complete}

    def _round_ancestor(event):
        seen = set()
        while event is not None and event["args"].get("span_id") not in seen:
            seen.add(event["args"]["span_id"])
            if event["name"] == "round":
                return event
            parent = event["args"].get("parent_id")
            event = by_id.get(parent)
        return None

    rounds = [e for e in complete if e["name"] == "round"]
    assert len(rounds) >= 3
    for child_name in (
        "checkpoint.save",
        "checkpoint.manifest",
        "xla.compile",
    ):
        children = [e for e in complete if e["name"] == child_name]
        assert children, "no {} spans exported".format(child_name)
        assert any(
            _round_ancestor(c) is not None for c in children
        ), "{} has no round ancestor".format(child_name)
    # the checkpoint save sits under the callback's phase span, which sits
    # under the round: a three-level chain, not a flat list
    save = next(e for e in complete if e["name"] == "checkpoint.save")
    phase = by_id.get(save["args"].get("parent_id"))
    assert phase is not None and phase["name"] == "checkpoint"


# --------------------------------------------------- flight recorder (chaos)
@pytest.mark.chaos
def test_watchdog_abort_dumps_flight_recorder(
    tracing_on, tmp_path, monkeypatch, capsys
):
    """Exit-79 drill: request_abort leaves a flight-recorder dump on disk
    carrying the wedged (still-open) round span, and the training.abort
    record names the dump path."""
    monkeypatch.setenv("SM_TRACE_EXPORT_DIR", str(tmp_path))
    codes = []
    monkeypatch.setattr(watchdog, "_exit", codes.append)
    watchdog._reset_abort_for_tests()
    wedged = tracing.start_span("round", attributes={"round": 5})
    tracing.record_span("collective.dispatch", duration_s=0.001)
    try:
        watchdog.request_abort("round_deadline", 79, last_round=5)
    finally:
        tracing.finish_span(wedged)
        watchdog._reset_abort_for_tests()
    assert codes == [79]
    dump = tmp_path / "flight-recorder-rank0.json"
    assert dump.is_file()
    doc = json.loads(dump.read_text())
    assert doc["otherData"]["abort_reason"] == "round_deadline"
    assert doc["otherData"]["exit_code"] == 79
    in_flight = [
        e
        for e in doc["traceEvents"]
        if e["ph"] == "X" and e["args"].get("in_flight")
    ]
    assert any(e["name"] == "round" for e in in_flight)
    aborts = _records(capsys.readouterr().out, "training.abort")
    assert aborts and aborts[-1]["flight_recorder"] == str(dump)


@pytest.mark.chaos
def test_abort_dump_defaults_to_durable_checkpoint_dir(
    tracing_on, tmp_path, monkeypatch, capsys
):
    """Without SM_TRACE_EXPORT_DIR the dump must land somewhere the
    platform uploads — the live checkpoint dir — not a cwd that dies with
    the container."""
    monkeypatch.delenv("SM_TRACE_EXPORT_DIR", raising=False)
    ckpt_dir = tmp_path / "ckpt"
    saver = SaveCheckpointCallBack(str(ckpt_dir))
    codes = []
    monkeypatch.setattr(watchdog, "_exit", codes.append)
    watchdog._reset_abort_for_tests()
    try:
        watchdog.request_abort("round_deadline", 79)
    finally:
        watchdog._reset_abort_for_tests()
        saver.stop()
    assert codes == [79]
    assert (ckpt_dir / "flight-recorder-rank0.json").is_file()


@pytest.mark.chaos
def test_abort_dump_failure_never_blocks_exit(
    tracing_on, monkeypatch, capsys
):
    monkeypatch.setenv("SM_TRACE_EXPORT_DIR", "/proc/definitely-unwritable")
    codes = []
    monkeypatch.setattr(watchdog, "_exit", codes.append)
    watchdog._reset_abort_for_tests()
    try:
        watchdog.request_abort("round_deadline", 79)
    finally:
        watchdog._reset_abort_for_tests()
    assert codes == [79]
    aborts = _records(capsys.readouterr().out, "training.abort")
    assert aborts and "flight_recorder" not in aborts[-1]


# ------------------------------------------------- serving trace propagation
class TestServingPropagation:
    def test_wsgi_span_trace_id_matches_echoed_header(self, tracing_on):
        def app(environ, start_response):
            start_response("200 OK", [("Content-Type", "text/plain")])
            return [b"ok"]

        wrapped = instrument_wsgi(app, registry=MetricsRegistry())
        captured = {}

        def start_response(status, headers, exc_info=None):
            captured.update(dict(headers))

        wrapped(
            {
                "PATH_INFO": "/invocations",
                "REQUEST_METHOD": "POST",
                "HTTP_X_REQUEST_ID": "trace-me-1",
            },
            start_response,
        )
        assert captured["X-Request-Id"] == "trace-me-1"
        reqs = [
            s for s in tracing.snapshot_spans() if s.name == "http.request"
        ]
        assert reqs and reqs[-1].trace_id == "trace-me-1"
        assert reqs[-1].attributes["status"] == "200"

    def test_custom_attributes_header_feeds_trace_id(self, tracing_on):
        def app(environ, start_response):
            start_response("200 OK", [])
            return [b"ok"]

        wrapped = instrument_wsgi(app, registry=MetricsRegistry())
        headers = {}
        wrapped(
            {
                "PATH_INFO": "/invocations",
                "REQUEST_METHOD": "POST",
                "HTTP_X_AMZN_SAGEMAKER_CUSTOM_ATTRIBUTES": "trace_id=cust-77",
            },
            lambda status, h, exc_info=None: headers.update(dict(h)),
        )
        assert headers["X-Request-Id"] == "cust-77"
        reqs = [
            s for s in tracing.snapshot_spans() if s.name == "http.request"
        ]
        assert reqs[-1].trace_id == "cust-77"

    def test_batcher_worker_span_carries_request_trace(self, tracing_on):
        batcher = PredictBatcher(
            lambda feats: feats.sum(axis=1),
            max_batch_rows=256,
            registry=MetricsRegistry(),
            name="trace-test",
        )
        set_request_id("req-abc")
        root = tracing.start_span(
            "http.request", trace_id="req-abc", root=True
        )
        try:
            # 64 rows > GRAFT_HOST_PREDICT_ROWS default: queue path, so the
            # dispatch runs on the worker thread
            out = batcher.predict(np.ones((64, 4), np.float32))
        finally:
            tracing.finish_span(root)
            clear_request_id()
        assert out.shape == (64,)
        spans = tracing.snapshot_spans()
        queue_spans = [s for s in spans if s.name == "batcher.queue"]
        dispatch = [s for s in spans if s.name == "batcher.dispatch"]
        assert queue_spans and queue_spans[-1].trace_id == "req-abc"
        assert dispatch, "worker never traced the dispatch"
        assert dispatch[-1].trace_id == "req-abc"
        assert dispatch[-1].tid != threading.get_ident()
        assert dispatch[-1].attributes["rows"] == 64

    def test_full_request_path_joins_one_trace(self, tracing_on):
        """WSGI -> app -> batcher queue -> worker dispatch: one trace id,
        the one echoed to the client."""
        from sagemaker_xgboost_container_tpu.serving.app import make_app

        class _Svc:
            model = object()
            model_format = "json"
            objective = "reg:squarederror"
            num_class = ""

            def __init__(self):
                self._batcher = PredictBatcher(
                    lambda feats: np.asarray(feats)[:, 0],
                    registry=MetricsRegistry(),
                    name="trace-e2e",
                )

            def load_model(self):
                return self.model_format

            def predict(self, dtest, content_type):
                return self._batcher.predict(
                    np.asarray(dtest.features, np.float32)
                )

        app = make_app(scoring_service=_Svc())
        body = ("\n".join("{0}.0,2.0,3.0".format(i) for i in range(64))).encode()
        import io

        headers = {}

        def start_response(status, hdrs, exc_info=None):
            headers["status"] = status
            headers.update(dict(hdrs))

        result = app(
            {
                "PATH_INFO": "/invocations",
                "REQUEST_METHOD": "POST",
                "CONTENT_TYPE": "text/csv",
                "CONTENT_LENGTH": str(len(body)),
                "HTTP_X_REQUEST_ID": "joined-1",
                "wsgi.input": io.BytesIO(body),
            },
            start_response,
        )
        assert headers["status"].startswith("200"), result
        assert headers["X-Request-Id"] == "joined-1"
        spans = tracing.snapshot_spans()
        names = {
            s.name for s in spans if s.trace_id == "joined-1"
        }
        assert {"http.request", "batcher.queue", "batcher.dispatch"} <= names


# ------------------------------------------------------ device-sync sampling
def test_device_sync_phases_and_attribution_record(monkeypatch, capsys):
    """SM_TRACE_DEVICE_SYNC=1 splits each dispatch into host_dispatch /
    device_sync phases_ms keys and the run ends with one
    training.attribution record (works without SM_TRACE — the phase layer
    is always on)."""
    monkeypatch.setenv("SM_TRACE_DEVICE_SYNC", "1")
    rng = np.random.RandomState(0)
    X = rng.rand(300, 4).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    train(
        {"objective": "binary:logistic", "max_depth": 3},
        DataMatrix(X, labels=y),
        num_boost_round=3,
        callbacks=[RoundTimer(num_rows=300, log_every=0)],
    )
    out = capsys.readouterr().out
    rounds = _records(out, "training.round")
    assert rounds
    assert any(
        "host_dispatch" in r["phases_ms"] and "device_sync" in r["phases_ms"]
        for r in rounds
    )
    attr = _records(out, "training.attribution")
    assert len(attr) == 1
    rec = attr[0]
    for key in (
        "compile_ms",
        "host_ms",
        "device_ms",
        "collective_ms",
        "compile_pct",
        "host_pct",
        "device_pct",
        "collective_pct",
        "total_ms",
    ):
        assert key in rec, key
    assert rec["rounds"] == 3
    assert rec["host_ms"] > 0.0


def test_device_sync_off_still_splits_every_dispatch(monkeypatch, capsys):
    """With SM_TRACE_DEVICE_SYNC unset every dispatch still records its
    host_dispatch / device_sync phases (the tree transfer blocks anyway);
    the variable only adds the block_until_ready fence."""
    import jax

    monkeypatch.delenv("SM_TRACE_DEVICE_SYNC", raising=False)
    fences = []
    real_fence = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready", lambda x: fences.append(1) or real_fence(x)
    )
    rng = np.random.RandomState(1)
    X = rng.rand(200, 4).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    train(
        {"objective": "binary:logistic", "max_depth": 3},
        DataMatrix(X, labels=y),
        num_boost_round=2,
        callbacks=[RoundTimer(log_every=0)],
    )
    rounds = _records(capsys.readouterr().out, "training.round")
    assert rounds
    for rec in rounds:
        assert rec["phases_ms"]["host_dispatch"] >= 0.0
        assert rec["phases_ms"]["device_sync"] > 0.0
        # the covering spans stay out of the flat per-round phases
        assert "host_turnaround" not in rec["phases_ms"]
        assert "callbacks" not in rec["phases_ms"]
    assert fences == []  # no fence was added
