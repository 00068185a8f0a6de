"""Fleet observability plane (telemetry/fleet.py).

Covers the 2-rank loopback drill (real traced training on rank 0 slowed by
the SM_FAULT_SPEC sleep action + a synthetic fast rank 1 shipping through
the real framed-TCP path -> one merged trace-fleet.json with both pid lanes
sharing round ids and a training.skew record naming the slow rank + phase),
the unset-knob guard (no threads, no sockets, no spans shipped), the
collector's skew fold per phase, the /status + /debug/flight payload
shapes, and the SIGQUIT inspection dump (kill -3 without aborting).
"""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.telemetry import fleet, tracing
from sagemaker_xgboost_container_tpu.telemetry.registry import MetricsRegistry
from sagemaker_xgboost_container_tpu.training.profiling import RoundTimer
from sagemaker_xgboost_container_tpu.utils import faults
from tests.util_ports import free_port


def _wait_for(predicate, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return predicate()


@pytest.fixture
def fleet_env(monkeypatch):
    for knob in (
        fleet.FLEET_TRACE_ENV,
        fleet.FLEET_TRACE_PORT_ENV,
        fleet.FLEET_FLUSH_ENV,
        fleet.STATUS_PORT_ENV,
    ):
        monkeypatch.delenv(knob, raising=False)
    monkeypatch.setenv("SM_TRACE", "1")
    tracing._reset_for_tests()
    fleet._reset_for_tests()
    yield monkeypatch
    fleet._reset_for_tests()
    tracing._reset_for_tests()
    faults.reset()


def _records(out, metric):
    needle = '"metric": "{}"'.format(metric)
    return [json.loads(l) for l in out.splitlines() if needle in l]


def _wire_round(rank, round_index, dur_us, phases=()):
    """Synthetic wire spans for one round: phase children then the root."""
    base = float(round_index) * 1_000_000.0
    spans = []
    for i, (name, phase_dur_us) in enumerate(phases):
        spans.append(
            {
                "name": name,
                "trace_id": "t{}-{}".format(rank, round_index),
                "span_id": "s{}-{}-{}".format(rank, round_index, i),
                "start_us": base + i,
                "dur_us": float(phase_dur_us),
                "tid": 1,
                "thread_name": "MainThread",
            }
        )
    spans.append(
        {
            "name": "round",
            "trace_id": "t{}-{}".format(rank, round_index),
            "span_id": "s{}-{}-root".format(rank, round_index),
            "start_us": base,
            "dur_us": float(dur_us),
            "tid": 1,
            "thread_name": "MainThread",
            "attributes": {"round": round_index},
        }
    )
    return spans


# ------------------------------------------------------------ knob guard
class TestUnsetKnobGuard:
    def test_no_plane_no_threads_no_spans(self, fleet_env):
        before = set(threading.enumerate())
        assert fleet.start_fleet_plane(["a", "b"], "a") is None
        assert fleet.active_plane() is None
        assert set(threading.enumerate()) == before
        # spans finish locally but nothing ships: the seq watermark exists,
        # yet no shipper thread was ever created to read it
        with tracing.trace_span("round", attributes={"round": 0}):
            pass
        assert set(threading.enumerate()) == before

    def test_stop_when_inert_is_safe(self, fleet_env):
        fleet.stop_fleet_plane()
        assert fleet.export_fleet_trace(default_dir=".") is None


# --------------------------------------------------------- loopback drill
class TestTwoRankLoopback:
    def test_merged_trace_and_skew_attribution(self, fleet_env, tmp_path, capfd):
        fleet_env.setenv(fleet.FLEET_TRACE_ENV, "1")
        fleet_env.setenv(fleet.FLEET_TRACE_PORT_ENV, str(free_port()))
        fleet_env.setenv(fleet.FLEET_FLUSH_ENV, "0.2")
        # rank 0 is the injected-slow rank: every round_end stalls outside
        # any instrumented phase span, so the excess must classify as wire
        faults.configure("training.round_end:sleep:0.05")
        tracing.set_rank(0)
        plane = fleet.start_fleet_plane(["algo-1", "algo-2"], "algo-1")
        assert plane is not None and plane.collector is not None
        rounds = 3
        rng = np.random.RandomState(0)
        X = rng.rand(128, 4).astype(np.float32)
        y = (X[:, 0] > 0.5).astype(np.float32)
        train(
            {"objective": "binary:logistic", "max_depth": 2},
            DataMatrix(X, labels=y),
            num_boost_round=rounds,
            callbacks=[RoundTimer(num_rows=128, log_every=0, emit_structured=False)],
        )
        # synthetic fast rank 1: same round ids, millisecond rounds
        rank1 = []
        for r in range(rounds):
            rank1.extend(
                _wire_round(1, r, dur_us=1000.0, phases=(("host_dispatch", 300.0),))
            )
        shipper = fleet.SpanShipper(
            rank=1,
            host="algo-2",
            collector_addr=("127.0.0.1", plane.collector.port),
            interval=0.2,
            span_source=lambda: rank1,
        )
        assert shipper.send_once()
        path = fleet.export_fleet_trace(default_dir=str(tmp_path))
        assert path and os.path.isfile(path)
        with open(path) as f:
            doc = json.load(f)
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert {e["pid"] for e in spans} == {0, 1}
        round_ids = {}
        for e in spans:
            if e["name"] == "round" and "round" in e.get("args", {}):
                round_ids.setdefault(e["pid"], set()).add(e["args"]["round"])
        assert round_ids[0] & round_ids[1] == set(range(rounds))
        reports = plane.collector.skew_snapshot()
        assert len(reports) == rounds
        for report in reports:
            assert report["critical_rank"] == 0
            assert report["phase"] == "wire"
            # the injected 50 ms stall, halved: a 2-rank median interpolates
            # to the midpoint, so skew = (slow - fast) / 2
            assert report["skew_ms"] >= 20.0
        out = capfd.readouterr().out
        skew_records = _records(out, "training.skew")
        assert len(skew_records) == rounds
        assert all(r["critical_rank"] == 0 for r in skew_records)
        exports = _records(out, "training.fleet_export")
        assert exports and exports[0]["ranks"] == [0, 1]

    def test_shipper_survives_absent_collector(self, fleet_env):
        reg = MetricsRegistry()
        shipper = fleet.SpanShipper(
            rank=1,
            host="algo-2",
            collector_addr=("127.0.0.1", free_port()),
            interval=0.2,
            timeout=0.5,
            span_source=lambda: _wire_round(1, 0, dur_us=100.0),
            registry=reg,
        )
        assert shipper.send_once() is False
        assert shipper._m_failed.value >= 1
        assert len(shipper._pending) > 0  # retained for retry, bounded


# --------------------------------------------------------------- skew fold
class TestSkewFold:
    def test_phase_attribution_collective(self, fleet_env):
        reg = MetricsRegistry()
        collector = fleet.FleetCollector(num_ranks=2, port=0, registry=reg)
        try:
            # rank 1 slow, excess inside collective.dispatch
            collector.fold(
                {
                    "type": "spans",
                    "rank": 0,
                    "spans": _wire_round(
                        0, 0, dur_us=10_000.0, phases=(("collective.dispatch", 1000.0),)
                    ),
                }
            )
            collector.fold(
                {
                    "type": "spans",
                    "rank": 1,
                    "spans": _wire_round(
                        1,
                        0,
                        dur_us=50_000.0,
                        phases=(("collective.dispatch", 41_000.0),),
                    ),
                }
            )
            reports = collector.skew_snapshot()
            assert len(reports) == 1
            assert reports[0]["critical_rank"] == 1
            assert reports[0]["phase"] == "collective"
            assert reports[0]["skew_ms"] == pytest.approx(20.0, abs=0.5)
        finally:
            collector.stop()

    def test_junk_batches_dropped(self, fleet_env):
        reg = MetricsRegistry()
        collector = fleet.FleetCollector(num_ranks=2, port=0, registry=reg)
        try:
            assert collector.fold(None) is False
            assert collector.fold({"type": "nope"}) is False
            assert collector.fold({"type": "spans", "rank": 7, "spans": []}) is False
            assert collector.fold({"type": "spans", "rank": 0, "spans": "x"}) is False
            assert collector.span_counts() == {0: 0, 1: 0}
        finally:
            collector.stop()

    def test_single_rank_round_never_reports(self, fleet_env):
        reg = MetricsRegistry()
        collector = fleet.FleetCollector(num_ranks=1, port=0, registry=reg)
        try:
            collector.fold(
                {"type": "spans", "rank": 0, "spans": _wire_round(0, 0, 5000.0)}
            )
            assert collector.skew_snapshot() == []
        finally:
            collector.stop()


# ------------------------------------------------------------ status plane
class TestStatusEndpoint:
    def test_status_and_flight_payloads(self, fleet_env, tmp_path):
        fleet_env.setenv(fleet.STATUS_PORT_ENV, str(free_port()))
        tracing.set_rank(0)
        plane = fleet.start_fleet_plane(["algo-1"], "algo-1")
        assert plane is not None and plane.status_server is not None
        assert plane.shipper is None and plane.collector is None
        fleet.note_status(
            rounds_planned=10,
            last_checkpoint={"path": str(tmp_path / "ckpt.5"), "round": 5},
        )
        fleet.note_attribution({"total_ms": 123.0, "host_pct": 50.0})
        port = plane.status_server.port
        with tracing.trace_span("round", attributes={"round": 0}):
            with urllib.request.urlopen(
                "http://127.0.0.1:{}/debug/flight".format(port), timeout=5
            ) as resp:
                flight = json.loads(resp.read().decode("utf-8"))
        with urllib.request.urlopen(
            "http://127.0.0.1:{}/status".format(port), timeout=5
        ) as resp:
            status = json.loads(resp.read().decode("utf-8"))
        assert status["rounds_planned"] == 10
        assert status["last_checkpoint"]["round"] == 5
        assert status["attribution"]["total_ms"] == 123.0
        assert "round" in status and "uptime_s" in status
        assert flight["rank"] == 0
        names = {s["name"] for s in flight["spans"]}
        assert "round" in names  # the open span is visible live
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                "http://127.0.0.1:{}/nope".format(port), timeout=5
            )
        assert err.value.code == 404

    def test_backend_init_error_surfaces(self, fleet_env):
        fleet.note_status(backend_init_error="coordinator unreachable")
        assert status_has("backend_init_error", "coordinator unreachable")
        fleet.note_status(backend_init_error=None)
        assert "backend_init_error" not in fleet.status_snapshot()


def status_has(key, value):
    return fleet.status_snapshot().get(key) == value


# ------------------------------------------------------------ sigquit dump
class TestSigquitDump:
    def test_kill_minus_3_dumps_without_aborting(self, fleet_env, tmp_path, capfd):
        fleet_env.setenv("SM_TRACE_EXPORT_DIR", str(tmp_path))
        tracing.set_rank(0)
        with tracing.trace_span("round", attributes={"round": 1}):
            pass
        assert fleet.install_sigquit_handler(default_dir=str(tmp_path)) is True
        try:
            os.kill(os.getpid(), signal.SIGQUIT)
            status_path = tmp_path / "fleet-status-rank0.json"
            assert _wait_for(status_path.is_file, timeout=60)
            # the file exists from the moment the dump thread opens it; the
            # document and the record are there when that thread has ended
            for thread in threading.enumerate():
                if thread.name == "sigquit-dump":
                    thread.join(timeout=60)
                    assert not thread.is_alive()
            with open(str(status_path)) as f:
                doc = json.load(f)
            assert "round" in doc and "uptime_s" in doc
            out = capfd.readouterr().out
            assert _records(out, "training.sigquit_dump")
        finally:
            signal.signal(signal.SIGQUIT, signal.SIG_DFL)
