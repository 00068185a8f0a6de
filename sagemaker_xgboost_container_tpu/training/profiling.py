"""First-class training profiling (SURVEY.md §5: the reference's only tracing
was wall-clock tracker logs; smdebug was installed but disabled).

Three light-weight hooks:

* ``RoundTimer`` — per-round wall time + throughput. Always feeds the
  telemetry layer: every round emits one structured JSON stdout record
  (``training.round``) carrying the round latency and a per-phase breakdown
  (the span recorder drains into it), and observes the
  ``training_round_seconds`` registry histogram. Human-readable per-round
  log lines stay opt-in via ``log_every`` (SM_ROUND_TIMING); the end-of-run
  summary reports mean, p50, and p95.
* ``xla_trace`` — context manager around training that writes a JAX profiler
  trace (TensorBoard-viewable) when ``SM_PROFILER_TRACE_DIR`` is set.
* the span API (``telemetry.span``) — algorithm_train wraps data ingest,
  the boosting loop, and model save in named phases.
"""

import contextlib
import logging
import os
import time

from ..telemetry import (
    REGISTRY,
    ROUND_STATE,
    compile_stats,
    emit_metric,
    get_round_fields,
    pop_recorder,
    push_recorder,
    tracing,
)
from ..telemetry import percentile  # noqa: F401  (canonical home: telemetry.registry)
from ..telemetry import device as device_telemetry
from ..utils.envconfig import env_int
from ..utils.faults import fault_point

logger = logging.getLogger(__name__)

TRACE_DIR_ENV = "SM_PROFILER_TRACE_DIR"

#: emit a rolling ``training.attribution`` record every N rounds (0 = only
#: the final one at after_training) — a week-long job surfaces attribution
#: mid-flight instead of only at the end, and /status reads the same data
ATTRIBUTION_EVERY_ENV = "SM_ATTRIBUTION_EVERY"

ROUND_HISTOGRAM = "training_round_seconds"


class RoundTimer:
    """Per-round timing callback; rides the standard booster protocol.

    ``emit_structured`` controls the per-round ``training.round`` stdout
    record (default on; SM_STRUCTURED_METRICS=false silences it globally).
    ``log_every=0`` disables the human-readable per-round log lines while
    keeping the structured emission and the end-of-run summary.
    ``fold`` tags every record in k-fold CV runs (each fold trains its own
    callback stack, so per-epoch records from different folds must stay
    distinguishable for the CloudWatch regexes).
    """

    def __init__(self, num_rows=None, log_every=10, emit_structured=True, fold=None):
        self.num_rows = num_rows
        self.log_every = log_every
        self.emit_structured = emit_structured
        self.fold = fold
        self._attr_every = env_int(ATTRIBUTION_EVERY_ENV, 0, minimum=0)
        # HBM watermark cadence (SM_DEVICE_TELEMETRY + SM_HBM_SAMPLE_EVERY):
        # 0 when the device plane is unarmed — resolved once here so the
        # per-round path never reads env
        self._hbm_every = device_telemetry.sample_cadence()
        self._last = None
        self._times = []
        self._recorder = None
        self._round_span = None
        self._compile_base = None
        self._compile_total_s = 0.0
        self._phase_totals = {}

    def before_training(self, model):
        self._last = time.perf_counter()
        # collect span phases (checkpoint saves, eval monitor, ...) per round;
        # popped in after_training. Thread-local, so parallel fold loops on
        # other threads never cross-talk.
        self._recorder = push_recorder()
        # per-round compile accounting: XLA compiles completed during a
        # round (the jax.monitoring listener feeds compile_stats) become a
        # `compile` phase key instead of silently inflating build_eval
        self._compile_base = compile_stats()["seconds"]
        self._compile_total_s = 0.0
        self._phase_totals = {}
        if tracing.enabled():
            # per-round ROOT span: stays open for the whole round, so the
            # phase spans (checkpoint, consensus, eval_monitor, ...) and the
            # booster's dispatch/collective/compile spans nest under it
            self._round_span = tracing.start_span("round")
        return model

    def after_iteration(self, model, epoch, evals_log):
        # chaos hook: the one per-round fault point every training run owns
        # (RoundTimer is always in the stack) — lets drills stall a round
        # (watchdog tests) or deliver SIGTERM mid-training deterministically
        fault_point("training.round_end", round=epoch)
        now = time.perf_counter()
        if self._last is not None:
            elapsed = now - self._last
            self._times.append(elapsed)
            REGISTRY.histogram(
                ROUND_HISTOGRAM, help="Boosting round wall time"
            ).observe(elapsed)
            # feed the cluster heartbeat's round state (telemetry/cluster.py):
            # a deque append under a lock — negligible, so always on
            ROUND_STATE.note_round(epoch, elapsed)
            if self._hbm_every and epoch % self._hbm_every == 0:
                # per-round HBM watermark (shares the cached device-memory
                # walk with the heartbeat plane; ships to rank 0 with the
                # next span frame)
                device_telemetry.sample_watermark(epoch)
            phases = self._recorder.drain() if self._recorder is not None else {}
            compile_now = compile_stats()["seconds"]
            compile_delta = (
                max(compile_now - self._compile_base, 0.0)
                if self._compile_base is not None
                else 0.0
            )
            self._compile_base = compile_now
            self._compile_total_s += compile_delta
            # NOTE: a compile that completes inside a dispatch is already
            # subtracted from the host_dispatch phase at the source
            # (booster._timed_dispatch measures the exact overlap),
            # so compile + host_dispatch + build_eval sum without double
            # counting; values only clamp here against float noise
            for name, seconds in phases.items():
                self._phase_totals[name] = (
                    self._phase_totals.get(name, 0.0) + seconds
                )
            if self.emit_structured:
                # callback work is measured by its spans; XLA compiles that
                # completed this round get their own key; the remainder is
                # device compute: binning (first round), tree build, eval.
                # One record per round — the CloudWatch-regex contract.
                overhead = sum(phases.values())
                phases_ms = {
                    k: round(max(v, 0.0) * 1000, 3)
                    for k, v in sorted(phases.items())
                }
                if compile_delta > 0:
                    phases_ms["compile"] = round(compile_delta * 1000, 3)
                phases_ms["build_eval"] = round(
                    max(elapsed - overhead - compile_delta, 0.0) * 1000, 3
                )
                fields = {
                    "round": epoch,
                    "round_ms": round(elapsed * 1000, 3),
                    "phases_ms": phases_ms,
                }
                # session-owned extras (hist_comm + per-round collective
                # bytes/ms on a mesh — see booster.py)
                fields.update(get_round_fields())
                if self.fold is not None:
                    fields["fold"] = self.fold
                if self.num_rows and elapsed > 0:
                    fields["rows_per_sec"] = round(self.num_rows / elapsed, 1)
                emit_metric("training.round", **fields)
            if (
                self.emit_structured
                and self._attr_every
                and (epoch + 1) % self._attr_every == 0
            ):
                self._emit_attribution(
                    sum(self._times), rolling=True, round_index=epoch
                )
            if self.log_every and (epoch + 1) % self.log_every == 0:
                recent = self._times[-self.log_every :]
                mean = sum(recent) / len(recent)
                msg = "round {}: {:.1f} ms/round".format(epoch, mean * 1000)
                if self.num_rows and mean > 0:
                    msg += " ({:.2f}M rows/sec)".format(
                        self.num_rows / mean / 1e6
                    )
                logger.info(msg)
        if self._round_span is not None:
            # RoundTimer is last in the callback stack, so every phase span
            # of round `epoch` has already closed under this span; rotate
            tracing.finish_span(self._round_span, round=epoch)
            self._round_span = tracing.start_span("round")
        self._last = now
        return False

    def after_training(self, model):
        if self._round_span is not None:
            # the span opened after the last round covers post-training
            # callback work (final checkpoint flush, early-stopping trim)
            tracing.finish_span(self._round_span, tail=True)
            self._round_span = None
        if self._recorder is not None:
            pop_recorder(self._recorder)
            self._recorder = None
        if self._times:
            total = sum(self._times)
            p50 = percentile(self._times, 0.5)
            p95 = percentile(self._times, 0.95)
            # guard: a ~0 total (trivial data, coarse clocks) must not divide
            rate = len(self._times) / total if total > 0 else float("inf")
            logger.info(
                "trained %d rounds in %.2fs (%.2f rounds/sec, "
                "p50 %.1f ms, p95 %.1f ms)",
                len(self._times),
                total,
                rate,
                p50 * 1000,
                p95 * 1000,
            )
            if self.emit_structured:
                fields = {
                    "rounds": len(self._times),
                    "total_s": round(total, 3),
                    "p50_ms": round(p50 * 1000, 3),
                    "p95_ms": round(p95 * 1000, 3),
                }
                if self.fold is not None:
                    fields["fold"] = self.fold
                emit_metric("training.summary", **fields)
                self._emit_attribution(total)
        return model

    def _emit_attribution(self, total_s, rolling=False, round_index=None):
        """One ``training.attribution`` record: where the run's wall time
        went — XLA compile (the jax.monitoring listener), host dispatch /
        device compute (the `host_dispatch` / `device_sync` spans every
        dispatch records), and the calibrated histogram collectives. Fields
        are 0.0 when the matching instrumentation wasn't armed, so the
        record shape is stable.

        ``rolling=True`` marks the SM_ATTRIBUTION_EVERY mid-job emissions
        (cumulative since the start of training — same shape, plus the
        round index) so CloudWatch regexes can tell them from the final
        after_training record."""
        comm_per_round = get_round_fields().get("hist_comm_ms") or 0.0
        fields = attribution_fields(
            total_ms=total_s * 1000.0,
            compile_ms=self._compile_total_s * 1000.0,
            host_ms=max(self._phase_totals.get("host_dispatch", 0.0), 0.0)
            * 1000.0,
            device_ms=self._phase_totals.get("device_sync", 0.0) * 1000.0,
            collective_ms=float(comm_per_round) * len(self._times),
        )
        fields["rounds"] = len(self._times)
        if rolling:
            fields["rolling"] = True
        if round_index is not None:
            fields["round"] = round_index
        if self.fold is not None:
            fields["fold"] = self.fold
        emit_metric("training.attribution", **fields)
        # publish the same shape to the rank-0 /status endpoint (inert — a
        # dict update — when the fleet plane never starts)
        from ..telemetry import fleet

        fleet.note_attribution(fields)


def attribution_fields(total_ms, compile_ms, host_ms, device_ms, collective_ms):
    """The shared compile/host/device/collective attribution shape — stable
    keys for CloudWatch regexes, used by the ``training.attribution``
    record. Percentages are shares of
    ``total_ms`` (0.0 when the window is empty)."""

    def pct(ms):
        return round(ms / total_ms * 100.0, 1) if total_ms > 0 else 0.0

    return {
        "total_ms": round(total_ms, 3),
        "compile_ms": round(compile_ms, 3),
        "host_ms": round(host_ms, 3),
        "device_ms": round(device_ms, 3),
        "collective_ms": round(collective_ms, 3),
        "compile_pct": pct(compile_ms),
        "host_pct": pct(host_ms),
        "device_pct": pct(device_ms),
        "collective_pct": pct(collective_ms),
    }


@contextlib.contextmanager
def xla_trace():
    """Capture a JAX profiler trace when SM_PROFILER_TRACE_DIR is set.

    Hardened: the trace is diagnostics, never a correctness dependency — the
    directory is created when missing, and a profiler that refuses to start
    (already-active session, unwritable volume) or to stop logs a warning
    and lets training proceed/finish. A successful capture emits one
    ``training.trace`` record carrying the output path, so the artifact is
    discoverable from the job log alone.
    """
    trace_dir = os.environ.get(TRACE_DIR_ENV)
    if not trace_dir:
        yield
        return
    import jax

    started = False
    try:
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        started = True
    except Exception as e:
        logger.warning(
            "could not start XLA profiler trace in %s (%s); training "
            "continues untraced",
            trace_dir,
            e,
        )
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                logger.warning(
                    "XLA profiler stop_trace failed (%s); trace in %s may "
                    "be incomplete",
                    e,
                    trace_dir,
                )
            else:
                logger.info("Wrote XLA profiler trace to %s", trace_dir)
                emit_metric("training.trace", trace_dir=trace_dir)
