"""Hierarchical tracing: spans with parent links, a bounded flight recorder,
and Chrome-trace/Perfetto JSON export.

The PR-1 span layer is a flat ``{phase: seconds}`` accumulator: it can say a
round spent 1.4 ms in ``checkpoint``, but not that the checkpoint's manifest
write happened *inside* round 12, or that the first round's 40 s was an XLA
compile and not tree building. This module adds the missing structure while
keeping the dependency-free, env-gated discipline of the rest of the
telemetry layer:

* **Spans** — id + parent link + attributes + wall window, propagated
  through a thread-local stack so nested ``span()``/``trace_span()`` calls
  form a tree without any caller threading context by hand. Cross-thread
  hops (the serving batcher's worker) pass an explicit parent context.
* **Flight recorder** — finished spans land in a bounded ring buffer
  (``SM_TRACE_BUFFER`` spans); a hung or aborting process dumps the last N
  spans — including still-open ones, flagged ``in_flight`` — as the
  post-mortem for "which round / which collective was live when the
  watchdog fired" (wired into ``watchdog.request_abort``, exits 79/80/81).
* **Chrome-trace export** — one JSON file per rank (``trace-rank<r>.json``),
  loadable in ``chrome://tracing`` / Perfetto / TensorBoard's trace viewer.
  Events are complete (``"ph": "X"``) events in microseconds with
  ``span_id``/``parent_id``/``trace_id`` in ``args`` so the tree survives
  the export round-trip.

Everything is gated on ``SM_TRACE``: unset (the default) means the fast
path is one cached-boolean check per call site — no spans, no buffer
growth, no threads (the tracer never creates any), no export files.
"""

import contextlib
import json
import logging
import os
import sys
import threading
import time
import uuid

from ..utils.envconfig import env_bool, env_int

logger = logging.getLogger(__name__)

TRACE_ENV = "SM_TRACE"
TRACE_BUFFER_ENV = "SM_TRACE_BUFFER"
TRACE_EXPORT_DIR_ENV = "SM_TRACE_EXPORT_DIR"
# read by models/booster.py (_TrainingSession resolves it once, host-side,
# at session construction — never on the traced round path)
DEVICE_SYNC_ENV = "SM_TRACE_DEVICE_SYNC"

DEFAULT_BUFFER_SPANS = 4096

# perf_counter base: Chrome-trace ts only needs internal consistency, and a
# monotonic clock keeps spans orderable across NTP steps
_T0 = time.perf_counter()


def _now_us():
    return (time.perf_counter() - _T0) * 1e6


def new_id():
    return uuid.uuid4().hex[:16]


class Span:
    """One timed node in the trace tree. Finish on the thread that started
    it (the thread-local stack is popped by identity)."""

    __slots__ = (
        "trace_id",
        "span_id",
        "parent_id",
        "name",
        "attributes",
        "start_us",
        "dur_us",
        "tid",
        "thread_name",
        "seq",
        "annotation",
    )

    def __init__(self, name, trace_id, parent_id, attributes=None):
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_id()
        self.parent_id = parent_id
        self.attributes = dict(attributes or {})
        self.start_us = _now_us()
        self.dur_us = None  # None while open
        self.tid = threading.get_ident()
        self.thread_name = threading.current_thread().name
        # recorder-append sequence number, stamped when the span lands in
        # the flight recorder — the fleet shipper's drain watermark
        # (telemetry/fleet.py ships spans with seq > last-shipped)
        self.seq = None
        # the entered jax.profiler.TraceAnnotation of the same name (or None)
        self.annotation = None

    def context(self):
        return (self.trace_id, self.span_id)


# --------------------------------------------------------------------- state
_tls = threading.local()

_state_lock = threading.Lock()
_enabled = None  # cached SM_TRACE verdict; None = not yet resolved
_rank = 0
_recorder = None  # deque of finished Span, created lazily
_live = {}  # span_id -> open Span (for flight-recorder dumps)
_seq = 0  # monotonic recorder-append counter (survives ring-buffer drops)


def enabled():
    """Cached ``SM_TRACE`` verdict — the per-call-site fast path is one
    function call and a boolean test. Tests toggle via ``_reset_for_tests``."""
    global _enabled
    value = _enabled
    if value is None:
        with _state_lock:
            if _enabled is None:
                _enabled = env_bool(TRACE_ENV, False)
            value = _enabled
    return value


def set_rank(rank):
    """Record this process's rank for export file names/metadata (wired by
    the distributed-training pre-exec; standalone processes stay rank 0)."""
    global _rank
    _rank = int(rank)


def get_rank():
    return _rank


def _get_recorder():
    global _recorder
    rec = _recorder
    if rec is None:
        import collections

        with _state_lock:
            if _recorder is None:
                _recorder = collections.deque(
                    maxlen=env_int(
                        TRACE_BUFFER_ENV, DEFAULT_BUFFER_SPANS, minimum=16
                    )
                )
            rec = _recorder
    return rec


def _reset_for_tests():
    """Drop the cached enable verdict, the ring buffer, live spans, and the
    current thread's span stack (other threads' stacks die with them)."""
    global _enabled, _recorder, _rank, _seq
    with _state_lock:
        _enabled = None
        _recorder = None
        _rank = 0
        _seq = 0
        _live.clear()
    _tls.stack = []


def _stack():
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def current_span():
    stack = _stack()
    return stack[-1] if stack else None


def current_context():
    """(trace_id, span_id) of this thread's innermost open span, or None.
    Hand it to another thread (``parent=`` on start) to keep its spans in
    the same tree — the batcher worker pattern."""
    span = current_span()
    return span.context() if span is not None else None


def _resolve_parent(parent, trace_id, root):
    """-> (trace_id, parent_id) honoring explicit parent > thread-local >
    fresh root. ``parent`` may be a Span or a (trace_id, span_id) tuple."""
    if parent is not None:
        if isinstance(parent, Span):
            return parent.trace_id, parent.span_id
        return parent[0], parent[1]
    if not root:
        implicit = current_span()
        if implicit is not None:
            return implicit.trace_id, implicit.span_id
    return trace_id or new_id(), None


# ------------------------------------------------- profiler annotations
_annotation_cls = None  # jax.profiler.TraceAnnotation, once jax is loaded


def annotate(name, attributes=None):
    """Enter a ``jax.profiler.TraceAnnotation`` named ``name`` and return it
    (leave it with ``__exit__``), or None while jax is not loaded. While a
    profiler session runs (``jax.profiler.start_trace``, ``xla_trace``,
    ``/debug/profile``) the annotation is an event on the host plane of the
    same ``.xplane.pb`` as the device operations, on the profiler's clock;
    with no session it is a flag test. jax is looked up, never imported: a
    process that has not imported it has no session to write into."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        cls = _annotation_cls = jax.profiler.TraceAnnotation
    annotation = cls(name, **attributes) if attributes else cls(name)
    annotation.__enter__()
    return annotation


# ----------------------------------------------------------------- span API
def start_span(name, attributes=None, parent=None, trace_id=None, root=False):
    """Open a span (None when tracing is disabled). ``parent`` overrides the
    thread-local context (cross-thread); ``trace_id`` seeds a new trace (the
    serving request id); ``root=True`` ignores any open span on this thread."""
    if not enabled():
        return None
    tid, parent_id = _resolve_parent(parent, trace_id, root)
    span = Span(name, tid, parent_id, attributes)
    _stack().append(span)
    with _state_lock:
        _live[span.span_id] = span
    span.annotation = annotate(name, attributes)
    return span


def finish_span(span, **attributes):
    """Close ``span`` (no-op on None), merging ``attributes``, and append it
    to the flight recorder."""
    if span is None:
        return
    if span.annotation is not None:
        span.annotation.__exit__(None, None, None)
        span.annotation = None
    span.dur_us = max(_now_us() - span.start_us, 0.0)
    if attributes:
        span.attributes.update(attributes)
    stack = _stack()
    if span in stack:
        stack.remove(span)
    # append under the state lock: snapshot_spans() copies the deque under
    # the same lock, and a lock-free append racing that copy would raise
    # "deque mutated during iteration" — on the abort path that would cost
    # the flight-recorder dump at exactly the moment it exists for
    recorder = _get_recorder()  # resolve BEFORE the lock (it may take it)
    global _seq
    with _state_lock:
        _live.pop(span.span_id, None)
        _seq += 1
        span.seq = _seq
        recorder.append(span)


@contextlib.contextmanager
def trace_span(name, attributes=None, parent=None, trace_id=None, root=False):
    """Context-managed span; yields the Span (or None when disabled)."""
    if not enabled():
        yield None
        return
    span = start_span(
        name, attributes=attributes, parent=parent, trace_id=trace_id, root=root
    )
    try:
        yield span
    finally:
        finish_span(span)


def record_span(name, duration_s=0.0, attributes=None, parent=None, ended_s_ago=0.0):
    """Record an already-completed span ending *now*, or ``ended_s_ago``
    seconds ago (for event-driven durations: an XLA compile reported by
    ``jax.monitoring``; the start-up intervals in front of ``train()``).
    Parented to the current thread context unless overridden."""
    if not enabled():
        return None
    tid, parent_id = _resolve_parent(parent, None, False)
    span = Span(name, tid, parent_id, attributes)
    span.dur_us = max(float(duration_s), 0.0) * 1e6
    span.start_us = max(span.start_us - max(float(ended_s_ago), 0.0) * 1e6 - span.dur_us, 0.0)
    recorder = _get_recorder()
    global _seq
    with _state_lock:
        _seq += 1
        span.seq = _seq
        recorder.append(span)
    return span


def record_compile(duration_s):
    """An XLA backend compile as a span (fed by the ``jax.monitoring``
    listener in telemetry/cluster.py) — first-round compile becomes a
    visible tree node instead of anonymous ``build_eval`` time."""
    return record_span(
        "xla.compile", duration_s, attributes={"kind": "backend_compile"}
    )


# ------------------------------------------------------------------- export
def snapshot_spans(include_open=False):
    """Finished spans oldest-first (plus open ones, ``in_flight``-flagged,
    when asked — the abort-dump view of what was live). The deque copy runs
    under the state lock so concurrent finish/record appends from serving
    or supervisor threads can never break the abort-path dump."""
    recorder = _get_recorder()
    with _state_lock:
        spans = list(recorder)
    if include_open:
        now_us = _now_us()
        with _state_lock:
            open_spans = list(_live.values())
        for span in open_spans:
            ghost = Span(span.name, span.trace_id, span.parent_id, span.attributes)
            ghost.span_id = span.span_id
            ghost.start_us = span.start_us
            ghost.dur_us = max(now_us - span.start_us, 0.0)
            ghost.tid = span.tid
            ghost.thread_name = span.thread_name
            ghost.attributes["in_flight"] = True
            spans.append(ghost)
    return spans


def span_to_wire(span):
    """Canonical flat-dict form of a finished span: the fleet shipper's wire
    payload (telemetry/fleet.py) and the event-builder input — one
    serialization for the local export and the cross-rank merge."""
    wire = {
        "name": span.name,
        "trace_id": span.trace_id,
        "span_id": span.span_id,
        "start_us": round(span.start_us, 3),
        "dur_us": round(span.dur_us or 0.0, 3),
        "tid": span.tid,
        "thread_name": span.thread_name,
    }
    if span.parent_id:
        wire["parent_id"] = span.parent_id
    if span.attributes:
        wire["attributes"] = dict(span.attributes)
    return wire


def events_from_wire(wire_spans, pid, process_label):
    """Chrome-trace events (process/thread metadata + complete "X" events)
    for one pid lane. ``pid`` is the rank, so per-rank lanes stack in a
    single Perfetto view — both the per-rank export and the merged
    ``trace-fleet.json`` build their lanes through this one function."""
    events = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": pid,
            "tid": 0,
            "args": {"name": process_label},
        }
    ]
    thread_names = {}
    for wire in wire_spans:
        thread_names.setdefault(wire.get("tid", 0), wire.get("thread_name", ""))
    for tid, tname in sorted(thread_names.items(), key=lambda kv: str(kv[0])):
        events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": tname},
            }
        )
    for wire in wire_spans:
        args = dict(wire.get("attributes") or {})
        args["span_id"] = wire.get("span_id")
        args["trace_id"] = wire.get("trace_id")
        if wire.get("parent_id"):
            args["parent_id"] = wire["parent_id"]
        events.append(
            {
                "name": wire.get("name", "?"),
                "cat": "span",
                "ph": "X",
                "pid": pid,
                "tid": wire.get("tid", 0),
                "ts": round(float(wire.get("start_us") or 0.0), 3),
                "dur": round(float(wire.get("dur_us") or 0.0), 3),
                "args": args,
            }
        )
    return events


def chrome_trace_doc(spans=None, extra_metadata=None):
    """-> Chrome-trace JSON object (dict): ``traceEvents`` of complete
    ("X") events in microseconds plus process/thread metadata events. Rank
    is the pid (per-rank files merge cleanly in one Perfetto view)."""
    if spans is None:
        spans = snapshot_spans()
    rank = get_rank()
    events = events_from_wire(
        [span_to_wire(span) for span in spans],
        pid=rank,
        process_label="rank {} (os pid {})".format(rank, os.getpid()),
    )
    metadata = {"rank": rank, "os_pid": os.getpid(), "spans": len(spans)}
    if extra_metadata:
        metadata.update(extra_metadata)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": metadata,
    }


def _write_doc(directory, filename, doc):
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, filename)
    with open(path, "w") as f:
        json.dump(doc, f)
        f.write("\n")
    return path


def export_traces(default_dir=None, filename=None):
    """End-of-run export: write this rank's Chrome trace into
    ``SM_TRACE_EXPORT_DIR`` (falling back to ``default_dir`` — the model
    dir on training jobs) and emit one ``training.trace_export`` record.
    Returns the path, or None when tracing is disabled / no dir resolves."""
    if not enabled():
        return None
    directory = os.environ.get(TRACE_EXPORT_DIR_ENV) or default_dir
    if not directory:
        return None
    doc = chrome_trace_doc()
    path = _write_doc(
        directory, filename or "trace-rank{}.json".format(get_rank()), doc
    )
    from .emit import emit_metric

    emit_metric(
        "training.trace_export", path=path, spans=doc["otherData"]["spans"]
    )
    logger.info(
        "exported %d trace spans to %s", doc["otherData"]["spans"], path
    )
    return path


def dump_flight_recorder(default_dir=None, reason=None, exit_code=None):
    """Abort-path dump: the last-N finished spans *plus* every still-open
    span (the wedged round / collective, flagged ``in_flight``) into
    ``flight-recorder-rank<r>.json``. Never raises — the exit must happen
    even when the disk is the thing that is broken. Returns the path or
    None (disabled, or the write failed)."""
    if not enabled():
        return None
    directory = os.environ.get(TRACE_EXPORT_DIR_ENV) or default_dir or "."
    extra = {}
    if reason is not None:
        extra["abort_reason"] = reason
    if exit_code is not None:
        extra["exit_code"] = exit_code
    try:
        doc = chrome_trace_doc(
            spans=snapshot_spans(include_open=True), extra_metadata=extra
        )
        path = _write_doc(
            directory, "flight-recorder-rank{}.json".format(get_rank()), doc
        )
    except Exception as e:
        logger.error("flight-recorder dump failed (%s); continuing abort", e)
        return None
    logger.error(
        "flight recorder dumped to %s (%d spans, incl. in-flight)",
        path,
        doc["otherData"]["spans"],
    )
    return path
