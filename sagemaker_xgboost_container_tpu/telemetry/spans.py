"""Span API: time named phases into the registry, stdout records, and an
optional per-round phase accumulator.

Three consumers, one call site:

* ``span("data_ingest", emit=True)`` — one-off phases (algorithm_train's
  ingest/train/save) record a ``training.phase`` stdout line and a
  ``training_phase_seconds{phase=...}`` histogram observation.
* ``PhaseRecorder`` — per-round breakdown: while a recorder is installed on
  this thread (``RoundTimer`` installs one for the whole training run), every
  finished span also accumulates into it; the timer drains it each round so
  the round record carries ``phases_ms``.
* the registry — every span observes ``training_phase_seconds`` so phase
  latencies show up in ``/metrics`` exposition too.

Recorders are thread-local: the booster's callback loop is single-threaded,
and parallel serving threads never share a recorder by accident.

With hierarchical tracing armed (``SM_TRACE``, telemetry/tracing.py) every
``span()`` additionally opens a tracer span, so existing call sites upgrade
in place: the flat per-round phases become children of the per-round root
span RoundTimer owns. Disabled (the default), the only added cost is one
cached-boolean check.

One clock with the device trace: every span also enters a
``jax.profiler.TraceAnnotation`` of its name (``tracing.annotate``), so
while a profiler session runs the program's spans land in the same
``.xplane.pb`` as the device operations, on its clock. With no session the
annotation is a flag test; jax is never imported from here.
"""

import bisect
import contextlib
import os
import sys
import threading
import time

from . import tracing
from .emit import emit_metric
from .registry import REGISTRY

_tls = threading.local()

PHASE_HISTOGRAM = "training_phase_seconds"


class PhaseRecorder:
    """Accumulates ``{phase: seconds}`` between drains (single-thread use)."""

    def __init__(self):
        self.phases = {}

    def add(self, name, seconds):
        self.phases[name] = self.phases.get(name, 0.0) + seconds

    def drain(self):
        drained, self.phases = self.phases, {}
        return drained


def _stack():
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def push_recorder(recorder=None):
    """Install a recorder on this thread; pair with ``pop_recorder``."""
    recorder = recorder or PhaseRecorder()
    _stack().append(recorder)
    return recorder


def pop_recorder(recorder):
    stack = _stack()
    if recorder in stack:
        stack.remove(recorder)


def active_recorder():
    stack = _stack()
    return stack[-1] if stack else None


PHASE_BYTES_COUNTER = "training_phase_bytes_total"


def _open_phases():
    phases = getattr(_tls, "open_phases", None)
    if phases is None:
        phases = _tls.open_phases = []
    return phases


def current_phase():
    """The spans open on this thread, outermost first and joined by ``/``
    (``setup.first_dispatch/setup.first_dispatch.load/host_dispatch``; ""
    outside any): the ``phase`` label of ``xla_program_seconds_total``
    (telemetry/cluster.py)."""
    return "/".join(getattr(_tls, "open_phases", ()))


def outermost_phase():
    """The outermost span open on this thread ("" outside any): the
    ``phase`` label of ``xla_program_wall_seconds_total``."""
    phases = getattr(_tls, "open_phases", ())
    return phases[0] if phases else ""


class OpenSpan:
    """A span between :func:`begin_span` and :func:`end_span`, for work that
    no ``with`` block can hold (``host_turnaround`` runs from the end of one
    ``run_rounds()`` into the next). End it on the thread that began it; a
    span ended elsewhere (its owner collected on another thread) still
    leaves the open phases of the thread that began it."""

    __slots__ = (
        "name", "covering", "registry", "start", "tspan", "annotation", "bytes", "phases",
    )

    def __init__(self, name, covering, registry, attributes):
        self.name = name
        self.covering = covering
        self.registry = registry or REGISTRY
        attributes = attributes or {}
        self.bytes = {
            "up": attributes.get("bytes_up", 0),
            "down": attributes.get("bytes_down", 0),
        }
        # the tracer's span enters the profiler annotation itself when armed
        self.tspan = (
            tracing.start_span(name, attributes) if tracing.enabled() else None
        )
        self.annotation = (
            tracing.annotate(name, attributes) if self.tspan is None else None
        )
        self.phases = _open_phases()
        self.phases.append(name)
        self.start = time.perf_counter()

    def add_bytes(self, up=0, down=0):
        """Bytes moved host to device (``up``) and back inside the span,
        where they are known only once the work is done."""
        self.bytes["up"] += up
        self.bytes["down"] += down


def begin_span(name, covering=False, registry=None, attributes=None):
    return OpenSpan(name, covering, registry, attributes)


def _observe_phase(registry, name, seconds):
    (registry or REGISTRY).histogram(
        PHASE_HISTOGRAM,
        help="Wall time of named training phases",
        labels={"phase": name},
    ).observe(seconds)


def record_past_span(name, seconds, ended_s_ago=0.0, attributes=None, registry=None):
    """A span of an interval already past (``seconds`` long, over
    ``ended_s_ago`` seconds ago), for work that ran before anything could
    open one: it lands in the phase histogram and, when armed, in the tracer
    (``tracing.record_span``). No annotation: the profiler takes no event
    after the fact. Never in a ``PhaseRecorder``: it is no part of a round."""
    seconds = max(float(seconds), 0.0)
    _observe_phase(registry, name, seconds)
    tracing.record_span(name, seconds, attributes=attributes, ended_s_ago=ended_s_ago)
    return seconds


def end_span(open_span, emit=False):
    """Close ``open_span``: the duration lands in the phase histogram, in
    this thread's ``PhaseRecorder`` unless the span is ``covering``, and
    its ``bytes_up`` / ``bytes_down`` attributes in the byte counter."""
    elapsed = time.perf_counter() - open_span.start
    name = open_span.name
    phases = open_span.phases
    if phases and phases[-1] == name:
        phases.pop()
    elif name in phases:  # ended out of order: drop it all the same
        phases.remove(name)
    if open_span.tspan is not None:
        tracing.finish_span(open_span.tspan)
    elif open_span.annotation is not None:
        open_span.annotation.__exit__(None, None, None)
    registry = open_span.registry
    _observe_phase(registry, name, elapsed)
    for direction, nbytes in open_span.bytes.items():
        if not nbytes:
            continue
        registry.counter(
            PHASE_BYTES_COUNTER,
            help="Bytes moved between host and device inside named phases",
            labels={"phase": name, "direction": direction},
        ).inc(nbytes)
    if not open_span.covering:
        recorder = active_recorder()
        if recorder is not None:
            recorder.add(name, elapsed)
    if emit:
        emit_metric("training.phase", phase=name, seconds=round(elapsed, 6))
    return elapsed


@contextlib.contextmanager
def span(name, emit=False, registry=None, attributes=None, covering=False):
    """Time the enclosed block as phase ``name``.

    The duration always lands in the phase histogram and in this thread's
    active ``PhaseRecorder`` (if any); ``emit=True`` additionally writes one
    ``training.phase`` stdout record — use it for one-off phases, never for
    per-round work (the round record owns that). ``covering=True`` marks a
    span that contains other spans (``host_turnaround`` over ``commit``,
    ``callbacks`` over ``checkpoint``): it stays out of the recorder, whose
    phases a round record sums. ``attributes`` go to the tracer's span and
    the profiler annotation; ``bytes_up`` / ``bytes_down`` among them, and
    what the block adds through the yielded span's ``add_bytes``, count into
    ``training_phase_bytes_total{phase,direction}``.
    """
    open_span = begin_span(name, covering, registry, attributes)
    try:
        yield open_span
    finally:
        end_span(open_span, emit=emit)


# ------------------------------------------------------------------ start-up
def add_interval(covered, start, end):
    """``(start, end)`` merged into ``covered``, a sorted tuple of disjoint
    intervals: the new tuple and the seconds that were not covered before.
    Pure: the wall seconds of work on several threads are the sum of what
    each of its intervals adds, in whatever order they come."""
    if end <= start:
        return covered, 0.0
    # disjoint and sorted, so the ends are sorted as the starts are
    first = bisect.bisect_left(covered, start, key=lambda interval: interval[1])
    last = bisect.bisect_right(covered, end, key=lambda interval: interval[0])
    added = end - start
    for lo, hi in covered[first:last]:
        added -= min(hi, end) - max(lo, start)
    if first < last:
        start, end = min(start, covered[first][0]), max(end, covered[last - 1][1])
    return covered[:first] + ((start, end),) + covered[last:], max(added, 0.0)


def union_seconds(intervals):
    """Seconds covered by ``(start, end)`` intervals, overlaps counted once."""
    covered, total = (), 0.0
    for start, end in intervals:
        covered, added = add_interval(covered, start, end)
        total += added
    return total


def process_start_time(fallback):
    """When this process began, seconds since the epoch: its start in
    ``/proc/self/stat`` (clock ticks after boot) against the boot-time clock,
    ``psutil`` where that cannot be read, else ``fallback`` (the package's
    first line). Never later than ``fallback``, nor a week before it (a
    clock namespace that ``/proc`` does not share)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
        started = time.time() - age
    except Exception:
        try:
            import psutil

            started = float(psutil.Process().create_time())
        except Exception:
            return fallback
    return started if 0.0 <= fallback - started < 7 * 86400.0 else fallback


def _backend_is_up():
    """Whether jax has brought its back end up; None where that cannot be
    told (jax not imported, or without the accessor)."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    check = getattr(bridge, "backends_are_initialized", None)
    return None if check is None else bool(check())


_startup_lock = threading.Lock()
_startup_done = set()  # which of "process", "before_train" are on record


def record_startup(entering_train=False, registry=None):
    """What ran between the start of the process and the program's own
    spans, as spans of intervals already past; each once a process, however
    often this is called (``train()`` calls it at its entry with
    ``entering_train``, ``sagemaker_train`` at its start):

    * gauge ``process_start_time_seconds`` (:func:`process_start_time`);
    * ``startup.package_import``: the seconds inside the package's own
      import statements up to now (the union of the package's
      ``IMPORT_INTERVALS``; attribute ``jax_inside``: one of them was the
      first to import jax, whose seconds are then in it);
    * ``startup.before_train`` (only when ``entering_train``): process start
      to the entry of the first ``train()``. What ``startup.package_import``
      leaves of it is the caller's: data ingest in a job (span
      ``data_ingest``), and the back end where the caller brought it up;
    * ``startup.backend_init`` (only when ``entering_train``: a job's
      ``jax.distributed.initialize`` has to come before it): jax 0.9 reports
      no event for its back end coming up, so the span is round the first
      device enumeration, made here, at the entry of ``train()``, if nobody
      has made it. Where the caller enumerated first there is no span.
    """
    now = time.time()
    with _startup_lock:
        todo = {"process"} | ({"before_train"} if entering_train else set())
        todo -= _startup_done
        _startup_done.update(todo)
    if not todo:
        return
    import sagemaker_xgboost_container_tpu as package

    intervals = list(package.IMPORT_INTERVALS)
    first_line = intervals[0][0]
    started = process_start_time(first_line)
    if "process" in todo:
        (registry or REGISTRY).gauge(
            "process_start_time_seconds",
            help="Start time of the process, seconds since the epoch",
        ).set(started)
        record_past_span(
            "startup.package_import",
            union_seconds((start, end) for start, end, _jax in intervals),
            ended_s_ago=now - max(end for _start, end, _jax in intervals),
            attributes={
                "jax_inside": any(jax_inside for _start, _end, jax_inside in intervals),
                "imports": len(intervals),
            },
            registry=registry,
        )
    if "before_train" in todo:
        record_past_span(
            "startup.before_train",
            now - started,
            ended_s_ago=time.time() - now,
            attributes={"process_start": round(started, 3)},
            registry=registry,
        )
        if _backend_is_up() is False:
            with span("startup.backend_init", registry=registry, covering=True):
                sys.modules["jax"].local_devices()


def note_phase_memory(phase, devices, registry=None):
    """The fullest chip's memory at the end of a set-up phase, in gauges
    ``setup_hbm_bytes{phase, what}``: ``in_use``, ``peak`` (the allocator's
    high-water mark so far) and ``reserved`` (what the runtime holds for
    programs' scratch, which the v5e keeps out of ``peak``). One
    ``memory_stats()`` a chip; fullest by ``in_use`` plus ``reserved``. No
    gauge where the back end reports no stats, as the CPU's does not."""
    fullest = None
    for device in devices:
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        read = {
            "in_use": int(stats.get("bytes_in_use", 0)),
            "peak": int(stats.get("peak_bytes_in_use", 0)),
            "reserved": int(stats.get("bytes_reserved", 0)),
        }
        if fullest is None or read["in_use"] + read["reserved"] > (
            fullest["in_use"] + fullest["reserved"]
        ):
            fullest = read
    for what, value in (fullest or {}).items():
        (registry or REGISTRY).gauge(
            "setup_hbm_bytes",
            help="Device memory of the fullest chip at the end of a set-up phase",
            labels={"phase": phase, "what": what},
        ).set(value)
    return fullest
