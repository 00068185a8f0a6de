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

import contextlib
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
    (``setup.first_dispatch/host_dispatch``; "" outside any): the ``phase``
    label of the program-load counters (telemetry/cluster.py)."""
    return "/".join(getattr(_tls, "open_phases", ()))


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
    registry.histogram(
        PHASE_HISTOGRAM,
        help="Wall time of named training phases",
        labels={"phase": name},
    ).observe(elapsed)
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
