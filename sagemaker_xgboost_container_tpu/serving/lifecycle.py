"""Serving lifecycle & request-plane supervision.

The reference container delegates serving lifecycle to its MMS/gunicorn
frontend (PAPER.md §1): the Java frontend owns readiness, drain on SIGTERM,
per-request timeouts, and worker supervision, and the Python handlers never
have to. Our single process owns the TPU *and* the HTTP surface, so the
same contract has to live here:

* **Health state machine** — ``starting → ready → degraded → draining →
  stopped``, consulted by ``/ping`` on both serving apps. ``degraded`` is
  derived live from the circuit breaker(s) this lifecycle was told about
  (the PR-3 saturation breaker and the predict watchdog both flip it);
  ``draining``/``stopped`` answer 503 + ``Retry-After`` so the load
  balancer deregisters the instance while in-flight work finishes.
* **In-flight latch** — the WSGI middleware reports request start/finish
  (finish = the response body fully written, via the result iterable's
  ``close()``), feeding the ``serving_inflight`` gauge and the drain wait.
* **Request deadlines** — ``SM_REQUEST_DEADLINE_S`` arms a per-request
  budget apportioned across the ``decode`` / ``queue`` / ``predict`` /
  ``encode`` stages. Expiry raises :class:`DeadlineExceeded` (a
  ``TimeoutError`` subclass, so the existing saturation handling turns it
  into 503 + ``Retry-After`` through the breaker feed) and counts
  ``serving_deadline_exceeded_total{stage}``.
* **Predict watchdog** — ``SM_PREDICT_STUCK_S`` arms a monitor thread (the
  PR-3 round-watchdog pattern) that detects a batcher wedged inside one
  dispatch (a stalled device dispatch: the exec lock never releases, every
  later request hangs). On detection it trips the breaker open, emits one
  ``serving.stuck`` record with the flight-recorder span tree, and — per
  ``SM_PREDICT_STUCK_ACTION`` — either keeps shedding until the dispatch
  returns (``shed``, default) or aborts the process with
  ``EXIT_PREDICT_STUCK`` so the platform restarts a clean one (``abort``).
  Never a silent wedge.

Everything is resolved ONCE at lifecycle construction via ``envconfig``
and inert by default: no deadline knob -> no per-request clock reads, no
stuck knob -> no monitor thread, and with no lifecycle installed (tests
constructing the WSGI apps directly) every hook below is a no-op.
"""

import logging
import os
import threading
import time

from ..constants import EXIT_PREDICT_STUCK
from ..constants import SM_MODEL_DIR as SM_MODEL_DIR_ENV
from ..telemetry import tracing
from ..telemetry.emit import emit_metric
from ..telemetry.registry import REGISTRY
from ..utils.envconfig import env_bool, env_float

logger = logging.getLogger(__name__)

GRACEFUL_DRAIN_ENV = "SM_GRACEFUL_DRAIN"
DRAIN_TIMEOUT_ENV = "SM_DRAIN_TIMEOUT_S"
REQUEST_DEADLINE_ENV = "SM_REQUEST_DEADLINE_S"
PREDICT_STUCK_ENV = "SM_PREDICT_STUCK_S"
PREDICT_STUCK_ACTION_ENV = "SM_PREDICT_STUCK_ACTION"

STARTING, READY, DEGRADED, DRAINING, STOPPED = (
    "starting", "ready", "degraded", "draining", "stopped",
)

#: ``serving_state`` gauge encoding (documented in docs/observability.md)
_STATE_GAUGE = {STARTING: 0.0, READY: 1.0, DEGRADED: 2.0, DRAINING: 3.0, STOPPED: 4.0}

_STUCK_ACTIONS = ("shed", "abort")

#: request budget stages (closed label set for the deadline counter)
STAGES = ("decode", "queue", "predict", "encode")

# test hook: chaos tests replace this to observe the exit instead of dying
_exit = os._exit

_abort_lock = threading.Lock()
_aborting = False


def _stuck_action():
    raw = (os.getenv(PREDICT_STUCK_ACTION_ENV) or "shed").strip().lower()
    if raw not in _STUCK_ACTIONS:
        logger.warning(
            "ignoring malformed %s=%r (expected one of %s); using 'shed'",
            PREDICT_STUCK_ACTION_ENV, raw, _STUCK_ACTIONS,
        )
        return "shed"
    return raw


class DeadlineExceeded(TimeoutError):
    """A request blew its ``SM_REQUEST_DEADLINE_S`` budget in ``stage``.

    Subclasses ``TimeoutError`` deliberately: the invocation paths already
    turn batcher timeouts into 503 + ``Retry-After`` and feed the breaker —
    deadline expiry is the same saturation protocol, just attributed to a
    stage.
    """

    def __init__(self, stage, budget_s):
        super(DeadlineExceeded, self).__init__(
            "request deadline exceeded in stage {!r} (budget {:.3f}s)".format(
                stage, budget_s
            )
        )
        self.stage = stage
        self.budget_s = budget_s


def note_deadline_exceeded(stage, registry=None):
    """Count one per-stage deadline expiry (label set bounded by STAGES)."""
    reg = registry or REGISTRY
    reg.counter(
        "serving_deadline_exceeded_total",
        "Requests that blew the SM_REQUEST_DEADLINE_S budget, by stage",
        {"stage": stage if stage in STAGES else "other"},
    ).inc()


def expire(stage, budget_s, registry=None):
    """Count and raise a :class:`DeadlineExceeded` for ``stage``."""
    note_deadline_exceeded(stage, registry=registry)
    raise DeadlineExceeded(stage, budget_s)


class RequestDeadline:
    """One request's time budget, drawn down across stages.

    Stages don't get fixed slices: each draws from whatever remains when it
    runs (a slow decode leaves less for predict), which matches how the
    wall clock actually bills the client. ``check(stage)`` raises when the
    budget is gone; ``remaining()`` bounds blocking waits (the batcher's
    queue/dispatch wait).
    """

    __slots__ = ("budget_s", "_deadline", "_clock")

    def __init__(self, budget_s, clock=time.monotonic):
        self.budget_s = float(budget_s)
        self._clock = clock
        self._deadline = clock() + self.budget_s

    def remaining(self):
        return max(0.0, self._deadline - self._clock())

    def expired(self):
        return self._clock() >= self._deadline

    def check(self, stage):
        if self.expired():
            expire(stage, self.budget_s)


class PredictWatchdog:
    """Monitor thread detecting a batcher wedged inside one dispatch.

    The batcher's worker holds ``_exec_lock`` around every ``predict_fn``
    run; a dispatch that never returns (wedged device runtime) therefore
    hangs every later request with no error — the failure mode the queue
    timeout converts into 60s client timeouts, forever. The watchdog polls
    each registered batcher's :meth:`dispatch_age_s`; one stuck episode:

    * trips the associated breaker OPEN on every check while stuck (the
      cooldown keeps restarting, so ``/ping`` stays 503 and new requests
      shed instead of queueing behind the wedge),
    * emits ONE ``serving.stuck`` record with the in-flight span tree
      (flight-recorder dump when ``SM_TRACE`` is armed),
    * with ``action='abort'``, aborts the process with
      ``EXIT_PREDICT_STUCK`` — a restart gets a clean device runtime.

    When the dispatch finally returns, the episode clears with a log line
    and the breaker recovers through its normal half-open probe.
    """

    def __init__(self, stuck_s, action="shed", check_interval=None,
                 clock=time.monotonic):
        self.stuck_s = float(stuck_s)
        self.action = action
        if check_interval is None:
            # the re-forced breaker is what keeps /ping unready while stuck:
            # checking less often than the breaker cooldown would let it
            # half-open between checks and flap a wedged instance back into
            # rotation, so the interval stays under half the cooldown
            from .breaker import SHED_COOLDOWN_ENV

            cooldown = env_float(
                SHED_COOLDOWN_ENV, 5.0, minimum=0.1, maximum=3600.0
            )
            check_interval = min(self.stuck_s / 4.0, cooldown / 2.0)
        self.check_interval = max(check_interval, 0.05)
        self._clock = clock
        self._lock = threading.Lock()
        self._targets = {}   # name -> (batcher, breaker)
        self._stuck = set()  # names in a stuck episode (log/record once)
        self._stop = threading.Event()
        self._thread = None

    def register(self, name, batcher, breaker=None):
        with self._lock:
            self._targets[name] = (batcher, breaker)
        self.start()

    def unregister(self, name):
        with self._lock:
            self._targets.pop(name, None)
            self._stuck.discard(name)

    def start(self):
        with self._lock:
            if self._thread is not None:
                return self
            # fresh event per thread generation: a start() after stop()
            # must not inherit the set event (the new thread would exit on
            # its first wait — an armed-looking watchdog checking nothing),
            # and the old thread keeps ITS event so it still stops
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._run, args=(self._stop,),
                daemon=True, name="predict-watchdog",
            )
            self._thread.start()
        logger.info(
            "predict watchdog armed: %s after a dispatch exceeds %.1fs",
            self.action, self.stuck_s,
        )
        return self

    def stop(self):
        with self._lock:
            stop_event = self._stop
            thread, self._thread = self._thread, None
        stop_event.set()
        if thread is not None:
            thread.join(timeout=5.0)

    # ------------------------------------------------------------- internals
    def _run(self, stop_event):
        while not stop_event.wait(self.check_interval):
            try:
                self.check_once()
            except Exception:
                logger.exception("predict watchdog check failed; continuing")

    def check_once(self):
        with self._lock:
            targets = dict(self._targets)
        for name, (batcher, breaker) in targets.items():
            age = batcher.dispatch_age_s()
            if age is not None and age > self.stuck_s:
                self._handle_stuck(name, batcher, breaker, age)
            else:
                with self._lock:
                    was_stuck = name in self._stuck
                    self._stuck.discard(name)
                if was_stuck:
                    logger.warning(
                        "predict dispatch on batcher %r returned after a "
                        "stuck episode; breaker recovers via its half-open "
                        "probe", name,
                    )

    def _handle_stuck(self, name, batcher, breaker, age):
        with self._lock:
            first = name not in self._stuck
            self._stuck.add(name)
        # keep the breaker's cooldown restarting every check: while the
        # dispatch is wedged the instance must stay unready and shedding
        if breaker is not None:
            breaker.force_open("predict_stuck")
        if not first:
            if self.action == "abort":
                self._abort(name, batcher, age, dump=None)
            return
        requests, rows = batcher.dispatch_info()
        logger.error(
            "predict dispatch STUCK on batcher %r: one dispatch has run "
            "%.1fs (> %.1fs deadline, %d request(s) / %d row(s) aboard) — "
            "wedged device runtime; action=%s",
            name, age, self.stuck_s, requests, rows, self.action,
        )
        dump = tracing.dump_flight_recorder(
            default_dir=os.environ.get(SM_MODEL_DIR_ENV) or None,
            reason="predict_stuck",
        )
        fields = {
            "batcher": name,
            "stuck_s": round(age, 1),
            "deadline_s": self.stuck_s,
            "requests": requests,
            "rows": rows,
            "action": self.action,
        }
        if dump:
            fields["flight_recorder"] = dump
        emit_metric("serving.stuck", **fields)
        if self.action == "abort":
            self._abort(name, batcher, age, dump=dump)

    def _abort(self, name, batcher, age, dump=None):
        abort_serving(
            "predict_stuck",
            EXIT_PREDICT_STUCK,
            batcher=name,
            stuck_s=round(age, 1),
            flight_recorder=dump,
        )


def abort_serving(reason, exit_code, **fields):
    """Dump the flight recorder, emit one ``serving.abort`` record, hard-exit.

    The serving twin of ``training/watchdog.request_abort``: safe from any
    thread, first caller wins (a drain timing out while the watchdog aborts
    must not fight over the exit code), and the dump can never block the
    exit.
    """
    global _aborting
    with _abort_lock:
        if _aborting:
            return
        _aborting = True
    logger.error(
        "ABORTING serving (%s, exit code %d): the platform restarts a "
        "clean instance", reason, exit_code,
    )
    try:
        if not fields.get("flight_recorder"):
            fields["flight_recorder"] = tracing.dump_flight_recorder(
                default_dir=os.environ.get(SM_MODEL_DIR_ENV) or None,
                reason=reason,
                exit_code=exit_code,
            )
    except Exception:
        logger.exception("flight-recorder dump failed; exiting anyway")
    fields = {k: v for k, v in fields.items() if v is not None}
    emit_metric("serving.abort", reason=reason, exit_code=exit_code, **fields)
    _exit(exit_code)


def _reset_abort_for_tests():
    global _aborting
    with _abort_lock:
        _aborting = False


class ServingLifecycle:
    """The serving process's health state machine + in-flight latch.

    One instance per server process, installed via :func:`install`; the
    WSGI apps and middleware consult it through the module-level helpers so
    code paths without a server (unit tests, library callers) behave exactly as
    before.
    """

    def __init__(self, registry=None, clock=time.monotonic):
        # knobs resolve exactly once, here (envconfig: malformed values
        # warn-once and fall back; out-of-range clamp)
        self.graceful_drain = env_bool(GRACEFUL_DRAIN_ENV, True)
        self.drain_timeout_s = env_float(
            DRAIN_TIMEOUT_ENV, 30.0, minimum=0.0, maximum=3600.0
        )
        self.request_deadline_s = env_float(
            REQUEST_DEADLINE_ENV, 0.0, minimum=0.0, maximum=3600.0
        )
        self.predict_stuck_s = env_float(
            PREDICT_STUCK_ENV, 0.0, minimum=0.0, maximum=3600.0
        )
        self.predict_stuck_action = _stuck_action()
        self._clock = clock
        self._cond = threading.Condition()
        self._publish_lock = threading.Lock()
        self._base_state = STARTING
        self._last_published = STARTING
        self._inflight = 0
        self._breakers = []
        reg = registry or REGISTRY
        self._m_inflight = reg.gauge(
            "serving_inflight",
            "In-flight HTTP requests (response not yet fully written)",
        )
        self._m_state = reg.gauge(
            "serving_state",
            "Lifecycle state (0 starting, 1 ready, 2 degraded, 3 draining, "
            "4 stopped)",
        )
        self._m_drain = reg.gauge(
            "serving_drain_seconds",
            "Duration of the last SIGTERM drain (set when the drain settles)",
        )
        self._m_inflight.set(0.0)
        self._m_state.set(_STATE_GAUGE[STARTING])
        self.watchdog = None
        if self.predict_stuck_s > 0:
            self.watchdog = PredictWatchdog(
                self.predict_stuck_s, action=self.predict_stuck_action
            )

    # ----------------------------------------------------------- state plane
    @property
    def state(self):
        """Effective state: ``degraded`` is derived live from the breakers
        so ``/ping`` can never disagree with the shed decision. Reading it
        also publishes the effective value (gauge + one ``serving.lifecycle``
        record per change) — ``/ping`` polls it every few seconds on a real
        endpoint, so ready↔degraded flips reach the telemetry surface even
        though no code path "transitions" into the derived state."""
        return self._publish_state()

    def _publish_state(self):
        """Derive + publish under one lock hold.

        The derivation happens INSIDE the publish critical section: a
        publisher that derived its value before losing the CPU would
        otherwise overwrite a newer publication with a stale one (e.g. a
        /ping poll stamping `ready` over the drain's `draining` and leaving
        the gauge wrong for the whole drain). Re-deriving at publish time
        makes late publishers converge on the current truth instead.
        """
        with self._publish_lock:
            with self._cond:
                base = self._base_state
            effective = base
            if base == READY and any(b.degraded for b in self._breakers):
                effective = DEGRADED
            prev, self._last_published = self._last_published, effective
            if prev != effective:
                self._m_state.set(_STATE_GAUGE[effective])
                emit_metric("serving.lifecycle", state=effective, prev=prev)
                logger.info("serving lifecycle: %s -> %s", prev, effective)
            return effective

    @property
    def accepting(self):
        """False once draining/stopped: new /invocations + /ping get 503."""
        with self._cond:
            return self._base_state not in (DRAINING, STOPPED)

    def note_breaker(self, breaker):
        """Tell the lifecycle about a breaker feeding the degraded signal."""
        if breaker is not None and breaker not in self._breakers:
            self._breakers.append(breaker)

    def _set_state(self, state, only_from=None):
        """Atomically move the base state. ``only_from`` makes it a
        compare-and-set — the guard and the write share one lock hold, so a
        mark_ready racing a SIGTERM can never overwrite DRAINING with READY.
        Returns the previous state, or None when the guard refused."""
        with self._cond:
            if only_from is not None and self._base_state not in only_from:
                return None
            prev, self._base_state = self._base_state, state
        self._publish_state()
        return prev

    def mark_ready(self):
        """First successful model load: ``starting -> ready`` (idempotent,
        and atomic with the drain guard: a load completing mid-drain never
        un-drains)."""
        self._set_state(READY, only_from=(STARTING,))

    def begin_drain(self):
        """Stop accepting: /ping flips 503 so the load balancer deregisters.
        Returns False when already draining/stopped (duplicate SIGTERM)."""
        return self._set_state(DRAINING, only_from=(STARTING, READY)) is not None

    def mark_stopped(self):
        self._set_state(STOPPED)

    # -------------------------------------------------------- in-flight latch
    def request_started(self):
        with self._cond:
            self._inflight += 1
            self._m_inflight.set(float(self._inflight))

    def request_finished(self):
        with self._cond:
            self._inflight = max(0, self._inflight - 1)
            self._m_inflight.set(float(self._inflight))
            self._cond.notify_all()

    @property
    def inflight(self):
        with self._cond:
            return self._inflight

    def wait_drained(self, timeout):
        """Block until in-flight hits 0; -> False on timeout (wedged)."""
        deadline = self._clock() + max(0.0, timeout)
        with self._cond:
            while self._inflight > 0:
                remaining = deadline - self._clock()
                if remaining <= 0:
                    return False
                self._cond.wait(remaining)
            return True

    def observe_drain_seconds(self, seconds):
        self._m_drain.set(round(seconds, 3))

    # ------------------------------------------------------------- deadlines
    def request_deadline(self):
        """-> a fresh :class:`RequestDeadline`, or None when the knob is off."""
        if self.request_deadline_s <= 0:
            return None
        return RequestDeadline(self.request_deadline_s, clock=self._clock)

    # -------------------------------------------------------------- watchdog
    def register_batcher(self, name, batcher, breaker=None):
        self.note_breaker(breaker)
        if self.watchdog is not None:
            self.watchdog.register(name, batcher, breaker)

    def unregister_batcher(self, name):
        if self.watchdog is not None:
            self.watchdog.unregister(name)

    def shutdown(self):
        """Stop owned threads (tests, restart drills)."""
        if self.watchdog is not None:
            self.watchdog.stop()


# ------------------------------------------------------- module-level install
_install_lock = threading.Lock()
_current = None


def install(lifecycle):
    """Make ``lifecycle`` the process's active lifecycle and wire the WSGI
    in-flight tracker. Returns the lifecycle for chaining."""
    global _current
    from ..telemetry import wsgi as telemetry_wsgi

    with _install_lock:
        _current = lifecycle
    telemetry_wsgi.set_request_tracker(lifecycle)
    emit_metric("serving.lifecycle", state=lifecycle.state, prev=None)
    return lifecycle


def uninstall():
    """Clear the active lifecycle (tests, restart drills)."""
    global _current
    from ..telemetry import wsgi as telemetry_wsgi

    with _install_lock:
        lifecycle, _current = _current, None
    telemetry_wsgi.set_request_tracker(None)
    if lifecycle is not None:
        lifecycle.shutdown()
    return lifecycle


def current():
    return _current


# Convenience hooks: every one is a no-op without an installed lifecycle so
# apps built directly in tests keep today's behavior byte-for-byte.
def mark_ready():
    lifecycle = _current
    if lifecycle is not None:
        lifecycle.mark_ready()


def accepting():
    lifecycle = _current
    return True if lifecycle is None else lifecycle.accepting


def observe(breaker=None):
    """Publish the effective state from a readiness poll.

    The /ping handlers call this each poll: the LB's health-check cadence is
    what surfaces derived ready<->degraded flips to the gauge/records (no
    code path "transitions" into the derived state, so something has to
    read it). ``breaker`` lets the handler register its breaker late —
    the apps are often built before a lifecycle is installed, and a
    breaker-without-batcher config would otherwise never be noted.
    Returns the effective state, or None with no lifecycle installed.
    """
    lifecycle = _current
    if lifecycle is None:
        return None
    if breaker is not None:
        lifecycle.note_breaker(breaker)
    return lifecycle.state


def request_deadline():
    lifecycle = _current
    return None if lifecycle is None else lifecycle.request_deadline()


def register_batcher(name, batcher, breaker=None):
    lifecycle = _current
    if lifecycle is not None:
        lifecycle.register_batcher(name, batcher, breaker)


def unregister_batcher(name):
    lifecycle = _current
    if lifecycle is not None:
        lifecycle.unregister_batcher(name)
