"""Serving SLO plane: rolling-window latency percentiles vs an explicit
target, with burn-rate.

The serving stack has latency *metrics* (``serving_request_seconds``) but no
*objective* to judge them against: ROADMAP item 3's "millions of users"
scale-out needs a machine-readable "are we inside SLO right now" signal that
a fleet scheduler and the PR-9 lifecycle can both consult.
This module provides it:

* ``SM_SLO_P95_MS`` arms the plane (unset/0 = completely inert: no window,
  no metric series, no per-request work beyond one ``is None`` test);
* every ``/invocations`` latency lands in a rolling ``SM_SLO_WINDOW_S``
  window (default 300 s);
* a sample over the target counts ``serving_slo_violation_total`` and the
  window's violating fraction over the 5% error budget (a p95 target
  tolerates 5% of requests above it) is published as
  ``serving_slo_burn_rate`` — 1.0 means burning exactly the budget,
  above 1.0 the SLO is being missed;
* the window object quacks like a circuit breaker (``.degraded``), so the
  serving lifecycle folds a sustained SLO burn into its derived
  ``degraded`` state (serving/lifecycle.py ``note_breaker``) — visible in
  ``serving_state`` and the ``serving.state`` records without flipping
  ``/ping`` (an SLO miss sheds nothing by itself; the saturation breaker
  owns that).

Fed by the WSGI middleware (telemetry/wsgi.py) for the ``/invocations``
route on BOTH serving apps, and read by the rank-0 ``/status`` endpoint
(telemetry/fleet.py).
"""

import collections
import logging
import threading
import time

from ..utils.envconfig import env_float
from .registry import REGISTRY, percentile

logger = logging.getLogger(__name__)

SLO_P95_ENV = "SM_SLO_P95_MS"
SLO_WINDOW_ENV = "SM_SLO_WINDOW_S"

DEFAULT_WINDOW_S = 300.0

#: a p95 objective tolerates 5% of requests above the target; burn rate is
#: the measured violating fraction divided by this budget
ERROR_BUDGET = 0.05

#: below this many samples the window stays out of ``degraded`` — a single
#: cold-start request must not flip the lifecycle state
MIN_SAMPLES = 20


def slo_target_ms():
    return env_float(SLO_P95_ENV, 0.0, minimum=0.0)


def slo_window_s():
    return env_float(SLO_WINDOW_ENV, DEFAULT_WINDOW_S, minimum=1.0)


class SloWindow:
    """Rolling latency window vs a p95 target.

    ``observe`` is O(amortized 1): append + trim + an incremental violation
    count; percentiles are computed only in :meth:`snapshot` (scrape /
    status cadence, not request cadence). ``clock`` is injectable
    so the burn-rate math is unit-testable without sleeping.
    """

    def __init__(self, target_p95_ms, window_s=None, registry=None, clock=None):
        self.target_p95_ms = float(target_p95_ms)
        self.window_s = float(window_s if window_s is not None else slo_window_s())
        self._clock = clock or time.monotonic
        self._lock = threading.Lock()
        self._samples = collections.deque()  # (t, latency_ms, violating)
        self._violating = 0
        reg = registry or REGISTRY
        # created (at zero) on install so both serving apps expose the
        # serving_slo_* series from the first scrape, not the first miss
        self._m_violations = reg.counter(
            "serving_slo_violation_total",
            "Requests over the SM_SLO_P95_MS latency target",
        )
        self._m_burn = reg.gauge(
            "serving_slo_burn_rate",
            "Rolling-window SLO violation fraction over the 5% error budget",
        )
        self._m_burn.set(0.0)

    # ------------------------------------------------------------- feed path
    def observe_seconds(self, elapsed_s):
        self.observe_ms(float(elapsed_s) * 1000.0)

    def observe_ms(self, latency_ms):
        now = self._clock()
        violating = latency_ms > self.target_p95_ms
        with self._lock:
            self._samples.append((now, float(latency_ms), violating))
            if violating:
                self._violating += 1
            self._trim_locked(now)
            burn = self._burn_locked()
        if violating:
            self._m_violations.inc()
        self._m_burn.set(round(burn, 4))

    def _trim_locked(self, now):
        cutoff = now - self.window_s
        samples = self._samples
        while samples and samples[0][0] < cutoff:
            _t, _ms, was_violating = samples.popleft()
            if was_violating:
                self._violating -= 1

    def _burn_locked(self):
        n = len(self._samples)
        if n == 0:
            return 0.0
        return (self._violating / n) / ERROR_BUDGET

    # ------------------------------------------------------------ read paths
    @property
    def degraded(self):
        """Breaker-shaped hook for the serving lifecycle: True while the
        window holds enough samples and the burn rate exceeds 1.0 (the
        error budget is being spent faster than the objective allows)."""
        with self._lock:
            self._trim_locked(self._clock())
            return len(self._samples) >= MIN_SAMPLES and self._burn_locked() > 1.0

    def snapshot(self):
        """-> dict(target/window/samples/p50/p95/violation_rate/burn_rate/
        degraded) — the shape ``/status`` publishes."""
        with self._lock:
            self._trim_locked(self._clock())
            lat = [ms for _t, ms, _v in self._samples]
            n = len(lat)
            violating = self._violating
            burn = self._burn_locked()
        return {
            "target_p95_ms": self.target_p95_ms,
            "window_s": self.window_s,
            "samples": n,
            "p50_ms": round(percentile(lat, 0.5), 3) if lat else 0.0,
            "p95_ms": round(percentile(lat, 0.95), 3) if lat else 0.0,
            "violation_rate": round(violating / n, 4) if n else 0.0,
            "burn_rate": round(burn, 4),
            "degraded": n >= MIN_SAMPLES and burn > 1.0,
        }


# ------------------------------------------------------------ process plane
_window_lock = threading.Lock()
_window = None


def maybe_install(registry=None):
    """Arm the process-wide SLO window when ``SM_SLO_P95_MS`` is set > 0.

    Called by the WSGI middleware at app-construction time, so BOTH serving
    apps (single-model and MME) get the same window and the
    ``serving_slo_*`` series without either importing this module
    explicitly. Idempotent; returns the active window or None (disarmed —
    zero objects, zero series)."""
    global _window
    if _window is not None:
        return _window
    target = slo_target_ms()
    if target <= 0:
        return None
    with _window_lock:
        if _window is None:
            _window = SloWindow(target, registry=registry)
            logger.info(
                "serving SLO armed: p95 target %.1f ms over a %.0fs window",
                _window.target_p95_ms,
                _window.window_s,
            )
    return _window


def active_window():
    """The installed window, or None when the plane is disarmed."""
    return _window


def _reset_for_tests():
    global _window
    with _window_lock:
        _window = None
