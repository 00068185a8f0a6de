"""The ``serve`` entrypoint: threaded WSGI server + mode dispatch.

Reference: serving.py:140-169 (gunicorn+Flask or MMS). Here a single process
owns the TPU and a thread pool handles HTTP (no gunicorn/gevent in the
image; prediction is a compiled XLA kernel, so the GIL is released during
compute and worker-per-copy is unnecessary). Dispatch:

* SAGEMAKER_MULTI_MODEL=true  -> multi-model manager app (mme.py),
* user inference module found -> its model_fn/input_fn/predict_fn/output_fn/
  transform_fn override the algorithm handlers (serving.py:63-134),
* otherwise                    -> algorithm-mode scoring app.

``OMP_NUM_THREADS`` defaults to 1 as in the reference (serving.py:46-60) so
host-side numpy work doesn't oversubscribe the VM.
"""

import hashlib
import importlib.util
import logging
import os
import signal
import sys
import threading
import time
from socketserver import ThreadingMixIn
from wsgiref.simple_server import WSGIRequestHandler, WSGIServer, make_server

from .. import constants
from .. import telemetry
from ..constants import EXIT_DRAIN_TIMEOUT
from ..utils.device_runtime import start_device_runtime
from ..utils.envconfig import env_float
from ..utils.logging_config import setup_main_logger
from . import lifecycle as lifecycle_mod
from . import serve_utils
from .app import ScoringService, make_app
from .mme import make_mme_app

logger = logging.getLogger(__name__)

METRICS_INTERVAL_ENV = "SM_METRICS_EMIT_INTERVAL_S"

HOOK_NAMES = ("model_fn", "input_fn", "predict_fn", "output_fn", "transform_fn")


class _ThreadedWSGIServer(ThreadingMixIn, WSGIServer):
    daemon_threads = True
    allow_reuse_address = True
    # socketserver's default listen backlog of 5 RSTs concurrent connects
    # beyond it (observed: 16 parallel clients losing connections); the
    # reference's gunicorn default is 2048
    request_queue_size = 2048


class _QuietHandler(WSGIRequestHandler):
    def log_message(self, format, *args):  # route access logs through logging
        logger.debug("%s - %s", self.address_string(), format % args)


def set_default_serving_env_if_unspecified():
    os.environ.setdefault("OMP_NUM_THREADS", constants.ONE_THREAD_PER_PROCESS)


def is_multi_model():
    return os.environ.get("SAGEMAKER_MULTI_MODEL", "").lower() == "true"


def _load_user_hooks(model_dir):
    """Import the customer's inference script if present; return hook dict.

    Import hygiene: the script dir lands on ``sys.path`` (user scripts
    import sibling helpers, lazily too — so a successful load keeps it,
    without duplicating an entry already there), and the module registers
    in ``sys.modules`` under a name derived from the script path (pickle /
    dataclass machinery resolves classes through it; a fixed name would
    alias distinct scripts). A FAILED exec rolls both back, so a broken
    script can't poison a retried load with a half-initialized module or a
    stale path entry.
    """
    program = os.environ.get("SAGEMAKER_PROGRAM")
    candidates = []
    if program:
        for base in (
            os.environ.get("SAGEMAKER_SUBMIT_DIRECTORY", ""),
            os.path.join(model_dir, "code"),
            model_dir,
        ):
            if base:
                candidates.append(os.path.join(base, program))
    script = next((c for c in candidates if os.path.isfile(c)), None)
    if script is None:
        return {}
    from ..utils.requirements import install_requirements_if_present

    install_requirements_if_present(os.path.dirname(script))
    script_dir = os.path.dirname(script)
    module_name = "user_inference_{}".format(
        hashlib.sha1(os.path.abspath(script).encode("utf-8")).hexdigest()[:12]
    )
    spec = importlib.util.spec_from_file_location(module_name, script)
    module = importlib.util.module_from_spec(spec)
    inserted = script_dir not in sys.path
    if inserted:
        sys.path.insert(0, script_dir)
    sys.modules[module_name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        sys.modules.pop(module_name, None)
        if inserted:
            try:
                sys.path.remove(script_dir)
            except ValueError:
                pass
        raise
    hooks = {name: getattr(module, name) for name in HOOK_NAMES if hasattr(module, name)}
    logger.info("Loaded user serving hooks from %s: %s", script, sorted(hooks))
    return hooks


def build_app():
    if is_multi_model():
        logger.info("Starting multi-model endpoint manager")
        app = make_mme_app()
        # MME starts empty by design (models arrive via POST /models):
        # there is no warmup to gate readiness on
        lifecycle_mod.mark_ready()
        return app
    model_dir = os.getenv(constants.SM_MODEL_DIR, "/opt/ml/model")
    hooks = _load_user_hooks(model_dir)
    return make_app(ScoringService(model_dir), hooks=hooks)


class MetricsReporter:
    """Stop-able periodic ``serving.snapshot`` emitter.

    ``Event.wait(interval)`` instead of a bare ``time.sleep`` so the loop is
    killable: tests and graceful shutdown call :meth:`stop` and the thread
    exits within one wait, instead of leaking an unkillable daemon per
    server start."""

    def __init__(self, interval, registry):
        self.interval = interval
        self._registry = registry
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="metrics-reporter"
        )

    def start(self):
        self._thread.start()
        return self

    def stop(self, timeout=5.0):
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout)

    def _run(self):
        while not self._stop.wait(self.interval):
            try:
                telemetry.emit_metric(
                    "serving.snapshot", **telemetry.snapshot_fields(self._registry)
                )
            except Exception:
                logger.exception("metrics reporter failed; continuing")


def start_metrics_reporter(interval=None, registry=None):
    """Start a daemon emitting one ``serving.snapshot`` structured record every
    ``SM_METRICS_EMIT_INTERVAL_S`` seconds — the CloudWatch-scrapable view of
    serving metrics for fleets without a Prometheus scraper. Off by default
    (interval unset/0/malformed — malformed values warn once via envconfig).
    Returns a :class:`MetricsReporter` stop handle, or None when disabled."""
    if interval is None:
        interval = env_float(METRICS_INTERVAL_ENV, 0.0, minimum=0.0)
    if interval <= 0:
        return None
    reporter = MetricsReporter(interval, registry or telemetry.REGISTRY).start()
    logger.info("Emitting serving metric snapshots every %.1fs", interval)
    return reporter


def drain_and_shutdown(httpd, lifecycle, reporter=None):
    """Settle in-flight work, then close the listener. The SIGTERM contract:

    1. ``begin_drain()`` — /ping answers 503 + Retry-After so the load
       balancer deregisters, /invocations refuses new work the same way.
       The listener stays OPEN: a connect that raced the drain gets an
       orderly 503, never a RST.
    2. In-flight requests (WSGI latch: response bodies fully written) get
       ``SM_DRAIN_TIMEOUT_S`` to finish.
    3. Drained -> stop the accept loop, close the listener, exit 0.
       Still-wedged requests past the deadline -> flight-recorder dump +
       one ``serving.abort`` record and a distinct exit code (83) so the
       platform log names the failure instead of a mystery SIGKILL.

    Legacy mode (``SM_GRACEFUL_DRAIN=false``) skips the wait but still
    shuts the server down in an orderly fashion (no ``SystemExit`` from a
    signal handler; daemon request threads die with the process, exactly
    the pre-drain behavior).

    Shared by the SIGTERM handler and the serve drill. Returns True on a
    clean drain (False only from the test hook's fake exit).
    """
    drain_start = time.monotonic()
    if lifecycle is not None and lifecycle.graceful_drain:
        lifecycle.begin_drain()
        drained = lifecycle.wait_drained(lifecycle.drain_timeout_s)
        lifecycle.observe_drain_seconds(time.monotonic() - drain_start)
        if not drained:
            logger.error(
                "drain timed out after %.1fs with %d request(s) still in "
                "flight — wedged predict; exiting %d for a clean restart",
                lifecycle.drain_timeout_s, lifecycle.inflight, EXIT_DRAIN_TIMEOUT,
            )
            if reporter is not None:
                reporter.stop(timeout=2.0)
            from .lifecycle import abort_serving

            abort_serving(
                "drain_timeout",
                EXIT_DRAIN_TIMEOUT,
                inflight=lifecycle.inflight,
                drain_timeout_s=lifecycle.drain_timeout_s,
            )
            return False  # only reachable when the exit hook is faked
        logger.info(
            "drain complete in %.2fs; closing the listener",
            time.monotonic() - drain_start,
        )
    elif lifecycle is not None:
        lifecycle.begin_drain()  # still flip /ping for the shutdown window
        logger.info("graceful drain disabled (%s=false): immediate shutdown",
                    lifecycle_mod.GRACEFUL_DRAIN_ENV)
    if reporter is not None:
        reporter.stop(timeout=2.0)
    telemetry.stop_fleet_plane()
    httpd.shutdown()
    httpd.server_close()
    if lifecycle is not None:
        lifecycle.mark_stopped()
    return True


def serving_entrypoint(port=None, block=True):
    set_default_serving_env_if_unspecified()
    setup_main_logger(__name__)
    port = int(port or os.getenv("SAGEMAKER_BIND_TO_PORT", 8080))
    # before the first compile: arm the compile cache, say what we run on.
    # Initializes the backend here, so a missing device fails the start
    # instead of the first request
    start_device_runtime("serve")
    # device-runtime gauges (XLA compile count/seconds, RSS, live device
    # bytes) feed /metrics and the snapshot records from serving startup on
    telemetry.register_runtime_gauges()
    # lifecycle state machine + in-flight latch + (env-gated) predict
    # watchdog; knobs resolve once here (docs/robustness.md §Serving
    # lifecycle)
    lifecycle = lifecycle_mod.install(lifecycle_mod.ServingLifecycle())
    app = build_app()
    # SLO window (armed by instrument_wsgi inside build_app when
    # SM_SLO_P95_MS is set) quacks like a breaker: a sustained burn over
    # the error budget shows as DEGRADED in serving_state/serving.state
    # without flipping /ping — an SLO miss sheds nothing by itself
    slo_window = telemetry.slo.active_window()
    if slo_window is not None:
        lifecycle_mod.observe(slo_window)
    # kill -3 dumps the flight recorder + status snapshot without killing
    # the endpoint (the wedged-predict watchdog owns the abort path)
    telemetry.install_sigquit_handler()
    # live /status endpoint (SM_STATUS_PORT) on the serving host too — the
    # drift section (docs/observability.md §Model window) is a serving-side
    # document; self-gated: no thread or socket unless the knob is set
    current_host = os.getenv("SM_CURRENT_HOST", "localhost")
    telemetry.start_fleet_plane([current_host], current_host)
    logger.info(
        "GET /metrics is %s (gate: %s=true)",
        "enabled" if telemetry.metrics_endpoint_enabled() else "disabled",
        telemetry.METRICS_ENDPOINT_ENV,
    )
    reporter = start_metrics_reporter()
    httpd = make_server(
        "0.0.0.0", port, app, server_class=_ThreadedWSGIServer, handler_class=_QuietHandler
    )

    shutdown_state = {"thread": None}
    shutdown_lock = threading.Lock()

    def _shutdown(signo, frame):
        # The handler runs ON the main thread, which is blocked inside
        # serve_forever: both the drain wait and httpd.shutdown() (which
        # blocks until the serve loop acknowledges) would deadlock here.
        # Hand the whole sequence to a supervisor thread and return, letting
        # serve_forever keep answering 503s until the drain settles.
        logger.info("Received signal %s, draining before shutdown", signo)
        with shutdown_lock:
            if shutdown_state["thread"] is not None:
                return  # duplicate SIGTERM while already draining
            shutdown_state["thread"] = threading.Thread(
                target=drain_and_shutdown,
                args=(httpd, lifecycle),
                kwargs={"reporter": reporter},
                daemon=True,
                name="serving-drain",
            )
            shutdown_state["thread"].start()

    signal.signal(signal.SIGTERM, _shutdown)
    logger.info("Serving on port %d", port)
    if block:
        httpd.serve_forever()
        with shutdown_lock:
            drainer = shutdown_state["thread"]
        if drainer is not None:
            drainer.join(timeout=lifecycle.drain_timeout_s + 10.0)
        serve_utils.join_predict_warmup(lifecycle.drain_timeout_s)
    return httpd


def main():
    serving_entrypoint()


if __name__ == "__main__":
    main()
