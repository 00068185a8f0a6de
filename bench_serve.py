#!/usr/bin/env python
"""Serving latency benchmark: p50/p99 of POST /invocations + restart churn.

The serving-side metric ("p50 serve-predict latency"). Runs the real
threaded WSGI server in-process against a trained abalone-sized model and
measures end-to-end HTTP latency for single-row csv payloads, then a batch
payload, then a **churn leg**: a rolling SIGTERM-restart cycle (graceful
drain via serving/lifecycle.py) under continuous client load, reporting the
p95 and error rate a fleet would see across deploys. Prints one JSON line
naming the platform, device kind and device count it ran on. A latency is a
device number: the run fails (exit 2) when jax finds no accelerator, unless
a CPU run was asked for by name with ``JAX_PLATFORMS=cpu``.
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

N_REQUESTS = int(os.getenv("BENCH_SERVE_REQUESTS", "300"))
CHURN_CYCLES = int(os.getenv("BENCH_SERVE_CHURN_CYCLES", "3"))
STEADY_SECONDS = float(os.getenv("BENCH_SERVE_STEADY_S", "3"))


def _steady_leg(model_dir, single_payload):
    """Steady-state RPS/SLO leg (ROADMAP item 3's "steady-state RPS/SLO
    line"): a fresh server with the SLO window armed, two client threads at
    sustained load, reporting throughput and the window's own p95 /
    violation-rate view -> (steady_rps, slo_p95_ms, slo_violation_rate).

    The SLO target honors the operator's SM_SLO_P95_MS; unset, it defaults
    to 50 ms so the leg always exercises the violation accounting.
    """
    import urllib.request
    from wsgiref.simple_server import make_server

    from sagemaker_xgboost_container_tpu.serving.app import ScoringService, make_app
    from sagemaker_xgboost_container_tpu.serving.server import (
        _QuietHandler,
        _ThreadedWSGIServer,
    )
    from sagemaker_xgboost_container_tpu.telemetry import slo

    prior_target = os.environ.get(slo.SLO_P95_ENV)
    os.environ.setdefault(slo.SLO_P95_ENV, "50")
    slo._reset_for_tests()  # fresh window regardless of earlier legs
    app = make_app(ScoringService(model_dir))  # instrument_wsgi arms the SLO
    httpd = make_server(
        "127.0.0.1", 0, app,
        server_class=_ThreadedWSGIServer, handler_class=_QuietHandler,
    )
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = "http://127.0.0.1:{}/invocations".format(port)
    stop = threading.Event()
    counts = []
    lock = threading.Lock()

    def client():
        n = 0
        while not stop.is_set():
            req = urllib.request.Request(
                url, data=single_payload, method="POST",
                headers={"Content-Type": "text/csv"},
            )
            try:
                with urllib.request.urlopen(req, timeout=10) as resp:
                    resp.read()
                    n += 1
            except Exception:
                pass
        with lock:
            counts.append(n)

    clients = [threading.Thread(target=client, daemon=True) for _ in range(2)]
    t0 = time.perf_counter()
    for t in clients:
        t.start()
    time.sleep(STEADY_SECONDS)
    stop.set()
    for t in clients:
        t.join(timeout=15)
    elapsed = time.perf_counter() - t0
    httpd.shutdown()
    httpd.server_close()
    window = slo.active_window()
    snap = window.snapshot() if window is not None else {}
    slo._reset_for_tests()
    if prior_target is None:
        os.environ.pop(slo.SLO_P95_ENV, None)
    else:
        os.environ[slo.SLO_P95_ENV] = prior_target
    total = sum(counts)
    return (
        round(total / elapsed, 1) if elapsed > 0 else 0.0,
        snap.get("p95_ms", 0.0),
        snap.get("violation_rate", 0.0),
    )


def _churn_leg(model_dir, single_payload):
    """Rolling drain-restart cycles under load -> (p95_ms, error_rate, n).

    Each cycle: a fresh server + lifecycle, two client threads hammering
    /invocations, then a mid-traffic graceful drain (the SIGTERM sequence,
    invoked directly) and a restart. Non-200s and connection errors — the
    503s clients see while draining and the refused connects in the restart
    gap — count as errors: that's the fleet's view of a deploy.
    """
    import urllib.error
    import urllib.request
    from wsgiref.simple_server import make_server

    from sagemaker_xgboost_container_tpu.serving import lifecycle
    from sagemaker_xgboost_container_tpu.serving.app import ScoringService, make_app
    from sagemaker_xgboost_container_tpu.serving.server import (
        _QuietHandler,
        _ThreadedWSGIServer,
        drain_and_shutdown,
    )

    latencies = []
    outcomes = []  # True = 200 with a body
    lock = threading.Lock()

    for _cycle in range(CHURN_CYCLES):
        lc = lifecycle.install(lifecycle.ServingLifecycle())
        app = make_app(ScoringService(model_dir))
        httpd = make_server(
            "127.0.0.1", 0, app,
            server_class=_ThreadedWSGIServer, handler_class=_QuietHandler,
        )
        port = httpd.server_address[1]
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        url = "http://127.0.0.1:{}/invocations".format(port)
        stop = threading.Event()

        def client():
            while not stop.is_set():
                req = urllib.request.Request(
                    url, data=single_payload, method="POST",
                    headers={"Content-Type": "text/csv"},
                )
                t0 = time.perf_counter()
                try:
                    with urllib.request.urlopen(req, timeout=10) as resp:
                        resp.read()
                        ok = resp.status == 200
                except Exception:
                    ok = False
                elapsed = time.perf_counter() - t0
                with lock:
                    outcomes.append(ok)
                    if ok:
                        latencies.append(elapsed)
                if not ok:
                    # client retry backoff: without it a refused connect in
                    # the restart gap becomes a tight error loop that swamps
                    # the rate with thousands of sub-ms failures no real
                    # load-balancer client would issue
                    time.sleep(0.02)

        clients = [threading.Thread(target=client, daemon=True) for _ in range(2)]
        for t in clients:
            t.start()
        time.sleep(0.5)  # steady-state traffic
        drain_and_shutdown(httpd, lc)  # the SIGTERM sequence, in-process
        time.sleep(0.1)  # restart gap: connects here fail, and that counts
        stop.set()
        for t in clients:
            t.join(timeout=15)
        lifecycle.uninstall()

    total = len(outcomes)
    errors = total - sum(outcomes)
    lat = sorted(latencies)
    p95 = lat[max(0, int(len(lat) * 0.95) - 1)] * 1000 if lat else float("nan")
    return round(p95, 2), round(errors / total, 4) if total else 1.0, total


def _predict_compiled_cost(forest, num_feature, rows=256):
    """Compiled cost of the device predict kernel for one padded row bucket
    (the batch-256 leg's bucket): flops / bytes / HBM footprint via the same
    AOT introspection the training device window uses. Returns None when the
    forest is empty or introspection is unavailable."""
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.models.forest import predict_bucket
    from sagemaker_xgboost_container_tpu.ops.predict import (
        _forest_margin,
        _stacked_args,
    )
    from sagemaker_xgboost_container_tpu.telemetry import device as device_telemetry

    stacked = forest._stack(slice(0, len(forest.trees)))
    if stacked is None:
        return None
    bucket = predict_bucket(rows)
    x = jnp.zeros((bucket, num_feature), jnp.float32)
    lowered = _forest_margin.lower(
        *_stacked_args(stacked, "leaf_value"), x, stacked["depth"]
    )
    cost = device_telemetry.cost_from_compiled(lowered.compile())
    cost["rows"] = bucket
    cost["trees"] = len(forest.trees)
    return cost


def main():
    import urllib.request
    from wsgiref.simple_server import make_server

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train
    from sagemaker_xgboost_container_tpu.serving.app import ScoringService, make_app
    from sagemaker_xgboost_container_tpu.serving.serve_utils import (
        join_predict_warmup,
    )
    from sagemaker_xgboost_container_tpu.serving.server import (
        _QuietHandler,
        _ThreadedWSGIServer,
    )
    from sagemaker_xgboost_container_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    from sagemaker_xgboost_container_tpu.utils.device_runtime import (
        require_accelerator,
    )

    device = require_accelerator("bench_serve.py")
    enable_compile_cache()

    rng = np.random.RandomState(0)
    X = rng.rand(4000, 8).astype(np.float32)
    y = (X @ rng.rand(8).astype(np.float32) * 10).astype(np.float32)
    forest = train(
        {"max_depth": 6, "objective": "reg:squarederror"}, DataMatrix(X, labels=y),
        num_boost_round=100,
    )
    import tempfile

    model_dir = tempfile.mkdtemp()
    forest.save_model(os.path.join(model_dir, "xgboost-model"))

    app = make_app(ScoringService(model_dir))
    httpd = make_server(
        "127.0.0.1", 0, app, server_class=_ThreadedWSGIServer, handler_class=_QuietHandler
    )
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = "http://127.0.0.1:{}/invocations".format(port)

    def post(body):
        req = urllib.request.Request(
            base, data=body, method="POST", headers={"Content-Type": "text/csv"}
        )
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
        return time.perf_counter() - t0

    single = ",".join("%.4f" % v for v in X[0]).encode()
    batch = "\n".join(
        ",".join("%.4f" % v for v in row) for row in X[:256]
    ).encode()

    # trigger the model load, then let its background bucket warmup finish
    # BEFORE timing — an in-flight compile would pollute the first leg
    post(single)
    join_predict_warmup(300)

    # A/B the small-payload strategy: host numpy traversal (pinned to a
    # cutover that definitely includes 1 row) vs forcing the compiled device
    # kernel; the operator's own env value is restored for the batch leg
    prior = os.environ.get("GRAFT_HOST_PREDICT_ROWS")
    results = {}
    for label, rows in (("host", "32"), ("device", "0")):
        os.environ["GRAFT_HOST_PREDICT_ROWS"] = rows
        post(single)  # warm (jit cache on the device side)
        lat = sorted(post(single) for _ in range(N_REQUESTS))
        results["p50_single_row_ms_" + label] = round(lat[len(lat) // 2] * 1000, 2)
        results["p99_single_row_ms_" + label] = round(
            lat[int(len(lat) * 0.99) - 1] * 1000, 2
        )
    if prior is None:
        del os.environ["GRAFT_HOST_PREDICT_ROWS"]
    else:
        os.environ["GRAFT_HOST_PREDICT_ROWS"] = prior
    post(batch)
    blat = sorted(post(batch) for _ in range(50))
    httpd.shutdown()
    httpd.server_close()

    # steady-state leg: sustained RPS + the SLO window's own p95/violation
    # view (ROADMAP item 3), then the churn leg's rolling restarts
    steady_rps, slo_p95_ms, slo_violation_rate = _steady_leg(model_dir, single)
    churn_p95_ms, churn_error_rate, churn_requests = _churn_leg(model_dir, single)
    try:
        predict_compiled = _predict_compiled_cost(forest, X.shape[1])
    except Exception as e:  # introspection must never sink the benchmark
        sys.stderr.write("predict kernel cost introspection failed: {}\n".format(e))
        predict_compiled = None
    extra = {"predict_compiled": predict_compiled} if predict_compiled else {}
    print(
        json.dumps(
            {
                "metric": "serve /invocations latency (100-tree depth-6 model)",
                "device": device,
                **results,
                "p50_batch256_ms": round(blat[len(blat) // 2] * 1000, 2),
                "steady_rps": steady_rps,
                "slo_p95_ms": slo_p95_ms,
                "slo_violation_rate": slo_violation_rate,
                "churn_p95_ms": churn_p95_ms,
                "churn_error_rate": churn_error_rate,
                "churn_requests": churn_requests,
                "churn_cycles": CHURN_CYCLES,
                "unit": "ms",
                **extra,
            }
        )
    )


if __name__ == "__main__":
    main()
