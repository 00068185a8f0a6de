#!/usr/bin/env python
"""Benchmark: boosting rounds/sec of the XLA histogram tree builder.

Measures steady-state boosting throughput on a synthetic Higgs-like binary
classification task (BASELINE.json config #2: dense numeric features,
binary:logistic, hist) in ONE process and prints one JSON result line:

    {"metric": ..., "value": N, "unit": "rounds/sec", "vs_baseline": N,
     "device": {"platform": ..., "kind": ..., "count": N}, ...}

vs_baseline is measured against the north-star target of 5 boosting
rounds/sec (BASELINE.json) — the reference publishes no numbers of its own.

A rate is a device number: the run fails (exit 2) when jax finds no
accelerator. ``JAX_PLATFORMS=cpu`` is the explicit request for a CPU run
(functional checks only); its result line names ``platform: cpu`` like any
other and is never a stand-in for a chip measurement.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

N_ROWS = int(os.getenv("BENCH_ROWS", "1000000"))
N_FEATURES = int(os.getenv("BENCH_FEATURES", "28"))
MAX_DEPTH = int(os.getenv("BENCH_MAX_DEPTH", "8"))
WARMUP_ROUNDS = int(os.getenv("BENCH_WARMUP", "3"))
BENCH_ROUNDS = int(os.getenv("BENCH_ROUNDS_N", "20"))
NORTH_STAR_ROUNDS_PER_SEC = 5.0


def _make_data(n, d, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype(np.float32)
    logit = X[:, 0] * 0.8 + X[:, 1] * X[:, 2] * 0.5 + np.sin(X[:, 3]) - 0.2
    y = (logit + rng.randn(n) * 0.5 > 0).astype(np.float32)
    return X, y


def _task_setup(n, d, seed=0):
    """BENCH_TASK selects the measured workload: ``binary`` (default; BASELINE
    config #2 Higgs-like), ``multiclass`` (#3 CoverType-like, 7 classes),
    ``ranking`` (#4 MSLR-like LambdaMART, ~100-doc groups), or ``lossguide``
    (LightGBM-style leaf-wise growth at BENCH_MAX_LEAVES, default 255 — the
    O(max_leaves * n * d) rescan cost question). Returns
    (DataMatrix kwargs-ready pieces, params dict, task label)."""
    task = os.getenv("BENCH_TASK", "binary")
    rng = np.random.RandomState(seed)
    X, y = _make_data(n, d, seed)
    groups = None
    if task == "binary":
        params = {"objective": "binary:logistic"}
    elif task == "lossguide":
        params = {
            "objective": "binary:logistic",
            "grow_policy": "lossguide",
            "max_leaves": int(os.getenv("BENCH_MAX_LEAVES", "255")),
            "max_depth": 0,
        }
    elif task == "multiclass":
        score = X[:, 0] + 0.7 * X[:, 1] * X[:, 2] + 0.3 * rng.randn(n)
        y = np.digitize(score, np.quantile(score, np.linspace(0, 1, 8)[1:-1]))
        y = y.astype(np.float32)
        params = {"objective": "multi:softmax", "num_class": 7}
    elif task == "ranking":
        rel = X[:, 0] + np.sin(X[:, 1]) + 0.5 * rng.randn(n)
        y = np.digitize(rel, np.quantile(rel, [0.5, 0.75, 0.9, 0.97])).astype(
            np.float32
        )
        group_size = 100
        groups = np.full(n // group_size, group_size, np.int64)
        n_used = int(groups.sum())
        X, y = X[:n_used], y[:n_used]
        params = {"objective": "rank:ndcg"}
    else:
        raise ValueError("BENCH_TASK must be binary|multiclass|ranking|lossguide")
    return X, y, groups, params, task


def _final_train_metric(margins, y, task):
    """(metric name, value) of the trained margins on the bench's own train
    set — the model-quality stamp next to rounds/sec. Host numpy on the
    final margins only (one gather after the measured window, never inside
    it). Ranking would need grouped NDCG; skipped."""
    m = np.asarray(margins, np.float64)
    rows = len(y)
    eps = 1e-7
    if task in ("binary", "lossguide"):
        p = np.clip(1.0 / (1.0 + np.exp(-m.reshape(-1)[:rows])), eps, 1 - eps)
        return "logloss", float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))
    if task == "multiclass":
        m = m[:rows]
        e = np.exp(m - m.max(axis=1, keepdims=True))
        p = e / e.sum(axis=1, keepdims=True)
        picked = p[np.arange(rows), y.astype(np.int64)]
        return "mlogloss", float(-np.mean(np.log(np.clip(picked, eps, None))))
    return None, None


def main():
    import jax

    from sagemaker_xgboost_container_tpu.utils.compile_cache import (
        enable_compile_cache,
    )
    from sagemaker_xgboost_container_tpu.utils.device_runtime import (
        require_accelerator,
    )

    # no chip, no number: a missing accelerator is an error unless a CPU
    # run was asked for by name
    device = require_accelerator("bench.py")
    # persistent XLA compile cache, armed before the first compile
    enable_compile_cache()

    # attribution plumbing: the jax.monitoring compile listener feeds
    # compile_stats; every dispatch records host_dispatch/device_sync, and
    # SM_TRACE_DEVICE_SYNC=1 adds the fence on everything a dispatch put in
    # flight (the bench loop blocks per dispatch anyway, so it costs nothing)
    os.environ.setdefault("SM_TRACE_DEVICE_SYNC", "1")
    # arm the device window too: the session's compiled-cost introspection
    # (training.compiled, with the stage table's summary)
    os.environ.setdefault("SM_DEVICE_TELEMETRY", "1")
    # and the model window: the final JSON stamps a train metric + the last
    # round's learning stats so the result tracks model quality next to
    # rounds/sec (a perf win that degrades quality must be visible)
    os.environ.setdefault("SM_MODEL_TELEMETRY", "1")
    from sagemaker_xgboost_container_tpu.telemetry import register_runtime_gauges
    from sagemaker_xgboost_container_tpu.telemetry.cluster import compile_stats

    register_runtime_gauges()

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models.booster import (
        TrainConfig,
        _TrainingSession,
    )
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    X, y, groups, task_params, task = _task_setup(N_ROWS, N_FEATURES)
    dtrain = DataMatrix(X, labels=y, groups=groups)
    rounds_per_dispatch = int(os.getenv("BENCH_ROUNDS_PER_DISPATCH", "10"))
    params = dict(task_params)
    # task params may pin their own depth policy (lossguide: max_depth=0)
    params.setdefault("max_depth", MAX_DEPTH)
    params.update(
        eta=0.2,
        tree_method="hist",
        max_bin=256,
        _rounds_per_dispatch=rounds_per_dispatch,
    )
    config = TrainConfig(params)
    forest = Forest(
        objective_name=config.objective,
        objective_params={"num_class": config.num_class}
        if config.num_class
        else None,
        base_score=config.base_score,
        num_feature=dtrain.num_col,
        num_class=config.num_class,
    )
    # multi-device hosts measure the full data-parallel round (rows sharded
    # over all local devices, GRAFT_HIST_COMM selecting the histogram
    # collective) — the north-star is a v5p MESH rate, not a single chip.
    # BENCH_MESH=0 opts out; single-device runs (incl. the CPU fallback,
    # which never sets xla_force_host_platform_device_count) are unchanged.
    mesh = None
    mesh_note = ""
    if os.getenv("BENCH_MESH", "1") != "0" and len(jax.devices()) > 1:
        from jax.sharding import Mesh

        # BENCH_MESH_SHAPE=RxC builds a 2-D (data x feature) mesh over the
        # first R*C local devices — the communication-optimal 2-D lowering
        # (GRAFT_HIST_COMM=reduce_scatter x feature axis) is measured on
        # exactly the topology it targets. Empty/unset: the auto 1-D mesh.
        shape_spec = os.getenv("BENCH_MESH_SHAPE", "").strip()
        if shape_spec:
            try:
                rows, cols = (int(v) for v in shape_spec.lower().split("x"))
                if rows < 1 or cols < 1 or rows * cols > len(jax.devices()):
                    raise ValueError("shape exceeds device count")
                mesh = Mesh(
                    np.array(jax.devices()[: rows * cols]).reshape(rows, cols),
                    axis_names=("data", "feature"),
                )
                mesh_note = ", mesh={}x{} (data x feature) comm={}".format(
                    rows, cols, os.getenv("GRAFT_HIST_COMM", "psum")
                )
            except (ValueError, TypeError) as e:
                sys.stderr.write(
                    "BENCH_MESH_SHAPE={!r} invalid ({}); falling back to the "
                    "1-D data mesh\n".format(shape_spec, e)
                )
                mesh = None
        if mesh is None:
            mesh = Mesh(np.array(jax.devices()), axis_names=("data",))
            mesh_note = ", mesh={}xdata comm={}".format(
                len(jax.devices()), os.getenv("GRAFT_HIST_COMM", "psum")
            )
    session = _TrainingSession(config, dtrain, [], forest, mesh=mesh)

    # the round-latency distribution rides the same telemetry registry the
    # trainer uses (training_round_seconds / training_phase_seconds), so the
    # bench line carries registry-derived p50/p95 + a phase breakdown, not
    # just the mean
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY, span
    from sagemaker_xgboost_container_tpu.training.profiling import ROUND_HISTOGRAM

    round_hist = REGISTRY.histogram(ROUND_HISTOGRAM, help="Boosting round wall time")

    def _phase_sums():
        sums = {}
        for name, kind, _help, series in REGISTRY.collect():
            if name == "training_phase_seconds" and kind == "histogram":
                for metric in series:
                    sums[metric.labels.get("phase", "unknown")] = metric.sum
        return sums

    with span("warmup"):
        done = 0
        while done < WARMUP_ROUNDS:
            done += len(session.run_rounds()[0])
        jax.block_until_ready(session.margins)

    warmup_compile_s = compile_stats()["seconds"]
    pre_phases = _phase_sums()
    start = time.perf_counter()
    done = 0
    with span("measure"):
        # block per dispatch (not once at the end) so per-round latency is
        # observable; with K rounds per dispatch the extra syncs are ~2 of
        # BENCH_ROUNDS/K and amortize to noise
        while done < BENCH_ROUNDS:
            t0 = time.perf_counter()
            n = len(session.run_rounds()[0])
            jax.block_until_ready(session.margins)
            dt = time.perf_counter() - t0
            for _ in range(n):
                round_hist.observe(dt / max(n, 1))
            done += n
    elapsed = time.perf_counter() - start

    post_phases = _phase_sums()
    phases_ms = {k: round(v * 1000, 3) for k, v in post_phases.items()}

    # attribution of the MEASURED window: compile (jax.monitoring listener
    # delta; warmup compile reported separately — that's where first-round
    # compile lives), host dispatch vs device compute (the per-dispatch
    # fence spans), and the calibrated collective share on a mesh
    def _delta(key):
        return max(post_phases.get(key, 0.0) - pre_phases.get(key, 0.0), 0.0)

    from sagemaker_xgboost_container_tpu.telemetry import get_round_fields
    from sagemaker_xgboost_container_tpu.training.profiling import (
        attribution_fields,
    )

    compile_ms = max(compile_stats()["seconds"] - warmup_compile_s, 0.0) * 1000
    # a compile that fired inside a fenced dispatch is already inside the
    # host_dispatch span — re-attribute like RoundTimer does
    host_ms = max(_delta("host_dispatch") * 1000 - compile_ms, 0.0)
    attribution = attribution_fields(
        total_ms=elapsed * 1000.0,
        compile_ms=compile_ms,
        host_ms=host_ms,
        device_ms=_delta("device_sync") * 1000,
        collective_ms=float(get_round_fields().get("hist_comm_ms") or 0.0)
        * done,
    )
    attribution["warmup_compile_ms"] = round(warmup_compile_s * 1000, 3)

    rounds_per_sec = done / elapsed
    shape_note = (
        "{} leaves (leaf-wise)".format(params["max_leaves"])
        if task == "lossguide"
        else "depth {}".format(MAX_DEPTH)
    )
    doc = {
        "metric": "boosting rounds/sec (synthetic, {} rows x {} feat, {}, {}{})".format(
            N_ROWS, N_FEATURES, shape_note, params["objective"], mesh_note
        ),
        "value": round(rounds_per_sec, 3),
        "unit": "rounds/sec",
        "vs_baseline": round(rounds_per_sec / NORTH_STAR_ROUNDS_PER_SEC, 3),
        "device": device,
        "p50_ms": round(round_hist.quantile(0.5) * 1000, 3),
        "p95_ms": round(round_hist.quantile(0.95) * 1000, 3),
        "rounds_per_dispatch": session.rounds_per_dispatch,
        "phases_ms": phases_ms,
        "attribution": attribution,
    }
    # model-quality stamp (SM_MODEL_TELEMETRY): the final train metric plus
    # the last dispatch's on-device learning stats: quality next to
    # throughput
    try:
        metric_name, metric_value = _final_train_metric(session.margins, y, task)
        model_doc = {}
        if metric_name is not None:
            model_doc["train_metric"] = metric_name
            model_doc["train_value"] = round(metric_value, 6)
        if session.last_learning_stats:
            model_doc["learning"] = {
                k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in session.last_learning_stats[-1].items()
            }
        if model_doc:
            doc["model"] = model_doc
    except Exception as e:
        sys.stderr.write("model-quality stamp failed: {}\n".format(e))
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
