"""Traffic kind ``train_window_leafwise``: ``train_window`` for a job that
grows its trees loss-guided (``grow_policy=lossguide``, ``max_leaves``).

One call of the public ``models.train()``, timed exactly as ``train_window``
times it (its ``WindowCallback``, compile counter and ``plain_rounds`` are
imported, not copied). It differs in what it must: ``correct`` is decided by
``benchmark/reference/leafwise_reference.py``, which judges trees that are
not heaps and have no ``max_depth`` (every sibling pair's histogram sums,
every split's gain, the directly summed leaves, the logged loss) and holds
two things exactly: no tree has more than ``max_leaves`` leaves, and growth
was best-first by the node ids' own order of expansion. And the result
carries the traced rounds' trees and the training rows, from which
``readers/kernel_roofline_leafwise.py`` counts the needed row reads.

**The probe.** A program whose split steps are unrolled at trace time holds
``max_leaves - 1`` kernel call sites (95,708 equations at 255 leaves) and
would trace and lower for minutes in front of every run. ``run`` asks the
program first (the loss-guided build's jaxpr, traced over 8 rows and nothing
run, has as many equations at 16 leaves as at 32 where the loop is rolled) and
leaves at once, with a message and exit code 1, where it is not.
"""

import importlib
import shutil
import tempfile
import time

import numpy as np

from benchmark import limits
from benchmark.kinds.train_window import (
    COLD_COMPILE_S,
    WindowCallback,
    _CompileCounter,
    plain_rounds,
)
from benchmark.reference import gbt_reference, leafwise_reference
from benchmark.trace_reduce import TraceSummary


def build_equations(max_leaves):
    """Equations of the loss-guided build's jaxpr at ``max_leaves``, traced
    over 8 rows x 2 columns with the flat histogram: shapes only, no device."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs
    from sagemaker_xgboost_container_tpu.ops.lossguide import build_tree_lossguide

    knobs = resolve_hist_knobs()._replace(backend="cpu")  # no kernel body in the count
    rows = jax.ShapeDtypeStruct((8,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda bins, g, h, cuts: build_tree_lossguide(
            bins, g, h, cuts, max_leaves=max_leaves, num_bins=5, knobs=knobs
        )
    )(
        jax.ShapeDtypeStruct((8, 2), jnp.uint8), rows, rows,
        jax.ShapeDtypeStruct((2,), jnp.int32),
    )
    return len(jaxpr.jaxpr.eqns)


def require_rolled_steps():
    """Leave, exit code 1, unless the build's program is the same size
    whatever ``max_leaves``."""
    at_16, at_32 = build_equations(16), build_equations(32)
    if at_16 != at_32:
        raise SystemExit(
            "benchmark: this program unrolls a loss-guided build's split steps at "
            "trace time ({} equations at max_leaves 16, {} at 32): at the cell's "
            "max_leaves it would trace and lower one kernel body a step for minutes "
            "in front of every run. The cell needs the rolled step loop of "
            "ops/lossguide.py (PR 42).".format(at_16, at_32)
        )


def judge(forest, evals_log, config, x, y, k, compiles_in_window):
    """The checks of a loss-guided training cell: every number compared with
    its limit. Judged are the first and the last round of the first dispatch
    and the last round of the window's last dispatch, which stands on the
    state carried through every dispatch before it; the exact checks read
    every tree of the forest."""
    params = config["params"]
    metric = params["eval_metric"]
    logged = evals_log["train"][metric]
    check_at = sorted({0, k - 1, len(logged) - 1})
    rounds = plain_rounds(forest, len(logged))
    worst = leafwise_reference.check_rounds(
        rounds,
        check_at,
        x,
        y,
        params["objective"],
        float(params.get("base_score", 0.5)),
        float(params["eta"]),
        float(params["lambda"]),
        logged,
    )
    lim = config["check_limits"]  # each limit with its readings: PERF.md section 2
    checks = [limits.check(name, worst[name], lim.get(name)) for name in sorted(worst)]
    trees = [tree for rnd in rounds for _c, tree in rnd]
    max_leaves = int(params["max_leaves"])
    over = max(max(leafwise_reference.leaves(t) for t in trees) - max_leaves, 0)
    checks.append(limits.check("leaves_over_max", over, 0))
    checks.append(
        limits.check(
            "best_first_violations",
            sum(leafwise_reference.best_first_violations(t) for t in trees),
            0,
        )
    )
    if int(params.get("max_depth", 0)) > 0:
        deepest = max(gbt_reference.tree_depth(t) for t in trees)
        checks.append(
            limits.check("tree_depth_over_max", max(deepest - int(params["max_depth"]), 0), 0)
        )
    checks.append(limits.check("compiles_in_window", int(compiles_in_window), 0))
    # the loss has to fall from the first round to the last: a step that
    # returns its state unchanged leaves it where it was
    checks.append(limits.check("loss_not_falling", int(not logged[-1] < logged[0]), 0))
    return checks


def run(ctx, train_fn=None):
    """Drive one run. ``train_fn`` stands in for ``models.train`` in the tests
    that break the timed path underneath."""
    require_rolled_steps()
    config, traffic = ctx["config"], ctx["traffic"]
    k = int(config["rounds_per_dispatch"])
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # the fixed in-checkout directory, or the env's
    compiles = _CompileCounter()
    generator = importlib.import_module("benchmark.datagen." + config["generator"])
    t_generate = time.perf_counter()
    data = generator.make(config, ctx["seed"])
    sets = {name: DataMatrix(x, labels=y) for name, (x, y) in data.items()}
    params = dict(config["params"])
    params["_rounds_per_dispatch"] = k
    params["seed"] = ctx["seed"] % (1 << 31)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if ctx["trace"] else None
    warmup = int(traffic["warmup_dispatches"])
    traced = int(traffic.get("traced_dispatches", 1))
    window = WindowCallback(
        k, warmup, ctx["seconds"], compiles, trace_dir=trace_dir, traced=traced
    )
    t_call = time.perf_counter()
    wall_at_call = time.time()
    forest = (train_fn or models.train)(
        params,
        sets["train"],
        num_boost_round=1 << 20,
        evals=[(sets[name], name) for name in traffic["watchlist"]],
        callbacks=[window],
        verbose_eval=False,
    )
    ends = window.dispatch_ends
    first = ends[window.warmup - 1]
    in_window = ends[window.warmup:]
    rounds = k * len(in_window)
    t_check = time.perf_counter()
    x, y = data["train"]
    checks = judge(forest, window.evals_log, config, x, y, k, window.compiles_in_window)
    # a first run in a checkout compiles in front of the window: its setup_s
    # and train_first_round_s are not the warm ones
    cold_cache = window.compile_s_at_start > COLD_COMPILE_S
    depths = [t.depth() for t in forest.trees]
    print(
        "phases generate_s={:.3f} first_round_s={:.3f} window_s={:.3f} check_s={:.3f} "
        "compiles_before_window={} compile_s_before_window={:.3f} cold_cache={}".format(
            t_call - t_generate, first - t_call, in_window[-1] - first,
            time.perf_counter() - t_check, window.compiles_at_start,
            window.compile_s_at_start, cold_cache,
        )
    )
    print(
        "trees leaves={} depth={} dispatch_s={}".format(
            [int(np.count_nonzero(t.left < 0)) for t in forest.trees], depths,
            [round(b - a, 3) for a, b in zip(ends, ends[1:])],
        )
    )
    trace = None
    if trace_dir is not None:
        start, stop = window.trace_clock
        trace = TraceSummary.from_dir(trace_dir, window_s=stop - start)
        shutil.rmtree(trace_dir, ignore_errors=True)
    # the rounds the profiler covered: the window's first whole dispatches
    traced_rounds = plain_rounds(forest, forest.num_boosted_rounds)[k * warmup: k * (warmup + traced)]
    return {
        "checks": checks,
        "attempted": rounds,
        "failed": 0,
        "end_to_end": {
            "train_rounds_per_s": rounds / (in_window[-1] - first),
            "setup_s": (wall_at_call - ctx["t_process_start"]) + (first - t_call),
        },
        "host_spans": {"train_first_round_s": first - t_call},
        "memory_samples": window.memory_samples,
        "trace": trace,
        "traced_units": {"dispatch": window.traced, "round": window.traced * k},
        "traced_trees": [tree for rnd in traced_rounds for _c, tree in rnd],
        "train_x": x,
        "config": config,
        "traffic": traffic,
    }
