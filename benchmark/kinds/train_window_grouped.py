"""Traffic kind ``train_window_grouped``: ``train_window`` for a ranking job.

One call of the public ``models.train()`` over training and validation sets
that carry their query ``groups``, timed exactly as ``train_window`` times it
(its ``WindowCallback``, compile counter and ``plain_rounds`` are imported,
not copied). It differs only in what it must: the data are ``DataMatrix(x,
labels=y, groups=g)``, ``correct`` is decided by
``benchmark/reference/lambdamart_reference.py`` (a pairwise gradient over
groups, and the logged NDCG of every evaluation set in every judged round),
the program has to log one metric a round for every set, and the metric has
to rise, not fall.

A program that cannot compute the configuration's metric on the device
cannot run this cell at all: it would log once a dispatch, and before PR 28
it would also pad 18,919 groups to the largest (a dispatch of a minute or
more). ``run`` asks the program first and leaves at once, with a message
and exit code 1, where it cannot.
"""

import importlib
import shutil
import tempfile
import time

from benchmark import limits
from benchmark.kinds.train_window import (
    COLD_COMPILE_S,
    WindowCallback,
    _CompileCounter,
    plain_rounds,
    plain_tree,
)
from benchmark.reference import gbt_reference, lambdamart_reference
from benchmark.trace_reduce import TraceSummary


def judge(forest, evals_log, config, traffic, data, k, compiles_in_window):
    """The checks of a grouped training cell: every number compared with its
    limit. Judged are the first and the last round of the first dispatch and
    the last round of the window's last dispatch, which stands on the state
    carried through every dispatch before it."""
    params = config["params"]
    metric = params["eval_metric"]
    logged = {name: evals_log[name][metric] for name in traffic["watchlist"]}
    n_rounds = forest.num_boosted_rounds
    check_at = sorted({0, k - 1, n_rounds - 1})
    # a metric line for every round of every set: a program that evaluates
    # once a dispatch logs a K-th of them
    missing = sum(n_rounds - len(values) for values in logged.values())
    checks = [limits.check("metric_lines_missing", int(missing), 0)]
    if not missing:
        worst = lambdamart_reference.check_rounds(
            plain_rounds(forest, n_rounds),
            check_at,
            {name: data[name] for name in traffic["watchlist"]},
            float(params.get("base_score", 0.5)),
            float(params["eta"]),
            float(params["lambda"]),
            int(params["max_depth"]),
            metric,
            logged,
        )
        lim = config["check_limits"]  # each limit with its readings: PERF.md section 2
        checks += [limits.check(name, worst[name], lim.get(name)) for name in sorted(worst)]
        first, last = logged["train"][0], logged["train"][-1]
        # the metric has to rise from the first round to the last: a step
        # that returns its state unchanged leaves it where it was
        checks.append(limits.check("metric_not_rising", int(not last > first), 0))
    trees = [plain_tree(t) for t in forest.trees]
    deepest = max(gbt_reference.tree_depth(t) for t in trees)
    checks.append(
        limits.check("tree_depth_over_max", max(deepest - int(params["max_depth"]), 0), 0)
    )
    checks.append(limits.check("compiles_in_window", int(compiles_in_window), 0))
    # for the record (no limit): how full the judged trees are
    splits = [int((trees[r]["left"] >= 0).sum()) for r in check_at]
    checks.append(limits.check("judged_tree_splits_min", min(splits), None))
    return checks


def require_device_metric(params):
    """Leave, exit code 1, unless the program computes the metric on the device."""
    from sagemaker_xgboost_container_tpu.models import device_metrics

    metric, objective = params["eval_metric"], params["objective"]
    if device_metrics.make_device_metric(metric, objective) is None:
        raise SystemExit(
            "benchmark: this program cannot compute {} on the device for {}; the cell "
            "needs a metric line every round of a fused dispatch".format(metric, objective)
        )


def run(ctx, train_fn=None):
    """Drive one run. ``train_fn`` stands in for ``models.train`` in the tests
    that break the timed path underneath."""
    config, traffic = ctx["config"], ctx["traffic"]
    k = int(config["rounds_per_dispatch"])
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.utils.compile_cache import enable_compile_cache

    require_device_metric(config["params"])
    enable_compile_cache()  # the fixed in-checkout directory, or the env's
    compiles = _CompileCounter()
    generator = importlib.import_module("benchmark.datagen." + config["generator"])
    t_generate = time.perf_counter()
    data = generator.make(config, ctx["seed"])
    sets = {
        name: DataMatrix(x, labels=y, groups=groups) for name, (x, y, groups) in data.items()
    }
    params = dict(config["params"])
    params["_rounds_per_dispatch"] = k
    params["seed"] = ctx["seed"] % (1 << 31)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if ctx["trace"] else None
    window = WindowCallback(
        k,
        int(traffic["warmup_dispatches"]),
        ctx["seconds"],
        compiles,
        trace_dir=trace_dir,
        traced=int(traffic.get("traced_dispatches", 1)),
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
    checks = judge(
        forest, window.evals_log, config, traffic, data, k, window.compiles_in_window
    )
    # a first run in a checkout compiles in front of the window: its setup_s
    # and train_first_round_s are not the warm ones
    cold_cache = window.compile_s_at_start > COLD_COMPILE_S
    print(
        "phases generate_s={:.3f} first_round_s={:.3f} window_s={:.3f} check_s={:.3f} "
        "compiles_before_window={} compile_s_before_window={:.3f} cold_cache={}".format(
            t_call - t_generate, first - t_call, in_window[-1] - first,
            time.perf_counter() - t_check, window.compiles_at_start,
            window.compile_s_at_start, cold_cache,
        )
    )
    trace = None
    if trace_dir is not None:
        start, stop = window.trace_clock
        trace = TraceSummary.from_dir(trace_dir, window_s=stop - start)
        shutil.rmtree(trace_dir, ignore_errors=True)
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
        "config": config,
        "traffic": traffic,
    }
