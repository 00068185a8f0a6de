"""Traffic kind ``train_window_categorical``: ``train_window`` for a job whose
matrices name categorical columns (``DataMatrix(feature_types=...)``).

One call of the public ``models.train()``, timed exactly as ``train_window``
times it (its ``WindowCallback``, compile counter and ``plain_rounds`` are
imported, not copied). It differs in what it must: the matrices carry the
configuration's ``feature_types``; ``correct`` is decided by
``benchmark/reference/categorical_gbt_reference.py`` (the same teacher-forced
float64 sums, a set test where the judged node holds a set, and the
reference's own partition scan: ``cat_partition_regret``) and holds three
more things exactly: no threshold split sits on a categorical column, every
set is a legal one, and a column of fewer than ``max_cat_to_onehot``
categories splits one against the rest
(``benchmark/README-categorical.md``). The result carries the training
matrix's present cells, counted here from the generated floats, for
``readers/kernel_roofline_sparse.py``.

**The probe.** A program without feature types would train a category's code
as a number (or fail on the keyword somewhere inside a run). ``run`` hands
the program's ``DataMatrix`` a 4 x 2 matrix with ``feature_types=["q", "c"]``
first and leaves at once, with a message and exit code 1, where the types
are refused or not kept.
"""

import importlib
import resource
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
from benchmark.reference import categorical_gbt_reference as reference
from benchmark.reference import gbt_reference
from benchmark.trace_reduce import TraceSummary


def require_feature_types():
    """Leave, exit code 1, unless the program's ``DataMatrix`` takes and
    keeps ``feature_types``."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix

    rows = np.asarray([[0.5, 0.0], [1.5, 1.0], [2.5, 2.0], [3.5, 1.0]], np.float32)
    try:
        probe = DataMatrix(rows, labels=np.zeros(4, np.float32), feature_types=["q", "c"])
        kept = list(getattr(probe, "feature_types", None) or [])
    except TypeError as e:
        kept = "refused: {}".format(e)
    if kept != ["q", "c"]:
        raise SystemExit(
            "benchmark: this program's DataMatrix takes no feature types (a 4 x 2 probe "
            "with feature_types=['q', 'c'] gave {!r}): it would train a category's code as "
            "a number. The cell needs categorical training (data/categorical.py, "
            "ops/categorical.py; PR 50).".format(kept)
        )


def categorical_rounds(forest, n_rounds):
    """``plain_rounds`` with each tree's sets: {node: the codes that go right}."""
    rounds = plain_rounds(forest, n_rounds)
    for r, rnd in enumerate(rounds):
        lo = forest.iteration_indptr[r]
        for i, (_c, tree) in enumerate(rnd):
            held = getattr(forest.trees[lo + i], "categories", None) or {}
            tree["categories"] = {int(k): np.asarray(v, np.int64) for k, v in held.items()}
    return rounds


def judge(forest, evals_log, config, x, y, k, compiles_in_window):
    """The checks of a categorical training cell: every number compared with
    its limit. Judged are the first and the last round of the first dispatch
    and the last round of the window's last dispatch, which stands on the
    state carried through every dispatch before it; the exact checks read
    every tree of the forest."""
    params = config["params"]
    metric = params["eval_metric"]
    logged = evals_log["train"][metric]
    check_at = sorted({0, k - 1, len(logged) - 1})
    rounds = categorical_rounds(forest, len(logged))
    types = list(config["feature_types"])
    cardinality = reference.column_cardinalities(x, types)
    to_onehot = int(params.get("max_cat_to_onehot", 4))
    threshold = int(params.get("max_cat_threshold", 64))
    worst = reference.check_rounds(
        rounds,
        check_at,
        x,
        y,
        params["objective"],
        float(params.get("base_score", 0.5)),
        float(params["eta"]),
        float(params["lambda"]),
        int(params["max_depth"]),
        logged,
        cardinality,
        float(params.get("min_child_weight", 1.0)),
        to_onehot,
        threshold,
    )
    lim = config["check_limits"]  # each limit with its readings: PERF.md section 2
    checks = [limits.check(name, worst[name], lim.get(name)) for name in sorted(worst)]
    trees = [tree for rnd in rounds for _c, tree in rnd]
    deepest = max(gbt_reference.tree_depth(t) for t in trees)
    over = max(deepest - int(params["max_depth"]), 0)
    checks.append(limits.check("tree_depth_over_max", over, 0))
    checks.append(limits.check("compiles_in_window", int(compiles_in_window), 0))
    # the loss has to fall from the first round to the last: a step that
    # returns its state unchanged leaves it where it was
    checks.append(limits.check("loss_not_falling", int(not logged[-1] < logged[0]), 0))
    exact = reference.exact_checks(trees, types, cardinality, to_onehot, threshold)
    checks.extend(limits.check(name, int(exact[name]), 0) for name in sorted(exact))
    return checks


def run(ctx, train_fn=None):
    """Drive one run. ``train_fn`` stands in for ``models.train`` in the tests
    that break the timed path underneath."""
    require_feature_types()
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
    types = list(config["feature_types"])
    sets = {name: DataMatrix(x, labels=y, feature_types=types) for name, (x, y) in data.items()}
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
    # the process's high-water mark before the float64 reference adds its own (KB on Linux)
    host_peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    t_check = time.perf_counter()
    x, y = data["train"]
    checks = judge(forest, window.evals_log, config, x, y, k, window.compiles_in_window)
    # a first run in a checkout compiles in front of the window: its setup_s
    # and train_first_round_s are not the warm ones
    cold_cache = window.compile_s_at_start > COLD_COMPILE_S
    print(
        "phases generate_s={:.3f} first_round_s={:.3f} window_s={:.3f} check_s={:.3f} "
        "compiles_before_window={} compile_s_before_window={:.3f} cold_cache={} "
        "host_peak_rss_bytes={}".format(
            t_call - t_generate, first - t_call, in_window[-1] - first,
            time.perf_counter() - t_check, window.compiles_at_start,
            window.compile_s_at_start, cold_cache, host_peak,
        )
    )
    present = int(np.count_nonzero(~np.isnan(x)))
    set_splits = sum(len(getattr(t, "categories", None) or {}) for t in forest.trees)
    splits = sum(int(np.count_nonzero(np.asarray(t.left) >= 0)) for t in forest.trees)
    print(
        "input rows={} columns={} categorical={} present_cells={} positives_pct={:.3f} "
        "trees={} splits={} set_splits={} dispatch_s={}".format(
            x.shape[0], x.shape[1], types.count("c"), present, 100.0 * float(y.mean()),
            len(forest.trees), splits, set_splits,
            [round(b - a, 3) for a, b in zip(ends, ends[1:])],
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
        "train_cells_present": present,
        "config": config,
        "traffic": traffic,
    }
