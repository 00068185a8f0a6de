"""Traffic kind ``train_window``: one call of the public ``models.train()``.

Set-up makes the data from the seed and calls ``train()`` once with an
``after_iteration`` callback. The callback's calls arrive in bursts of K at
the end of each fused dispatch, so dispatch boundaries are visible from
outside the program. The first ``warmup_dispatches`` are warm-up (their end
gives ``train_first_round_s`` and starts the window); the window ends at the
first dispatch end at or after ``--seconds``, and the rate is whole
dispatches over the exact time they took. ``correct`` is decided after the
window by ``benchmark/reference/gbt_reference.py`` and by nothing timed.
"""

import shutil
import tempfile
import time

import numpy as np

from benchmark import harness, limits
from benchmark.reference import gbt_reference
from benchmark.trace_reduce import TraceSummary


COLD_COMPILE_S = 10.0  # XLA compile seconds in front of the window that mark a cold cache


class _CompileCounter:
    """Counts XLA backend compilations and their seconds (``jax.monitoring``).
    A persistent-cache hit fires no backend_compile event; nine small programs
    compile in every run all the same (PERF.md section 7)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kwargs):
        if event.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += duration


class WindowCallback:
    """Times dispatch ends, opens and closes the window, and in a traced run
    wraps ``traced_dispatches`` whole dispatches in the profiler."""

    def __init__(self, k, warmup, seconds, compiles, trace_dir=None, traced=1):
        self.k, self.warmup, self.seconds = k, warmup, seconds
        self.compiles = compiles
        self.trace_dir, self.traced = trace_dir, traced
        self.dispatch_ends = []          # host clock at the end of each dispatch
        self.window_start = None
        self.compiles_at_start = None
        self.compiles_in_window = None
        self.compile_s_at_start = None
        self.memory_samples = []         # bytes taken on the fullest chip, at the window's start
        self.trace_clock = None          # (start, stop) host clock of the trace
        self._trace_from = None

    def after_iteration(self, forest, rnd, evals_log):
        self.evals_log = evals_log  # train() keeps it to itself otherwise
        if (rnd + 1) % self.k:
            return False
        now = time.perf_counter()
        self.dispatch_ends.append(now)
        done = len(self.dispatch_ends)
        if done < self.warmup:
            return False
        if done == self.warmup:
            self.window_start = now
            self.compiles_at_start = self.compiles.count
            self.compile_s_at_start = self.compiles.seconds
            self.memory_samples.append(harness.memory_taken())
            if self.trace_dir is not None:
                import jax

                # the traced run profiles the window's first whole dispatches
                jax.profiler.start_trace(self.trace_dir)
                self._trace_from = time.perf_counter()
            return False
        if self.trace_dir is not None and self.trace_clock is None:
            if done - self.warmup == self.traced:
                import jax

                self.trace_clock = (self._trace_from, time.perf_counter())
                jax.profiler.stop_trace()
        if now - self.window_start >= self.seconds and (
            self.trace_dir is None or self.trace_clock is not None
        ):
            self.compiles_in_window = self.compiles.count - self.compiles_at_start
            return True
        return False


def plain_tree(tree):
    """A program ``Tree`` as the plain arrays the reference judges."""
    return {
        "feature": np.asarray(tree.feature, np.int64),
        "threshold": np.asarray(tree.threshold, np.float32),
        "default_left": np.asarray(tree.default_left, bool),
        "left": np.asarray(tree.left, np.int64),
        "right": np.asarray(tree.right, np.int64),
        "value": np.asarray(tree.value, np.float32),
        "gain": np.asarray(tree.gain, np.float32),
        "sum_hess": np.asarray(tree.sum_hess, np.float32),
    }


def plain_rounds(forest, n_rounds):
    rounds = []
    for r in range(n_rounds):
        lo, hi = forest.iteration_indptr[r], forest.iteration_indptr[r + 1]
        rounds.append(
            [(int(forest.tree_info[i]), plain_tree(forest.trees[i])) for i in range(lo, hi)]
        )
    return rounds


def judge(forest, evals_log, config, x, y, k, compiles_in_window):
    """The checks of a training cell: every number compared with its limit.
    Judged are the first and the last round of the first dispatch and the last
    round of the window's last dispatch, which stands on the state carried
    through every dispatch before it."""
    params = config["params"]
    metric = params["eval_metric"]
    logged = evals_log["train"][metric]
    check_at = sorted({0, k - 1, len(logged) - 1})
    worst = gbt_reference.check_rounds(
        plain_rounds(forest, len(logged)),
        check_at,
        x,
        y,
        params["objective"],
        float(params.get("base_score", 0.5)),
        float(params["eta"]),
        float(params["lambda"]),
        int(params["max_depth"]),
        logged,
    )
    lim = config["check_limits"]  # each limit with its readings: PERF.md section 2
    checks = [limits.check(name, worst[name], lim.get(name)) for name in sorted(worst)]
    deepest = max(gbt_reference.tree_depth(plain_tree(t)) for t in forest.trees)
    over = max(deepest - int(params["max_depth"]), 0)
    checks.append(limits.check("tree_depth_over_max", over, 0))
    checks.append(limits.check("compiles_in_window", int(compiles_in_window), 0))
    # the loss has to fall from the first round to the last: a step that
    # returns its state unchanged leaves it where it was
    checks.append(limits.check("loss_not_falling", int(not logged[-1] < logged[0]), 0))
    return checks


def run(ctx, train_fn=None):
    """Drive one run. ``train_fn`` stands in for ``models.train`` in the test
    that breaks the timed path underneath."""
    config, traffic = ctx["config"], ctx["traffic"]
    k = int(config["rounds_per_dispatch"])
    import importlib

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
        forest, window.evals_log, config, *data["train"], k, window.compiles_in_window
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
