"""Traffic kind ``train_window_sparse``: ``train_window`` for a job whose
matrices are scipy CSR and stay sparse from the generator to the chip.

One call of the public ``models.train()``, timed exactly as ``train_window``
times it (its ``WindowCallback``, compile counter and ``plain_rounds`` are
imported, not copied). It differs in what it must: the generator hands CSR
and the matrices are sparse ``DataMatrix``; ``correct`` is decided by
``benchmark/reference/sparse_gbt_reference.py`` (the same teacher-forced
float64 sums over CSR rows, an absent cell routed by ``default_left``) and
holds two more things exactly: every split names an original column and a
threshold that is a cut of that column's own values, and the program's
gauge ``bundle_conflict_rows`` reads 0 (no row lost a cell to a bundle).
The result carries the training matrix's present cells, counted here from
the generated CSR, for ``readers/kernel_roofline_sparse.py``.

**The probe.** A program that densifies a sparse ``DataMatrix`` would ask
the host for rows x columns x 4 bytes (206 GB at the cell's size) and be
killed. ``run`` hands the program a 4 x 8 CSR first and leaves at once, with
a message and exit code 1, where the matrix comes back dense.
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
from benchmark.readers import program_phase
from benchmark.reference import gbt_reference, sparse_gbt_reference
from benchmark.trace_reduce import TraceSummary


def require_sparse_matrix():
    """Leave, exit code 1, unless the program's ``DataMatrix`` keeps a CSR
    matrix sparse."""
    import scipy.sparse as sp

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix

    probe = DataMatrix(
        sp.csr_matrix(
            (np.ones(4, np.float32), np.arange(4), np.arange(5)), shape=(4, 8)
        ),
        labels=np.zeros(4, np.float32),
    )
    if not getattr(probe, "is_sparse", False):
        raise SystemExit(
            "benchmark: this program's DataMatrix densifies a CSR matrix at once "
            "(a 4 x 8 probe came back as {}): at the cell's size that is rows x "
            "columns x 4 bytes of host memory, 206 GB. The cell needs the sparse "
            "DataMatrix and the bundled layout of data/bundling.py (PR 46).".format(
                type(getattr(probe, "features", None)).__name__
            )
        )


def judge(forest, evals_log, config, x, y, k, compiles_in_window):
    """The checks of a sparse training cell: every number compared with its
    limit. Judged are the first and the last round of the first dispatch and
    the last round of the window's last dispatch, which stands on the state
    carried through every dispatch before it; the exact checks read every
    tree of the forest."""
    params = config["params"]
    metric = params["eval_metric"]
    logged = evals_log["train"][metric]
    check_at = sorted({0, k - 1, len(logged) - 1})
    rounds = plain_rounds(forest, len(logged))
    worst = sparse_gbt_reference.check_rounds(
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
    checks.append(
        limits.check(
            "splits_off_own_cuts", int(sparse_gbt_reference.splits_off_own_cuts(trees, x)), 0
        )
    )
    # the program's own count of rows that lost a cell to a bundle; a program
    # without the gauge did not bundle, and -1 says so without failing it
    conflicts = program_phase.series("bundle_conflict_rows")
    checks.append(
        limits.check(
            "bundle_conflict_rows", int(conflicts[0].value) if conflicts else -1, 0
        )
    )
    return checks


def run(ctx, train_fn=None):
    """Drive one run. ``train_fn`` stands in for ``models.train`` in the tests
    that break the timed path underneath."""
    require_sparse_matrix()
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
    print(
        "phases generate_s={:.3f} first_round_s={:.3f} window_s={:.3f} check_s={:.3f} "
        "compiles_before_window={} compile_s_before_window={:.3f} cold_cache={}".format(
            t_call - t_generate, first - t_call, in_window[-1] - first,
            time.perf_counter() - t_check, window.compiles_at_start,
            window.compile_s_at_start, cold_cache,
        )
    )
    present = int(np.count_nonzero(~np.isnan(x.data)))
    print(
        "input rows={} columns={} present_cells={} a_row={:.3f} filled_pct={:.4f} "
        "positives_pct={:.3f} dispatch_s={}".format(
            x.shape[0], x.shape[1], present, present / x.shape[0],
            100.0 * present / (x.shape[0] * x.shape[1]), 100.0 * float(y.mean()),
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
