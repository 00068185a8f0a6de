"""The loss-guided training cell (PR 42), `higgs-leafwise-l255.train-fused`:
its configuration and entries, the kind end to end at a tiny size on the CPU
with `correct` true through the leaf-wise reference, each of the ways it must
read `correct: false`, the probe that sends an unrolled program away, and the
roofline's count of needed row reads.

No module-level jax or topology calls: jax is imported inside the tests.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, needed_work, needed_work_leafwise  # noqa: E402
from benchmark.readers import kernel_roofline_leafwise, program_phase  # noqa: E402
from benchmark.reference import gbt_reference, leafwise_reference  # noqa: E402

CELL = "higgs-leafwise-l255.train-fused"
# The configuration's limits are read on the chip at its own size (PERF.md
# section 2). The CPU's flat histogram sums a node's rows in float32: the
# tiny runs read at most 2e-6 on the histogram gaps and 5e-9 on a median
# gain, the interpreted kernel's two bf16 passes 1e-5, its one pass 5e-3 and
# up (the control below).
CPU_LIMITS = {
    "direct_hess_err": 2e-4, "direct_hess_err_p90": 5e-4, "direct_hess_err_max": 1e-3,
    "gain_err_median": 1e-5, "leaf_sum_hess_rel": 2e-4, "leaf_value_err": 1e-4,
    "loss_abs": 8e-5,
}
TINY = {
    "train_rows": 5000, "validation_rows": 700, "rounds_per_dispatch": 2,
    "check_limits": CPU_LIMITS,
}
NEW_METRICS = {
    "hist_kernel_roofline_leafwise": "kernel_roofline_leafwise",
    "step_pick_ms_per_round": "stage_ms",
    "split_steps_per_round": "program_phase",
    "eval_walk_levels": "program_phase",
}
JOINED = {
    "train_first_round_s", "train_host_gap_ms_per_dispatch", "round_device_ms",
    "hist_kernel_ms_per_round", "device_idle_pct.train", "hist_stage_ms_per_round",
    "split_scan_ms_per_round", "route_rows_ms_per_round", "leaf_margin_ms_per_round",
    "eval_apply_ms_per_round", "eval_metric_ms_per_round", "round_unnamed_device_pct",
    "setup_sketch_s", "setup_bin_apply_s", "setup_upload_s", "setup_program_load_s",
    "setup_unnamed_s", "train_host_turnaround_ms_per_dispatch", "grad_ms_per_round",
}


def cell_files():
    return harness.resolve_cell(harness.load_benchmark(), CELL)


def tiny(**params):
    _cell, config, _traffic = cell_files()
    overrides = dict({"max_leaves": 15, "min_child_weight": 5}, **params)
    return dict(TINY, params=dict(config["params"], **overrides))


def cell_context(seed, seconds=0.2, **params):
    cell, config, traffic = cell_files()
    config.update(tiny(**params))
    return {
        "cell": cell, "config": config, "traffic": traffic, "seed": seed,
        "seconds": seconds, "trace": False, "t_process_start": 0.0,
    }


def failed(run):
    return {c["name"] for c in run["checks"] if not c["ok"]}


# ------------------------------------------------- configuration and entries
def test_configuration_is_the_published_comparison_with_nothing_reduced():
    bench = harness.load_benchmark()
    cell, config, traffic = cell_files()
    entry = {c["name"]: c for c in bench["configs"]}["higgs-leafwise-l255"]
    assert entry["reduced"] == [] == config["reduced"]
    assert len(entry["source"]) <= 200 and "xgboost_hist" in entry["source"]
    assert (config["train_rows"], config["validation_rows"]) == (10_500_000, 500_000)
    assert config["train_rows"] == config["published_train_rows"]
    assert config["validation_rows"] == config["published_validation_rows"]
    assert config["num_feature"] == 28 and config["rounds_per_dispatch"] == 2
    params = config["params"]
    assert (params["grow_policy"], params["max_depth"], params["max_leaves"]) == (
        "lossguide", 0, 255)
    assert (params["eta"], params["min_child_weight"], params["max_bin"]) == (0.1, 100, 256)
    assert len(config["assumed"]) >= 5
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert traffic["kind"] == "train_window_leafwise"
    assert traffic["watchlist"] == ["train", "validation"]
    # every limit the judge compares is in the file, and no other
    judged = set(leafwise_reference.NUMBERS[:6]) | {"loss_abs"}
    assert set(config["check_limits"]) == judged


def test_cell_is_on_the_lists_it_can_report_and_on_no_other():
    bench = harness.load_benchmark()
    e2e = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", CELL)}
    assert e2e == {"train_rounds_per_s", "setup_s"}
    layer = {m["name"]: m for m in harness.cell_metrics(bench, "per_layer", CELL, e2e)}
    assert set(layer) == JOINED | set(NEW_METRICS)
    # the depth-wise reader multiplies by max_depth (0 here); a loss-guided
    # round has no node_totals stage
    assert "hist_kernel_roofline" not in layer and "node_totals_ms_per_round" not in layer
    for name, reader in NEW_METRICS.items():
        entry = layer[name]
        assert entry["workloads"] == [CELL] and entry["moves"] == "train_rounds_per_s"
        spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
        assert spec["reader"] == reader
        read, _args = harness.load_reader(name)
        assert callable(read)
    assert layer["hist_kernel_roofline_leafwise"]["unit"] == "%"
    # no other cell's line gains a metric
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = {m["name"] for m in harness.cell_metrics(bench, "per_layer", other["name"], e2e)}
            assert not names & set(NEW_METRICS)


# --------------------------------------------------------- the kind, end to end
def test_cell_prints_one_well_formed_correct_line(capsys):
    rc = harness.run_cell(CELL, 2**31 + 42, 0.2, False, 0.0, shrink=tiny())
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is True, out
    assert set(line["metrics"]) == {"train_rounds_per_s", "setup_s"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    for name in ("direct_hess_err", "leaves_over_max", "best_first_violations",
                 "compiles_in_window", "loss_not_falling", "loss_abs"):
        assert any(o.startswith("check {}: value=".format(name)) for o in out), name
    assert any(o.startswith("trees leaves=[15, 15") for o in out)


def test_run_sets_the_gauges_and_hands_over_the_traced_trees():
    from benchmark.kinds import train_window_leafwise

    ctx = cell_context(2**31 + 43)
    run = train_window_leafwise.run(ctx)
    assert not failed(run), run["checks"]
    assert len(run["traced_trees"]) == 2 and run["train_x"].shape == (5000, 28)
    # a CPU trace has no device plane: the gauges are there, the readers say
    # nothing under a device metric's name
    assert program_phase.totals(program_phase.series("round_split_steps"))[0] == 14
    depth_gauge = program_phase.totals(program_phase.series("tree_depth_max"))[0]
    # the deepest leaf over every tree of the session, the traced ones among them
    assert depth_gauge >= max(gbt_reference.node_depths(t).max() for t in run["traced_trees"])
    assert kernel_roofline_leafwise.read(run, {"pattern": "graft_level_histogram"}) is None
    assert program_phase.read(run, {"metric": "round_split_steps", "reduce": "sum"}) is None


def test_one_pass_histogram_is_not_correct():
    """The control the chip runs as ``GRAFT_HIST_MM_PREC=bf16``: the kernel,
    interpreted, with one bf16 pass and with its two."""
    from benchmark.kinds import train_window_leafwise
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs

    def kernel(precision):
        knobs = resolve_hist_knobs()._replace(backend="tpu", precision=precision)
        return lambda *a, **kw: models.train(*a, hist_knobs=knobs, **kw)

    sound = train_window_leafwise.run(cell_context(44, max_leaves=8), train_fn=kernel("bf16x2"))
    assert not failed(sound), sound["checks"]
    control = train_window_leafwise.run(cell_context(44, max_leaves=8), train_fn=kernel("bf16"))
    assert {"direct_hess_err", "direct_hess_err_p90"} <= failed(control), control["checks"]


def test_first_positive_gain_instead_of_the_best_is_not_correct():
    """A build that splits the first leaf with a positive gain grows sound
    trees out of order: only the exact check sees it."""
    from benchmark.kinds import train_window_leafwise
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.ops import lossguide

    class FirstPositive:
        def __getattr__(self, name):
            return getattr(lossguide_jnp, name)

        @staticmethod
        def argmax(gains):
            return lossguide_jnp.argmax(gains > lossguide.MIN_SPLIT_LOSS)

    lossguide_jnp = lossguide.jnp

    def greedy_train(*args, **kwargs):
        lossguide.jnp = FirstPositive()
        try:
            return models.train(*args, **kwargs)
        finally:
            lossguide.jnp = lossguide_jnp

    run = train_window_leafwise.run(cell_context(45), train_fn=greedy_train)
    assert failed(run) == {"best_first_violations"}, run["checks"]


def test_a_leaf_too_many_is_not_correct():
    from benchmark.kinds import train_window_leafwise
    from sagemaker_xgboost_container_tpu import models

    def one_more(params, *args, **kwargs):
        return models.train(dict(params, max_leaves=params["max_leaves"] + 1), *args, **kwargs)

    run = train_window_leafwise.run(cell_context(46), train_fn=one_more)
    checks = {c["name"]: c for c in run["checks"]}
    assert checks["leaves_over_max"]["value"] == 1 and not checks["leaves_over_max"]["ok"]


# ------------------------------------------------------------------ the probe
def test_probe_sends_an_unrolled_program_away_within_seconds(monkeypatch):
    """The driver lays this PR's benchmark files over the parent, whose build
    unrolls its steps in Python: the kind has to leave before it traces 254
    kernel bodies. A stand-in with the parent's shape of program."""
    from benchmark.kinds import train_window_leafwise
    from sagemaker_xgboost_container_tpu.ops import lossguide

    def unrolled(bins, grad, hess, num_cuts, max_leaves, num_bins, **_kwargs):
        out = grad
        for t in range(max_leaves - 1):
            out = out * 0.5 + hess * t
        return {}, out

    monkeypatch.setattr(lossguide, "build_tree_lossguide", unrolled)
    start = time.perf_counter()
    with pytest.raises(SystemExit) as leaving:
        train_window_leafwise.run(cell_context(47))
    assert time.perf_counter() - start < 10
    # a message, so exit code 1
    assert isinstance(leaving.value.code, str) and "unrolls" in leaving.value.code


def test_probe_lets_the_rolled_program_through_quickly():
    from benchmark.kinds import train_window_leafwise

    start = time.perf_counter()
    train_window_leafwise.require_rolled_steps()
    assert time.perf_counter() - start < 10


# ----------------------------------------------------- the roofline's count
def complete_tree(depth):
    """A complete tree that splits on column 0 at thresholds that halve."""
    n = 2 ** (depth + 1) - 1
    ids = np.arange(n)
    internal = ids < 2**depth - 1
    tree = {
        "feature": np.zeros(n, np.int64),
        "threshold": np.zeros(n, np.float32),
        "default_left": np.zeros(n, bool),
        "left": np.where(internal, 2 * ids + 1, -1),
        "right": np.where(internal, 2 * ids + 2, -1),
    }
    lo, hi = {0: 0.0}, {0: 1.0}
    for node in ids[internal]:
        mid = (lo[node] + hi[node]) / 2
        tree["threshold"][node] = mid
        lo[2 * node + 1], hi[2 * node + 1] = lo[node], mid
        lo[2 * node + 2], hi[2 * node + 2] = mid, hi[node]
    return tree


def test_needed_rows_of_a_complete_tree_are_depth_times_rows():
    """What ties the two rooflines: a complete depth-d tree needs the d x n
    row reads ``readers/kernel_roofline.py`` counts for a depth-wise round."""
    x = np.random.default_rng(5).random((4000, 3), dtype=np.float32)
    for depth in (1, 3, 5):
        assert needed_work_leafwise.histogram_rows(complete_tree(depth), x) == depth * 4000
    work = needed_work_leafwise.tree_histograms([complete_tree(3)] * 2, x, 28, 257)
    assert work == needed_work.level_histogram(2 * 3 * 4000, 28, 257)


def test_needed_rows_of_a_chain_are_the_rows_that_reach_each_split():
    """Not a heap: the right child splits again and again. A row is read once
    at every depth at which its node splits, no more."""
    x = np.linspace(0, 1, 1000, endpoint=False, dtype=np.float32)[:, None]
    # node 0 splits at 0.5; its right child (2) at 0.75; that one's right (4) at 0.875
    tree = {
        "feature": np.zeros(7, np.int64),
        "threshold": np.array([0.5, 0, 0.75, 0, 0.875, 0, 0], np.float32),
        "default_left": np.zeros(7, bool),
        "left": np.array([1, -1, 3, -1, 5, -1, -1]),
        "right": np.array([2, -1, 4, -1, 6, -1, -1]),
    }
    assert needed_work_leafwise.histogram_rows(tree, x) == 1000 + 500 + 250


class FakeTrace:
    busy_s = 1.0

    def __init__(self, seconds):
        self.seconds = seconds

    def kernel_events(self, pattern):
        return self.seconds


def test_roofline_reader_is_least_time_over_kernel_time_and_never_clipped():
    from benchmark import peaks

    x = np.random.default_rng(6).random((2000, 28), dtype=np.float32)
    run = {
        "trace": FakeTrace([0.25, 0.25]), "traced_trees": [complete_tree(4)], "train_x": x,
        "config": {"num_feature": 28, "params": {"max_bin": 256}},
        "device_kind": "TPU v5 lite",
    }
    least, bound = needed_work.least_seconds(
        needed_work.level_histogram(4 * 2000, 28, 257), peaks.peaks_for("TPU v5 lite")
    )
    assert bound == "memory"
    got = kernel_roofline_leafwise.read(run, {"pattern": "graft_level_histogram"})
    assert got == pytest.approx(100.0 * least / 0.5)
    run["trace"] = FakeTrace([least / 4])
    assert kernel_roofline_leafwise.read(run, {"pattern": "x"}) == pytest.approx(400.0)
    # nothing to read: no trace, no kernel events, no traced trees (a kind
    # that hands none over)
    assert kernel_roofline_leafwise.read(dict(run, trace=None), {"pattern": "x"}) is None
    assert kernel_roofline_leafwise.read(dict(run, trace=FakeTrace([])), {"pattern": "x"}) is None
    assert kernel_roofline_leafwise.read(dict(run, traced_trees=None), {"pattern": "x"}) is None
