"""The sparse training cell (PR 46), `allstate-onehot-d8.train-fused`: its
configuration and entries, the generator, the kind end to end at a tiny size
on the CPU with `correct` true through the sparse reference, the controls
that must read `correct: false`, the probe that sends a program away whose
`DataMatrix` densifies, and the roofline's count of present cells.

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

from benchmark import harness, needed_work, needed_work_sparse, peaks  # noqa: E402
from benchmark.datagen import allstate_like  # noqa: E402
from benchmark.readers import kernel_roofline, kernel_roofline_sparse  # noqa: E402

CELL = "allstate-onehot-d8.train-fused"
CONFIG = "allstate-onehot-d8"
# The configuration's limits are read on the chip at its own size (PERF.md
# section 2). The CPU's flat histogram sums a node's rows in float32: the
# tiny runs read at most 4e-5 on the histogram gaps and 5e-8 on a median gain;
# a deepest leaf's float32 total of some 10,000 rows of which 1 % are
# positives reads up to 1.2e-4 on its value (five seeds).
CPU_LIMITS = {
    "direct_hess_err": 2e-4, "direct_hess_err_p90": 5e-4, "direct_hess_err_max": 1e-3,
    "gain_err_median": 1e-5, "leaf_sum_hess_rel": 3e-4, "leaf_value_err": 5e-4,
    "loss_abs": 8e-5,
}
TINY = {
    "train_rows": 20000, "validation_rows": 3000, "rounds_per_dispatch": 2,
    "check_limits": CPU_LIMITS,
}
NEW_METRICS = {
    "hist_kernel_roofline_sparse": ("kernel_roofline_sparse", "train_rounds_per_s"),
    "setup_bundle_plan_s": ("program_phase", "setup_s"),
    "present_cells_pct": ("gauge_ratio", "setup_s"),
    "bundled_width_pct": ("gauge_ratio", "setup_s"),
    "bundle_bin_fill_pct": ("gauge_ratio", "setup_s"),
}
# the per-layer lists every one-tree depth-wise cell is on
JOINED = (
    "train_first_round_s", "train_host_gap_ms_per_dispatch", "round_device_ms",
    "hist_kernel_ms_per_round", "device_idle_pct.train", "grad_ms_per_round",
    "hist_stage_ms_per_round", "node_totals_ms_per_round", "split_scan_ms_per_round",
    "route_rows_ms_per_round", "leaf_margin_ms_per_round", "eval_apply_ms_per_round",
    "eval_metric_ms_per_round", "round_unnamed_device_pct", "setup_sketch_s",
    "setup_bin_apply_s", "setup_upload_s", "setup_program_load_s", "setup_unnamed_s",
    "train_host_turnaround_ms_per_dispatch",
)


def cell_files():
    return harness.resolve_cell(harness.load_benchmark(), CELL)


def tiny(**params):
    _cell, config, _traffic = cell_files()
    overrides = dict({"max_depth": 4, "min_child_weight": 5}, **params)
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
def test_cell_resolves_to_files_that_exist():
    bench = harness.load_benchmark()
    cell, config, traffic = cell_files()
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert os.path.isfile(os.path.join(ROOT, entry["file"]))
    assert entry["file"] == "benchmark/configs/{}.json".format(CONFIG)
    assert cell["config"] == CONFIG and cell["traffic"] == "train-fused-sparse"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert traffic["kind"] == "train_window_sparse"
    assert callable(harness.load_kind(traffic).run)
    assert traffic["watchlist"] == ["train", "validation"]
    assert (traffic["warmup_dispatches"], traffic["traced_dispatches"]) == (1, 1)
    for module in ("datagen/allstate_like.py", "reference/sparse_gbt_reference.py",
                   "needed_work_sparse.py", "readers/kernel_roofline_sparse.py",
                   "README-sparse.md"):
        assert os.path.isfile(os.path.join(ROOT, "benchmark", module)), module
    assert config["generator"] == "allstate_like"


def test_configuration_is_the_published_set_with_nothing_reduced():
    bench = harness.load_benchmark()
    _cell, config, _traffic = cell_files()
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == [] == config["reduced"]
    assert len(entry["source"]) <= 200 and "Allstate" in entry["source"]
    assert config["architecture"] is None
    assert config["num_feature"] == 4228 == allstate_like.NUM_FEATURE
    assert config["published_train_rows"] == 13_184_290
    assert (config["train_rows"], config["validation_rows"]) == (12_184_290, 1_000_000)
    assert config["train_rows"] + config["validation_rows"] == config["published_train_rows"]
    assert config["rounds_per_dispatch"] == 8
    assert config["params"] == {
        "objective": "binary:logistic", "tree_method": "hist", "max_depth": 8, "eta": 0.1,
        "min_child_weight": 100, "lambda": 1.0, "max_bin": 256, "eval_metric": "logloss",
    }
    assert len(config["assumed"]) >= 6
    # every limit the judge compares is in the file, and no other
    assert set(config["check_limits"]) == set(CPU_LIMITS)


@pytest.mark.parametrize("name", JOINED + ("train_rounds_per_s",))
def test_cell_is_on_each_list_of_the_one_tree_depth_wise_cells(name):
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in entries[name]["workloads"]
    assert "higgs-d8.train-fused" in entries[name]["workloads"]


def test_cell_is_on_no_other_list_and_its_new_metrics_are_its_own():
    bench = harness.load_benchmark()
    e2e = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", CELL)}
    assert e2e == {"train_rounds_per_s", "setup_s"}
    layer = {m["name"]: m for m in harness.cell_metrics(bench, "per_layer", CELL, e2e)}
    assert set(layer) == set(JOINED) | set(NEW_METRICS)
    # the dense reader counts rows x 4,228 bins a level: 103 GB, a share near
    # or over 100 % that is a fault in the count, not a fast kernel
    assert "hist_kernel_roofline" not in layer
    for name, (reader, moves) in NEW_METRICS.items():
        entry = layer[name]
        assert CELL in entry["workloads"] and len(entry["workloads"]) == 1
        assert entry["moves"] == moves
        spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
        assert spec["reader"] == reader
        read, _args = harness.load_reader(name)
        assert callable(read)
    assert layer["hist_kernel_roofline_sparse"]["unit"] == "%"
    assert layer["hist_kernel_roofline_sparse"]["layer"] == "level histogram kernel"
    # no other cell's line gains a metric
    for other in bench["workloads"]:
        if other["name"] != CELL:
            names = {m["name"] for m in harness.cell_metrics(bench, "per_layer", other["name"], e2e)}
            assert not names & set(NEW_METRICS), other["name"]


# -------------------------------------------------------------- the generator
def test_generator_makes_the_stated_shape_from_the_seed():
    config = {"num_feature": 4228, "train_rows": 30000, "validation_rows": 4000}
    data = allstate_like.make(config, 2**31 + 7)
    x, y = data["train"]
    xv, _yv = data["validation"]
    assert x.shape == (30000, 4228) and xv.shape == (4000, 4228)
    assert x.dtype == np.float32 and x.has_sorted_indices
    a_row = x.nnz / x.shape[0]
    assert abs(a_row - allstate_like.PRESENT_A_ROW) < 0.1 and 30.5 < a_row < 32.5
    assert 0.003 < y.mean() < 0.03  # positives near 1 %
    # a row holds at most one column of a one-hot group, and 1.0 there
    start = allstate_like.GROUP_START
    sizes = np.asarray(allstate_like.GROUP_SIZES)
    assert int(sizes.sum()) == 4213 and sorted(sizes)[-3:] == [75, 1300, 2700]
    group_of = np.searchsorted(start, x.indices, side="right") - 1
    one_hot = x.indices >= allstate_like.NUM_NUMERIC
    assert np.all(x.data[one_hot] == 1.0)
    rows = np.repeat(np.arange(x.shape[0]), np.diff(x.indptr))[one_hot]
    cell = rows * len(sizes) + group_of[one_hot]
    assert len(np.unique(cell)) == len(cell)
    # the same seed gives the same rows, another seed others
    again = allstate_like.make(config, 2**31 + 7)["train"][0]
    assert (again != x).nnz == 0
    other = allstate_like.make(config, 2**31 + 8)["train"][0]
    assert (other != x).nnz > 0
    with pytest.raises(ValueError):
        allstate_like.make(dict(config, num_feature=28), 1)


# --------------------------------------------------------- the kind, end to end
def test_cell_prints_one_well_formed_correct_line(capsys):
    rc = harness.run_cell(CELL, 2**31 + 46, 0.2, False, 0.0, shrink=tiny())
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is True, out
    assert set(line["metrics"]) == {"train_rounds_per_s", "setup_s"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    for name in ("direct_hess_err", "tree_depth_over_max", "compiles_in_window",
                 "loss_not_falling", "splits_off_own_cuts", "bundle_conflict_rows", "loss_abs"):
        assert any(o.startswith("check {}: value=".format(name)) for o in out), name
    assert any(o.startswith("input rows=20000 columns=4228 present_cells=") for o in out)


def test_run_bundles_the_columns_and_hands_over_the_present_cells():
    from benchmark.kinds import train_window_sparse
    from benchmark.readers import program_phase

    run = train_window_sparse.run(cell_context(2**31 + 47))
    assert not failed(run), run["checks"]
    x = allstate_like.make(run["config"], 2**31 + 47)["train"][0]
    assert run["train_cells_present"] == x.nnz
    gauge = lambda name: program_phase.series(name)[0].value  # noqa: E731
    assert gauge("train_cells_present") == x.nnz
    assert gauge("train_columns_total") == 4228
    assert 32 <= gauge("train_bundle_columns") <= 64
    assert gauge("bundle_bin_slots") == gauge("train_bundle_columns") * 256
    assert gauge("bundle_conflict_rows") == 0
    # a CPU run has no device trace: the reader says nothing under a device
    # metric's name
    assert kernel_roofline_sparse.read(run, {"pattern": "graft_level_histogram"}) is None


def test_absent_sent_left_whatever_the_tree_records_is_not_correct(monkeypatch):
    """The control the chip runs as ``scripts/absent_left_control.py``."""
    import importlib.util

    from benchmark.kinds import train_window_sparse
    from sagemaker_xgboost_container_tpu.ops.bundle import BundleTables

    spec = importlib.util.spec_from_file_location(
        "absent_left_control", os.path.join(ROOT, "scripts", "absent_left_control.py")
    )
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    sound = train_window_sparse.run(cell_context(48))
    assert not failed(sound), sound["checks"]
    monkeypatch.setattr(BundleTables, "go_right", BundleTables.__dict__["go_right"])
    control.install()
    flipped = train_window_sparse.run(cell_context(48))
    # (the median gap reads 0: half the sibling pairs below a misrouted split
    # are empty on both sides; the tails, the leaves and the loss are not)
    assert {"direct_hess_err_p90", "leaf_sum_hess_rel", "loss_abs"} <= failed(flipped), (
        flipped["checks"]
    )


def test_one_pass_histogram_is_not_correct():
    """The control the chip runs as ``GRAFT_HIST_MM_PREC=bf16``: the kernel,
    interpreted, with one bf16 pass and with its two."""
    from benchmark.kinds import train_window_sparse
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs

    def kernel(precision):
        knobs = resolve_hist_knobs()._replace(backend="tpu", precision=precision)
        return lambda *a, **kw: models.train(*a, hist_knobs=knobs, **kw)

    small = dict(max_depth=3)
    sound = train_window_sparse.run(cell_context(49, **small), train_fn=kernel("bf16x2"))
    assert not failed(sound), sound["checks"]
    control = train_window_sparse.run(cell_context(49, **small), train_fn=kernel("bf16"))
    assert {"direct_hess_err", "direct_hess_err_p90"} <= failed(control), control["checks"]


def test_a_split_mapped_back_to_a_neighbouring_column_is_not_correct():
    """A bundled program's split is a position of a shared bin column: one
    mapped back to the wrong member names a column whose cuts the threshold
    is none of (or rows the sums were not taken over)."""
    from benchmark.kinds import train_window_sparse
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.bundling import BundlePlan

    real = BundlePlan.original_splits

    def off_by_one(self, padded):
        out = real(self, padded)
        shared = np.asarray(out["feature"]) >= allstate_like.NUM_NUMERIC
        out["feature"] = np.where(shared, out["feature"] + 1, out["feature"]).astype(np.int32)
        return out

    def train(*args, **kwargs):
        BundlePlan.original_splits = off_by_one
        try:
            return models.train(*args, **kwargs)
        finally:
            BundlePlan.original_splits = real

    run = train_window_sparse.run(cell_context(50), train_fn=train)
    assert "direct_hess_err" in failed(run) or "splits_off_own_cuts" in failed(run), run["checks"]
    assert failed(run), run["checks"]


def test_a_continuous_column_is_held_to_its_range_and_a_few_valued_one_to_its_pairs(monkeypatch):
    import scipy.sparse as sp

    from benchmark.reference import sparse_gbt_reference

    rng = np.random.default_rng(3)
    dense = np.zeros((4000, 2), np.float32)
    dense[:, 0] = rng.normal(size=4000)             # 4,000 distinct values
    dense[::2, 1] = rng.integers(1, 6, 2000)        # five distinct values, half the rows
    x = sp.csr_matrix(dense)

    def tree(feature, threshold):
        return {"feature": np.array([feature, 0, 0]), "left": np.array([1, -1, -1]),
                "threshold": np.array([threshold, 0, 0], np.float32)}

    count = sparse_gbt_reference.splits_off_own_cuts
    lo, hi = np.sort(dense[:, 0])[[10, 11]]
    midpoint = np.float32((lo + hi) * np.float32(0.5))
    assert count([tree(0, midpoint), tree(1, 2.5), tree(1, 3.0)], x) == 0
    assert count([tree(0, midpoint + np.float32(1e-3))], x) == 1   # no midpoint of two values
    assert count([tree(1, 2.25)], x) == 1
    # over the pair check's size a column is held to its range alone
    monkeypatch.setattr(sparse_gbt_reference, "PAIR_CHECK_MAX_VALUES", 1000)
    assert count([tree(0, midpoint + np.float32(1e-3)), tree(1, 2.5)], x) == 0
    assert count([tree(0, dense[:, 0].max() + 1.0), tree(1, 2.25)], x) == 2


# ------------------------------------------------------------------ the probe
def test_probe_sends_a_program_that_densifies_away_at_once(monkeypatch):
    """The driver lays this PR's benchmark files over the parent, whose
    ``DataMatrix`` densifies a CSR matrix at once: 206 GB at the cell's size.
    The kind has to leave before it makes a single row. A stand-in with the
    parent's shape of matrix."""
    from benchmark.kinds import train_window_sparse
    from sagemaker_xgboost_container_tpu.data import matrix

    class Densifying:
        def __init__(self, features, labels=None, **_kwargs):
            self.features = np.asarray(features.toarray(), np.float32)
            self.labels = labels

    monkeypatch.setattr(matrix, "DataMatrix", Densifying)
    made = []
    monkeypatch.setattr(allstate_like, "make", lambda *a: made.append(a))
    start = time.perf_counter()
    with pytest.raises(SystemExit) as leaving:
        train_window_sparse.run(cell_context(51))
    assert time.perf_counter() - start < 10 and not made
    # a message, so exit code 1
    assert isinstance(leaving.value.code, str) and "densifies" in leaving.value.code


def test_probe_lets_the_sparse_matrix_through_quickly():
    from benchmark.kinds import train_window_sparse

    start = time.perf_counter()
    train_window_sparse.require_sparse_matrix()
    assert time.perf_counter() - start < 10


# ----------------------------------------------------- the roofline's count
def test_needed_work_counts_present_cells_by_hand():
    # the cell's own numbers: 12,184,290 rows x 30.97 present cells in u16
    # bins + 12 B a row = 0.90 GB a level, 1.1 ms at 819 GB/s
    work = needed_work_sparse.level_histogram(12_184_290, 377_300_000, 257)
    assert work == {"bytes": 377_300_000 * 2 + 12_184_290 * 12, "ops": 377_300_000 * 2}
    least, bound = needed_work.least_seconds(work, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(900.8e6 / 819e9, rel=1e-3)
    assert needed_work_sparse.level_histogram(1000, 5000, 256)["bytes"] == 5000 + 12000
    three = needed_work_sparse.level_histogram(1000, 5000, 257, trees=3)  # bins read once
    assert three == {"bytes": 5000 * 2 + 3 * 12000, "ops": 5000 * 2 * 3}
    # a matrix with every cell present needs what the dense count says
    assert needed_work_sparse.level_histogram(1000, 28000, 257) == needed_work.level_histogram(
        1000, 28, 257
    )


class FakeTrace:
    busy_s = 1.0

    def __init__(self, seconds):
        self.seconds = seconds

    def kernel_events(self, pattern):
        return self.seconds


def test_roofline_on_the_recorded_kernel_time_reads_under_one_per_cent_and_the_dense_count_near_one_hundred():
    """The traced dispatch of seed 4600046102 on a v5e (PR 46): 64 kernel
    events, 1,045.30140375 ms a round. The sparse count reads 0.84 %; the
    dense count of the same configuration (rows x 4,228 bins a level, 103 GB)
    reads 96 %, which no kernel that latches 46 bin columns can have: a fault
    in the count, so the cell is not on `hist_kernel_roofline`'s list."""
    _cell, config, _traffic = cell_files()
    run = {
        "trace": FakeTrace([8 * 1.04530140375 / 64] * 64), "train_cells_present": 377_140_797,
        "config": config, "device_kind": "TPU v5 lite", "traced_units": {"dispatch": 1, "round": 8},
    }
    sparse = kernel_roofline_sparse.read(run, {"pattern": "graft_level_histogram"})
    assert sparse == pytest.approx(0.8414821898546737, rel=1e-9) and sparse < 100
    dense = kernel_roofline.read(run, {"pattern": "graft_level_histogram", "work": "level_histogram"})
    assert 90 < dense < 105 and dense > 100 * sparse
    # a kernel as fast as the needed reads allow would read 100 %, never more
    level = needed_work_sparse.level_histogram(12_184_290, 377_140_797, 257)
    least, _bound = needed_work.least_seconds(level, peaks.peaks_for("TPU v5 lite"))
    run["trace"] = FakeTrace([least] * 64)
    assert kernel_roofline_sparse.read(run, {"pattern": "x"}) == pytest.approx(100.0)
    # nothing to read: no trace, no kernel events, no count of present cells
    assert kernel_roofline_sparse.read(dict(run, trace=None), {"pattern": "x"}) is None
    assert kernel_roofline_sparse.read(dict(run, trace=FakeTrace([])), {"pattern": "x"}) is None
    assert kernel_roofline_sparse.read(dict(run, train_cells_present=None), {"pattern": "x"}) is None
