"""The categorical training cell (PR 50), `allstate-cat-d8.train-fused`: its
configuration and entries, the generator tied to the one-hot cell's, the kind
end to end at a tiny size on the CPU with `correct` true through the
categorical reference, the controls that must read `correct: false`, the
exact checks on doctored trees, and the probe that sends a program away whose
`DataMatrix` takes no feature types.

No module-level jax or topology calls: jax is imported inside the tests.
"""

import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, needed_work_sparse  # noqa: E402
from benchmark.datagen import allstate_cat_like, allstate_like  # noqa: E402
from benchmark.reference import categorical_gbt_reference as reference  # noqa: E402

CELL = "allstate-cat-d8.train-fused"
CONFIG = "allstate-cat-d8"
TWIN = "allstate-onehot-d8"
# The configuration's limits are read on the chip at its own size (PERF.md
# section 2). The CPU's flat histogram sums a node's rows in float32: the
# tiny runs' readings are `test_sparse_cell.py`'s, and the partition scan's
# regret reads at most 2e-12 (five seeds).
CPU_LIMITS = {
    "direct_hess_err": 2e-4, "direct_hess_err_p90": 5e-4, "direct_hess_err_max": 1e-3,
    "gain_err_median": 1e-5, "leaf_sum_hess_rel": 3e-4, "leaf_value_err": 5e-4,
    "loss_abs": 8e-5, "cat_partition_regret": 1e-4,
}
TINY = {
    "train_rows": 30000, "validation_rows": 3000, "rounds_per_dispatch": 2,
    "check_limits": CPU_LIMITS,
}
NEW_METRICS = {
    "cat_scan_ms_per_round": ("stage_ms", "train_rounds_per_s", "round program"),
    "cat_splits_pct": ("gauge_ratio", "train_rounds_per_s", "round program"),
    "cat_set_words_per_node": ("program_phase", "train_rounds_per_s", "row routing"),
    "setup_cat_encode_s": (
        "program_phase", "setup_s", "train set-up in front of the first round"
    ),
    "hist_kernel_roofline_cat": (
        "kernel_roofline_sparse", "train_rounds_per_s", "level histogram kernel"
    ),
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
# the split of set-up that the dense cells with a sketch of their own report:
# the cell runs the same set-up, but two accepted tests hold these lists to
# their cells exactly (test_setup_timeline_metrics.py, test_criteo_ckpt_cells.py),
# so the cell joins them in a benchmark PR and not here (PERF.md section 7)
SETUP_DETAIL = (
    "missing_cells_pct", "sketch_cut_fill_pct", "startup_before_train_s",
    "startup_package_import_s", "setup_sketch_stage_s", "setup_sketch_transfer_s",
    "setup_sketch_kernel_s", "setup_bin_apply_transfer_s", "setup_bin_apply_kernel_s",
    "setup_first_dispatch_load_s", "setup_program_load_wall_s", "setup_program_cache_load_s",
    "setup_program_trace_lower_s", "setup_sketch_hbm_peak_bytes", "train_hbm_resident_bytes",
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


def script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "scripts", name + ".py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ------------------------------------------------- configuration and entries
def test_cell_resolves_to_files_that_exist():
    bench = harness.load_benchmark()
    cell, config, traffic = cell_files()
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert os.path.isfile(os.path.join(ROOT, entry["file"]))
    assert entry["file"] == "benchmark/configs/{}.json".format(CONFIG)
    assert cell["config"] == CONFIG and cell["traffic"] == "train-fused-categorical"
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert traffic["kind"] == "train_window_categorical"
    assert callable(harness.load_kind(traffic).run)
    assert traffic["watchlist"] == ["train", "validation"]
    assert (traffic["warmup_dispatches"], traffic["traced_dispatches"]) == (1, 1)
    for module in ("datagen/allstate_cat_like.py", "reference/categorical_gbt_reference.py",
                   "README-categorical.md"):
        assert os.path.isfile(os.path.join(ROOT, "benchmark", module)), module
    for control in ("cat_code_order_control.py", "cat_unknown_left_control.py"):
        assert os.path.isfile(os.path.join(ROOT, "scripts", control)), control
    assert config["generator"] == "allstate_cat_like"


def test_configuration_is_the_twins_table_before_its_encoding():
    bench = harness.load_benchmark()
    _cell, config, _traffic = cell_files()
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    twin = harness.load_json(ROOT, "benchmark", "configs", TWIN + ".json")
    assert entry["reduced"] == [] == config["reduced"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "Allstate" in entry["source"] and "categorical.rst" in entry["source"]
    assert config["architecture"] is None
    assert config["num_feature"] == 32 == allstate_cat_like.NUM_FEATURE
    assert config["feature_types"] == ["q"] * 15 + ["c"] * 17
    assert config["feature_types"] == allstate_cat_like.FEATURE_TYPES
    for key in ("published_train_rows", "train_rows", "validation_rows", "rounds_per_dispatch"):
        assert config[key] == twin[key], key
    assert (config["train_rows"], config["validation_rows"]) == (12_184_290, 1_000_000)
    assert config["params"] == dict(twin["params"], max_cat_to_onehot=4, max_cat_threshold=64)
    assert len(config["assumed"]) >= 8 and config["guarantees"]
    # every limit the judge compares is in the file, and no other
    assert set(config["check_limits"]) == set(CPU_LIMITS)
    assert all(v is not None for v in config["check_limits"].values())


@pytest.mark.parametrize("name", JOINED + ("train_rounds_per_s",))
def test_cell_is_on_each_list_of_the_one_tree_depth_wise_cells(name):
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in entries[name]["workloads"]
    assert "allstate-onehot-d8.train-fused" in entries[name]["workloads"]


@pytest.mark.parametrize("name", SETUP_DETAIL)
def test_cell_leaves_the_lists_that_accepted_tests_pin_as_they_were(name):
    bench = harness.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    pinned = "mslr-ndcg.train-fused-grouped" in entry["workloads"]  # the timeline's cells
    assert entry["workloads"][-1] == (
        "criteo-tb-d8-host4.train-fused-mesh" if pinned else "criteo-tb-d8.train-fused"
    )
    assert entry["moves"] == "setup_s"
    assert "criteo-tb-d8.train-fused" in entry["workloads"]
    assert TWIN + ".train-fused" not in entry["workloads"]
    # the reader is there for the day the cell joins the list
    read, _args = harness.load_reader(name)
    assert callable(read)


def test_cell_is_on_no_other_list_and_its_new_metrics_are_its_own():
    bench = harness.load_benchmark()
    e2e = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", CELL)}
    assert e2e == {"train_rounds_per_s", "setup_s"}
    layer = {m["name"]: m for m in harness.cell_metrics(bench, "per_layer", CELL, e2e)}
    assert set(layer) == set(JOINED) | set(NEW_METRICS)
    for name, (reader, moves, layer_name) in NEW_METRICS.items():
        entry = layer[name]
        assert entry["workloads"] == [CELL]
        assert (entry["moves"], entry["layer"]) == (moves, layer_name)
        spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
        assert spec["reader"] == reader
        read, _args = harness.load_reader(name)
        assert callable(read)
    assert layer["hist_kernel_roofline_cat"]["unit"] == "%"
    assert layer["cat_splits_pct"]["better"] == next(
        m["better"] for m in bench["per_layer"] if m["name"] == "class_trees_per_round"
    )
    # no other cell's line gains a metric
    for other in bench["workloads"]:
        if other["name"] != CELL:
            theirs = harness.cell_metrics(bench, "per_layer", other["name"], e2e)
            assert not {m["name"] for m in theirs} & set(NEW_METRICS), other["name"]


def test_new_readers_return_nothing_on_a_program_without_the_mechanism():
    """The parent's registry holds no such series and its stage table no such
    stage: every new reader gives None and the line leaves the metric out."""
    from benchmark.readers import gauge_ratio, program_phase, stage_ms

    run = {"trace": None, "traced_units": {"round": 8}}
    for name in ("cat_splits_pct", "cat_set_words_per_node", "setup_cat_encode_s"):
        spec = harness.load_json(harness.HERE, "layer_metrics", name + ".json")
        args = json.loads(json.dumps(spec["args"]).replace("cat_", "absent_"))
        module = {"gauge_ratio": gauge_ratio, "program_phase": program_phase}[spec["reader"]]
        assert module.read(run, args) is None
    run["stage_seconds"] = {"hist": 1.0}
    assert stage_ms.read(run, {"stage": "cat_scan", "per": "round"}) is None


# -------------------------------------------------------------- the generator
def test_one_hot_encoding_the_table_gives_the_twins_matrix():
    """Same seed, same table: column ``15 + g`` one-hot encoded at
    ``GROUP_START[g] + code`` is ``allstate_like``'s CSR, cell for cell."""
    seed = 3_000_000_123
    shape = {"train_rows": 50000, "validation_rows": 4000}
    dense = allstate_cat_like.make(
        dict(shape, num_feature=32, feature_types=allstate_cat_like.FEATURE_TYPES), seed
    )
    onehot = allstate_like.make(dict(shape, num_feature=4228), seed)
    for name in ("train", "validation"):
        x, y = dense[name]
        csr, y_twin = onehot[name]
        assert np.array_equal(y, y_twin)
        assert x.dtype == np.float32 and x.shape == (shape[name + "_rows"], 32)
        rows, slots = np.nonzero(~np.isnan(x))
        held = x[rows, slots]
        group = np.maximum(slots - allstate_like.NUM_NUMERIC, 0)
        numeric = slots < allstate_like.NUM_NUMERIC
        column = np.where(numeric, slots, allstate_like.GROUP_START[group] + held.astype(np.int64))
        value = np.where(numeric, held, np.float32(1.0))
        assert np.array_equal(np.diff(csr.indptr), (~np.isnan(x)).sum(axis=1))
        assert np.array_equal(csr.indices, column) and np.array_equal(csr.data, value)
    x, _y = dense["train"]
    sizes = reference.column_cardinalities(x, allstate_cat_like.FEATURE_TYPES)
    assert sizes[:15] == [0] * 15
    assert all(s <= g for s, g in zip(sizes[15:], allstate_like.GROUP_SIZES))
    assert sizes[15:29] == list(allstate_like.GROUP_SIZES[:14])  # the small ones fill up


def test_generator_makes_other_rows_from_another_seed_and_refuses_another_shape():
    shape = {"train_rows": 2000, "validation_rows": 100, "num_feature": 32,
             "feature_types": allstate_cat_like.FEATURE_TYPES}
    a = allstate_cat_like.make(shape, 5)["train"][0]
    b = allstate_cat_like.make(shape, 6)["train"][0]
    again = allstate_cat_like.make(shape, 5)["train"][0]
    assert np.array_equal(a, again, equal_nan=True) and not np.array_equal(a, b, equal_nan=True)
    with pytest.raises(ValueError):
        allstate_cat_like.make(dict(shape, num_feature=4228), 5)
    with pytest.raises(ValueError):
        allstate_cat_like.make(dict(shape, feature_types=["q"] * 32), 5)


# ------------------------------------------------------------------ the kind
def test_cell_prints_one_well_formed_correct_line(capsys):
    rc = harness.run_cell(CELL, 3_000_000_041, 0.2, False, time.time(), shrink=tiny())
    assert rc == 0
    out = capsys.readouterr().out.strip().split("\n")
    line = json.loads(out[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"train_rounds_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    checks = [row for row in out if row.startswith("check ")]
    named = {row.split()[1].rstrip(":") for row in checks}
    assert {"cat_partition_regret", "ordinal_split_on_categorical", "cat_set_invalid",
            "cat_onehot_rule_broken", "tree_depth_over_max", "compiles_in_window",
            "loss_not_falling"} <= named
    assert any(row.startswith("input rows=30000 columns=32 categorical=17") for row in out)


def test_run_trains_sets_and_hands_over_the_present_cells():
    from benchmark.kinds import train_window_categorical
    from benchmark.readers import program_phase

    run = train_window_categorical.run(cell_context(44))
    assert not failed(run), run["checks"]
    x, _y = allstate_cat_like.make(run["config"], 44)["train"]
    assert run["train_cells_present"] == int((~np.isnan(x)).sum())
    # what the new per-layer metrics read is there
    sets = program_phase.series("tree_cat_splits_total")
    splits = program_phase.series("tree_splits_total")
    assert sets and splits and 0 < sets[0].value <= splits[0].value
    assert program_phase.series("cat_set_words")[0].value == 85
    assert program_phase.series("train_bin_columns")[0].value == 47
    assert program_phase.series("train_columns_total")[0].value == 32
    assert program_phase.series(
        "training_phase_seconds", {"phase": "setup.cat_encode"}
    )[0].count >= 3  # the layout, the training rows, the cuts, the validation rows
    # the count of needed work the roofline takes
    level = needed_work_sparse.level_histogram(30000, run["train_cells_present"], 257)
    assert level["bytes"] == run["train_cells_present"] * 2 + 30000 * 12


@pytest.fixture
def drop_compiled_kernels():
    """The interpreted kernels this file builds are dropped again: the tests
    of ``tests/test_hist_kernel_pack.py`` count the packed bodies their own
    calls build, in whatever worker they share with this file."""
    yield
    import jax

    from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod

    hist_mod._pallas_hist_fn.cache_clear()
    hist_mod._pallas_hist_packed_fn.cache_clear()
    jax.clear_caches()


def test_one_pass_histogram_is_not_correct(drop_compiled_kernels):
    """The control the chip runs as ``GRAFT_HIST_MM_PREC=bf16``: the kernel,
    interpreted, with one bf16 pass and with its two."""
    from benchmark.kinds import train_window_categorical
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs

    def kernel(precision):
        knobs = resolve_hist_knobs()._replace(backend="tpu", precision=precision)
        return lambda *a, **kw: models.train(*a, hist_knobs=knobs, **kw)

    small = dict(max_depth=3)
    context = dict(cell_context(49, **small))
    context["config"] = dict(context["config"], train_rows=8000, validation_rows=500)
    sound = train_window_categorical.run(dict(context), train_fn=kernel("bf16x2"))
    assert not failed(sound), sound["checks"]
    control = train_window_categorical.run(dict(context), train_fn=kernel("bf16"))
    assert {"direct_hess_err", "direct_hess_err_p90"} <= failed(control), control["checks"]


def test_prefix_sets_in_code_order_are_not_correct(monkeypatch):
    """The control the chip runs as ``scripts/cat_code_order_control.py``:
    every sum stays right, the regret of the search carries the verdict."""
    from benchmark.kinds import train_window_categorical
    from sagemaker_xgboost_container_tpu.ops import categorical

    monkeypatch.setattr(categorical, "_rank", categorical._rank)  # put back afterwards
    script("cat_code_order_control").install()
    run = train_window_categorical.run(cell_context(45))
    assert failed(run) == {"cat_partition_regret"}, run["checks"]
    regret = next(c for c in run["checks"] if c["name"] == "cat_partition_regret")
    assert regret["value"] > 0.1


def test_missing_sent_left_whatever_the_tree_records_is_not_correct(monkeypatch):
    """The control the chip runs as ``scripts/cat_unknown_left_control.py``."""
    from benchmark.kinds import train_window_categorical
    from sagemaker_xgboost_container_tpu.ops.categorical import CatTables

    monkeypatch.setattr(CatTables, "go_right", CatTables.go_right)  # put back afterwards
    script("cat_unknown_left_control").install()
    run = train_window_categorical.run(cell_context(46))
    assert {"leaf_sum_hess_rel", "loss_abs"} <= failed(run), run["checks"]


# ------------------------------------------------------------ the exact checks
def stump(feature, categories=None, threshold=0.5):
    return {
        "feature": np.asarray([feature, 0, 0]),
        "threshold": np.asarray([threshold, 0, 0], np.float32),
        "default_left": np.asarray([True, False, False]),
        "left": np.asarray([1, -1, -1]),
        "right": np.asarray([2, -1, -1]),
        "value": np.asarray([0, -0.1, 0.1], np.float32),
        "gain": np.asarray([1.0, 0, 0], np.float32),
        "sum_hess": np.asarray([2.0, 1, 1], np.float32),
        "categories": {} if categories is None else {0: np.asarray(categories)},
    }


EXACT_CASES = {
    "sound_set": (stump(1, [0, 2]), (0, 0, 0)),
    "sound_threshold": (stump(0), (0, 0, 0)),
    "sound_one_against_the_rest": (stump(2, [1]), (0, 0, 0)),
    "threshold_on_a_categorical_column": (stump(1), (1, 0, 0)),
    "set_on_a_numeric_column": (stump(0, [1]), (0, 1, 0)),
    "empty_set": (stump(1, []), (0, 1, 0)),
    "code_past_the_column": (stump(1, [0, 5]), (0, 1, 0)),
    "negative_code": (stump(1, [-1, 0]), (0, 1, 0)),
    "every_category": (stump(1, [0, 1, 2, 3, 4]), (0, 1, 0)),
    "set_over_the_cap": (stump(3, list(range(8))), (0, 1, 0)),
    "pair_under_the_onehot_rule": (stump(2, [0, 1]), (0, 0, 1)),
}


@pytest.mark.parametrize("name", sorted(EXACT_CASES))
def test_exact_checks_count_each_fault(name):
    tree, (ordinal, invalid, onehot) = EXACT_CASES[name]
    got = reference.exact_checks([tree], ["q", "c", "c", "c"], [0, 5, 3, 40], 4, 7)
    assert got == {
        "ordinal_split_on_categorical": ordinal, "cat_set_invalid": invalid,
        "cat_onehot_rule_broken": onehot,
    }


def test_reference_routes_by_the_set_and_sums_each_category():
    x = np.asarray(
        [[0.0, 0.0], [0.0, 2.0], [0.0, 4.0], [0.0, np.nan], [0.0, 7.0], [0.0, -1.0], [0.0, 2.9]],
        np.float32,
    )
    tree = stump(1, [2, 4])
    table = reference.set_table(tree, 5)
    leaf = reference.route(tree, x, table)[-1]
    #            0 out   2 in   4 in   NaN: default left   7, -1: no category   2.9: code 2
    assert list(leaf) == [1, 2, 2, 1, 1, 1, 2]
    tree["default_left"][0] = False
    assert list(reference.route(tree, x, table)[-1]) == [1, 2, 2, 2, 1, 1, 2]
    g = np.arange(7, dtype=np.float64)
    sums = reference.category_sums(tree, reference.route(tree, x, table), x, g, np.ones(7), [0, 5])
    assert list(sums[0][2]) == [1, 0, 2, 0, 1, 3]          # rows a category; last: none
    assert list(sums[0][0]) == [0, 0, 1 + 6, 0, 2, 3 + 4 + 5]


def test_references_scan_is_the_brute_force_best_and_regret_measures_the_set():
    rng = np.random.default_rng(3)
    for C in (4, 6, 8):
        sums = np.stack([
            rng.normal(size=C + 1), rng.uniform(1, 3, C + 1), rng.integers(1, 9, C + 1),
        ]).astype(np.float64)
        best = reference.best_partition(sums, 1.0, 1.0, 4, 64)
        gains = []
        for mask in range(1, 2**C - 1):
            codes = [c for c in range(C) if mask >> c & 1]
            gains += [reference.set_gain(sums, codes, dl, 1.0) for dl in (True, False)]
        assert best == pytest.approx(max(gains), rel=1e-12)
        assert min(gains) < best
    # one against the rest below the rule; the cap above it
    few = reference.best_partition(sums[:, [0, 1, 2, -1]], 1.0, 1.0, 4, 64)
    assert few == pytest.approx(max(
        reference.set_gain(sums[:, [0, 1, 2, -1]], [c], dl, 1.0)
        for c in range(3) for dl in (True, False)
    ))
    assert reference.best_partition(sums, 1.0, 1.0, 4, 1) <= best
    assert reference.best_partition(sums, 1.0, 1e9, 4, 64) == -np.inf


# ------------------------------------------------------------------ the probe
def test_probe_sends_a_program_without_feature_types_away_at_once(monkeypatch):
    from benchmark.kinds import train_window_categorical
    from sagemaker_xgboost_container_tpu.data import matrix

    class ParentMatrix:  # the parent's signature
        def __init__(self, features, labels=None, weights=None, groups=None,
                     feature_names=None):
            self.features = features

    monkeypatch.setattr(matrix, "DataMatrix", ParentMatrix)
    started = time.perf_counter()
    with pytest.raises(SystemExit) as left:
        train_window_categorical.run(cell_context(47))
    assert time.perf_counter() - started < 5.0
    assert "no feature types" in str(left.value.code) and left.value.code != 0

    class DropsThem(ParentMatrix):  # takes the keyword and ignores it
        def __init__(self, features, feature_types=None, **kwargs):
            super().__init__(features, **kwargs)

    monkeypatch.setattr(matrix, "DataMatrix", DropsThem)
    with pytest.raises(SystemExit, match="no feature types"):
        train_window_categorical.require_feature_types()


def test_probe_lets_the_program_through_quickly():
    from benchmark.kinds import train_window_categorical

    started = time.perf_counter()
    train_window_categorical.require_feature_types()
    assert time.perf_counter() - started < 2.0
