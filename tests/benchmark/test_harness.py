"""The benchmark's own tests: each kind end to end at a tiny size on the CPU,
both checks shown to fail, the trace reduction against a recorded trace.

No module-level jax or topology calls: jax is imported inside the tests.
"""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, limits, needed_work, peaks, trace_reduce  # noqa: E402
from benchmark.datagen import seeded_forest  # noqa: E402
from benchmark.reference import forest_reference  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
# The configurations' limits are set from the chip's lowering. The CPU sums a
# node's rows one after another in float32 and reads up to 2.3e-5 where the
# chip reads 3e-6 (and 1.5e-4 on a median gain), so the tiny CPU runs take wider limits on the
# float32 leaf numbers: a bfloat16 leaf still misses them by two hundred times.
CPU_LIMITS = {
    "direct_hess_err": 5e-4, "direct_hess_err_p90": 5e-3, "direct_hess_err_max": 5e-3,
    "gain_err_median": 1e-3, "leaf_sum_hess_rel": 2e-4, "leaf_value_err": 1e-4, "loss_abs": 8e-5,
}
TINY_TRAIN = {
    "train_rows": 3000, "validation_rows": 800, "rounds_per_dispatch": 2,
    "check_limits": CPU_LIMITS,
}


def tiny_params(config):
    return dict(config["params"], max_depth=3)


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def cell_context(workload, seed, seconds=1.0, trace=False):
    bench = harness.load_benchmark()
    cell, config, traffic = harness.resolve_cell(bench, workload)
    return {
        "cell": cell, "config": config, "traffic": traffic, "seed": seed,
        "seconds": seconds, "trace": trace, "t_process_start": 0.0,
    }


def test_every_cell_resolves_to_files_that_exist():
    bench = harness.load_benchmark()
    assert bench["command"] == ["python3", "benchmark/run.py"]
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and cell["chips"] == 1
        _cell, config, traffic = harness.resolve_cell(bench, cell["name"])
        assert hasattr(harness.load_kind(traffic), "run")
        assert os.path.exists(
            os.path.join(ROOT, "benchmark", "datagen", config["generator"] + ".py")
        )
        e2e = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", cell["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.cell_metrics(bench, "per_layer", cell["name"], e2e)
        assert layer, "every cell reports a per-layer metric"
        for m in layer:
            read, _args = harness.load_reader(m["name"])
            assert callable(read) and m["moves"] in e2e
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1


def test_training_cell_prints_one_well_formed_line(capsys):
    workload = "higgs-d8.train-fused"
    bench = harness.load_benchmark()
    _cell, config, _traffic = harness.resolve_cell(bench, workload)
    shrink = dict(TINY_TRAIN, params=tiny_params(config))
    rc = harness.run_cell(workload, 2**31 + 11, 0.5, False, 0.0, shrink=shrink)
    line, out = last_line(capsys)
    assert rc == 0 and line["correct"] is True, out
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"train_rounds_per_s", "setup_s"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert any(o.startswith("check sum_hess_rel: value=") and "limit=" in o for o in out)
    assert any(o.startswith("phases ") and "cold_cache=" in o for o in out)


def test_traced_training_run_reports_per_layer_metrics(capsys):
    workload = "higgs-d8.train-fused"
    _cell, config, _traffic = harness.resolve_cell(harness.load_benchmark(), workload)
    shrink = dict(TINY_TRAIN, params=tiny_params(config))
    rc = harness.run_cell(workload, 2**31 + 12, 0.2, True, 0.0, shrink=shrink)
    line, out = last_line(capsys)
    assert rc == 0 and line["correct"] is True, out
    # the CPU's trace has no device plane: the trace's readers find nothing
    # to read and are left out, the host span is there
    assert set(line["metrics"]) == {"train_first_round_s"}
    assert line["metrics"]["train_first_round_s"]["value"] > 0
    assert "train_rounds_per_s" not in line["metrics"]


# UCI Covertype's shape (benchmark/datagen/covtype_like.py): seven trees a
# round through the reference's softmax half. Proved on the chip at the
# published size in PR 25, and left out as a cell for its size (PERF.md section 7).
MULTICLASS = {
    "generator": "covtype_like", "num_feature": 54, "rounds_per_dispatch": 2,
    "train_rows": 3000, "validation_rows": 800,
    "class_frequencies": [211840, 283301, 35754, 2747, 9493, 17367, 20510],
    "params": {
        "objective": "multi:softmax", "num_class": 7, "tree_method": "hist", "max_bin": 256,
        "max_depth": 3, "eta": 0.1, "lambda": 1.0, "eval_metric": "mlogloss",
    },
    "check_limits": CPU_LIMITS,
}


def test_multiclass_run_of_the_kind_is_correct():
    from benchmark.kinds import train_window

    ctx = cell_context("higgs-d8.train-fused", 2**31 + 13, seconds=0.2)
    ctx["config"] = dict(MULTICLASS)
    run = train_window.run(ctx)
    assert all(c["ok"] for c in run["checks"]), run["checks"]
    assert run["attempted"] >= 2 and run["end_to_end"]["train_rounds_per_s"] > 0


def _train_tiny(k=2, seed=21):
    """A tiny forest through the public train(), with what the check needs."""
    from benchmark.datagen import higgs_like
    from benchmark.kinds import train_window
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix

    ctx = cell_context("higgs-d8.train-fused", seed)
    config = dict(ctx["config"], **TINY_TRAIN)
    config["params"] = tiny_params(config)
    data = higgs_like.make(config, seed)
    window = train_window.WindowCallback(k, 1, 0.0, train_window._CompileCounter())
    x, y = data["train"]
    forest = models.train(
        dict(config["params"], _rounds_per_dispatch=k), DataMatrix(x, labels=y),
        num_boost_round=2 * k, evals=[(DataMatrix(x, labels=y), "train")],
        callbacks=[window], verbose_eval=False,
    )
    return train_window, forest, window.evals_log, config, x, y, k


def test_training_check_passes_then_fails_on_bf16_leaves():
    train_window, forest, evals_log, config, x, y, k = _train_tiny()
    checks = train_window.judge(forest, evals_log, config, x, y, k, 0)
    assert all(c["ok"] for c in checks), checks
    for tree in forest.trees:  # the control: leaf values held in bfloat16
        tree.value = forest_reference._to_dtype(tree.value, "bfloat16").astype(np.float32)
    checks = train_window.judge(forest, evals_log, config, x, y, k, 0)
    failed = {c["name"] for c in checks if not c["ok"]}
    assert "leaf_value_err" in failed, checks


@pytest.mark.parametrize("seed", [21, 22, 2**31 + 23])
def test_training_control_histogram_in_bf16_is_over_the_limit(seed):
    """The control the chip runs with the program's own one-pass histogram
    (which the CPU's lowering does not have): the reference's node sums with
    every row's hessian held in bfloat16, put in the program's place."""
    from benchmark.reference import gbt_reference

    train_window, forest, evals_log, config, x, y, k = _train_tiny(seed=seed)
    params = config["params"]
    tree = forest.trees[1]  # round 0's hessians are all 0.25, which bfloat16 holds
    margin = gbt_reference.base_margin(params["objective"], 0.5) + gbt_reference.tree_margin(
        train_window.plain_tree(forest.trees[0]), x
    )
    g, h = gbt_reference.grad_hess(params["objective"], margin, y.astype(np.float64))
    plain = train_window.plain_tree(tree)
    _g, low_h, _a, _q = gbt_reference.node_sums(
        plain, x, g, forest_reference._to_dtype(h, "bfloat16")
    )
    from_histogram = gbt_reference.node_depths(plain) < params["max_depth"]
    tree.sum_hess = np.where(from_histogram, low_h, tree.sum_hess).astype(np.float32)
    checks = {c["name"]: c for c in train_window.judge(forest, evals_log, config, x, y, k, 0)}
    assert not checks["direct_hess_err"]["ok"], checks
    assert checks["direct_hess_err"]["value"] > 3 * CPU_LIMITS["direct_hess_err"]


def test_training_check_fails_when_the_carried_state_is_reset():
    """Margins reset between dispatches grow round 0's tree again: the last
    round of the last dispatch is judged on margins through every earlier tree."""
    train_window, forest, evals_log, config, x, y, k = _train_tiny()
    last = forest.iteration_indptr[-2]
    first = forest.trees[0]
    for name in ("feature", "threshold", "default_left", "left", "right", "value", "gain",
                 "sum_hess"):
        setattr(forest.trees[last], name, getattr(first, name))
    checks = train_window.judge(forest, evals_log, config, x, y, k, 0)
    failed = {c["name"] for c in checks if not c["ok"]}
    assert {"leaf_value_err", "loss_abs"} & failed, checks


def test_training_check_fails_on_one_wrong_histogram_node():
    """One sibling pair's histogram sums off by a hundredth: the median over
    pairs does not move, the widest gap does."""
    train_window, forest, evals_log, config, x, y, k = _train_tiny()
    tree = forest.trees[0]
    pair = [int(tree.left[0]), int(tree.right[0])]  # the root's children: from the histogram
    sum_hess = np.array(tree.sum_hess, np.float32)
    sum_hess[pair] *= np.float32(1.01)
    tree.sum_hess = sum_hess
    checks = {c["name"]: c for c in train_window.judge(forest, evals_log, config, x, y, k, 0)}
    assert checks["direct_hess_err"]["ok"] and not checks["direct_hess_err_max"]["ok"], checks


def test_training_run_is_not_correct_when_part_of_the_batch_is_left_out():
    """Drives a whole run of the kind with the timed path broken underneath:
    the objective drops the gradient of every other row."""
    from benchmark.kinds import train_window
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.models import objectives

    def broken_train(*args, **kwargs):
        sound = objectives.LogisticRegression.grad_hess

        def half(self, margin, label, weight):
            import jax.numpy as jnp

            keep = (jnp.arange(margin.shape[0]) % 2 == 0).astype(margin.dtype)
            g, h = sound(self, margin, label, weight)
            return g * keep, h * keep

        objectives.LogisticRegression.grad_hess = half
        try:
            return models.train(*args, **kwargs)
        finally:
            objectives.LogisticRegression.grad_hess = sound

    ctx = cell_context("higgs-d8.train-fused", 33, seconds=0.2)
    ctx["config"].update(TINY_TRAIN, params=tiny_params(ctx["config"]))
    run = train_window.run(ctx, train_fn=broken_train)
    assert not all(c["ok"] for c in run["checks"]), run["checks"]


def test_serving_control_in_bf16_is_over_the_limit():
    for seed in (1, 2, 2**31 + 5):
        _serving_control(seed)


def _serving_control(seed):
    spec = {"num_trees": 60, "max_depth": 8}
    trees = seeded_forest.make_trees(spec, 28, seed)
    rows = seeded_forest.make_rows(np.random.default_rng(seed), 512, 28)
    sound = forest_reference.predict(trees, rows)
    f32 = forest_reference.predict(trees, rows, dtype="float32")
    control = forest_reference.predict(trees, rows, dtype="bfloat16")
    limit = limits.SERVE["served_prob_gap"]
    assert np.max(np.abs(f32 - sound)) < limit / 3
    assert np.max(np.abs(control - sound)) > 3 * limit
    body = seeded_forest.encode_csv(rows[:4])
    parsed = np.asarray([line.split(",") for line in body.decode().split("\n")], np.float32)
    assert np.array_equal(parsed, rows[:4])


def test_no_chip_is_an_error_unless_the_cpu_was_asked_for_by_name(monkeypatch):
    assert harness.require_chips(1)[0].platform == "cpu"  # conftest asks by name
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as e:
        harness.require_chips(1)
    assert e.value.code == harness.EXIT_NO_CHIP


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    """One K=8 dispatch of higgs-d8 at 20,000 rows, recorded on a v5e (PR 25)."""
    import gzip

    path = tmp_path / "plugins" / "profile" / "t" / "tiny.xplane.pb"
    path.parent.mkdir(parents=True)
    with gzip.open(os.path.join(ROOT, "benchmark", "fixtures", "tiny_train.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    summary = trace_reduce.TraceSummary.from_dir(str(tmp_path), window_s=0.08164287)
    assert list(summary.planes) == ["/device:TPU:0"]
    assert summary.busy_s == pytest.approx(0.069156113, rel=1e-6)  # XLA Modules: 69.154 ms
    assert summary.idle_share == pytest.approx(0.152944, rel=1e-4)
    kernel = summary.kernel_events("graft_level_histogram")
    assert len(kernel) == 64 and sum(kernel) == pytest.approx(0.016380775, rel=1e-6)
    breakdown = summary.breakdown()
    assert breakdown["device_ops"][0][0] == "%fusion u16[20000]"
    assert breakdown["idle_gaps"][0][0] == "host:before_first_and_after_last_op"
    assert len(breakdown["device_ops"]) <= 10 and len(breakdown["idle_gaps"]) <= 10


def test_trace_reduction_on_synthetic_events():
    ops = [("while", 0.0, 100.0), ("a", 10.0, 20.0), ("b", 40.0, 50.0), ("c", 150.0, 50.0)]
    assert trace_reduce.busy_intervals(ops) == [[0.0, 100.0], [150.0, 200.0]]
    assert dict(trace_reduce.self_times(ops)) == {"while": 30.0, "a": 20.0, "b": 50.0, "c": 50.0}
    summary = trace_reduce.TraceSummary({"/device:TPU:0": ops}, window_s=250e-9)
    assert summary.busy_s == pytest.approx(150e-9)
    assert summary.idle_share == pytest.approx(0.4)
    assert summary.idle_gaps()[0] == ["after:while|before:c", pytest.approx(50e-9)]


def test_needed_work_by_hand_and_the_peaks_table():
    # PERF.md section 5: 900k rows x 28 u16 bins + 12 B a row = 61.2 MB, 74.7 us
    work = needed_work.level_histogram(900_000, 28, 257)
    assert work == {"bytes": 900_000 * (28 * 2 + 12), "ops": 900_000 * 28 * 2}
    least, bound = needed_work.least_seconds(work, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and least == pytest.approx(61.2e6 / 819e9)
    assert needed_work.level_histogram(1000, 10, 256)["bytes"] == 1000 * (10 + 12)
    seven = needed_work.level_histogram(1000, 10, 257, trees=7)  # bins read once
    assert seven == {"bytes": 1000 * (10 * 2 + 7 * 12), "ops": 1000 * 10 * 2 * 7}
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
