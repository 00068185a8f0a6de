"""The four-chip cell ``criteo-tb-d8-host4.train-fused-mesh``: its files, its
kind end to end on a CPU mesh of 4 at a tiny size, what it hands the readers,
its new reader, and its own control shown to fail.

``test_harness.py::test_every_cell_resolves_to_files_that_exist`` holds every
cell to one chip and stops at this one: what it checks of a cell is checked
here for the new cell. No module-level jax or topology calls.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.trace_reduce import TraceSummary  # noqa: E402

CELL = "criteo-tb-d8-host4.train-fused-mesh"
ONE_CHIP = "criteo-tb-d8.train-fused"
NEW_METRICS = {"hist_allreduce_ms_per_round", "setup_sketch_merge_s", "chip_busy_skew_pct"}
# as tests/benchmark/test_harness.py: the CPU's float32 sums read wider
CPU_LIMITS = {
    "direct_hess_err": 5e-4, "direct_hess_err_p90": 5e-3, "direct_hess_err_max": 5e-3,
    "gain_err_median": 1e-3, "leaf_sum_hess_rel": 2e-4, "leaf_value_err": 1e-4, "loss_abs": 8e-5,
}


def tiny(config):
    # 3,002 rows over 4 chips: the last share is padded
    return {
        "train_rows": 3002, "validation_rows": 801,
        "rows_a_chip": {"train": 751, "validation": 201},
        "rounds_per_dispatch": 2, "check_limits": CPU_LIMITS,
        "params": dict(config["params"], max_depth=3),
    }


def resolved():
    bench = harness.load_benchmark()
    return (bench,) + harness.resolve_cell(bench, CELL)


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


def context(seed, trace=False, **config):
    _bench, cell, base, traffic = resolved()
    return {
        "cell": cell, "config": dict(base, **tiny(base), **config), "traffic": traffic,
        "seed": seed, "seconds": 0.2, "trace": trace, "t_process_start": 0.0,
    }


def test_the_cell_resolves_to_files_that_exist():
    bench, cell, config, traffic = resolved()
    assert cell["chips"] == 4 and traffic["kind"] == "train_window_mesh"
    assert hasattr(harness.load_kind(traffic), "run")
    assert os.path.exists(os.path.join(ROOT, "benchmark", "datagen", config["generator"] + ".py"))
    e2e = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", CELL)}
    assert e2e == {"train_rounds_per_s", "setup_s"}
    layer = harness.cell_metrics(bench, "per_layer", CELL, e2e)
    for m in layer:
        read, _args = harness.load_reader(m["name"])
        assert callable(read) and m["moves"] in e2e
    # four-chip cells stay under a quarter of the cells, and one always may
    four = [c["name"] for c in bench["workloads"] if c["chips"] == 4]
    assert four == [CELL] and all(c["chips"] in (1, 4) for c in bench["workloads"])


def test_the_cell_reports_the_one_chip_cells_layers_and_its_own():
    bench = harness.load_benchmark()
    e2e = {"train_rounds_per_s", "setup_s"}
    mine = {m["name"] for m in harness.cell_metrics(bench, "per_layer", CELL, e2e)}
    one_chip = {m["name"] for m in harness.cell_metrics(bench, "per_layer", ONE_CHIP, e2e)}
    # properties of the data, which the one-chip cell reports already
    assert one_chip - mine == {"missing_cells_pct", "sketch_cut_fill_pct"}
    assert mine - one_chip == NEW_METRICS
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]


def test_the_configuration_is_four_shares_of_the_one_chip_configuration():
    bench, _cell, config, _traffic = resolved()
    one = harness.resolve_cell(bench, ONE_CHIP)[1]
    entry = {c["name"]: c for c in bench["configs"]}[config["name"]]
    assert entry["source"] == config["source"] and entry["reduced"] == config["reduced"]
    assert config["rows_a_chip"] == {"train": one["train_rows"], "validation": one["validation_rows"]}
    assert config["train_rows"] == 4 * one["train_rows"] == 65_549_964
    assert config["validation_rows"] == 4 * one["validation_rows"] == 2_785_544
    assert config["cluster"]["chips_run"] == 4 and config["cluster"]["mesh"] == {"data": 256}
    for key in ("num_feature", "params", "rounds_per_dispatch", "generator",
                "published_train_rows", "published_validation_rows"):
        assert config[key] == one[key], key
    assert set(config["check_limits"]) == set(one["check_limits"])


def test_the_cell_prints_one_well_formed_line_on_a_mesh_of_four(capsys):
    _bench, _cell, config, _traffic = resolved()
    rc = harness.run_cell(CELL, 2**31 + 41, 0.3, False, 0.0, shrink=tiny(config))
    line, out = last_line(capsys)
    assert rc == 0 and line["correct"] is True, out
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"train_rounds_per_s", "setup_s"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert any(o.startswith("check direct_hess_err: value=") and "limit=" in o for o in out)


def test_the_traced_run_reports_per_layer_metrics(capsys):
    _bench, _cell, config, _traffic = resolved()
    rc = harness.run_cell(CELL, 2**31 + 42, 0.2, True, 0.0, shrink=tiny(config))
    line, out = last_line(capsys)
    assert rc == 0 and line["correct"] is True, out
    # the CPU's trace has no device plane: the trace's readers, and the
    # program's spans with them, are left out; the host span is there
    assert set(line["metrics"]) == {"train_first_round_s"}
    assert line["device"]["count"] == 4 and "breakdown" in line


def test_the_kind_hands_the_readers_the_first_chips_rows_and_trains_on_the_mesh():
    from benchmark.kinds import train_window_mesh
    from sagemaker_xgboost_container_tpu import models

    seen = []

    def train(*args, mesh=None, **kwargs):
        seen.append(mesh)
        return models.train(*args, mesh=mesh, **kwargs)

    ctx = context(2**31 + 43)
    run = train_window_mesh.run(ctx, train_fn=train)
    assert all(c["ok"] for c in run["checks"]), run["checks"]
    (mesh,) = seen
    assert mesh.axis_names == ("data",) and mesh.shape["data"] == 4
    assert run["config"]["train_rows"] == 751 and run["config"]["validation_rows"] == 201
    assert ctx["config"]["train_rows"] == 3002  # the data and the reference take every row
    # the program says what its mesh and its collective are
    from benchmark.readers import program_phase

    assert program_phase.series("mesh_data_shards")[0].value == 4
    assert program_phase.series("hist_allreduce_bytes_per_round")[0].value > 0
    assert program_phase.series("training_phase_seconds", {"phase": "setup.sketch_merge"})


@pytest.mark.parametrize("chips", [3, 16])
def test_the_kind_refuses_a_mesh_that_is_not_the_cells_chips(monkeypatch, chips):
    from benchmark.kinds import train_window_mesh
    from sagemaker_xgboost_container_tpu.training import algorithm_train

    real = algorithm_train.training_mesh
    # a job's mesh that is not the cell's: two devices, whatever is asked for
    monkeypatch.setattr(algorithm_train, "training_mesh", lambda cap=None: real(2))
    with pytest.raises(SystemExit, match="`data` mesh of {}".format(chips)):
        train_window_mesh.job_mesh(chips)


def test_chip_busy_skew_reads_the_busiest_chip_over_the_least_busy():
    read, args = harness.load_reader("chip_busy_skew_pct")
    ops = lambda busy_ns: [("%fusion.1 = f32[8]{0} fusion()", 0.0, busy_ns)]  # noqa: E731
    two = TraceSummary({"/device:TPU:0": ops(1000.0), "/device:TPU:1": ops(1250.0)}, 1.0)
    assert read({"trace": two}, args) == pytest.approx(25.0)
    # nested events count once: busy is the union of the intervals
    nested = [("%while.1 = () while()", 0.0, 1000.0), ("%fusion.2 = f32[8]{0} fusion()", 10.0, 500.0)]
    same = TraceSummary({"/device:TPU:0": nested, "/device:TPU:1": ops(1000.0)}, 1.0)
    assert read({"trace": same}, args) == pytest.approx(0.0)
    one = TraceSummary({"/device:TPU:0": ops(1000.0)}, 1.0)
    assert read({"trace": one}, args) is None and read({"trace": None}, args) is None
    idle = TraceSummary({"/device:TPU:0": ops(1000.0), "/device:TPU:1": []}, 1.0)
    assert read({"trace": idle}, args) is None


def test_a_shard_left_out_of_the_histogram_collective_is_not_correct(monkeypatch):
    """``scripts/psum_drop_control.py`` at a tiny size: with the last share's
    histograms out of every level's sums the children's sums miss a quarter of
    their rows, and the directly-summed gaps read thousands of times sound."""
    from benchmark.kinds import train_window_mesh
    from sagemaker_xgboost_container_tpu.ops import histogram, lossguide, tree_build

    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    import psum_drop_control

    for module in (histogram, tree_build, lossguide):  # undone when the test ends
        monkeypatch.setattr(module, "apply_hist_collective", module.apply_hist_collective)
    sound = {c["name"]: c for c in train_window_mesh.run(context(2**31 + 44))["checks"]}
    assert all(c["ok"] for c in sound.values()), sound
    psum_drop_control.install()
    broken = {c["name"]: c for c in train_window_mesh.run(context(2**31 + 44))["checks"]}
    failed = {name for name, c in broken.items() if not c["ok"]}
    assert {"direct_hess_err", "direct_hess_err_p90", "gain_err_median"} <= failed, broken
    assert broken["direct_hess_err"]["value"] > 1e3 * sound["direct_hess_err"]["value"]
