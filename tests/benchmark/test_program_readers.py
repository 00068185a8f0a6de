"""The readers of what the program says of itself (PR 26): ``stage_ms`` over
the recorded v5e trace with a hand-made stage table, ``program_phase`` and
``setup_unnamed`` over a filled registry, and a CPU rehearsal of the cell
whose metrics files resolve and whose readers leave out what a CPU run
cannot say.

No module-level jax or topology calls: jax is imported inside the tests.
"""

import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, trace_reduce  # noqa: E402
from benchmark.readers import program_phase, setup_unnamed, stage_ms  # noqa: E402

CELL = "higgs-d8.train-fused"
STAGE_METRICS = {
    "grad_ms_per_round": "grad", "hist_stage_ms_per_round": "hist",
    "node_totals_ms_per_round": "node_totals", "split_scan_ms_per_round": "split_scan",
    "route_rows_ms_per_round": "route_rows", "leaf_margin_ms_per_round": "leaf_margin",
    "eval_apply_ms_per_round": "eval_apply", "eval_metric_ms_per_round": "eval_metric",
}
HOST_METRICS = [
    "setup_sketch_s", "setup_bin_apply_s", "setup_upload_s", "setup_program_load_s",
    "setup_unnamed_s", "train_host_turnaround_ms_per_dispatch",
]


@pytest.fixture
def recorded_trace(tmp_path):
    """One K=8 dispatch of higgs-d8 at 20,000 rows, recorded on a v5e (PR 25)."""
    path = tmp_path / "plugins" / "profile" / "t" / "tiny.xplane.pb"
    path.parent.mkdir(parents=True)
    with gzip.open(os.path.join(ROOT, "benchmark", "fixtures", "tiny_train.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    return trace_reduce.TraceSummary.from_dir(str(tmp_path), window_s=0.08164287)


@pytest.fixture
def registry():
    """The program's registry, emptied before and after."""
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    REGISTRY.reset()
    yield REGISTRY
    REGISTRY.reset()


def hand_made_table(trace):
    """Every `u16[20000]` fusion is routing, every kernel call the histogram,
    every `s32[5000]` fusion the validation rows; the rest finds no stage."""
    table = {}
    for name, _start, _dur in trace.first_chip:
        instruction = stage_ms.INSTRUCTION.match(name).group(1)
        if "graft_level_histogram" in name:
            table[instruction] = "hist"
        elif name.startswith("%fusion") and " = u16[20000]" in name:
            table[instruction] = "route_rows"
        elif name.startswith("%fusion") and " = s32[5000]" in name:
            table[instruction] = "eval_apply"
    return table


def spec(metric):
    return harness.load_json(ROOT, "benchmark", "layer_metrics", metric + ".json")


def test_every_new_metric_has_its_entry_its_file_and_its_reader():
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric in [*STAGE_METRICS, "round_unnamed_device_pct", *HOST_METRICS]:
        entry = entries[metric]
        assert entry["workloads"] == [CELL] and entry["better"] == "lower"
        read, _args = harness.load_reader(metric)
        assert callable(read)
    for metric, stage in STAGE_METRICS.items():
        assert spec(metric) == {"reader": "stage_ms", "args": {"stage": stage, "per": "round"}}
        assert entries[metric]["source"] == "device_trace"
        assert entries[metric]["moves"] == "train_rounds_per_s"
    for metric in HOST_METRICS[:-1]:
        assert entries[metric]["moves"] == "setup_s"
    # what the program names a stage, the benchmark reads under the same name
    from sagemaker_xgboost_container_tpu.telemetry import device

    assert set(STAGE_METRICS.values()) <= set(device.STAGES)


def test_stage_ms_sums_to_the_busy_time_and_reports_the_rest_as_unnamed(recorded_trace):
    run = {
        "trace": recorded_trace,
        "traced_units": {"dispatch": 1, "round": 8},
        "stage_table": hand_made_table(recorded_trace),
    }
    per_round = {
        stage: stage_ms.read(run, {"stage": stage, "per": "round"})
        for stage in ("hist", "route_rows", "eval_apply", "")
    }
    assert all(v > 0 for v in per_round.values())
    # self-times are disjoint: the stages and the unnamed rest are the busy time
    assert 8 * sum(per_round.values()) == pytest.approx(1e3 * recorded_trace.busy_s, rel=1e-6)
    # the kernel's events are leaves, so its stage reads what kernel_ms reads
    kernel_ms = 1e3 * sum(recorded_trace.kernel_events("graft_level_histogram")) / 8
    assert per_round["hist"] == pytest.approx(kernel_ms, rel=1e-6)
    unnamed_pct = stage_ms.read(run, {"stage": "", "share": True})
    assert unnamed_pct == pytest.approx(
        100.0 * per_round[""] / sum(per_round.values()), rel=1e-9
    )
    assert 0 < unnamed_pct < 100
    # a stage the program does not have is left out, not read as zero
    assert stage_ms.read(run, {"stage": "hist_allreduce", "per": "round"}) is None


def test_stage_ms_finds_nothing_without_a_table_or_a_device_trace(recorded_trace, monkeypatch):
    units = {"dispatch": 1, "round": 8}
    args = {"stage": "grad", "per": "round"}
    # a program that publishes no table (a parent before PR 26)
    monkeypatch.setattr(stage_ms, "program_stage_table", lambda: None)
    assert stage_ms.read({"trace": recorded_trace, "traced_units": units}, args) is None
    # a CPU rehearsal: the trace has no device plane, and nothing is lowered
    monkeypatch.setattr(stage_ms, "program_stage_table", lambda: pytest.fail("lowered"))
    empty = trace_reduce.TraceSummary({}, window_s=1.0)
    assert stage_ms.read({"trace": empty, "traced_units": units}, args) is None
    assert stage_ms.read({"trace": None, "traced_units": units}, args) is None


def test_program_phase_and_setup_unnamed_over_a_filled_registry(registry, recorded_trace):
    from sagemaker_xgboost_container_tpu.telemetry import spans

    def observe(phase, seconds, times=1):
        for _ in range(times):
            registry.histogram(spans.PHASE_HISTOGRAM, labels={"phase": phase}).observe(seconds)

    observe("setup.sketch", 14.0)
    observe("setup.bin_apply", 6.0)
    observe("setup.bin_apply", 2.0)
    observe("setup.upload", 1.0, times=3)
    observe("setup.program_build", 0.25)
    observe("setup.first_dispatch", 33.0)
    observe("host_turnaround", 0.040, times=2)
    observe("callbacks", 0.005, times=8)
    for stage, phase, seconds in [
        ("cache_load", "setup.first_dispatch/host_dispatch", 1.5),
        ("lower", "setup.sketch", 0.5),
        ("compile", "stage_table", 30.0),
    ]:
        registry.counter(
            "xla_program_seconds_total", labels={"stage": stage, "phase": phase}
        ).inc(seconds)
    run = {"trace": recorded_trace, "host_spans": {"train_first_round_s": 61.0}}
    values = {}
    for metric in HOST_METRICS:
        read, args = harness.load_reader(metric)
        values[metric] = read(run, args)
    assert values["setup_sketch_s"] == pytest.approx(14.0)
    assert values["setup_bin_apply_s"] == pytest.approx(8.0)  # summed over matrices
    assert values["setup_upload_s"] == pytest.approx(3.0)
    # every stage of every phase but the stage table's own lookup
    assert values["setup_program_load_s"] == pytest.approx(2.0)
    assert values["setup_unnamed_s"] == pytest.approx(61.0 - (14 + 8 + 3 + 0.25 + 33))
    # the mean turnaround, less the caller's callbacks inside it
    assert values["train_host_turnaround_ms_per_dispatch"] == pytest.approx(
        1e3 * (0.080 - 0.040) / 2
    )
    # a program without these spans (the parent): nothing to read, nothing raised
    registry.reset()
    for metric in HOST_METRICS:
        read, args = harness.load_reader(metric)
        assert read(run, args) is None
    assert setup_unnamed.read({"trace": recorded_trace, "host_spans": {}}, spec("setup_unnamed_s")["args"]) is None
    assert program_phase.series("training_phase_seconds") == []


def test_cpu_rehearsal_reads_the_host_metrics_and_leaves_the_trace_ones_out(
    registry, recorded_trace, capsys
):
    """The cell's own files at a tiny size through ``models.train()``. The
    CPU's trace has no device plane, so the line leaves out what a CPU run
    cannot say; with the recorded device trace in its place the same run
    reads the six metrics of the program's spans and counters."""
    from benchmark.kinds import train_window

    bench = harness.load_benchmark()
    cell, config, traffic = harness.resolve_cell(bench, CELL)
    config.update({
        "train_rows": 3000, "validation_rows": 800, "rounds_per_dispatch": 2,
        "params": dict(config["params"], max_depth=3),
    })
    run = train_window.run({
        "cell": cell, "config": config, "traffic": traffic, "seed": 2**31 + 26,
        "seconds": 0.2, "trace": True, "t_process_start": 0.0,
    })
    capsys.readouterr()
    metrics = [
        m["name"]
        for m in harness.cell_metrics(bench, "per_layer", CELL, {"train_rounds_per_s", "setup_s"})
    ]
    assert len(metrics) == 21

    def read_all():
        return {m: harness.load_reader(m)[0](run, harness.load_reader(m)[1]) for m in metrics}

    on_cpu = {m: v for m, v in read_all().items() if v is not None}
    assert set(on_cpu) == {"train_first_round_s"}
    run["trace"] = recorded_trace  # stands in for a device run of the same spans
    run["device_kind"] = "TPU v5 lite"
    with_device = {m: v for m, v in read_all().items() if v is not None}
    trace_only = {
        "train_host_gap_ms_per_dispatch", "round_device_ms", "hist_kernel_ms_per_round",
        "hist_kernel_roofline", "device_idle_pct.train",
    }
    assert set(with_device) >= {"train_first_round_s", *HOST_METRICS} | trace_only
    assert with_device["setup_sketch_s"] > 0 and with_device["setup_upload_s"] > 0
    assert 0 <= with_device["setup_unnamed_s"] < 0.5 * with_device["train_first_round_s"]
    assert with_device["setup_program_load_s"] > 0
    assert with_device["train_host_turnaround_ms_per_dispatch"] > 0
    json.dumps(with_device)
