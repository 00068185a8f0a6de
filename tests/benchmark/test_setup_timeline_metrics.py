"""The set-up timeline's per-layer metrics (PR 36): every second of
``setup_s`` under a span of the program's, read through ``program_phase``
and, where a mesh's shards run side by side, ``phase_a_shard``. One case a
metric: its entry, its file, its reader, the cells that report it, what it
reads from a filled registry, and nothing on a CPU run or from a program
without the series (a parent).

No module-level jax or topology calls.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.readers import phase_a_shard, program_phase  # noqa: E402

CELLS = [
    "mslr-ndcg.train-fused-grouped",
    "criteo-tb-d8.train-fused",
    "criteo-tb-d8-host4.train-fused-mesh",
]
STARTUP, SETUP = "process start-up", "train set-up in front of the first round"
#: metric -> (layer, source, reader, what it reads from `filled()` on four shards)
METRICS = {
    "startup_before_train_s": (STARTUP, "program_span", "program_phase", 17.5),
    "startup_package_import_s": (STARTUP, "program_span", "program_phase", 3.25),
    "setup_sketch_stage_s": (SETUP, "program_span", "phase_a_shard", 3 * 1.5),
    "setup_sketch_transfer_s": (SETUP, "program_span", "phase_a_shard", 3 * 0.5),
    "setup_sketch_kernel_s": (SETUP, "program_span", "phase_a_shard", 3 * 4.0),
    "setup_bin_apply_transfer_s": (SETUP, "program_span", "phase_a_shard", 2 * 2 * 0.75),
    "setup_bin_apply_kernel_s": (SETUP, "program_span", "phase_a_shard", 2 * 2 * 0.125),
    "setup_first_dispatch_load_s": (SETUP, "program_span", "program_phase", 6.0),
    "setup_program_load_wall_s": (SETUP, "program_counter", "program_phase", 7.0),
    "setup_program_cache_load_s": (SETUP, "program_counter", "program_phase", 1.5),
    "setup_program_trace_lower_s": (SETUP, "program_counter", "program_phase", 2.0 + 0.5),
    "setup_sketch_hbm_peak_bytes": (SETUP, "program_counter", "program_phase", 900 + 11_000),
    "train_hbm_resident_bytes": (SETUP, "program_counter", "program_phase", 4_000),
}
#: its file and reader are in, its entry is not: the benchmark enumerates the
#: devices itself before `train()`, so no cell has the span (PERF.md section 7)
FILE_ONLY = {"startup_backend_init_s": ("program_phase", 12.0)}


class _Trace:
    busy_s = 1.0  # stands in for a run with work on a device


ON_DEVICE, ON_CPU = {"trace": _Trace()}, {"trace": None}


@pytest.fixture
def registry():
    """The program's registry, emptied before and after."""
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    REGISTRY.reset()
    yield REGISTRY
    REGISTRY.reset()


def filled(registry, shards=4):
    """A mesh run's series: ``shards`` chips side by side, the sketch in three
    blocks, two matrices binned in two blocks of rows each."""
    from sagemaker_xgboost_container_tpu.telemetry import spans

    def observe(phase, seconds, times=1):
        for _ in range(times):
            registry.histogram(spans.PHASE_HISTOGRAM, labels={"phase": phase}).observe(seconds)

    observe("startup.before_train", 17.5)
    observe("startup.package_import", 3.25)
    observe("startup.backend_init", 12.0)
    observe("setup.sketch", 20.0)
    observe("setup.sketch.shard", 19.0, times=shards)
    for part, seconds in (("stage", 1.5), ("transfer", 0.5), ("kernel", 4.0), ("fetch", 0.01)):
        observe("setup.sketch." + part, seconds, times=3 * shards)
    observe("setup.bin_apply", 2.0, times=2)
    observe("setup.bin_apply.shard", 1.9, times=2 * shards)
    for part, seconds in (("stage", 0.001), ("transfer", 0.75), ("kernel", 0.125)):
        observe("setup.bin_apply." + part, seconds, times=2 * 2 * shards)
    observe("setup.first_dispatch", 17.0)
    observe("setup.first_dispatch.load", 6.0)
    observe("setup.first_dispatch.run", 11.0)
    for phase, seconds in (("setup.sketch", 1.0), ("setup.first_dispatch", 6.0), ("stage_table", 30.0)):
        registry.counter("xla_program_wall_seconds_total", labels={"phase": phase}).inc(seconds)
    for stage, phase, seconds in (
        ("cache_load", "setup.first_dispatch/setup.first_dispatch.load/host_dispatch", 1.5),
        ("trace", "setup.first_dispatch/setup.first_dispatch.load/host_dispatch", 2.0),
        ("lower", "setup.sketch/setup.sketch.shard/setup.sketch.kernel", 0.5),
        ("compile", "setup.upload", 0.25),
        ("compile", "stage_table", 30.0),
        ("trace", "stage_table", 3.0),
    ):
        registry.counter(
            "xla_program_seconds_total", labels={"stage": stage, "phase": phase}
        ).inc(seconds)
    for phase, in_use, peak, reserved in (
        ("setup.sketch", 2_500, 900, 11_000), ("setup.first_dispatch", 4_000, 5_000, 7_000),
    ):
        for what, value in (("in_use", in_use), ("peak", peak), ("reserved", reserved)):
            registry.gauge("setup_hbm_bytes", labels={"phase": phase, "what": what}).set(value)


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_timeline_metric_has_its_entry_its_file_and_its_reader(metric, registry):
    layer, source, reader, value = METRICS[metric]
    bench = harness.load_benchmark()
    entry = {m["name"]: m for m in bench["per_layer"]}[metric]
    assert entry["workloads"] == CELLS
    assert (entry["layer"], entry["source"]) == (layer, source)
    assert (entry["moves"], entry["better"]) == ("setup_s", "lower")
    assert entry["unit"] == ("bytes" if metric.endswith("_bytes") else "s")
    spec = harness.load_json(ROOT, "benchmark", "layer_metrics", metric + ".json")
    assert spec["reader"] == reader
    read, args = harness.load_reader(metric)
    # a program without the series (the parent): nothing to read, nothing raised
    assert read(ON_DEVICE, args) is None
    filled(registry)
    assert read(ON_DEVICE, args) == pytest.approx(value)
    # a CPU run takes other paths through set-up: its seconds stand under no such name
    assert read(ON_CPU, args) is None
    assert read({}, args) is None


@pytest.mark.parametrize("metric", sorted(FILE_ONLY))
def test_a_metric_no_cell_can_report_has_its_file_and_no_entry(metric, registry):
    reader, value = FILE_ONLY[metric]
    bench = harness.load_benchmark()
    assert metric not in {m["name"] for m in bench["per_layer"]}
    assert harness.load_json(ROOT, "benchmark", "layer_metrics", metric + ".json")["reader"] == reader
    read, args = harness.load_reader(metric)
    assert read(ON_DEVICE, args) is None
    filled(registry)
    assert read(ON_DEVICE, args) == pytest.approx(value) and read(ON_CPU, args) is None


@pytest.mark.parametrize("shards", [1, 4])
def test_seconds_a_shard_are_the_sum_over_the_spans_divided_by_the_shards(shards, registry):
    filled(registry, shards)
    args = {"phase": "setup.bin_apply.transfer", "shard_phase": "setup.bin_apply.shard",
            "whole_phase": "setup.bin_apply"}
    # two matrices, two blocks each, on every shard: summed over matrices, a shard
    assert phase_a_shard.read(ON_DEVICE, args) == pytest.approx(2 * 2 * 0.75)
    assert phase_a_shard.spans_of("setup.bin_apply.shard") == (pytest.approx(1.9 * 2 * shards), 2 * shards)
    # one of the three without its spans: nothing, and no division by zero
    for key in args:
        assert phase_a_shard.read(ON_DEVICE, dict(args, **{key: "no.such.span"})) is None


def test_every_listed_cell_reports_setup_s_and_the_first_cell_is_left_as_it_was():
    bench = harness.load_benchmark()
    for cell in CELLS:
        e2e = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", cell)}
        mine = {m["name"] for m in harness.cell_metrics(bench, "per_layer", cell, e2e)}
        assert "setup_s" in e2e and set(METRICS) <= mine
    first = {
        m["name"]
        for m in harness.cell_metrics(bench, "per_layer", "higgs-d8.train-fused", {"setup_s"})
    }
    assert not first & (set(METRICS) | set(FILE_ONLY))
    assert program_phase.ran_on_device(ON_DEVICE) and not program_phase.ran_on_device(ON_CPU)


def test_the_accounts_close_over_a_cpu_session(registry):
    """What the chip runs are held to, at a tiny size: the program-load wall
    is at most the thread-seconds and at most the call, and the first
    dispatch's two halves lie inside it (what is in front of them, the
    dispatch's own small programs, has no span of its own)."""
    import time

    import numpy as np
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix

    rng = np.random.RandomState(36)
    x = rng.rand(700, 5).astype(np.float32)
    data = DataMatrix(x, labels=(x[:, 0] > 0.5).astype(np.float32))
    started = time.perf_counter()
    models.train(
        {"objective": "binary:logistic", "max_depth": 3, "_rounds_per_dispatch": 2,
         "eval_metric": "logloss", "eta": 0.36},
        data, num_boost_round=4, evals=[(data, "train")], verbose_eval=False,
    )
    call = time.perf_counter() - started

    def read(metric):
        reader, args = harness.load_reader(metric)
        return reader(ON_DEVICE, args)

    wall, threads = read("setup_program_load_wall_s"), read("setup_program_load_s")
    assert 0 < wall <= call and wall <= threads * (1 + 1e-6)
    first = phase_a_shard.spans_of("setup.first_dispatch")[0]
    halves = read("setup_first_dispatch_load_s") + phase_a_shard.spans_of("setup.first_dispatch.run")[0]
    assert 0 < halves <= first
    # a cold run loads nothing from the cache: the reader then reports nothing
    assert read("setup_program_trace_lower_s") + (read("setup_program_cache_load_s") or 0.0) <= threads
    assert read("train_hbm_resident_bytes") is None  # the CPU reports no memory stats
