"""The training job names its own work (PR 26): stage names inside the round
program and the table that publishes them, always-on spans over set-up and
the dispatch loop, profiler annotations of the same names, and the
program-load counters labelled with the open span.

CPU, tiny sizes. jax is imported inside the tests.
"""

import gc
import subprocess
import sys
import time

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.telemetry import (
    REGISTRY,
    cluster,
    device,
    spans,
    tracing,
)

SETUP_ORDER = [
    "setup.sketch",
    "setup.bin_apply",
    "setup.upload",
    "setup.program_build",
    "setup.first_dispatch",
]
ONE_TREE_STAGES = {
    "grad", "hist", "node_totals", "split_scan", "route_rows", "leaf_margin",
    "eval_apply", "eval_metric", "pack",
}


def _data(n=600, d=5, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, d).astype(np.float32)
    y = (X @ rng.rand(d).astype(np.float32) > 1.2).astype(np.float32)
    return X, y


def _train(mesh=None, rounds=4, k=2, callbacks=None):
    X, y = _data()
    Xv, yv = _data(200, seed=4)
    dtrain = DataMatrix(X, labels=y)
    return train(
        {"objective": "binary:logistic", "max_depth": 3, "eval_metric": "logloss",
         "_rounds_per_dispatch": k},
        dtrain,
        num_boost_round=rounds,
        evals=[(dtrain, "train"), (DataMatrix(Xv, labels=yv), "validation")],
        verbose_eval=False,
        mesh=mesh,
        callbacks=callbacks,
    )


@pytest.fixture
def finished_spans(monkeypatch):
    """Every span that ends, in order: (name, seconds, covering)."""
    from sagemaker_xgboost_container_tpu.models import booster

    ended = []
    real = spans.end_span

    def recording(open_span, emit=False):
        elapsed = real(open_span, emit=emit)
        ended.append((open_span.name, elapsed, open_span.covering))
        return elapsed

    monkeypatch.setattr(spans, "end_span", recording)
    monkeypatch.setattr(booster, "end_span", recording)
    return ended


# ------------------------------------------------------------------- spans
def test_importing_telemetry_does_not_import_jax():
    code = (
        "import sys; import sagemaker_xgboost_container_tpu.telemetry as t\n"
        "with t.span('x'): pass\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_span_enters_and_leaves_an_annotation_of_its_name(monkeypatch):
    import jax  # noqa: F401  annotate() looks jax up, it never imports it

    seen = []

    class Annotation:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            seen.append(("enter", self.name, self.kwargs))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(tracing, "_annotation_cls", Annotation)
    with spans.span("setup.sketch", attributes={"rows": 7}):
        with spans.span("inner"):
            assert spans.current_phase() == "setup.sketch/inner"
    assert seen == [
        ("enter", "setup.sketch", {"rows": 7}),
        ("enter", "inner", {}),
        ("exit", "inner"),
        ("exit", "setup.sketch"),
    ]
    assert spans.current_phase() == ""


def test_armed_tracer_span_carries_the_annotation(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name, **kwargs):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setenv(tracing.TRACE_ENV, "1")
    tracing._reset_for_tests()
    monkeypatch.setattr(tracing, "_annotation_cls", Annotation)
    try:
        with spans.span("commit"):  # one annotation, entered by the tracer's span
            pass
        tracing.finish_span(tracing.start_span("checkpoint"))
    finally:
        monkeypatch.delenv(tracing.TRACE_ENV)
        tracing._reset_for_tests()
    assert seen == [
        ("enter", "commit"), ("exit", "commit"),
        ("enter", "checkpoint"), ("exit", "checkpoint"),
    ]


def test_covering_span_stays_out_of_the_round_recorder():
    recorder = spans.push_recorder()
    try:
        with spans.span("callbacks", covering=True):
            with spans.span("checkpoint"):
                pass
    finally:
        spans.pop_recorder(recorder)
    assert set(recorder.phases) == {"checkpoint"}


def test_span_bytes_count_by_phase_and_direction():
    from sagemaker_xgboost_container_tpu.telemetry import MetricsRegistry

    registry = MetricsRegistry()
    with spans.span("setup.bin_apply", registry=registry,
                    attributes={"bytes_up": 100, "bytes_down": 40}) as s:
        s.add_bytes(up=11)
    counted = {
        (m.labels["phase"], m.labels["direction"]): m.value
        for name, _k, _h, fam in registry.collect()
        if name == spans.PHASE_BYTES_COUNTER
        for m in fam
    }
    assert counted == {("setup.bin_apply", "up"): 111, ("setup.bin_apply", "down"): 40}


def test_train_leaves_its_spans_in_order_and_they_cover_the_call(finished_spans):
    started = time.perf_counter()
    _train(rounds=6, k=2)
    wall = time.perf_counter() - started
    names = [n for n, _s, _c in finished_spans]
    # set-up, in the order the work happens; one bin_apply per matrix
    firsts = [names.index(n) for n in SETUP_ORDER]
    assert firsts == sorted(firsts), names
    assert names.count("setup.sketch") == 1
    assert names.count("setup.bin_apply") == 2  # one per matrix: train, validation
    assert names.count("setup.upload") == 3  # train bins, labels+margins, eval sets
    # then per dispatch: host_dispatch, device_sync, host_turnaround
    assert names.count("host_dispatch") == names.count("device_sync") == 3
    assert names.count("host_turnaround") == 3  # the last one ends with train()
    loop = [n for n in names if n in ("host_dispatch", "device_sync", "host_turnaround")]
    assert loop[:2] == ["host_dispatch", "device_sync"]  # inside setup.first_dispatch
    assert loop[2:] == ["host_dispatch", "host_turnaround", "device_sync"] * 2 + ["host_turnaround"]
    assert names.count("commit") == names.count("eval_log") == names.count("callbacks") == 6
    # the top-level spans cover the call
    top = set(SETUP_ORDER) | {"device_sync", "host_turnaround"}
    covered = sum(s for n, s, _c in finished_spans if n in top)
    first = names.index("setup.first_dispatch")
    covered -= sum(s for n, s, _c in finished_spans[:first] if n == "device_sync")
    assert covered >= 0.95 * wall, (covered, wall)
    assert covered <= 1.001 * wall


@pytest.mark.parametrize("way_out", ["returns", "callback_raises", "session_dropped"])
def test_no_span_outlives_the_code_that_opened_it(way_out):
    """`host_turnaround` runs from the end of one dispatch into the next, so
    no ``with`` block holds it: ``train()`` closes it on every way out, and a
    caller that drives ``run_rounds()`` itself and drops the session closes
    it with the session. The open span would otherwise name every later
    phase of this thread (the program-load counters' ``phase`` label)."""
    assert spans.current_phase() == ""
    if way_out == "returns":
        _train()
    elif way_out == "callback_raises":

        class Raises:
            def after_iteration(self, forest, rnd, evals_log):
                assert spans.current_phase() == "host_turnaround/callbacks"
                raise RuntimeError("a callback's own failure")

        with pytest.raises(RuntimeError, match="a callback's own failure"):
            _train(callbacks=[Raises()])
    else:
        from sagemaker_xgboost_container_tpu.models.booster import (
            Forest,
            TrainConfig,
            _TrainingSession,
        )

        X, y = _data()
        config = TrainConfig({"objective": "binary:logistic", "max_depth": 2})
        forest = Forest(
            objective_name=config.objective, base_score=config.base_score,
            num_feature=X.shape[1],
        )
        session = _TrainingSession(config, DataMatrix(X, labels=y), [], forest)
        session.run_rounds()
        assert spans.current_phase() == "host_turnaround"
        del session, forest
        gc.collect()
    assert spans.current_phase() == ""


def test_train_with_no_variable_set_adds_no_fence_and_computes_no_table(monkeypatch):
    import jax

    device._reset_for_tests()
    fences = []
    monkeypatch.setattr(jax, "block_until_ready", lambda x: fences.append(1) or x)
    gc.collect()
    before = len(jax.live_arrays())
    _train()
    gc.collect()
    assert fences == []
    assert device._round_program is not None and device._stage_table is None
    assert len(jax.live_arrays()) == before  # the session and its buffers are gone


# ------------------------------------------------------------- stage table
def test_stage_table_names_every_stage_on_one_device_and_holds_no_array():
    import jax

    device._reset_for_tests()
    _train()
    gc.collect()
    before = len(jax.live_arrays())
    table = device.round_program_stages()
    gc.collect()
    assert len(jax.live_arrays()) == before
    assert set(table.values()) == ONE_TREE_STAGES  # no collective on one device
    assert device._stage_table[1]["route_rows"]["instructions"] > 0


def test_stage_table_names_the_collective_on_a_four_device_mesh():
    import jax
    from jax.sharding import Mesh

    device._reset_for_tests()
    mesh = Mesh(np.array(jax.devices()[:4]), axis_names=("data",))
    _train(mesh=mesh)
    stages = set(device.round_program_stages().values())
    assert stages == ONE_TREE_STAGES | {"hist_allreduce"}


def test_stage_table_lookup_is_a_span_of_its_own(finished_spans):
    device._reset_for_tests()
    _train()
    del finished_spans[:]
    device.round_program_stages()
    device.round_program_stages()  # cached: no second compile
    assert [n for n, _s, _c in finished_spans] == ["stage_table"]


# ---------------------------------------------------------------- listener
def _program_counts():
    return {
        (m.labels["stage"], m.labels["phase"]): m.value
        for name, _k, _h, fam in REGISTRY.collect()
        if name == "xla_programs_total"
        for m in fam
    }


def test_listener_counts_a_compile_and_a_cache_load_under_the_open_phase():
    before = _program_counts()
    with spans.span("setup.sketch"):
        cluster._on_jax_duration_event(
            "/jax/core/compile/backend_compile_duration", 0.25, fun_name="jit(kernel)"
        )
        with spans.span("inner"):
            cluster._on_jax_duration_event(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.01
            )
            cluster._on_jax_duration_event(
                "/jax/core/compile/backend_compile_duration", 0.02, fun_name="jit(apply)"
            )
    after = _program_counts()

    def grew(stage, phase):
        return after.get((stage, phase), 0) - before.get((stage, phase), 0)

    assert grew("compile", "setup.sketch") == 1
    assert grew("cache_load", "setup.sketch/inner") == 1
    assert grew("compile", "setup.sketch/inner") == 0
    assert cluster.program_events()[-2:] == [
        ("compile", "setup.sketch", "jit(kernel)", 0.25),
        ("cache_load", "setup.sketch/inner", "jit(apply)", 0.02),
    ]


def test_listener_counts_nested_trace_seconds_once():
    def seconds():
        return sum(
            m.value
            for name, _k, _h, fam in REGISTRY.collect()
            if name == "xla_program_seconds_total"
            for m in fam
            if m.labels == {"stage": "trace", "phase": "nested-trace-test"}
        )

    with spans.span("nested-trace-test"):
        time.sleep(0.03)
        cluster._on_jax_duration_event("/jax/core/compile/jaxpr_trace_duration", 0.01)
        cluster._on_jax_duration_event("/jax/core/compile/jaxpr_trace_duration", 0.01)
        # the outer trace ends last and contains both
        cluster._on_jax_duration_event("/jax/core/compile/jaxpr_trace_duration", 0.03)
    assert seconds() == pytest.approx(0.03)


def test_a_training_session_installs_the_listener(monkeypatch):
    monkeypatch.setattr(cluster, "_compile_listener_installed", False)
    installed = []
    from jax import monitoring

    monkeypatch.setattr(
        monitoring, "register_event_duration_secs_listener", installed.append
    )
    _train()
    _train()
    assert installed == [cluster._on_jax_duration_event]  # once, by the first session


# ------------------------------------------------- the set-up timeline (PR 36)
def _phases(registry):
    """{phase: (count, seconds)} of the registry's phase histogram."""
    return {
        m.labels["phase"]: (m.count, m.sum)
        for name, _k, _h, fam in registry.collect()
        if name == spans.PHASE_HISTOGRAM
        for m in fam
    }


def _gauges(registry, name):
    return {
        tuple(sorted(m.labels.items())): m.value
        for n, _k, _h, fam in registry.collect()
        if n == name
        for m in fam
    }


def test_startup_spans_are_recorded_once_however_often_the_function_is_called(monkeypatch):
    from sagemaker_xgboost_container_tpu.telemetry.registry import MetricsRegistry

    import jax

    jax.local_devices()  # the caller enumerates first, as the benchmark's harness does
    registry = MetricsRegistry()
    monkeypatch.setattr(spans, "_startup_done", set())
    started = time.time()
    spans.record_startup(registry=registry)  # a job's start: no train() yet
    assert "startup.before_train" not in _phases(registry)
    for _ in range(3):
        spans.record_startup(entering_train=True, registry=registry)
    spans.record_startup(registry=registry)
    phases = _phases(registry)
    assert phases["startup.package_import"][0] == phases["startup.before_train"][0] == 1
    # the imports lie inside the process's life up to train()
    assert phases["startup.before_train"][1] >= phases["startup.package_import"][1] > 0
    began = _gauges(registry, "process_start_time_seconds")[()]
    assert began <= started and phases["startup.before_train"][1] >= started - began
    # the back end was up already: nothing to time, and no span
    assert "startup.backend_init" not in phases


def test_train_times_the_back_end_where_it_is_the_first_to_enumerate(monkeypatch):
    from sagemaker_xgboost_container_tpu.telemetry.registry import MetricsRegistry

    import jax  # noqa: F401  record_startup looks jax up, it never imports it

    registry = MetricsRegistry()
    monkeypatch.setattr(spans, "_startup_done", set())
    monkeypatch.setattr(spans, "_backend_is_up", lambda: False)
    spans.record_startup(registry=registry)  # a job's start: jax.distributed may still come
    assert "startup.backend_init" not in _phases(registry)
    spans.record_startup(entering_train=True, registry=registry)
    spans.record_startup(entering_train=True, registry=registry)
    assert _phases(registry)["startup.backend_init"][0] == 1


def test_train_records_the_startup_spans_at_its_first_entry_only(monkeypatch):
    monkeypatch.setattr(spans, "_startup_done", set())
    before = _phases(REGISTRY).get("startup.before_train", (0, 0.0))[0]
    _train()
    _train()
    assert _phases(REGISTRY)["startup.before_train"][0] == before + 1


def test_process_start_is_never_later_than_the_packages_first_line():
    import sagemaker_xgboost_container_tpu as package

    first_line = package.IMPORT_INTERVALS[0][0]
    assert spans.process_start_time(first_line) <= first_line
    # a clock that /proc does not share: the package's first line stands in
    assert spans.process_start_time(first_line - 30 * 86400.0) == first_line - 30 * 86400.0
    assert all(end >= start for start, end, _jax in package.IMPORT_INTERVALS)
    assert len(package.IMPORT_INTERVALS) >= 2  # the package's own, and `models`


def test_a_past_span_lands_in_the_histogram_and_the_tracer_where_it_ended(monkeypatch):
    from sagemaker_xgboost_container_tpu.telemetry.registry import MetricsRegistry

    registry = MetricsRegistry()
    recorded = []
    monkeypatch.setattr(
        tracing, "record_span",
        lambda name, seconds, attributes=None, ended_s_ago=0.0: recorded.append(
            (name, seconds, attributes, ended_s_ago)
        ),
    )
    assert spans.record_past_span("startup.x", 2.5, 0.5, {"a": 1}, registry) == 2.5
    assert spans.record_past_span("startup.x", -1.0, registry=registry) == 0.0  # a clock set back
    assert _phases(registry)["startup.x"] == (2, 2.5)
    assert recorded == [("startup.x", 2.5, {"a": 1}, 0.5), ("startup.x", 0.0, None, 0.0)]


WALL_CASES = [
    # intervals as (thread, start, end) in the order their events arrive; wall seconds
    ("nothing", [], 0.0),
    ("one", [("a", 1.0, 3.0)], 2.0),
    ("empty_and_backwards", [("a", 2.0, 2.0), ("a", 5.0, 4.0)], 0.0),
    ("apart", [("a", 1.0, 2.0), ("a", 5.0, 7.0)], 3.0),
    ("touching", [("a", 1.0, 2.0), ("a", 2.0, 3.0)], 2.0),
    ("two_threads_overlap", [("a", 0.0, 4.0), ("b", 2.0, 6.0)], 6.0),
    ("two_threads_side_by_side", [("a", 0.0, 5.0), ("b", 0.0, 5.0)], 5.0),
    ("inner_traces_end_first", [("a", 1.0, 2.0), ("a", 3.0, 4.0), ("a", 0.0, 5.0)], 5.0),
    ("one_inside_another_later", [("a", 0.0, 10.0), ("b", 3.0, 4.0)], 10.0),
    ("bridges_two", [("a", 0.0, 2.0), ("b", 4.0, 6.0), ("a", 1.0, 5.0)], 6.0),
    ("out_of_order", [("b", 8.0, 9.0), ("a", 0.0, 1.0), ("b", 0.5, 8.5)], 9.0),
]


@pytest.mark.parametrize("case", WALL_CASES, ids=[c[0] for c in WALL_CASES])
def test_wall_seconds_are_the_union_of_the_intervals_over_all_threads(case):
    _name, arrivals, wall = case
    covered, total = (), 0.0
    for _thread, start, end in arrivals:
        before = covered
        covered, added = spans.add_interval(covered, start, end)
        assert added >= 0.0 and before == before[:]  # pure: the old tuple stands
        total += added
        assert all(a[1] < b[0] for a, b in zip(covered, covered[1:])), covered  # disjoint, sorted
    assert total == pytest.approx(wall)
    assert sum(hi - lo for lo, hi in covered) == pytest.approx(wall)
    assert spans.union_seconds((s, e) for _t, s, e in arrivals) == pytest.approx(wall)
    # whatever the order the events come in
    assert spans.union_seconds((s, e) for _t, s, e in reversed(arrivals)) == pytest.approx(wall)


def test_listener_counts_program_wall_once_under_the_outermost_phase(monkeypatch):
    import threading

    def wall():
        return {k: v for k, v in _gauges(REGISTRY, "xla_program_wall_seconds_total").items()}

    monkeypatch.setattr(cluster, "_program_wall", ())
    clock = [100.0]
    monkeypatch.setattr(cluster.time, "perf_counter", lambda: clock[0])
    before = wall()
    key = (("phase", "wall-test"),)

    def load(seconds, ends_at):
        clock[0] = ends_at
        cluster._on_jax_duration_event("/jax/core/compile/jaxpr_to_mlir_module_duration", seconds)

    def on_another_thread():
        with spans.span("wall-test"):
            load(3.0, 104.0)  # 101 to 104, beside the first thread's 100 to 103

    with spans.span("wall-test"):
        with spans.span("inner"):
            load(3.0, 103.0)
        thread = threading.Thread(target=on_another_thread)
        thread.start()
        thread.join()
        load(1.0, 102.0)  # inside both: adds nothing
    assert wall()[key] - before.get(key, 0.0) == pytest.approx(4.0)
    assert (("phase", "wall-test/inner"),) not in wall()  # the outermost span names it


class _Chip:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        if isinstance(self.stats, Exception):
            raise self.stats
        return self.stats


def test_memory_gauges_are_absent_where_the_back_end_reports_no_stats():
    import jax
    from sagemaker_xgboost_container_tpu.telemetry.registry import MetricsRegistry

    registry = MetricsRegistry()
    silent = [_Chip(None), _Chip({}), _Chip(RuntimeError("no stats"))]
    assert spans.note_phase_memory("setup.sketch", silent, registry) is None
    assert spans.note_phase_memory("setup.sketch", jax.local_devices(), registry) is None  # the CPU
    assert _gauges(registry, "setup_hbm_bytes") == {}
    chips = [
        _Chip({"bytes_in_use": 5, "peak_bytes_in_use": 9, "bytes_reserved": 1}),
        _Chip(None),
        _Chip({"bytes_in_use": 4, "peak_bytes_in_use": 30, "bytes_reserved": 3}),
    ]
    fullest = spans.note_phase_memory("setup.sketch", chips, registry)
    assert fullest == {"in_use": 4, "peak": 30, "reserved": 3}  # by what is taken now
    assert _gauges(registry, "setup_hbm_bytes") == {
        (("phase", "setup.sketch"), ("what", what)): value for what, value in fullest.items()
    }


def test_a_cpu_session_reads_memory_five_times_and_sets_no_gauge(monkeypatch):
    from sagemaker_xgboost_container_tpu.models import booster

    read = []
    real = spans.note_phase_memory
    monkeypatch.setattr(
        booster, "note_phase_memory",
        lambda phase, devices: read.append((phase, len(list(devices)))) or real(phase, devices),
    )
    _train(rounds=6, k=2)
    assert read == [(phase, 1) for phase in SETUP_ORDER]  # once a phase, one chip
    assert _gauges(REGISTRY, "setup_hbm_bytes") == {}


@pytest.mark.parametrize("blocks", [1, 3])
def test_child_spans_cover_a_shard_of_the_sketch_and_of_the_bin_apply(
    blocks, finished_spans, monkeypatch
):
    from sagemaker_xgboost_container_tpu.data import binning

    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "device")
    rows, columns = 42_000, 6
    if blocks == 3:  # two columns a sketch block, a third of the rows a bin-apply block
        monkeypatch.setattr(
            binning, "DEVICE_BLOCK_BYTES", binning.DEVICE_BYTES_PER_VALUE * rows * 2
        )
    rng = np.random.RandomState(blocks)
    x = rng.randn(rows, columns).astype(np.float32)
    x[rng.rand(rows) < 0.1, 2] = np.nan
    cuts = binning.sketch_shards([x], [None], 64)
    binned = binning.apply_shards([x], cuts, 64)
    assert np.asarray(binned[0]).shape == x.shape
    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "host")
    assert np.asarray(binned[0]).tobytes() == binning.apply_cut_points(x, cuts, 64).tobytes()

    def seconds(name):
        return [s for n, s, _c in finished_spans if n == name]

    for phase, parts in (
        ("setup.sketch", ("stage", "transfer", "kernel", "fetch")),
        ("setup.bin_apply", ("stage", "transfer", "kernel")),
    ):
        (shard,) = seconds(phase + ".shard")[:1]
        counts = {part: len(seconds(phase + "." + part)) for part in parts}
        # the sketch stages and puts the weights too, the bin-apply puts the cuts
        extra = {"stage": phase == "setup.sketch", "transfer": True}
        assert counts == {part: blocks + extra.get(part, 0) for part in parts}, counts
        covered = sum(sum(seconds(phase + "." + part)) for part in parts)
        assert 0.95 * shard <= covered <= shard, (phase, covered, shard)
        assert all(c for n, _s, c in finished_spans if n.startswith(phase + "."))  # covering


def test_nothing_new_runs_inside_a_dispatch_after_the_first(finished_spans):
    import collections

    _train(rounds=8, k=2)  # four dispatches: the first is set-up, three follow
    names = [n for n, _s, _c in finished_spans]
    first = names.index("setup.first_dispatch")
    # the first dispatch's two halves, once each and inside it
    assert names[first - 4 : first] == [
        "host_dispatch", "setup.first_dispatch.load", "device_sync", "setup.first_dispatch.run",
    ]
    # from there on the spans of a dispatch and of a round are the parent's, by name and count
    assert collections.Counter(names[first + 1 :]) == {
        "host_dispatch": 3, "device_sync": 3, "host_turnaround": 4,
        "commit": 8, "eval_log": 8, "callbacks": 8,
    }
    assert not any(n.startswith(("setup.", "startup.")) for n in names[first + 1 :])


def test_the_collective_is_counted_and_no_span_states_its_estimate(monkeypatch, tmp_path):
    """`collective.dispatch` recorded the calibrated latency of the psum alone
    as a span's duration; the counters and the gauge stay."""
    import jax
    from jax.sharding import Mesh

    recorded = []
    real = tracing.record_span
    monkeypatch.setattr(tracing, "enabled", lambda: True)
    monkeypatch.setattr(
        tracing, "record_span",
        lambda name, *a, **k: recorded.append(name) or real(name, *a, **k),
    )
    _train(mesh=Mesh(np.array(jax.devices()[:2]), ("data",)))
    assert "collective.dispatch" not in recorded
    assert any(
        name == "hist_comm_bytes_total" and any(m.value > 0 for m in fam)
        for name, _k, _h, fam in REGISTRY.collect()
    )
