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
