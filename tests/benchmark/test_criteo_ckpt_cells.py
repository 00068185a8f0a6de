"""PR 32's cells on the CPU at a tiny size: the `criteo_like` generator, the
`criteo-tb-d8.train-fused` cell through the harness, `higgs-d8.train-ckpt`
(the configuration `higgs-d8` under the traffic `train-ckpt`; its files are
here, its entry in `BENCHMARK.json` waits on a steadier host path: PERF.md
section 7) through its kind, and the faults each has to catch.

No module-level jax or topology calls: jax is imported inside the tests.
"""

import json

import numpy as np
import pytest

from test_harness import TINY_TRAIN, cell_context, last_line, tiny_params  # noqa: E402 (sets sys.path)

from benchmark import harness  # noqa: E402
from benchmark.datagen import criteo_like  # noqa: E402
from benchmark.kinds import train_window, train_window_ckpt  # noqa: E402
from benchmark.readers import gauge_ratio, program_phase  # noqa: E402

CRITEO = "criteo-tb-d8.train-fused"
CKPT = "higgs-d8.train-ckpt"
NEW_METRICS = {  # None: the metric's file is here, its cell is not in BENCHMARK.json yet
    "missing_cells_pct": CRITEO,
    "sketch_cut_fill_pct": CRITEO,
    "ckpt_save_ms_per_round": None,
    "ckpt_write_ms_per_round": None,
}


def tiny(workload, **more):
    _cell, config, _traffic = harness.resolve_cell(harness.load_benchmark(), workload)
    return dict(TINY_TRAIN, params=tiny_params(config), **more)


def ckpt_context(seed, seconds=1.0):
    """The context `harness.run_cell` would hand the kind, at a tiny size."""
    config = harness.load_json(harness.HERE, "configs", "higgs-d8.json")
    config.update(TINY_TRAIN, params=tiny_params(config))
    return {
        "cell": {"name": CKPT, "config": "higgs-d8", "traffic": "train-ckpt", "chips": 1},
        "config": config,
        "traffic": harness.load_json(harness.HERE, "traffic", "train-ckpt.json"),
        "seed": seed, "seconds": seconds, "trace": False, "t_process_start": 0.0,
    }


# ------------------------------------------------------------------ generator
@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_criteo_like_is_a_function_of_the_seed_alone(monkeypatch, seed):
    config = {"train_rows": 5000, "validation_rows": 1200, "num_feature": 39}
    monkeypatch.setattr(criteo_like, "ROW_CHUNK", 1024)
    first = criteo_like.make(config, seed)
    monkeypatch.setattr(criteo_like, "THREADS", 1)
    again = criteo_like.make(config, seed)
    other = criteo_like.make(config, seed + 1)
    for name, rows in (("train", 5000), ("validation", 1200)):
        x, y = first[name]
        assert x.shape == (rows, 39) and x.dtype == np.float32 and y.shape == (rows,)
        assert x.tobytes() == again[name][0].tobytes() and y.tobytes() == again[name][1].tobytes()
        assert x.tobytes() != other[name][0].tobytes()
    with pytest.raises(ValueError):
        criteo_like.make(dict(config, num_feature=28), seed)


def test_criteo_like_meets_its_stated_rates_cardinalities_and_click_rate():
    n = 400000
    data = criteo_like.make({"train_rows": n, "validation_rows": 1000, "num_feature": 39}, 11)
    x, y = data["train"]
    missing = np.isnan(x)
    rates = missing.mean(axis=0)
    sigma = np.sqrt(criteo_like.MISSING * (1 - criteo_like.MISSING) / n)
    assert (np.abs(rates - criteo_like.MISSING) <= 4 * sigma + 1e-6).all(), rates
    assert abs(missing.mean() - criteo_like.MISSING_SHARE) < 5e-4
    assert 0.135 < criteo_like.MISSING_SHARE < 0.145
    assert abs(y.mean() - criteo_like.CLICK_RATE) < 0.002 and set(np.unique(y)) == {0.0, 1.0}
    counts = x[:, : criteo_like.NUM_COUNTS]
    present = counts[~np.isnan(counts)]
    assert (present >= 0).all() and (present == np.floor(present)).all()
    for c in range(criteo_like.NUM_COUNTS):  # a spike at 0 and 1, and a heavy tail
        col = counts[:, c][~missing[:, c]]
        assert np.median(col) < np.quantile(col, 0.999) / 20
    assert ((counts == 0) | (counts == 1)).sum() > 0.3 * present.size
    for c, card in enumerate(criteo_like.CARDINALITIES, start=criteo_like.NUM_COUNTS):
        col = x[:, c][~missing[:, c]]
        assert col.min() == 0 and col.max() <= card - 1 and (col == np.floor(col)).all()
        distinct = np.unique(col)
        if card <= 200:  # every code of a small column is drawn
            assert distinct.size == card, (c, card, distinct.size)
        else:
            assert distinct.size > min(card, n) // 20
        # frequency-ranked: code 0 is the commonest
        assert (col == 0).sum() >= (col == 1).sum() >= (col == 3).sum()


# ---------------------------------------------------------------- criteo cell
def test_criteo_cell_prints_one_well_formed_correct_line(capsys):
    rc = harness.run_cell(
        CRITEO, 2**31 + 9, 1.0, False, 0.0,
        shrink=tiny(CRITEO, train_rows=20000, validation_rows=3000),
    )
    line, out = last_line(capsys)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rounds_per_s", "setup_s"}
    assert any(o.startswith("check direct_hess_err:") for o in out)


def test_criteo_run_with_nan_rows_misrouted_is_not_correct():
    """The control's fault at a tiny size: every split sends its missing rows
    to the other side than the one the histograms were built for."""
    from sagemaker_xgboost_container_tpu import models

    def misrouting_train(*args, **kwargs):
        forest = models.train(*args, **kwargs)
        for tree in forest.trees:
            tree.default_left = ~np.asarray(tree.default_left, bool)
        return forest

    ctx = cell_context(CRITEO, 31)
    ctx["config"].update(tiny(CRITEO, train_rows=20000, validation_rows=3000))
    sound = train_window.run(dict(ctx))
    assert all(c["ok"] for c in sound["checks"]), sound["checks"]
    broken = train_window.run(dict(ctx), train_fn=misrouting_train)
    failed = {c["name"] for c in broken["checks"] if not c["ok"]}
    assert "direct_hess_err_max" in failed and "leaf_sum_hess_rel" in failed, broken["checks"]


# ------------------------------------------------------------------ ckpt cell
def test_ckpt_run_is_correct_and_counts_whole_rounds():
    traffic = harness.load_json(harness.HERE, "traffic", "train-ckpt.json")
    assert harness.load_kind(traffic) is train_window_ckpt
    run = train_window_ckpt.run(ckpt_context(2**31 + 10))
    assert all(c["ok"] for c in run["checks"]), run["checks"]
    assert {
        "ckpt_rounds_behind_over_one", "ckpt_rounds_short_of_its_name", "ckpt_trees_differing",
        "loss_abs", "direct_hess_err",
    } <= {c["name"] for c in run["checks"]}
    assert run["attempted"] >= 1 and run["traced_units"] == {"dispatch": 8, "round": 8}
    assert set(run["end_to_end"]) == {"train_rounds_per_s", "setup_s"}


def _train_with(wrap_saver):
    from sagemaker_xgboost_container_tpu import models

    def train_fn(params, dtrain, callbacks=None, **kwargs):
        saver, window = callbacks
        return models.train(params, dtrain, callbacks=[wrap_saver(saver), window], **kwargs)

    return train_fn


class _StaleSaver:
    """Writes, under each round's name, the forest as it stood a round before."""

    def __init__(self, saver):
        self.saver, self.before = saver, None

    def after_iteration(self, forest, rnd, evals_log):
        from sagemaker_xgboost_container_tpu.models.forest import Forest

        stale, self.before = self.before, Forest.load_json(forest.save_json())
        if stale is not None:
            self.saver.after_iteration(stale, rnd, evals_log)
        return False


class _LateSaver:
    """Saves every third round only: the newest file is up to two rounds old."""

    def __init__(self, saver):
        self.saver = saver

    def after_iteration(self, forest, rnd, evals_log):
        if rnd % 3 == 0:
            self.saver.after_iteration(forest, rnd, evals_log)
        return False


class _OtherTreeSaver:
    """Saves a forest whose newest tree has one leaf value off by one ulp."""

    def __init__(self, saver):
        self.saver = saver

    def after_iteration(self, forest, rnd, evals_log):
        from sagemaker_xgboost_container_tpu.models.forest import Forest

        other = Forest.load_json(forest.save_json())
        value = np.array(other.trees[-1].value, np.float32)
        value[-1] = np.nextafter(value[-1], np.float32(np.inf))
        other.trees[-1].value = value
        self.saver.after_iteration(other, rnd, evals_log)
        return False


@pytest.mark.parametrize(
    "saver,fails",
    [
        (_StaleSaver, "ckpt_rounds_short_of_its_name"),
        (_OtherTreeSaver, "ckpt_trees_differing"),
    ],
)
def test_a_checkpoint_that_is_stale_or_differs_is_not_correct(saver, fails):
    run = train_window_ckpt.run(ckpt_context(41), train_fn=_train_with(saver))
    failed = {c["name"] for c in run["checks"] if not c["ok"]}
    assert failed == {fails}, run["checks"]


@pytest.mark.parametrize("rounds,behind_ok", [(4, True), (5, True), (6, False)])
def test_a_checkpoint_more_than_a_round_behind_is_not_correct(tmp_path, rounds, behind_ok):
    """A saver that writes every third round: after 4 rounds the newest file
    holds them all, after 5 it lacks one (allowed), after 6 two (not)."""
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.training.checkpointing import SaveCheckpointCallBack

    rng = np.random.RandomState(0)
    x = rng.randn(500, 4).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.float32)
    saver = SaveCheckpointCallBack(str(tmp_path))
    forest = models.train(
        {"objective": "binary:logistic", "max_depth": 2}, DataMatrix(x, labels=y),
        num_boost_round=rounds, callbacks=[_LateSaver(saver)],
    )
    checks = {c["name"]: c for c in train_window_ckpt.judge_checkpoint(str(tmp_path), forest, rounds)}
    assert checks["ckpt_rounds_behind_over_one"]["ok"] is behind_ok, checks
    assert checks["ckpt_trees_differing"]["ok"] and checks["ckpt_rounds_short_of_its_name"]["ok"]
    empty = tmp_path / "empty"
    empty.mkdir()
    (lost,) = train_window_ckpt.judge_checkpoint(str(empty), forest, rounds)
    assert not lost["ok"] and lost["value"] == rounds


def test_trees_differing_counts_bits_not_values():
    tree = {"value": np.asarray([0.0, -0.0], np.float32), "left": np.asarray([1, -1])}
    same = [[(0, dict(tree))]]
    assert train_window_ckpt.trees_differing(same, [[(0, dict(tree))]]) == 0
    flipped = dict(tree, value=np.asarray([-0.0, -0.0], np.float32))  # equal as numbers
    assert train_window_ckpt.trees_differing(same, [[(0, flipped)]]) == 1
    assert train_window_ckpt.trees_differing(same, [[(1, dict(tree))]]) == 1
    assert train_window_ckpt.trees_differing(same, [[(0, tree), (0, tree)]]) == 2


# -------------------------------------------------------------------- metrics
@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_each_new_metric_has_its_file_its_reader_and_its_entry(metric):
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    cell = NEW_METRICS[metric]
    assert (metric in entries) == (cell is not None)
    if cell is not None:
        e2e = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", cell)}
        assert entries[metric]["workloads"] == [cell] and entries[metric]["moves"] in e2e
    read, args = harness.load_reader(metric)
    assert callable(read) and args
    # a parent without the gauges and spans reports nothing, and does not raise
    assert read({"trace": None}, dict(args, over="no_such_gauge", metric="no_such_metric")) is None


def test_the_new_cell_reports_every_metric_the_first_cell_reports():
    bench = harness.load_benchmark()
    assert CKPT not in {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        if "higgs-d8.train-fused" in m["workloads"]:
            assert m["workloads"][-1] == CRITEO, m["name"]
    rate = {m["name"]: m for m in bench["end_to_end"]}["train_rounds_per_s"]
    assert rate["workloads"][-1] == CRITEO
    config = {c["name"]: c for c in bench["configs"]}["criteo-tb-d8"]
    assert config["reduced"] == ["train_rows", "validation_rows"] and len(config["source"]) <= 200
    file = harness.load_json(harness.ROOT, config["file"])
    assert file["train_rows"] == -(-file["published_train_rows"] // 256) == 16387491
    assert file["validation_rows"] == -(-file["published_validation_rows"] // 256) == 696386
    assert file["cluster"]["mesh"] == {"data": 256} and "v5e-256" in file["deployment"]


def test_checkpoint_spans_feed_the_ckpt_metrics(tmp_path):
    """`ckpt_save_ms_per_round` and `ckpt_write_ms_per_round` read the spans
    `SaveCheckpointCallBack` leaves, the write inside the save."""
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.training.checkpointing import SaveCheckpointCallBack

    def spans(phase):
        found = program_phase.series("training_phase_seconds", {"phase": phase})
        return program_phase.totals(found)

    before = {phase: spans(phase) for phase in ("checkpoint.save", "checkpoint.write")}
    rng = np.random.RandomState(1)
    x = rng.randn(300, 3).astype(np.float32)
    models.train(
        {"objective": "binary:logistic", "max_depth": 2},
        DataMatrix(x, labels=(x[:, 0] > 0).astype(np.float32)),
        num_boost_round=3, callbacks=[SaveCheckpointCallBack(str(tmp_path))],
    )
    save, write = (
        tuple(np.subtract(spans(phase), before[phase]))
        for phase in ("checkpoint.save", "checkpoint.write")
    )
    assert save[1] == write[1] == 3 and 0 < write[0] <= save[0]
    for metric, phase in (("ckpt_save_ms_per_round", "checkpoint.save"),
                          ("ckpt_write_ms_per_round", "checkpoint.write")):
        _read, args = harness.load_reader(metric)
        assert args["where"] == {"phase": phase} and args["reduce"] == "mean"
        assert args["scale"] == 1000.0


def test_set_up_gauges_say_what_the_binned_matrix_holds():
    """`missing_cells_pct` and `sketch_cut_fill_pct` over the program's own
    gauges after a tiny training session on click-log columns."""
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix

    data = criteo_like.make({"train_rows": 4000, "validation_rows": 10, "num_feature": 39}, 3)
    x, y = data["train"]
    models.train(
        {"objective": "binary:logistic", "max_depth": 2, "max_bin": 256},
        DataMatrix(x, labels=y), num_boost_round=1,
    )
    _read, args = harness.load_reader("missing_cells_pct")
    assert gauge_ratio.read({}, args) == pytest.approx(100.0 * np.isnan(x).mean(), abs=1e-9)
    _read, args = harness.load_reader("sketch_cut_fill_pct")
    fill = gauge_ratio.read({}, args)
    slots = program_phase.series("sketch_cut_slots")[0].value
    assert slots == 39 * 255 and 5.0 < fill < 60.0
    # C6 has three values and NaN: two of its 255 slots are cuts
    assert json.dumps(fill) and program_phase.series("sketch_cuts_selected")[0].value < slots
