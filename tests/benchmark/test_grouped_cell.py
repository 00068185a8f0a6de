"""The grouped training cell (PR 28): its generator, its plain reference, its
kind end to end at a tiny size on the CPU with `correct` true, two faults
that must read `correct: false`, and the files of its per-layer metrics.

No module-level jax or topology calls: jax is imported inside the tests.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.datagen import mslr_like  # noqa: E402
from benchmark.readers import gauge_ratio  # noqa: E402
from benchmark.reference import lambdamart_reference  # noqa: E402

CELL = "mslr-ndcg.train-fused-grouped"
OTHER = "higgs-d8.train-fused"
# The configuration's limits are read on the chip at its own size. The tiny
# CPU runs read (five seeds) at most 7.3e-6 / 1.3e-5 / 1.4e-5 on the three
# histogram gaps, 1.8e-7 on a median gain, 1.4e-6 and 1.8e-6 on a deepest
# leaf and 7e-8 on the metric; the two faults below read 0.46 and 6,850,
# 1.5e-3 and 15, 0.054 and 474, 0.028 and 6.8, and the truncated group 0.0197
# on the metric.
CPU_LIMITS = {
    "direct_hess_err": 1e-4, "direct_hess_err_p90": 2e-4, "direct_hess_err_max": 2e-4,
    "gain_err_median": 1e-5, "leaf_sum_hess_rel": 2e-5, "leaf_value_err": 2e-5,
    "ndcg_abs": 2e-6,
}
TINY = {
    "train_rows": 6000, "validation_rows": 2500, "train_groups": 50, "validation_groups": 20,
    "max_group_size": 300, "rounds_per_dispatch": 2, "check_limits": CPU_LIMITS,
}


def tiny_config(config):
    return dict(config, **TINY, params=dict(config["params"], max_depth=3, min_child_weight=1))


def cell_context(seed, seconds=0.3):
    cell, config, traffic = harness.resolve_cell(harness.load_benchmark(), CELL)
    return {
        "cell": cell, "config": tiny_config(config), "traffic": traffic, "seed": seed,
        "seconds": seconds, "trace": False, "t_process_start": 0.0,
    }


def failed(run):
    return sorted(c["name"] for c in run["checks"] if not c["ok"])


# ---------------------------------------------------------------- generator
def test_generator_meets_the_published_totals_and_sizes_exactly():
    _cell, config, _traffic = harness.resolve_cell(harness.load_benchmark(), CELL)
    assert (config["train_rows"], config["train_groups"]) == (2270296, 18919)
    assert (config["validation_rows"], config["validation_groups"]) == (753611, 6306)
    assert config["num_feature"] == 136 and config["max_group_size"] == 1251
    assert config["reduced"] == [] and config["rounds_per_dispatch"] == 8
    for rows, groups, stream in ((2270296, 18919, 0), (753611, 6306, 1)):
        sizes = mslr_like.group_sizes(groups, rows, 1251, 3000028001, stream)
        assert len(sizes) == groups and int(sizes.sum()) == rows
        assert sizes.min() == 1 and sizes.max() == 1251
        # about 0.5 G pairs over the 2.27M training documents: 220 a document
        assert 150 < float(np.square(sizes.astype(np.float64)).sum()) / rows < 300


@pytest.mark.parametrize("seed", [5, 2**31 + 17])
def test_generator_is_a_function_of_the_seed(seed):
    config = dict(TINY, num_feature=136)
    one, again = mslr_like.make(config, seed), mslr_like.make(config, seed)
    other = mslr_like.make(config, seed + 1)
    for name, rows, groups in (("train", 6000, 50), ("validation", 2500, 20)):
        x, y, sizes = one[name]
        assert x.shape == (rows, 136) and x.dtype == np.float32 and not np.isnan(x).any()
        assert len(sizes) == groups and int(sizes.sum()) == rows and sizes.max() == 300
        assert set(np.unique(y)) <= {0.0, 1.0, 2.0, 3.0, 4.0}
        for a, b in zip(one[name], again[name]):
            assert np.array_equal(a, b)
        assert not np.array_equal(x, other[name][0])
    x, y, _sizes = one["train"]
    shares = np.bincount(y.astype(int), minlength=5) / len(y)
    assert 0.45 < shares[0] < 0.6 and 0.25 < shares[1] < 0.4 and shares[4] < 0.03
    # counts with few distinct values beside continuous scores
    assert len(np.unique(x[:, 0])) <= 13 and len(np.unique(x[:, 95])) == 2
    assert len(np.unique(x[:, 15])) > 5000


def test_generator_refuses_totals_no_sizes_can_meet():
    with pytest.raises(ValueError):
        mslr_like.group_sizes(10, 400, 1251, 1, 0)  # one group of 1,251 needs more rows
    with pytest.raises(ValueError):
        mslr_like.group_sizes(3, 5000, 1251, 1, 0)


# ---------------------------------------------------------------- reference
def test_reference_gradient_is_the_sum_over_pairs_written_out():
    score = np.asarray([0.5, 0.5, -1.0, 2.0], np.float32)  # a tie: position breaks it
    label = np.asarray([1.0, 3.0, 0.0, 1.0])
    g, h = lambdamart_reference.group_grad_hess(score, label)
    ranks = [2, 3, 4, 1]
    assert lambdamart_reference.ranks_descending(score).tolist() == ranks
    gain = 2.0 ** label - 1.0
    max_dcg = sum(v / np.log2(i + 2) for i, v in enumerate(sorted(gain, reverse=True)))
    want_g, want_h = np.zeros(4), np.zeros(4)
    for i in range(4):
        for j in range(4):
            if label[i] > label[j]:
                rho = 1.0 / (1.0 + np.exp(float(score[i]) - float(score[j])))
                w = abs(gain[i] - gain[j]) * abs(
                    1 / np.log2(1 + ranks[i]) - 1 / np.log2(1 + ranks[j])
                ) / max_dcg
                want_g[i] -= rho * w
                want_g[j] += rho * w
                want_h[i] += rho * (1 - rho) * w
                want_h[j] += rho * (1 - rho) * w
    np.testing.assert_allclose(g, want_g, rtol=1e-12)
    np.testing.assert_allclose(h, want_h, rtol=1e-12)
    assert abs(g.sum()) < 1e-12  # every pair pulls one document up and one down


def test_reference_ndcg_counts_a_group_without_relevance_as_one():
    margin = np.asarray([0.1, 0.9, 0.5, 0.3, 0.2], np.float32)
    label = np.asarray([0.0, 0.0, 2.0, 0.0, 1.0])
    sizes = np.asarray([2, 3])
    second = (3.0 / np.log2(2) + 1.0 / np.log2(4)) / (3.0 / np.log2(2) + 1.0 / np.log2(3))
    assert lambdamart_reference.ndcg(margin, label, sizes) == pytest.approx((1.0 + second) / 2)
    assert lambdamart_reference.ndcg(margin, label, sizes, k=1) == pytest.approx(1.0)
    assert lambdamart_reference.metric_k("ndcg@10") == 10
    assert lambdamart_reference.metric_k("ndcg") is None
    with pytest.raises(ValueError):
        lambdamart_reference.metric_k("map@10")


# ------------------------------------------------------------------- the kind
def test_grouped_cell_prints_one_well_formed_correct_line(capsys):
    _cell, config, _traffic = harness.resolve_cell(harness.load_benchmark(), CELL)
    shrink = {k: v for k, v in tiny_config(config).items() if k in TINY or k == "params"}
    rc = harness.run_cell(CELL, 2**31 + 5, 0.3, False, 0.0, shrink=shrink)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is True, out
    assert set(line["metrics"]) == {"train_rounds_per_s", "setup_s"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    for name in ("ndcg_abs", "direct_hess_err", "metric_lines_missing", "metric_not_rising"):
        assert any(o.startswith("check {}: value=".format(name)) for o in out), name


def test_traced_grouped_run_leaves_out_what_a_cpu_run_cannot_say(capsys):
    _cell, config, _traffic = harness.resolve_cell(harness.load_benchmark(), CELL)
    shrink = {k: v for k, v in tiny_config(config).items() if k in TINY or k == "params"}
    rc = harness.run_cell(CELL, 2**31 + 6, 0.2, True, 0.0, shrink=shrink)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True
    # no device plane in a CPU trace: the host span and the share the program
    # states from its shapes are there, the trace's readers are left out
    assert set(line["metrics"]) == {"train_first_round_s", "rank_pair_fill_pct"}
    assert 33.0 <= line["metrics"]["rank_pair_fill_pct"]["value"] <= 100.0


def test_pairwise_gradients_under_ndcgs_name_are_not_correct():
    from benchmark.kinds import train_window_grouped
    from sagemaker_xgboost_container_tpu import models

    def unweighted(params, *args, **kwargs):  # the pair weight |delta NDCG| dropped
        return models.train(dict(params, objective="rank:pairwise"), *args, **kwargs)

    run = train_window_grouped.run(cell_context(7), train_fn=unweighted)
    assert {"direct_hess_err", "gain_err_median", "leaf_sum_hess_rel", "leaf_value_err"} <= set(
        failed(run)
    )
    assert "ndcg_abs" not in failed(run)  # the metric itself is still the right one


def test_a_truncated_group_is_not_correct(monkeypatch):
    from benchmark.kinds import train_window_grouped
    from sagemaker_xgboost_container_tpu.models import booster
    from sagemaker_xgboost_container_tpu.ops import ranking

    whole = booster.build_group_layout

    def truncating(groups, widths=None):
        """The widest bucket's first group cut to its first 32 documents."""
        layout = whole(groups, widths)
        indices = [index.copy() for index in layout.indices]
        indices[-1][0, 32:] = -1
        slots = ranking._row_slots(indices, int(np.sum(groups)))
        return ranking.GroupLayout(tuple(indices), slots, layout.empty_groups)

    monkeypatch.setattr(booster, "build_group_layout", truncating)
    run = train_window_grouped.run(cell_context(7))
    assert {"leaf_sum_hess_rel", "leaf_value_err", "ndcg_abs"} <= set(failed(run))


def test_a_program_that_logs_once_a_dispatch_is_not_correct():
    from benchmark.kinds import train_window_grouped
    from sagemaker_xgboost_container_tpu import models

    def host_cadence(params, dtrain, **kwargs):
        """A custom metric beside the built-in one keeps evaluation on the
        host: one line a dispatch, as before this PR."""
        return models.train(params, dtrain, feval=lambda margin, dm: [], **kwargs)

    run = train_window_grouped.run(cell_context(8), train_fn=host_cadence)
    assert failed(run) == ["metric_lines_missing"]


def test_a_program_without_the_device_metric_leaves_at_once(monkeypatch):
    from benchmark.kinds import train_window_grouped
    from sagemaker_xgboost_container_tpu.models import device_metrics

    # the parent of PR 28 knows no `ndcg` on the device
    monkeypatch.setattr(device_metrics, "make_device_metric", lambda *a, **k: None)
    monkeypatch.setattr(mslr_like, "make", lambda *a: pytest.fail("no data is made"))
    with pytest.raises(SystemExit) as left:
        train_window_grouped.run(cell_context(9))
    assert "cannot compute ndcg@10 on the device" in str(left.value.code)


# ------------------------------------------------------------ metrics' files
NEW_METRICS = {
    "rank_gather_ms_per_round": ("stage_ms", "rank_gather"),
    "rank_pairs_ms_per_round": ("stage_ms", "rank_pairs"),
    "rank_scatter_ms_per_round": ("stage_ms", "rank_scatter"),
}


def test_new_metrics_have_their_entries_files_and_readers():
    from sagemaker_xgboost_container_tpu.telemetry import device

    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for metric, (reader, stage) in NEW_METRICS.items():
        spec = harness.load_json(ROOT, "benchmark", "layer_metrics", metric + ".json")
        assert spec == {"reader": reader, "args": {"stage": stage, "per": "round"}}
        assert stage in device.STAGES
        assert entries[metric]["workloads"] == [CELL]
        assert entries[metric]["layer"] == "grouped gradient"
        assert entries[metric]["moves"] == "train_rounds_per_s"
    assert entries["rank_pair_fill_pct"]["workloads"] == [CELL]
    assert entries["setup_group_layout_s"]["moves"] == "setup_s"
    # the metrics that every training cell reports name their cells now
    for metric in ("round_device_ms", "hist_kernel_roofline", "device_idle_pct.train"):
        assert entries[metric]["workloads"] == [OTHER, CELL]
    # and no cell reports a per-layer metric that moves what it does not report
    for cell in (OTHER, CELL):
        e2e = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", cell)}
        assert e2e == {"train_rounds_per_s", "setup_s"}
        for m in harness.cell_metrics(bench, "per_layer", cell, e2e):
            assert m["moves"] in e2e and callable(harness.load_reader(m["name"])[0])


def test_gauge_ratio_reads_the_programs_gauges_and_nothing_where_there_are_none():
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    args = harness.load_json(ROOT, "benchmark", "layer_metrics", "rank_pair_fill_pct.json")["args"]
    REGISTRY.reset()
    try:
        assert gauge_ratio.read({}, args) is None  # a parent without the gauges
        REGISTRY.gauge("rank_pairs_real", "").set(30.0)
        assert gauge_ratio.read({}, args) is None
        REGISTRY.gauge("rank_pair_slots", "").set(0.0)
        assert gauge_ratio.read({}, args) is None
        REGISTRY.gauge("rank_pair_slots", "").set(80.0)
        assert gauge_ratio.read({}, args) == pytest.approx(37.5)
    finally:
        REGISTRY.reset()
