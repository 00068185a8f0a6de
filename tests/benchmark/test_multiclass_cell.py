"""The multi-class training cell (PR 39), `mnist8m-mc10.train-fused`: its
generator, its configuration, the kind end to end at a tiny size on the CPU
with `correct` true through the reference's softmax half, the files of its
per-layer metrics, and the gauges they read.

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
from benchmark.datagen import mnist8m_like  # noqa: E402
from benchmark.readers import gauge_ratio, program_phase  # noqa: E402

CELL = "mnist8m-mc10.train-fused"
# The configuration's limits are read on the chip at its own size. The CPU
# sums a node's rows one after another in float32: the tiny runs read (four
# seeds) at most 6.1e-4 / 1.4e-3 / 3.0e-3 on the three histogram gaps and
# 1.2e-4 on a median gain, so they take wider limits there; a one-pass
# bfloat16 histogram reads 8e-3 on the first where the float32 one reads 6e-4.
CPU_LIMITS = {
    "direct_hess_err": 2e-3, "direct_hess_err_p90": 5e-3, "direct_hess_err_max": 1e-2,
    "gain_err_median": 1e-3, "leaf_sum_hess_rel": 2e-4, "leaf_value_err": 1e-4, "loss_abs": 8e-5,
}
# ten classes, depth 5, gamma 4 and min_child_weight 6 stay the configuration's
TINY = {
    "train_rows": 4000, "validation_rows": 625, "rounds_per_dispatch": 2,
    "check_limits": CPU_LIMITS,
}


def cell_config():
    _cell, config, _traffic = harness.resolve_cell(harness.load_benchmark(), CELL)
    return config


# ---------------------------------------------------------------- generator
@pytest.mark.parametrize("seed", [7, 2**31 + 39])
def test_generator_makes_whole_pixels_from_the_seed_and_nothing_else(seed):
    config = dict(cell_config(), train_rows=20000, validation_rows=625)
    one, again = mnist8m_like.make(config, seed), mnist8m_like.make(config, seed)
    other = mnist8m_like.make(config, seed + 1)
    x, y = one["train"]
    assert x.shape == (20000, 784) and x.dtype == np.float32 and y.dtype == np.float32
    assert one["validation"][0].shape == (625, 784)
    assert x.tobytes() == again["train"][0].tobytes() and y.tobytes() == again["train"][1].tobytes()
    assert x.tobytes() != other["train"][0].tobytes()
    assert not np.isnan(x).any() and x.min() == 0 and x.max() == 255
    assert (x == np.floor(x)).all()
    assert 0.17 < (x != 0).mean() < 0.22  # MNIST's share of lit pixels
    lit = (x != 0).mean(axis=0)
    # a border that is dark in every row, a ring that is dark in nearly every row
    assert 80 <= (lit == 0).sum() <= 240 and ((lit > 0) & (lit < 1e-2)).sum() >= 60
    counts = np.bincount(y.astype(np.int64), minlength=10) / len(y)
    want = np.asarray(mnist8m_like.CLASS_COUNTS) / 60000.0
    assert set(np.unique(y)) == set(range(10)) and np.abs(counts - want).max() < 0.01


def test_generator_refuses_another_width_or_class_count():
    config = cell_config()
    with pytest.raises(ValueError):
        mnist8m_like.make(dict(config, num_feature=28), 1)
    with pytest.raises(ValueError):
        mnist8m_like.make(dict(config, params=dict(config["params"], num_class=7)), 1)


def test_every_class_shows_in_several_columns_and_flipped_strokes_overlap_them():
    """A depth-5 tree has something to find and something left over."""
    table = mnist8m_like._picture_table().reshape(128, mnist8m_like.PLACEMENTS, 784)
    centred = table[:, mnist8m_like.PLACEMENTS // 2]
    glyphs = centred[list(mnist8m_like.GLYPHS)] > 0
    for a in range(10):
        for b in range(a + 1, 10):
            assert (glyphs[a] != glyphs[b]).sum() >= 12, (a, b)  # a stroke less its corners
    # an 8 with its middle stroke dark is a 0's picture
    eight, zero = mnist8m_like.GLYPHS[8], mnist8m_like.GLYPHS[0]
    assert eight ^ (1 << 6) == zero


# ------------------------------------------------------------ configuration
def test_configuration_states_the_published_set_its_share_and_cuts_no_width():
    bench = harness.load_benchmark()
    entry = {c["name"]: c for c in bench["configs"]}["mnist8m-mc10"]
    config = cell_config()
    assert entry["source"] == config["source"] and len(config["source"]) <= 200
    assert entry["reduced"] == config["reduced"] == ["train_rows", "validation_rows"]
    assert (config["published_train_rows"], config["published_validation_rows"]) == (8100000, 10000)
    shards = config["cluster"]["mesh"]["data"]
    assert config["cluster"]["slice"] == "v5e-16" and config["cluster"]["chips_run"] == 1
    assert config["train_rows"] == -(-8100000 // shards) == 506250
    assert config["validation_rows"] == -(-10000 // shards) == 625
    params = config["params"]
    assert config["num_feature"] == 784 and config["generator"] == "mnist8m_like"
    assert (params["objective"], params["num_class"], params["eval_metric"]) == (
        "multi:softmax", 10, "mlogloss",
    )
    assert (params["max_depth"], params["eta"], params["gamma"], params["min_child_weight"]) == (
        5, 0.2, 4, 6,
    )
    assert params["max_bin"] == 256 and config["rounds_per_dispatch"] in (4, 8)
    assert set(config["check_limits"]) == set(CPU_LIMITS)
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("mnist8m-mc10", "train-fused", 1)


# ------------------------------------------------------------------ the kind
def test_multiclass_cell_prints_one_well_formed_correct_line(capsys):
    rc = harness.run_cell(CELL, 2**31 + 39, 0.3, False, 0.0, shrink=TINY)
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    assert rc == 0 and line["correct"] is True, out
    assert set(line["metrics"]) == {"train_rounds_per_s", "setup_s"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    # the reference's softmax half judged ten trees a round
    assert any(o.startswith("check loss_abs: value=") for o in out)
    assert any(o.startswith("check gain_err_median: value=") for o in out)


def test_a_gain_stored_with_gamma_taken_off_is_not_correct():
    """The reference recomputes a split's gain from its own sums: a forest
    that stores it less gamma (as the program did before PR 39) misses
    `gain_err_median` by four orders."""
    from benchmark.kinds import train_window
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix

    config = dict(cell_config(), **TINY)
    x, y = mnist8m_like.make(config, 11)["train"]
    k = 2
    window = train_window.WindowCallback(k, 1, 0.0, train_window._CompileCounter())
    forest = models.train(
        dict(config["params"], _rounds_per_dispatch=k), DataMatrix(x, labels=y),
        num_boost_round=k, evals=[(DataMatrix(x, labels=y), "train")],
        callbacks=[window], verbose_eval=False,
    )
    checks = train_window.judge(forest, window.evals_log, config, x, y, k, 0)
    assert all(c["ok"] for c in checks), checks
    gamma = np.float32(config["params"]["gamma"])
    stopped_early = 0
    for tree in forest.trees:
        internal = np.asarray(tree.left) >= 0
        stopped_early += int(internal.sum() < 2 ** config["params"]["max_depth"] - 1)
        tree.gain = np.where(internal, np.asarray(tree.gain) - gamma, tree.gain).astype(np.float32)
    assert stopped_early >= 10  # gamma and min_child_weight are live in these trees
    checks = {c["name"]: c for c in train_window.judge(forest, window.evals_log, config, x, y, k, 0)}
    assert not checks["gain_err_median"]["ok"], checks


def test_a_program_that_stores_the_gain_less_gamma_leaves_at_once(monkeypatch):
    """The parent of PR 39 under this cell's files: the generator asks the
    program for the stored gain of one known split and makes no data."""
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import tree_build

    assert mnist8m_like._stored_gain_of_a_known_split() == pytest.approx(3.2)
    real = tree_build.build_tree

    def as_the_parent_stored_it(*args, gamma=0.0, **kwargs):
        tree, row_out = real(*args, gamma=gamma, **kwargs)
        tree["gain"] = jnp.where(tree["gain"] > 0, tree["gain"] - gamma, tree["gain"])
        return tree, row_out

    monkeypatch.setattr(tree_build, "build_tree", as_the_parent_stored_it)
    monkeypatch.setattr(mnist8m_like, "_picture_table", lambda: pytest.fail("no data is made"))
    with pytest.raises(SystemExit) as left:
        mnist8m_like.make(dict(cell_config(), **TINY), 9)
    assert "stores a split's gain as 2.700" in str(left.value.code)


# ------------------------------------------------------------ metrics' files
NEW_METRICS = {
    "class_trees_per_round": (
        "trees", "round program", "train_rounds_per_s",
        {"reader": "program_phase", "args": {"metric": "round_class_trees", "reduce": "sum"}},
    ),
    "hist_tiles_latched_per_round": (
        "tiles", "level histogram kernel", "train_rounds_per_s",
        {"reader": "program_phase",
         "args": {"metric": "hist_onehot_tiles_per_round", "reduce": "sum"}},
    ),
    "constant_columns_pct": (
        "%", "train set-up in front of the first round", "setup_s",
        {"reader": "gauge_ratio",
         "args": {"over": "train_columns_constant", "under": "train_columns_total",
                  "scale": 100.0}},
    ),
}


def test_new_metrics_have_their_entries_files_and_readers():
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW_METRICS)
    for metric, (unit, layer, moves, spec) in NEW_METRICS.items():
        entry = entries[metric]
        assert harness.load_json(ROOT, "benchmark", "layer_metrics", metric + ".json") == spec
        assert entry["workloads"] == [CELL] and entry["source"] == "program_counter"
        assert (entry["unit"], entry["layer"], entry["moves"]) == (unit, layer, moves)
        assert entry["better"] == "lower" and callable(harness.load_reader(metric)[0])
    # the cell reports what every training cell reports, and `grad` besides
    e2e = {m["name"] for m in harness.cell_metrics(bench, "end_to_end", CELL)}
    assert e2e == {"train_rounds_per_s", "setup_s"}
    reported = {m["name"] for m in harness.cell_metrics(bench, "per_layer", CELL, e2e)}
    everywhere = {
        m["name"] for m in bench["per_layer"]
        if {"higgs-d8.train-fused", "criteo-tb-d8-host4.train-fused-mesh",
            "mslr-ndcg.train-fused-grouped", "criteo-tb-d8.train-fused"} <= set(m["workloads"])
    }
    assert len(everywhere) == 20 and everywhere <= reported
    assert reported == everywhere | {"grad_ms_per_round"} | set(NEW_METRICS)
    for m in bench["per_layer"]:
        if CELL in m["workloads"] and m["name"] not in NEW_METRICS:
            assert m["workloads"][-1] == CELL  # appended, nothing moved


# ------------------------------------------------------- the gauges they read
def _session(params, x, y):
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models.booster import TrainConfig, _TrainingSession
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    cfg = TrainConfig(dict({"max_depth": 3, "max_bin": 32}, **params))
    forest = Forest(objective_name=cfg.objective, objective_params=cfg.objective_params,
                    base_score=cfg.base_score, num_feature=x.shape[1])
    return _TrainingSession(cfg, DataMatrix(x, labels=y), [], forest)


def _gauge(name):
    found = program_phase.series(name)
    return found[0].value if found else None


@pytest.mark.parametrize(
    "params, trees",
    [
        ({"objective": "multi:softmax", "num_class": 10}, 10),
        ({"objective": "multi:softprob", "num_class": 10, "num_parallel_tree": 2}, 20),
        ({"objective": "binary:logistic"}, 1),
    ],
    ids=["ten_classes", "ten_classes_two_parallel", "binary"],
)
def test_session_says_its_trees_a_round_and_its_constant_columns(params, trees):
    """`round_class_trees` is classes x num_parallel_tree (a binary session
    reads 1: nothing of a ten-class session's is left standing), and the
    constant columns are counted from the bins: one value in every row."""
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    config = dict(cell_config(), train_rows=600, validation_rows=8)
    x, y = mnist8m_like.make(config, 3)["train"]
    x = x.copy()
    x[:, 5] = 7.0                  # one value that is not zero: constant all the same
    x[:300, 6] = 9.0               # two values: not constant
    constant = int((x == x[0]).all(axis=0).sum())
    assert 100 < constant < 784 - 300
    if trees == 1:
        y = (y > 4).astype(np.float32)
    REGISTRY.reset()
    try:
        assert _gauge("round_class_trees") is None
        assert gauge_ratio.read({}, NEW_METRICS["constant_columns_pct"][3]["args"]) is None
        _session(params, x, y)
        assert _gauge("round_class_trees") == trees
        assert _gauge("train_columns_total") == 784
        assert _gauge("train_columns_constant") == constant
        assert gauge_ratio.read(
            {}, NEW_METRICS["constant_columns_pct"][3]["args"]
        ) == pytest.approx(100.0 * constant / 784)
    finally:
        REGISTRY.reset()


@pytest.mark.parametrize("depth, subtract", [(5, True), (5, False), (8, True)])
def test_ten_class_trees_latch_ten_times_the_one_tree_plan(depth, subtract):
    """Under the class `vmap` every class latches the level's one-hot tiles
    again: the plan of a ten-tree round is ten one-tree plans, to the tile."""
    from sagemaker_xgboost_container_tpu.ops.histogram import (
        resolve_hist_knobs, round_hist_levels, round_onehot_tiles,
    )

    levels = round_hist_levels("depthwise", depth, 0, subtract)
    prec = resolve_hist_knobs().precision
    one = round_onehot_tiles(levels, 506250, 784, 257, prec)
    ten = round_onehot_tiles(levels, 506250, 784, 257, prec, trees_per_round=10)
    assert one[0] > 0 and one[0] < one[1]  # the fold engages on the narrow levels
    assert ten == (10 * one[0], 10 * one[1])
