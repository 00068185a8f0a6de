"""Booster-core tests: the XLA tree builder learns and predicts correctly.

Strategy (no xgboost in the image): property tests — training loss decreases
monotonically-ish, the model beats a constant predictor by a wide margin on
learnable synthetic data, missing-value routing works, multi-class learns,
and the forest JSON round-trips through save/load with identical predictions.
"""

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import Forest, train
from sagemaker_xgboost_container_tpu.models.eval_metrics import evaluate as eval_metric


def _friedman(n=2000, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 5).astype(np.float32)
    y = (
        10 * np.sin(np.pi * X[:, 0] * X[:, 1])
        + 20 * (X[:, 2] - 0.5) ** 2
        + 10 * X[:, 3]
        + 5 * X[:, 4]
        + rng.randn(n) * 0.1
    ).astype(np.float32)
    return X, y


def test_regression_learns():
    X, y = _friedman()
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {"eta": "0.3", "max_depth": 5, "objective": "reg:squarederror"},
        dtrain,
        num_boost_round=30,
        evals=[(dtrain, "train")],
    )
    preds = forest.predict(X)
    base_rmse = float(np.sqrt(np.mean((y - y.mean()) ** 2)))
    model_rmse = eval_metric("rmse", preds, y)
    assert model_rmse < 0.15 * base_rmse, (model_rmse, base_rmse)


def test_training_loss_decreases():
    X, y = _friedman(800)
    dtrain = DataMatrix(X, labels=y)
    log = {}

    class Recorder:
        def after_iteration(self, model, epoch, evals_log):
            log.update(evals_log)
            return False

    train(
        {"eta": 0.3, "max_depth": 4},
        dtrain,
        num_boost_round=15,
        evals=[(dtrain, "train")],
        callbacks=[Recorder()],
    )
    series = log["train"]["rmse"]
    assert series[-1] < series[0] * 0.3
    assert all(b <= a * 1.05 for a, b in zip(series, series[1:]))


def test_binary_logistic():
    rng = np.random.RandomState(1)
    X = rng.randn(2000, 4).astype(np.float32)
    y = ((X[:, 0] + X[:, 1] * X[:, 2]) > 0).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3},
        dtrain,
        num_boost_round=25,
    )
    p = forest.predict(X)
    assert ((p > 0.5) == y).mean() > 0.93
    assert 0 < p.min() and p.max() < 1
    assert eval_metric("auc", p, y) > 0.97


def test_multiclass_softprob():
    rng = np.random.RandomState(2)
    X = rng.randn(1500, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)  # 3 classes
    dtrain = DataMatrix(X, labels=y.astype(np.float32))
    forest = train(
        {"objective": "multi:softprob", "num_class": 3, "max_depth": 4, "eta": 0.3},
        dtrain,
        num_boost_round=15,
    )
    prob = forest.predict(X)
    assert prob.shape == (1500, 3)
    np.testing.assert_allclose(prob.sum(axis=1), 1.0, atol=1e-5)
    assert (prob.argmax(axis=1) == y).mean() > 0.9


def test_missing_values_route_consistently():
    rng = np.random.RandomState(3)
    X = rng.randn(1200, 3).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32) * 2.0
    X_missing = X.copy()
    miss_mask = rng.rand(1200, 3) < 0.3
    X_missing[miss_mask] = np.nan
    dtrain = DataMatrix(X_missing, labels=y)
    forest = train({"max_depth": 4}, dtrain, num_boost_round=20)
    # train/serve consistency: binned training predictions == float predictions
    preds = forest.predict(X_missing)
    rmse = eval_metric("rmse", preds, y)
    assert rmse < 0.5


def test_json_roundtrip_prediction_identity():
    X, y = _friedman(500)
    dtrain = DataMatrix(X, labels=y)
    forest = train({"max_depth": 4}, dtrain, num_boost_round=8)
    blob = forest.save_json()
    loaded = Forest.load_json(blob)
    np.testing.assert_allclose(loaded.predict(X), forest.predict(X), rtol=1e-6)
    assert loaded.num_boosted_rounds == 8


def test_json_schema_shape():
    import json

    X, y = _friedman(300)
    forest = train({"max_depth": 3}, DataMatrix(X, labels=y), num_boost_round=2)
    doc = json.loads(forest.save_json())
    learner = doc["learner"]
    assert learner["objective"]["name"] == "reg:squarederror"
    trees = learner["gradient_booster"]["model"]["trees"]
    assert len(trees) == 2
    t = trees[0]
    n = int(t["tree_param"]["num_nodes"])
    for key in (
        "base_weights",
        "default_left",
        "left_children",
        "right_children",
        "loss_changes",
        "parents",
        "split_conditions",
        "split_indices",
        "sum_hessian",
    ):
        assert len(t[key]) == n, key
    # leaves marked with -1 children
    assert -1 in t["left_children"]


def test_resume_from_checkpoint(tmp_path):
    X, y = _friedman(600)
    dtrain = DataMatrix(X, labels=y)
    full = train({"max_depth": 4, "seed": 7}, dtrain, num_boost_round=10)
    half = train({"max_depth": 4, "seed": 7}, dtrain, num_boost_round=5)
    path = str(tmp_path / "ckpt.json")
    half.save_model(path)
    resumed = train({"max_depth": 4, "seed": 7}, dtrain, num_boost_round=5, xgb_model=path)
    assert resumed.num_boosted_rounds == 10
    # resumed model should be close to the full run (same greedy path)
    p_full, p_res = full.predict(X), resumed.predict(X)
    assert eval_metric("rmse", p_res, y) < eval_metric("rmse", half.predict(X), y)


def test_early_stopping_callback():
    X, y = _friedman(500)
    dtrain = DataMatrix(X, labels=y)

    class StopAt3:
        def after_iteration(self, model, epoch, evals_log):
            return epoch >= 2

    forest = train({"max_depth": 3}, dtrain, num_boost_round=50, callbacks=[StopAt3()])
    assert forest.num_boosted_rounds == 3


def test_weights_influence_training():
    rng = np.random.RandomState(4)
    X = rng.randn(1000, 2).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    w = np.where(y == 1, 10.0, 0.1).astype(np.float32)
    dtrain = DataMatrix(X, labels=y, weights=w)
    forest = train(
        {"objective": "binary:logistic", "max_depth": 3}, dtrain, num_boost_round=10
    )
    p = forest.predict(X)
    # heavily weighting positives pushes average prediction up
    assert p.mean() > 0.5


def test_gamma_pruning_reduces_tree_size():
    X, y = _friedman(800)
    dtrain = DataMatrix(X, labels=y)
    small = train({"max_depth": 6, "gamma": 1000.0}, dtrain, num_boost_round=3)
    big = train({"max_depth": 6, "gamma": 0.0}, dtrain, num_boost_round=3)
    assert sum(t.num_nodes for t in small.trees) < sum(t.num_nodes for t in big.trees)


def test_monotone_constraint_enforced():
    rng = np.random.RandomState(5)
    X = rng.rand(1500, 1).astype(np.float32)
    y = (np.sin(X[:, 0] * 6) + X[:, 0]).astype(np.float32)  # non-monotone signal
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {"max_depth": 4, "monotone_constraints": (1,), "tree_method": "hist"},
        dtrain,
        num_boost_round=10,
    )
    grid = np.linspace(0, 1, 200, dtype=np.float32).reshape(-1, 1)
    preds = forest.predict(grid)
    assert (np.diff(preds) >= -1e-5).all()


def test_monotone_constraint_enforced_lossguide():
    """Monotonicity must hold under best-first growth too (the constraint
    threads through every candidate-store refresh)."""
    rng = np.random.RandomState(5)
    X = rng.rand(1500, 1).astype(np.float32)
    y = (np.sin(X[:, 0] * 6) + X[:, 0]).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {
            "grow_policy": "lossguide",
            "max_leaves": 16,
            "max_depth": 0,
            "monotone_constraints": (1,),
            "tree_method": "hist",
        },
        dtrain,
        num_boost_round=10,
    )
    grid = np.linspace(0, 1, 200, dtype=np.float32).reshape(-1, 1)
    preds = forest.predict(grid)
    assert (np.diff(preds) >= -1e-5).all()


def test_subsample_and_colsample_still_learn():
    X, y = _friedman(1500)
    dtrain = DataMatrix(X, labels=y)
    forest = train(
        {"max_depth": 4, "subsample": 0.7, "colsample_bytree": 0.8, "seed": 9},
        dtrain,
        num_boost_round=25,
    )
    rmse = eval_metric("rmse", forest.predict(X), y)
    assert rmse < 1.5


def test_poisson_objective():
    rng = np.random.RandomState(6)
    X = rng.rand(1200, 3).astype(np.float32)
    lam = np.exp(X[:, 0] * 2)
    y = rng.poisson(lam).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    forest = train({"objective": "count:poisson", "max_depth": 3}, dtrain, num_boost_round=20)
    p = forest.predict(X)
    assert (p > 0).all()
    assert np.corrcoef(p, lam)[0, 1] > 0.9


def test_ubjson_save_roundtrip(tmp_path):
    from sagemaker_xgboost_container_tpu.models.compat import load_model_any_format

    X, y = _friedman(300)
    forest = train({"max_depth": 3}, DataMatrix(X, labels=y), num_boost_round=3)
    path = str(tmp_path / "model.ubj")
    forest.save_model(path)
    with open(path, "rb") as f:
        assert f.read(1) == b"{"  # UBJ object marker, not JSON text
    loaded, fmt = load_model_any_format(path)
    np.testing.assert_allclose(loaded.predict(X), forest.predict(X), rtol=1e-6)


def test_feature_importance():
    rng = np.random.RandomState(8)
    X = rng.rand(800, 4).astype(np.float32)
    # feature 2 carries nearly all signal
    y = (X[:, 2] * 10 + X[:, 0] * 0.5).astype(np.float32)
    forest = train({"max_depth": 4}, DataMatrix(X, labels=y), num_boost_round=10)
    weight = forest.get_score("weight")
    gain = forest.get_score("gain")
    total_gain = forest.get_score("total_gain")
    assert max(total_gain, key=total_gain.get) == "f2"
    assert weight["f2"] >= 1
    assert set(gain) <= {"f0", "f1", "f2", "f3"}
    # invalid type rejected
    from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc

    with pytest.raises(exc.UserError):
        forest.get_score("nope")


def test_get_dump_format():
    rng = np.random.RandomState(9)
    X = rng.rand(300, 3).astype(np.float32)
    y = (X[:, 1] * 5).astype(np.float32)
    forest = train({"max_depth": 2}, DataMatrix(X, labels=y), num_boost_round=2)
    dumps = forest.get_dump(with_stats=True)
    assert len(dumps) == 2
    first = dumps[0].splitlines()
    assert first[0].startswith("0:[f")
    assert "yes=" in first[0] and "no=" in first[0] and "missing=" in first[0]
    assert any("leaf=" in line for line in first)
    assert "gain=" in first[0] and "cover=" in first[0]


def test_output_margin_and_iteration_range():
    rng = np.random.RandomState(10)
    X = rng.rand(400, 3).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    forest = train(
        {"objective": "binary:logistic", "max_depth": 3}, DataMatrix(X, labels=y),
        num_boost_round=6,
    )
    margin = forest.predict(X, output_margin=True)
    prob = forest.predict(X)
    np.testing.assert_allclose(prob, 1 / (1 + np.exp(-margin)), rtol=1e-5)
    # iteration_range truncates the ensemble (ntree_limit analog)
    m3 = forest.predict_margin(X, iteration_range=(0, 3))
    full = forest.predict_margin(X)
    assert not np.allclose(m3, full)
    # first-3-rounds model == iteration_range(0,3)
    import json

    doc = json.loads(forest.save_json())
    doc["learner"]["gradient_booster"]["model"]["trees"] = doc["learner"][
        "gradient_booster"
    ]["model"]["trees"][:3]
    doc["learner"]["gradient_booster"]["model"]["tree_info"] = [0, 0, 0]
    doc["learner"]["gradient_booster"]["model"]["iteration_indptr"] = [0, 1, 2, 3]
    doc["learner"]["gradient_booster"]["model"]["gbtree_model_param"]["num_trees"] = "3"
    truncated = Forest.load_json(json.dumps(doc))
    np.testing.assert_allclose(truncated.predict_margin(X), m3, rtol=1e-5)


def test_pred_leaf():
    rng = np.random.RandomState(11)
    X = rng.rand(200, 3).astype(np.float32)
    y = (X[:, 0] * 4).astype(np.float32)
    forest = train({"max_depth": 3}, DataMatrix(X, labels=y), num_boost_round=4)
    leaves = forest.predict(X, pred_leaf=True)
    assert leaves.shape == (200, 4)
    assert leaves.dtype == np.int32
    # every reported node is a leaf of its tree
    for t in range(4):
        tree = forest.trees[t]
        assert tree.is_leaf[leaves[:, t]].all()
    # rows with equal features share leaves
    leaves2 = forest.predict(np.vstack([X[0], X[0]]), pred_leaf=True)
    assert (leaves2[0] == leaves2[1]).all()


def test_tree_method_binning_map():
    """tree_method mapping: exact -> data-sized bins (true exact-greedy
    candidate set; max_bin ignored, as xgboost ignores it for exact);
    approx -> bins ~ 1/sketch_eps; explicit max_bin wins for hist."""
    from sagemaker_xgboost_container_tpu.models.booster import TrainConfig

    cfg = TrainConfig({"tree_method": "exact"})
    assert cfg.max_bin is None and cfg.exact_binning
    assert TrainConfig({"tree_method": "exact", "max_bin": 64}).max_bin is None
    assert TrainConfig({"tree_method": "approx", "sketch_eps": 0.01}).max_bin == 100
    assert TrainConfig({}).max_bin == 256


def test_approx_resketch_matches_hist_quality(monkeypatch):
    """tree_method=approx (r5): per-dispatch hessian-weighted
    re-sketch, matching libxgboost's approx candidate refresh. Contract:
    (a) with GRAFT_APPROX_RESKETCH=0 the old single-sketch behavior is
    bit-identical to hist at the same candidate budget; (b) the default
    (re-sketch on) stays in the hist quality band on a fixture."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(9)
    X = rng.rand(3000, 6).astype(np.float32)
    y = (np.sin(5 * X[:, 0]) + X[:, 1] * X[:, 2] + 0.05 * rng.randn(3000)).astype(
        np.float32
    )

    f_approx = train(
        {"tree_method": "approx", "sketch_eps": 0.004, "max_depth": 4},
        DataMatrix(X, labels=y),
        num_boost_round=10,
    )
    monkeypatch.setenv("GRAFT_APPROX_RESKETCH", "0")
    f_static = train(
        {"tree_method": "approx", "sketch_eps": 0.004, "max_depth": 4},
        DataMatrix(X, labels=y),
        num_boost_round=10,
    )
    monkeypatch.delenv("GRAFT_APPROX_RESKETCH")
    f_hist = train(
        {"tree_method": "hist", "max_bin": 250, "max_depth": 4},
        DataMatrix(X, labels=y),
        num_boost_round=10,
    )
    # static-sketch approx IS hist at the same budget (old documented stance)
    np.testing.assert_allclose(
        np.asarray(f_static.predict(X)), np.asarray(f_hist.predict(X)),
        rtol=1e-5, atol=1e-6,
    )
    rmse_a = float(np.sqrt(np.mean((np.asarray(f_approx.predict(X)) - y) ** 2)))
    rmse_h = float(np.sqrt(np.mean((np.asarray(f_hist.predict(X)) - y) ** 2)))
    assert abs(rmse_a - rmse_h) < 0.05 * max(rmse_h, 1e-6), (rmse_a, rmse_h)


def test_approx_resketch_refreshes_cuts_and_evals():
    """The re-sketch actually moves candidate thresholds between dispatches
    (hessian mass concentrates on hard rows), and the incrementally
    maintained eval margins stay consistent with a fresh full-forest
    prediction after cuts change mid-training."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models.booster import (
        TrainConfig, _TrainingSession,
    )
    from sagemaker_xgboost_container_tpu.models import train
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    rng = np.random.RandomState(3)
    X = rng.randn(1500, 5).astype(np.float32)
    y = ((X[:, 0] + 0.3 * X[:, 1] ** 2) > 0.5).astype(np.float32)

    cfg = TrainConfig(
        {"tree_method": "approx", "max_bin": 64,
         "objective": "binary:logistic", "max_depth": 3}
    )
    forest = Forest(
        objective_name=cfg.objective, base_score=cfg.base_score,
        num_feature=X.shape[1],
    )
    session = _TrainingSession(cfg, DataMatrix(X, labels=y), [], forest)
    assert session.approx_resketch
    session.run_rounds()
    cuts_before = [np.asarray(c).copy() for c in session.cuts]
    session.run_rounds()  # triggers _resketch_bins
    session.end_turnaround()
    changed = any(
        a.shape != np.asarray(b).shape or not np.allclose(a, np.asarray(b))
        for a, b in zip(cuts_before, session.cuts)
    )
    assert changed, "re-sketch left every cut unchanged"

    # eval consistency end-to-end: incremental eval margins (re-binned on
    # every re-sketch) must agree with predicting the final forest fresh
    Xv = rng.randn(400, 5).astype(np.float32)
    yv = ((Xv[:, 0] + 0.3 * Xv[:, 1] ** 2) > 0.5).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    dval = DataMatrix(Xv, labels=yv)
    evals_result = {}

    class _Record:
        def after_iteration(self, model, epoch, evals_log):
            evals_result.update(evals_log)
            return False

    model = train(
        {"tree_method": "approx", "max_bin": 64, "max_depth": 3,
         "objective": "binary:logistic", "eval_metric": "logloss",
         "_rounds_per_dispatch": 2},
        dtrain,
        num_boost_round=6,
        evals=[(dtrain, "train"), (dval, "val")],
        callbacks=[_Record()],
    )
    p = np.clip(np.asarray(model.predict(Xv)), 1e-7, 1 - 1e-7)
    fresh = float(-np.mean(yv * np.log(p) + (1 - yv) * np.log(1 - p)))
    incremental = evals_result["val"]["logloss"][-1]
    assert abs(fresh - incremental) < 5e-3, (fresh, incremental)


def test_exact_wins_over_stale_sketch_eps():
    """A leftover approx-only sketch_eps must not affect tree_method=exact."""
    from sagemaker_xgboost_container_tpu.models.booster import TrainConfig

    assert TrainConfig({"tree_method": "exact", "sketch_eps": 0.3}).max_bin is None


def test_exact_matches_bruteforce_greedy():
    """tree_method=exact must reproduce the brute-force exact-greedy oracle
    even when distinct values far exceed the hist default of 256 bins —
    cuts land at EVERY adjacent-distinct midpoint (reference exact updater
    semantics, schema hyperparameter_validation.py:22-24)."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(9)
    n = 700  # > 2x256 distinct values per feature, so hist-256 would differ
    X = rng.randn(n, 4).astype(np.float32)
    y = (X[:, 0] * 1.5 + np.sin(3 * X[:, 1]) + 0.1 * rng.randn(n)).astype(
        np.float32
    )
    d = DataMatrix(X, labels=y)
    f_exact = train(
        {"tree_method": "exact", "max_depth": 3, "eta": 1.0},
        d,
        num_boost_round=1,
    )
    t = f_exact.trees[0]

    # brute-force greedy root split over all midpoints (exact semantics)
    def best_split(X, g, h, lam=1.0):
        best = (-np.inf, None, None)
        G, H = g.sum(), h.sum()
        parent = G * G / (H + lam)
        for f in range(X.shape[1]):
            vals = np.unique(X[:, f])
            for lo, hi in zip(vals[:-1], vals[1:]):
                thr = (lo + hi) / 2.0
                m = X[:, f] < thr
                Gl, Hl = g[m].sum(), h[m].sum()
                gain = (
                    Gl * Gl / (Hl + lam)
                    + (G - Gl) ** 2 / (H - Hl + lam)
                    - parent
                ) / 2.0
                if gain > best[0]:
                    best = (gain, f, thr)
        return best

    g = np.full(n, f_exact.base_score) - y  # squarederror grad at round 0
    h = np.ones(n)
    gain, feat, thr = best_split(X, g, h)
    assert t.feature[0] == feat
    # stored threshold is the midpoint between adjacent distinct values
    np.testing.assert_allclose(t.threshold[0], thr, rtol=1e-5)
