"""Categorical columns trained as categories (PR 50): the partition scan of
``ops/categorical.py`` against a brute-force search over every subset and
against the reference's own scan, the one-against-the-rest rule, missing and
out-of-range codes through training, the evaluation walk and
``ops/predict.py``, the forest's JSON, the layout's tables, the refused
combinations, and a dense session's round program left as it was.

No module-level jax or topology calls: jax is imported inside the tests.
"""

import itertools
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import categorical_gbt_reference as reference  # noqa: E402
from sagemaker_xgboost_container_tpu.data.categorical import (  # noqa: E402
    CatLayout,
    words_to_categories,
)
from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix  # noqa: E402
from sagemaker_xgboost_container_tpu.toolkit import exceptions as exc  # noqa: E402

MAX_BIN = 256
LAM = 1.0


# ------------------------------------------------------------ the partition scan
def level_histogram(layout, sums):
    """A level histogram ``[W, bin columns, B]`` pair that holds ``sums``
    ({column: float32 [2, W, C + 1]}: per category and, last, missing) at the
    places ``layout`` gives them; every node's total is the same in every
    bin column, as a real histogram's is."""
    W = next(iter(sums.values())).shape[1]
    B = layout.max_bin + 1
    G = np.zeros((2, W, layout.num_bin_columns, B), np.float32)
    total = next(iter(sums.values())).sum(axis=-1)  # [2, W]
    for f in range(layout.num_col):
        chunks = layout.chunks(f)
        if f not in sums:  # a column that holds one value: everything in bin 0
            G[:, :, chunks[0], 0] = total
            continue
        assert np.allclose(sums[f].sum(axis=-1), total, rtol=1e-5)
        for c in chunks:
            first, count = layout.col_first[c], layout.col_count[c]
            G[:, :, c, :count] = sums[f][:, :, first:first + count]
            # a row of another chunk, or a missing one, sits in the missing slot
            G[:, :, c, B - 1] = total - G[:, :, c, :count].sum(axis=-1)
    return G[0], G[1]


def random_sums(rng, W, C, missing=True, empty=0.0):
    g = rng.normal(size=(W, C + 1)).astype(np.float32)
    h = rng.uniform(0.5, 3.0, size=(W, C + 1)).astype(np.float32)
    gone = rng.random((W, C + 1)) < empty
    gone[:, -1] = not missing
    g[gone], h[gone] = 0.0, 0.0
    return np.stack([g, h])


def brute_force(sums, lam, mcw, sizes=None):
    """The best gain over every non-empty proper subset of the held
    categories sent right, the missing rows tried on both sides; float64."""
    g, h = sums[0, :-1].astype(np.float64), sums[1, :-1].astype(np.float64)
    g_m, h_m = float(sums[0, -1]), float(sums[1, -1])
    G, H = g.sum() + g_m, h.sum() + h_m
    held = np.flatnonzero(h > 0)
    best = -np.inf

    def score(a, b):
        return a * a / (b + lam)

    for k in sizes or range(1, len(held)):
        for subset in itertools.combinations(held, k):
            gr, hr = g[list(subset)].sum(), h[list(subset)].sum()
            for mg, mh in ((0.0, 0.0), (g_m, h_m)):
                r_g, r_h = gr + mg, hr + mh
                if H - r_h >= mcw and r_h >= mcw:
                    best = max(
                        best, 0.5 * (score(G - r_g, H - r_h) + score(r_g, r_h) - score(G, H))
                    )
    return best


def scan(layout, G, H, to_onehot=4, threshold=64, mcw=1.0):
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops.categorical import CatTables

    tables = CatTables(layout, to_onehot, threshold)
    num_cuts = jnp.zeros(layout.num_bin_columns, jnp.int32)
    out = tables.find_best_splits(
        jnp.asarray(G), jnp.asarray(H), num_cuts, reg_lambda=LAM, min_child_weight=mcw
    )
    return {k: np.asarray(v) for k, v in out.items()}


@pytest.mark.parametrize("C,seed", [(4, 0), (5, 1), (6, 2), (7, 3), (8, 4), (8, 5)])
def test_sorted_partition_scan_finds_the_best_subset(C, seed):
    """The sorted order is optimal for this score: where the cap does not
    bind, the scan's best gain is the best over all subsets."""
    rng = np.random.default_rng(seed)
    W = 6
    layout = CatLayout(["c"], [C], MAX_BIN)
    sums = random_sums(rng, W, C, missing=seed % 2 == 0, empty=0.15)
    G, H = level_histogram(layout, {0: sums})
    got = scan(layout, G, H, mcw=1.0)
    for w in range(W):
        want = brute_force(sums[:, w], LAM, 1.0)
        if not np.isfinite(want) or want <= 1e-6:
            continue
        assert got["gain"][w] == pytest.approx(want, rel=2e-5, abs=1e-6), w
        assert got["bin"][w] == MAX_BIN and got["feature"][w] == 0
        # the set the scan stores gives that gain, from the same sums
        codes = words_to_categories(got["cat_words"][w])
        assert 0 < len(codes) < C
        assert reference.set_gain(
            sums[:, w].astype(np.float64), codes, bool(got["default_left"][w]), LAM
        ) == pytest.approx(want, rel=2e-5, abs=1e-6)


@pytest.mark.parametrize("C,cap,seed", [(12, 3, 0), (40, 5, 1), (300, 7, 2), (700, 64, 3)])
def test_scan_agrees_with_the_references_scan_where_the_cap_binds(C, cap, seed):
    """Prefix sets from both ends of the order, at most ``max_cat_threshold``
    long: the program's gain is the reference's own, a column of several
    chunks included."""
    rng = np.random.default_rng(seed)
    W = 4
    layout = CatLayout(["q", "c"], [0, C], MAX_BIN)
    sums = random_sums(rng, W, C, empty=0.2)
    G, H = level_histogram(layout, {1: sums})
    got = scan(layout, G, H, threshold=cap, mcw=2.0)
    for w in range(W):
        counted = np.concatenate([sums[:, w], (sums[1:2, w] > 0).astype(np.float32)])
        want = reference.best_partition(counted.astype(np.float64), LAM, 2.0, 4, cap)
        assert got["gain"][w] == pytest.approx(want, rel=5e-5), w
        codes = words_to_categories(got["cat_words"][w])
        assert 0 < len(codes) <= cap and codes.max() < C
        assert got["feature"][w] == 1 and got["bin"][w] == MAX_BIN
        if cap < 8 and C <= 12:  # and no subset that small does better
            assert want == pytest.approx(
                brute_force(sums[:, w], LAM, 2.0, sizes=range(1, cap + 1)), rel=1e-6
            ) or want < brute_force(sums[:, w], LAM, 2.0, sizes=range(1, cap + 1))


@pytest.mark.parametrize("C", [2, 3])
def test_fewer_categories_than_max_cat_to_onehot_split_one_against_the_rest(C):
    rng = np.random.default_rng(C)
    W = 8
    layout = CatLayout(["c"], [C], MAX_BIN)
    sums = random_sums(rng, W, C)
    G, H = level_histogram(layout, {0: sums})
    got = scan(layout, G, H, to_onehot=4)
    for w in range(W):
        want = brute_force(sums[:, w], LAM, 1.0, sizes=[1])
        assert got["gain"][w] == pytest.approx(want, rel=2e-5, abs=1e-6)
        assert len(words_to_categories(got["cat_words"][w])) == 1
    # the same column above the rule's reach takes sets of any size
    pairs = scan(layout, G, H, to_onehot=2)
    assert (pairs["gain"] >= got["gain"] - 1e-6).all()


def test_a_threshold_split_wins_where_it_is_better_and_keeps_the_tie():
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops.categorical import CatTables

    rng = np.random.default_rng(7)
    layout = CatLayout(["q", "c"], [0, 5], MAX_BIN)
    W, B = 3, MAX_BIN + 1
    sums = random_sums(rng, W, 5)
    G, H = level_histogram(layout, {1: sums})
    # the numeric column: two bins that separate the gradient perfectly
    total_g, total_h = sums[0].sum(-1), sums[1].sum(-1)
    G[:, 0, :], H[:, 0, :] = 0.0, 0.0
    G[:, 0, 0], G[:, 0, 1] = total_g + 50.0, -50.0
    H[:, 0, 0], H[:, 0, 1] = total_h / 2, total_h / 2
    tables = CatTables(layout, 4, 64)
    out = tables.find_best_splits(
        jnp.asarray(G), jnp.asarray(H), jnp.asarray([1, 0], jnp.int32), reg_lambda=LAM
    )
    assert (np.asarray(out["feature"]) == 0).all() and (np.asarray(out["bin"]) == 0).all()
    assert not np.asarray(out["cat_words"]).any()


# ------------------------------------------------------------------ the layout
def test_layout_follows_the_cardinalities_alone():
    sizes = [0, 3, 255, 256, 1300, 0, 2700]
    types = ["q", "c", "c", "c", "c", "q", "c"]
    layout = CatLayout(types, sizes, MAX_BIN)
    assert [len(layout.chunks(f)) for f in range(7)] == [1, 1, 1, 2, 6, 1, 11]
    assert layout.num_bin_columns == 23 and layout.set_words == 85
    assert list(layout.col_first[layout.chunks(4)]) == [0, 255, 510, 765, 1020, 1275]
    assert list(layout.col_count[layout.chunks(4)]) == [255] * 5 + [25]
    assert list(layout.numeric_cut_counts([9] * 23)[[0, 1, 10, 11]]) == [9, 0, 0, 9]
    assert list(layout.reach(np.full(23, 9))[[0, 1, 2, 3, 4]]) == [9, 2, 254, 254, 0]


@pytest.mark.parametrize("C", [3, 255, 256, 700])
def test_a_categorical_columns_bin_is_its_code(C):
    from sagemaker_xgboost_container_tpu.data.binning import apply_cut_points

    rng = np.random.default_rng(C)
    n = 4000
    codes = rng.integers(0, C, n).astype(np.float32)
    codes[:C] = np.arange(C)  # every category held
    values = codes.copy()
    values[rng.random(n) < 0.1] = np.nan
    values[C:C + 5] = [-1.0, C, C + 40.0, 1e9, -0.5]  # evaluation rows: no category
    x = np.column_stack([rng.normal(size=n).astype(np.float32), values])
    layout = CatLayout(["q", "c"], [0, C], MAX_BIN)
    cuts = layout.cuts([np.asarray([0.0], np.float32)])
    bins = apply_cut_points(layout.expand(x), cuts, MAX_BIN)
    chunks = layout.chunks(1)
    for i in range(n):
        held = [(c, bins[i, c]) for c in chunks if bins[i, c] != MAX_BIN]
        v = values[i]
        if np.isnan(v):
            assert not held
        elif v < 0 or v >= C:
            assert held == [(chunks[0], MAX_BIN - 1)]  # present, and no category
        else:
            (c, b), = held
            assert layout.col_first[c] + b == int(v) and b < layout.col_count[c]


def test_a_training_code_that_is_no_category_is_the_users_error():
    x = np.asarray([[0.0, 1.0], [1.0, -2.0], [2.0, 0.0]], np.float32)
    with pytest.raises(exc.UserError, match="no category code"):
        CatLayout.of(DataMatrix(x, feature_types=["q", "c"]), MAX_BIN)
    x[1, 1] = 1.5
    with pytest.raises(exc.UserError, match="no category code"):
        CatLayout.of(DataMatrix(x, feature_types=["q", "c"]), MAX_BIN)


def test_feature_types_are_checked_and_kept():
    x = np.zeros((4, 3), np.float32)
    dm = DataMatrix(x, labels=np.zeros(4), feature_types=["float", "c", "int"])
    assert dm.feature_types == ["q", "c", "q"] and dm.has_categorical
    assert dm.slice([0, 2]).feature_types == ["q", "c", "q"]
    assert dm.pad_features(5).feature_types == ["q", "c", "q", "q", "q"]
    assert not DataMatrix(x).has_categorical
    with pytest.raises(exc.UserError, match="names 2 columns"):
        DataMatrix(x, feature_types=["q", "c"])
    with pytest.raises(exc.UserError, match="'q'.*'c'"):
        DataMatrix(x, feature_types=["q", "c", "category"])


# ---------------------------------------------------------------- end to end
def table(seed, n=3000, unknown=True):
    """Rows of two numeric and three categorical columns (3, 12 and 600
    categories), a label that follows them, and missing values in all."""
    rng = np.random.default_rng(seed)
    xn = rng.normal(size=(n, 2)).astype(np.float32)
    c1 = rng.integers(0, 3, n).astype(np.float32)
    c2 = rng.integers(0, 12, n).astype(np.float32)
    c3 = np.minimum(rng.geometric(0.01, n) - 1, 599).astype(np.float32)
    c3[-1] = 599  # the column's cardinality, whatever the rows
    effects = np.random.default_rng(99)
    e2, e3 = effects.normal(size=12), effects.normal(size=600)
    score = xn[:, 0] + e2[c2.astype(int)] + e3[c3.astype(int)] + (c1 == 1) - 0.5
    y = (score + rng.normal(size=n) > 0).astype(np.float32)
    if unknown:
        xn[rng.random(n) < 0.05, 1] = np.nan
        c1[rng.random(n) < 0.05] = np.nan
        c2[rng.random(n) < 0.1] = np.nan
        c3[:-1][rng.random(n - 1) < 0.05] = np.nan
    x = np.column_stack([xn[:, 0], c1, c2, xn[:, 1], c3]).astype(np.float32)
    return x, y


TYPES = ["q", "c", "c", "q", "c"]
PARAMS = {
    "objective": "binary:logistic", "tree_method": "hist", "max_depth": 4, "eta": 0.3,
    "max_bin": MAX_BIN, "eval_metric": "logloss", "min_child_weight": 1,
    "_rounds_per_dispatch": 2,
}


class KeepLog:
    def after_iteration(self, forest, rnd, evals_log):
        self.evals_log = {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
        return False


def train(x, y, evals=(), rounds=4, types=TYPES, **params):
    from sagemaker_xgboost_container_tpu import models

    keep = KeepLog()
    dtrain = DataMatrix(x, labels=y, feature_types=types)
    forest = models.train(
        dict(PARAMS, **params), dtrain, num_boost_round=rounds,
        evals=[(dtrain, "train")] + [
            (DataMatrix(ex, labels=ey, feature_types=types), name) for name, (ex, ey) in evals
        ],
        callbacks=[keep], verbose_eval=False,
    )
    return forest, keep.evals_log


def logloss(margin, y):
    p = 1.0 / (1.0 + np.exp(-margin.astype(np.float64)))
    p = np.clip(p, 1e-7, 1 - 1e-7)
    return float(np.mean(-(y * np.log(p) + (1 - y) * np.log(1 - p))))


def reference_margin(forest, x):
    """The float64 reference's own traversal of every tree."""
    from benchmark.kinds.train_window_categorical import categorical_rounds

    margin = np.full(len(x), np.log(forest.base_score / (1 - forest.base_score)))
    for rnd in categorical_rounds(forest, forest.num_boosted_rounds):
        for _c, tree in rnd:
            leaf = reference.route(tree, x, reference.set_table(tree, 1024))[-1]
            margin += tree["value"].astype(np.float64)[leaf]
    return margin


@pytest.fixture(scope="module")
def trained():
    x, y = table(0)
    ex, ey = table(1, n=1500)
    # evaluation rows whose values are no category of their columns, and NaN
    ex[:300, 4] = np.repeat([-1.0, 600.0, 5000.0, -0.5, 1e9, np.nan], 50)
    ex[300:400, 2] = np.repeat([12.0, 31.0, 32.0, 255.0, -3.0], 20)
    ex[400:450, 1] = np.repeat([3.0, np.nan], 25)
    forest, log = train(x, y, evals=[("validation", (ex, ey))])
    return forest, log, (x, y), (ex, ey)


def test_training_the_walk_and_the_served_forest_give_the_same_margins(trained):
    """Missing values follow ``default_left`` and a value that is no category
    goes left, in the build's routing (the training rows' margins), in the
    evaluation walk (the validation rows') and in ``ops/predict.py`` on the
    raw floats; the float64 reference routes the same way."""
    forest, log, (x, y), (ex, ey) = trained
    assert sum(len(t.categories) for t in forest.trees) > 10
    assert any(not t.default_left[n] for t in forest.trees for n in t.categories)
    for rows, labels, name in ((x, y, "train"), (ex, ey, "validation")):
        served = forest.predict_margin(rows)
        assert logloss(served, labels) == pytest.approx(log[name]["logloss"][-1], abs=2e-7)
        assert np.allclose(served, reference_margin(forest, rows), atol=2e-6)
        few = forest.predict_margin(rows[:20])  # the host traversal of a small payload
        assert np.array_equal(few, served[:20])
    assert log["train"]["logloss"][-1] < log["train"]["logloss"][0]


def test_the_sessions_shape_gauges_speak_in_the_inputs_columns():
    """What every session says of its binned matrix, a session with categories
    says in *original* columns (``_note_categorical_shape``): a cell is missing
    where the input held NaN, whatever chunks its column took, and the sketch's
    counts are the numeric columns' alone."""
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    x, y = table(3, n=1000)
    train(x, y, rounds=1)
    gauges = {
        name: family[0].value
        for name, kind, _h, family in REGISTRY.collect()
        if kind == "gauge" and family
    }
    assert gauges["train_cells_missing"] == float(np.isnan(x).sum())
    assert gauges["train_cells_total"] == float(x.size)
    assert gauges["sketch_cut_slots"] == 2.0 * (MAX_BIN - 1)
    assert 0 < gauges["sketch_cuts_selected"] <= gauges["sketch_cut_slots"]
    assert (gauges["train_columns_total"], gauges["train_columns_categorical"]) == (5.0, 3.0)
    # 3 and 12 categories a bin column each, 600 three chunks of 255
    assert (gauges["train_bin_columns"], gauges["cat_set_words"]) == (7.0, 19.0)


def test_no_threshold_splits_a_categorical_column_and_every_set_is_legal(trained):
    forest, _log, (x, _y), _eval = trained
    from benchmark.kinds.train_window_categorical import categorical_rounds

    trees = [t for rnd in categorical_rounds(forest, forest.num_boosted_rounds) for _c, t in rnd]
    sizes = reference.column_cardinalities(x, TYPES)
    assert sizes == [0, 3, 12, 0, 600]
    assert reference.exact_checks(trees, TYPES, sizes, 4, 64) == {
        "ordinal_split_on_categorical": 0, "cat_set_invalid": 0, "cat_onehot_rule_broken": 0,
    }
    split_on = {int(f) for t in trees for f in t["feature"][t["left"] >= 0]}
    assert split_on & {1, 2, 4} and split_on <= set(range(5))


def test_the_forests_json_is_xgboosts_and_reads_back_bit_for_bit(trained):
    forest, _log, (x, _y), (ex, _ey) = trained
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    doc = json.loads(forest.save_json())
    assert doc["learner"]["feature_types"] == ["float", "c", "c", "float", "c"]
    tree = doc["learner"]["gradient_booster"]["model"]["trees"][0]
    nodes = tree["categories_nodes"]
    assert nodes and [tree["split_type"][n] for n in nodes] == [1] * len(nodes)
    assert sum(tree["split_type"]) == len(nodes)
    assert tree["categories_segments"] == list(
        np.cumsum([0] + tree["categories_sizes"][:-1])
    )
    assert len(tree["categories"]) == sum(tree["categories_sizes"])
    back = Forest.load_json(forest.save_json())
    assert back.feature_types == ["float", "c", "c", "float", "c"]
    for rows in (x, ex):
        assert np.array_equal(back.predict_margin(rows), forest.predict_margin(rows))
    assert back.save_json() == forest.save_json()


def test_the_partition_scan_of_a_trained_forest_has_no_regret(trained):
    forest, log, (x, y), _eval = trained
    from benchmark.kinds.train_window_categorical import categorical_rounds

    rounds = categorical_rounds(forest, forest.num_boosted_rounds)
    worst = reference.check_rounds(
        rounds, [0, 3], x, y, "binary:logistic", 0.5, 0.3, 1.0, 4,
        log["train"]["logloss"], reference.column_cardinalities(x, TYPES), 1.0, 4, 64,
    )
    assert worst["cat_partition_regret"] < 1e-4 and worst["loss_abs"] < 1e-6
    assert worst["gain_err_median"] < 1e-5 and worst["direct_hess_err"] < 1e-3


def test_class_trees_and_parallel_trees_carry_their_sets():
    x, y = table(3, n=1500)
    y3 = (y + (x[:, 0] > 0.5)).astype(np.float32)
    forest, log = train(
        x, y3, rounds=2, objective="multi:softmax", num_class=3, eval_metric="mlogloss"
    )
    assert len(forest.trees) == 6 and sum(len(t.categories) for t in forest.trees) > 3
    assert log["train"]["mlogloss"][-1] < log["train"]["mlogloss"][0]
    predicted = forest.predict(x)
    assert predicted.shape == (1500,) and set(np.unique(predicted)) <= {0.0, 1.0, 2.0}
    bagged, log = train(x, y, rounds=2, num_parallel_tree=2, subsample=0.8)
    assert len(bagged.trees) == 4 and sum(len(t.categories) for t in bagged.trees) > 3
    assert logloss(bagged.predict_margin(x), y) == pytest.approx(
        log["train"]["logloss"][-1], abs=1e-6
    )


def test_a_column_of_one_category_or_none_never_splits():
    x, y = table(4, n=800, unknown=False)
    x[:, 1] = 0.0      # one category
    x[:, 2] = np.nan   # none at all
    forest, _log = train(x, y, rounds=2)
    split_on = {int(f) for t in forest.trees for f in t.feature[~t.is_leaf]}
    assert not split_on & {1, 2}


# --------------------------------------------------------- what is refused
def mesh_of_one():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]), ("data",))


REFUSED = {
    "lossguide": (dict(grow_policy="lossguide", max_leaves=8), "lossguide"),
    "approx": (dict(tree_method="approx"), "approx"),
    "exact": (dict(tree_method="exact"), "exact"),
    "dart": (dict(booster="dart"), "dart"),
    "gblinear": (dict(booster="gblinear"), "gblinear"),
    "update": (dict(process_type="update"), "update"),
    "monotone": (dict(monotone_constraints=(1, 0, 0, 0, 0)), "monotone_constraints"),
    "interaction": (dict(interaction_constraints=[[0, 1]]), "interaction_constraints"),
    "colsample_bytree": (dict(colsample_bytree=0.5), "colsample"),
    "colsample_bylevel": (dict(colsample_bylevel=0.5), "colsample"),
    "colsample_bynode": (dict(colsample_bynode=0.5), "colsample"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_a_combination_not_taken_yet_is_refused_by_name(name):
    from sagemaker_xgboost_container_tpu import models

    params, named = REFUSED[name]
    x, y = table(5, n=300)
    with pytest.raises(exc.UserError, match="Categorical columns.*" + named):
        models.train(
            dict(PARAMS, **params), DataMatrix(x, labels=y, feature_types=TYPES),
            num_boost_round=1,
        )


def test_a_mesh_sparse_input_and_mismatched_types_are_refused():
    import scipy.sparse as sp

    from sagemaker_xgboost_container_tpu import models

    x, y = table(6, n=300, unknown=False)
    typed = DataMatrix(x, labels=y, feature_types=TYPES)
    with pytest.raises(exc.UserError, match="device mesh"):
        models.train(PARAMS, typed, num_boost_round=1, mesh=mesh_of_one())
    with pytest.raises(exc.UserError, match="sparse"):
        models.train(
            PARAMS, DataMatrix(sp.csr_matrix(x), labels=y, feature_types=TYPES),
            num_boost_round=1,
        )
    with pytest.raises(exc.UserError, match="both or in neither"):
        models.train(
            PARAMS, DataMatrix(x, labels=y), num_boost_round=1, evals=[(typed, "validation")]
        )
    other = DataMatrix(x, labels=y, feature_types=["q", "c", "c", "c", "c"])
    with pytest.raises(exc.UserError, match="feature_types"):
        models.train(PARAMS, typed, num_boost_round=1, evals=[(other, "validation")])
    with pytest.raises(exc.UserError, match="at least 1"):
        models.train(dict(PARAMS, max_cat_threshold=0), typed, num_boost_round=1)


def test_a_set_table_wider_than_the_chips_select_pass_is_refused_on_the_chip():
    """One lowering of the set table on the chip: what it does not hold (a
    level's nodes x words over 16,384 entries) is the user's error there,
    and no row-length gather stands behind it; the CPU reads any width."""
    import jax

    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.ops import categorical
    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs

    assert categorical.set_table_fits(85, 8, "tpu")  # the benchmark's cell: 128 x 85
    assert not categorical.set_table_fits(85, 9, "tpu")
    assert categorical.set_table_fits(129, 8, "tpu") is False  # over 4,096 categories
    assert categorical.set_table_fits(2000, 12, "cpu")
    x, y = table(8, n=300)
    typed = DataMatrix(x, labels=y, feature_types=TYPES)  # 600 categories: 19 words
    on_chip = resolve_hist_knobs()._replace(backend="tpu")
    with pytest.raises(exc.UserError, match="600 categories at max_depth=11.*1024 x 19"):
        models.train(dict(PARAMS, max_depth=11), typed, num_boost_round=1, hist_knobs=on_chip)
    words = np.zeros((4, 19), np.int32)
    tables = categorical.CatTables(CatLayout.of(typed, MAX_BIN))
    lowered = {
        backend: str(jax.make_jaxpr(
            lambda w, n, v, b=backend: tables.set_word(w, n, v, b)
        )(words, np.zeros(8, np.int32), np.ones(8, np.int32)))
        for backend in ("tpu", "cpu")
    }
    assert "gather" not in lowered["tpu"] and "gather" in lowered["cpu"]


def test_the_three_hyperparameters_are_validated():
    from sagemaker_xgboost_container_tpu.algorithm import hyperparameters as hpv
    from sagemaker_xgboost_container_tpu.algorithm import metrics as metrics_mod

    hps = hpv.initialize(metrics_mod.initialize())
    base = {"num_round": "3", "objective": "binary:logistic", "tree_method": "hist"}
    got = hps.validate(dict(
        base, enable_categorical="true", max_cat_to_onehot="4", max_cat_threshold="64",
        feature_types="('q', 'c')",
    ))
    assert got["max_cat_to_onehot"] == 4 and got["max_cat_threshold"] == 64
    assert got["enable_categorical"] == "true" and got["feature_types"] == ("q", "c")
    for bad in (
        {"max_cat_to_onehot": "0"}, {"max_cat_threshold": "-1"}, {"enable_categorical": "yes"},
        {"feature_types": "('q', 'z')"},
        {"feature_types": "('q', 'c')"},  # categories without enable_categorical
    ):
        with pytest.raises(exc.UserError):
            hps.validate(dict(base, **bad))


# ------------------------------------------- the container's job, by ingest mode
JOB = {
    "objective": "binary:logistic", "tree_method": "hist", "max_depth": 3, "num_round": 2,
    "enable_categorical": "true", "feature_types": tuple(TYPES),
    "_num_devices": 1,  # one chip: the test host's eight virtual devices would make a mesh
}


def csv_channel(path, seed=7, n=600):
    x, y = table(seed, n=n, unknown=False)
    os.makedirs(path)
    np.savetxt(os.path.join(path, "part-00.csv"), np.column_stack([y, x]), delimiter=",",
               fmt="%.6g")
    return path


@pytest.mark.parametrize("mode", ["whole", "auto", "chunked"])
def test_the_container_job_trains_categories_or_refuses_in_every_ingest_mode(
        tmp_path, monkeypatch, mode):
    """A channel larger than one chunk: `auto` stays on the whole-file readers
    for a job that names categories, a forced `chunked` is refused by name,
    and what trains holds sets and no threshold over a code."""
    from sagemaker_xgboost_container_tpu.models.forest import Forest
    from sagemaker_xgboost_container_tpu.training import algorithm_train as at

    monkeypatch.setenv("SM_INGEST_MODE", mode)
    monkeypatch.setenv("SM_INGEST_CHUNK_BYTES", "4096")
    channel = csv_channel(str(tmp_path / "train"))
    if mode == "chunked":
        with pytest.raises(exc.UserError, match="feature_types 'c'"):
            at.get_validated_data_matrices(channel, None, "text/csv", train_cfg=dict(JOB))
        return
    tr, _va, tv = at.get_validated_data_matrices(channel, None, "text/csv", train_cfg=dict(JOB))
    assert isinstance(tr, DataMatrix)
    model_dir = str(tmp_path / "model")
    at.train_job(dict(JOB), tr, None, tv, model_dir, None, is_master=True)
    forest = Forest.load_model(os.path.join(model_dir, "xgboost-model"))
    assert [t == "c" for t in forest.feature_types] == [t == "c" for t in TYPES]
    on_sets, on_cuts = set(), set()
    for t in forest.trees:
        for node in np.flatnonzero(~t.is_leaf):
            (on_sets if int(node) in t.categories else on_cuts).add(int(t.feature[node]))
    assert on_sets and on_sets <= {1, 2, 4} and not on_cuts & {1, 2, 4}


def test_a_pre_binned_matrix_with_categories_is_refused_by_the_job(tmp_path, monkeypatch):
    """What chunked ingest hands over has sketched the codes as numbers:
    `train_job` refuses it and never trains them as ordered values."""
    from sagemaker_xgboost_container_tpu.data import streaming
    from sagemaker_xgboost_container_tpu.training import algorithm_train as at

    channel = csv_channel(str(tmp_path / "train"))
    binned = streaming.ingest_channel(channel, "text/csv", MAX_BIN)
    with pytest.raises(exc.UserError, match="SM_INGEST_MODE=whole"):
        at.train_job(dict(JOB), binned, None, binned, str(tmp_path / "model"), None,
                     is_master=True)
    ok, why, _ = streaming.supports_streaming(dict(JOB))
    assert not ok and "feature_types" in why
    assert streaming.supports_streaming(dict(JOB, enable_categorical="false"))[0]
    assert streaming.supports_streaming(dict(JOB, feature_types=("q",) * 5))[0]


# ------------------------------------------- a dense session's round program
def round_program_text(params, dtrain, evals=()):
    """The StableHLO text of the round program ``models.train`` would run."""
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.models import booster
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    config = booster.TrainConfig(params)
    forest = Forest(objective_name=config.objective, num_feature=dtrain.num_col)
    s = booster._TrainingSession(
        config, dtrain, list(evals), forest, metric_names=["logloss"], bundles=True
    )
    args = (
        s.bins, s.margins, s.labels, s.weights, s.num_cuts, s.rng,
        jax.ShapeDtypeStruct((s.bins.shape[1],), jnp.float32), s.monotone, s.rank_index_dev,
    )
    if s.use_scan_rounds:
        eval_m = tuple(m for m in s.eval_margins if m is not None)
        eval_blw = tuple(
            (s.eval_bins[i], s.eval_labels[i], s.eval_weights[i])
            for i in range(len(s.eval_bins)) if s.eval_bins[i] is not None
        )
        args += (eval_m, eval_blw, s.eval_layouts)
    return s._round_fn.lower(*args).as_text()


def test_a_dense_sessions_round_program_is_untouched_by_the_new_parameters():
    x, y = table(8, n=500, unknown=False)
    ex, ey = table(9, n=200, unknown=False)
    params = dict(PARAMS, max_depth=3)

    def text(p, types=None):
        dm = DataMatrix(x, labels=y, feature_types=types)
        dv = DataMatrix(ex, labels=ey, feature_types=types)
        return round_program_text(p, dm, [(dm, "train"), (dv, "validation")])

    plain = text(params)
    assert plain == text(dict(params, max_cat_to_onehot=9, max_cat_threshold=7))
    assert plain == text(dict(params, enable_categorical=True), types=["q"] * 5)
    assert text(params, types=TYPES) != plain


def test_the_round_program_follows_the_cardinalities_and_not_the_rows():
    """Two tables of the same types and cardinalities, other rows: one
    program, text for text (so it loads warm from the persistent cache)."""
    xa, ya = table(10, n=700, unknown=False)
    xb, yb = table(11, n=700, unknown=False)
    params = dict(PARAMS, max_depth=2)
    a = round_program_text(params, DataMatrix(xa, labels=ya, feature_types=TYPES))
    b = round_program_text(params, DataMatrix(xb, labels=yb, feature_types=TYPES))
    assert a == b
