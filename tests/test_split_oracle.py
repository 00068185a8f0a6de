"""Oracle tests: the jitted split scan and tree growth vs numpy brute force.

With max_bin >= #distinct values, binning is exact, so the XLA builder must
reproduce a brute-force exact-greedy XGBoost tree (same gain formula) node
for node. This is the strongest internal evidence of split-semantics parity
(missing-direction handling included) absent real xgboost in the image.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from sagemaker_xgboost_container_tpu.data.binning import (
    apply_cut_points,
    compute_cut_points,
)
from sagemaker_xgboost_container_tpu.ops.split import find_best_splits
from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree

LAM, GAMMA, MINCW = 1.0, 0.1, 1e-3


def _score(g, h):
    return g * g / (h + LAM)


def _brute_best_split(bins_col, grad, hess, n_cuts, missing_bin):
    """All (bin, missing-direction) splits for one feature, numpy."""
    best = (-np.inf, -1, False)
    present = bins_col != missing_bin
    g_tot, h_tot = grad.sum(), hess.sum()
    parent = _score(g_tot, h_tot)
    for b in range(n_cuts):
        left_mask = present & (bins_col <= b)
        for missing_left in (False, True):
            lm = left_mask | (~present if missing_left else np.zeros_like(left_mask))
            gl, hl = grad[lm].sum(), hess[lm].sum()
            gr, hr = g_tot - gl, h_tot - hl
            if hl < MINCW or hr < MINCW:
                continue
            gain = 0.5 * (_score(gl, hl) + _score(gr, hr) - parent) - GAMMA
            if gain > best[0]:
                best = (gain, b, missing_left)
    return best


def test_split_scan_matches_bruteforce():
    rng = np.random.RandomState(0)
    for trial in range(5):
        n, d, B = 300, 5, 9  # 8 data bins + missing
        bins = rng.randint(0, B, size=(n, d)).astype(np.int32)  # incl missing=8
        grad = rng.randn(n).astype(np.float32)
        hess = rng.rand(n).astype(np.float32) + 0.1
        num_cuts = np.full(d, B - 2, np.int32)  # splits legal at bins 0..6

        node_local = np.zeros(n, np.int32)
        from sagemaker_xgboost_container_tpu.ops.histogram import level_histogram

        G, H = level_histogram(
            jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
            jnp.asarray(node_local), 1, B,
        )
        splits = find_best_splits(
            G, H, jnp.asarray(num_cuts),
            reg_lambda=LAM, gamma=GAMMA, min_child_weight=MINCW,
        )
        got_gain = float(splits["gain"][0])
        got = (
            int(splits["feature"][0]),
            int(splits["bin"][0]),
            bool(splits["default_left"][0]),
        )

        best = (-np.inf, -1, -1, False)
        for f in range(d):
            gain, b, ml = _brute_best_split(bins[:, f], grad, hess, B - 2, B - 1)
            if gain > best[0]:
                best = (gain, f, b, ml)
        # the optimal gain must agree; feature/bin may tie, so check that the
        # chosen feature's own best split achieves the same gain
        assert abs(got_gain - best[0]) < 1e-3, (trial, got_gain, best)
        chosen_f = got[0]
        chosen_gain, _, _ = _brute_best_split(bins[:, chosen_f], grad, hess, B - 2, B - 1)
        assert abs(chosen_gain - best[0]) < 1e-3, (trial, chosen_gain, best)


def _brute_tree(X, grad, hess, depth):
    """Exact-greedy xgboost-gain tree on raw floats (missing=nan), numpy."""

    def best_split(rows):
        g_tot, h_tot = grad[rows].sum(), hess[rows].sum()
        parent = _score(g_tot, h_tot)
        best = (-np.inf, None, None, None)
        for f in range(X.shape[1]):
            vals = X[rows, f]
            present = ~np.isnan(vals)
            cands = np.unique(vals[present])
            for i in range(len(cands) - 1):
                thr = (cands[i] + cands[i + 1]) / 2.0
                for missing_left in (False, True):
                    lm = np.where(
                        np.isnan(vals), missing_left, vals < thr
                    )
                    gl, hl = grad[rows][lm].sum(), hess[rows][lm].sum()
                    gr, hr = g_tot - gl, h_tot - hl
                    if hl < MINCW or hr < MINCW:
                        continue
                    gain = 0.5 * (_score(gl, hl) + _score(gr, hr) - parent) - GAMMA
                    if gain > best[0] + 1e-9:
                        best = (gain, f, thr, missing_left)
        return best

    def leaf_value(rows):
        return -grad[rows].sum() / (hess[rows].sum() + LAM)

    preds = np.zeros(len(grad))

    def grow(rows, level):
        gain, f, thr, ml = best_split(rows)
        if level >= depth or gain <= 1e-6 or f is None:
            preds[rows] = leaf_value(rows)
            return
        vals = X[rows, f]
        lm = np.where(np.isnan(vals), ml, vals < thr)
        grow(rows[lm], level + 1)
        grow(rows[~lm], level + 1)

    grow(np.arange(len(grad)), 0)
    return preds


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tree_growth_matches_exact_greedy(seed):
    rng = np.random.RandomState(seed)
    n, d, depth = 400, 3, 3
    # few distinct values so binning is exact
    X = rng.randint(0, 12, size=(n, d)).astype(np.float32)
    X[rng.rand(n, d) < 0.15] = np.nan
    grad = rng.randn(n).astype(np.float32)
    hess = rng.rand(n).astype(np.float32) + 0.5

    cuts = compute_cut_points(X, None, 256)
    bins = apply_cut_points(X, cuts, 256).astype(np.int32)
    num_cuts = np.asarray([len(c) for c in cuts], np.int32)

    tree, row_out = build_tree(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(num_cuts),
        max_depth=depth, num_bins=257,
        reg_lambda=LAM, gamma=GAMMA, min_child_weight=MINCW, eta=1.0,
    )
    want = _brute_tree(X, grad, hess, depth)
    got = np.asarray(row_out)
    # identical greedy decisions -> identical leaf assignments and values
    # (ties between equal-gain splits may differ; require near-equality of
    # the induced predictions, which equal-gain ties preserve in expectation)
    mismatch = np.abs(got - want) > 1e-4
    assert mismatch.mean() < 0.02, (seed, mismatch.mean())


@pytest.mark.parametrize("missing_rate", [0.45, 0.77])
@pytest.mark.parametrize("missing_goes", ["left", "right"])
def test_split_and_default_direction_on_missing_heavy_counts(missing_rate, missing_goes):
    """Click-log columns: counts spiked at 0 with a heavy tail, NaN at 45 %
    and 77 %, and a gradient that depends on *whether* the value is there.
    The scan's split, and the side it sends the missing rows to, are the
    brute-force oracle's, through the sketch and the bin-apply."""
    rng = np.random.RandomState(int(missing_rate * 100))
    n, max_bin = 4000, 16
    counts = np.floor(2.0 * (rng.rand(n, 3) ** -0.8 - 1.0)).astype(np.float32)
    X = np.where(rng.rand(n, 3) < missing_rate, np.nan, counts).astype(np.float32)
    absent = np.isnan(X[:, 0])
    big = np.nan_to_num(X[:, 0]) >= 2.0
    # missing rows pull with the small values (left of the cut) or the large
    side = absent & (missing_goes == "right") | big
    grad = (np.where(side, -1.0, 1.0) + 0.3 * rng.randn(n)).astype(np.float32)
    hess = (0.03 + 0.02 * rng.rand(n)).astype(np.float32)  # a 3 % click rate's
    cuts = compute_cut_points(X, None, max_bin)
    bins = apply_cut_points(X, cuts, max_bin).astype(np.int32)
    assert abs((bins[:, 0] == max_bin).mean() - missing_rate) < 0.03
    num_cuts = np.asarray([len(c) for c in cuts], np.int32)

    from sagemaker_xgboost_container_tpu.ops.histogram import level_histogram

    G, H = level_histogram(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.zeros(n, jnp.int32), 1, max_bin + 1,
    )
    splits = find_best_splits(
        G, H, jnp.asarray(num_cuts), reg_lambda=LAM, gamma=GAMMA, min_child_weight=MINCW,
    )
    got = (int(splits["feature"][0]), int(splits["bin"][0]), bool(splits["default_left"][0]))
    g64, h64 = grad.astype(np.float64), hess.astype(np.float64)
    best = max(
        (_brute_best_split(bins[:, f], g64, h64, int(num_cuts[f]), max_bin) + (f,))
        for f in range(3)
    )
    gain, b, missing_left, f = best
    assert got == (f, b, missing_left) == (0, b, missing_goes == "left")
    assert abs(float(splits["gain"][0]) - gain) < 1e-3 * abs(gain)
    # the other placement of the same cut is clearly worse: the direction is
    # decided by the data, not by a tie
    present = bins[:, 0] != max_bin
    lm = (present & (bins[:, 0] <= b)) | (~present if not missing_left else False)
    gl, hl = g64[lm].sum(), h64[lm].sum()
    other = 0.5 * (
        _score(gl, hl) + _score(g64.sum() - gl, h64.sum() - hl) - _score(g64.sum(), h64.sum())
    ) - GAMMA
    assert other < 0.8 * gain


# ------------------------------------- ten class trees, gamma and mcw live
MC_GAMMA, MC_MINCW, MC_DEPTH, MC_BINS = 4.0, 6.0, 5, 16
MC_BAND = 2e-3  # a float32 scan beside a float64 oracle: nodes this near a rule are not judged


def _oracle_best(bins, g, h, num_cuts):
    """Over every (feature, cut) of one node's rows, float64, nothing missing:
    the gain ``0.5 * (GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l))`` and the two
    children's hessian sums, each ``[features, cuts]``."""
    cuts = int(num_cuts.max())
    left = np.stack([(bins <= b) for b in range(cuts)])            # [cuts, rows, features]
    gl = np.einsum("brf,r->fb", left, g)
    hl = np.einsum("brf,r->fb", left, h)
    gr, hr = g.sum() - gl, h.sum() - hl
    gain = 0.5 * (_score(gl, hl) + _score(gr, hr) - _score(g.sum(), h.sum()))
    legal = np.arange(cuts)[None, :] < num_cuts[:, None]
    return np.where(legal, gain, -np.inf), hl, hr


def _judge_tree(tree, bins, cuts, g, h, gamma, mincw):
    """Walk the program's tree by its own splits. Returns the names of the
    rules a node breaks: a kept split whose gain is under gamma, or whose
    child is under min_child_weight, or that is not the best of those both
    rules allow; a leaf above the last level where such a split was there."""
    broken = []
    num_cuts = np.asarray([len(c) for c in cuts])
    rows_of = {0: np.arange(len(g))}
    depth_of = {0: 0}
    stack = [0]
    while stack:
        node = stack.pop()
        rows, depth = rows_of[node], depth_of[node]
        gain, hl, hr = _oracle_best(bins[rows], g[rows], h[rows], num_cuts)
        allowed = np.where((hl >= mincw) & (hr >= mincw), gain, -np.inf)
        best = float(allowed.max()) if len(rows) else -np.inf
        if tree["left"][node] < 0:
            if depth < MC_DEPTH and best > gamma * (1 + MC_BAND):
                broken.append("a leaf where a split reached gamma")
            continue
        f = int(tree["feature"][node])
        b = int(np.flatnonzero(cuts[f] == tree["threshold"][node])[0])
        if gain[f, b] < gamma * (1 - MC_BAND):
            broken.append("gamma")
        if min(hl[f, b], hr[f, b]) < mincw * (1 - MC_BAND):
            broken.append("min_child_weight")
        # the scan's own pick is the best of what both rules allow
        if gain[f, b] < best - MC_BAND * abs(best) and "min_child_weight" not in broken:
            broken.append("not the best split")
        # stored is the split's own loss change, gamma not taken off
        assert abs(tree["gain"][node] - gain[f, b]) < 2e-3 * abs(gain[f, b]) + 1e-3
        go_left = bins[rows, f] <= b
        for child, mask in ((tree["left"][node], go_left), (tree["right"][node], ~go_left)):
            rows_of[int(child)], depth_of[int(child)] = rows[mask], depth + 1
            stack.append(int(child))
    return broken


@pytest.mark.parametrize(
    "ignored, want",
    [
        (None, set()),
        ("gamma", {"gamma"}),
        ("min_child_weight", {"min_child_weight"}),
    ],
    ids=["both_rules", "gamma_ignored", "min_child_weight_ignored"],
)
def test_ten_class_forest_keeps_a_split_exactly_where_gamma_and_child_weight_allow(ignored, want):
    """`mnist8m-mc10`'s trees stop early by two rules the teacher-forced
    reference cannot see: through `models.train()` (ten classes under the
    class `vmap`, two rounds, the generator's pictures with seeded labels), a
    split is kept exactly where its gain reaches `gamma` and both children's
    hessian sums reach `min_child_weight`, and is the best such split; a
    forest grown with either rule switched off breaks that rule and no other."""
    from benchmark.datagen import mnist8m_like
    from benchmark.kinds.train_window import plain_rounds
    from benchmark.reference import gbt_reference
    from sagemaker_xgboost_container_tpu import models
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix

    config = {"train_rows": 3000, "validation_rows": 8, "num_feature": 784,
              "params": {"num_class": 10}}
    x, y = mnist8m_like.make(config, 2**31 + 5)["train"]
    params = {
        "objective": "multi:softmax", "num_class": 10, "max_depth": MC_DEPTH, "eta": 0.2,
        "gamma": MC_GAMMA, "min_child_weight": MC_MINCW, "lambda": LAM, "max_bin": MC_BINS,
        "_rounds_per_dispatch": 2,
    }
    if ignored == "gamma":
        params["gamma"] = 0.0
    if ignored == "min_child_weight":
        params["min_child_weight"] = 0.0
    forest = models.train(params, DataMatrix(x, labels=y), num_boost_round=2, verbose_eval=False)
    cuts = compute_cut_points(x, None, MC_BINS)
    bins = apply_cut_points(x, cuts, MC_BINS).astype(np.int64)
    assert (bins < MC_BINS).all()  # nothing missing: every zero is a value

    margin = np.full((len(x), 10), gbt_reference.base_margin("multi:softmax", 0.5))
    broken, leaves_above_the_last_level, splits = set(), 0, 0
    for rnd in plain_rounds(forest, 2):
        g, h = gbt_reference.grad_hess("multi:softmax", margin, y.astype(np.float64))
        for c, tree in rnd:
            found = _judge_tree(tree, bins, cuts, g[:, c], h[:, c], MC_GAMMA, MC_MINCW)
            broken |= set(found)
            depth = gbt_reference.node_depths(tree)
            leaves_above_the_last_level += int(((tree["left"] < 0) & (depth < MC_DEPTH)).sum())
            splits += int((tree["left"] >= 0).sum())
        for c, tree in rnd:
            margin[:, c] += gbt_reference.tree_margin(tree, x)
    assert broken == want, broken
    if ignored is None:
        # both rules stop trees here: most of the twenty stop short of depth 5
        assert leaves_above_the_last_level >= 20 and splits >= 60


# ------------------------------------------- the bundled scan (sparse input)
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_bundled_split_scan_matches_bruteforce_per_original_column(seed):
    """The scan over bundle positions against a brute force over the
    ORIGINAL columns of the densified matrix: every cut of every column, the
    absent rows sent to either side."""
    from sagemaker_xgboost_container_tpu.data import bundling
    from sagemaker_xgboost_container_tpu.ops.histogram import level_histogram
    from tests.sparse_cases import densified, one_hot_csr

    max_bin = 256
    x, _y = one_hot_csr(500, seed, groups=(3, 4, 7, 30), numeric=2, unknown=0.15, absent=0.2)
    out = bundling.bundle_matrices(
        [x], None, max_bin,
        lambda block: compute_cut_points(block, None, max_bin),
        lambda block, cuts, name: apply_cut_points(block, cuts, max_bin, name=name),
        ["train"],
    )
    plan = out.plan
    rng = np.random.RandomState(seed)
    grad = rng.randn(500).astype(np.float32)
    hess = rng.rand(500).astype(np.float32) + 0.1
    G, H = level_histogram(
        jnp.asarray(out.bins[0]), jnp.asarray(grad), jnp.asarray(hess),
        jnp.zeros(500, jnp.int32), 1, max_bin + 1,
    )
    splits = plan.tables.find_best_splits(
        G, H, None, reg_lambda=LAM, gamma=GAMMA, min_child_weight=MINCW
    )
    got = plan.original_splits({"feature": np.asarray(splits["feature"]),
                                "bin": np.asarray(splits["bin"])})
    column, own_bin = int(got["feature"][0]), int(got["bin"][0])
    dense_bins = apply_cut_points(densified(x), plan.cut_points, max_bin)
    best = (-np.inf, -1, -1, False)
    for f in range(x.shape[1]):
        gain, b, ml = _brute_best_split(
            dense_bins[:, f].astype(np.int32), grad, hess, len(plan.cut_points[f]), max_bin
        )
        if gain > best[0]:
            best = (gain, f, b, ml)
    assert abs(float(splits["gain"][0]) - best[0]) < 1e-3, (float(splits["gain"][0]), best)
    chosen_gain, chosen_bin, chosen_left = _brute_best_split(
        dense_bins[:, column].astype(np.int32), grad, hess,
        len(plan.cut_points[column]), max_bin,
    )
    assert abs(chosen_gain - best[0]) < 1e-3
    # the node's range word is the chosen member's range
    word = int(splits["range"][0])
    b, p = int(splits["feature"][0]), int(splits["bin"][0])
    assert (word >> 9, word & 511) == (plan.tables.lo[b, p], plan.tables.hi[b, p])
    assert plan.tables.column[b, p] == column and p - plan.tables.lo[b, p] == own_bin
