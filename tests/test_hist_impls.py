"""Histogram engine tests: both builders, both node-total lowerings and the
subtraction path must produce the same trees as the flat scatter-add
reference, and the choosers pick one of each a backend.

The reference's hist tree builder delegates to libxgboost's hist updater
(reference algorithm_mode/train.py:367-376); sibling subtraction is
libxgboost's standard trick (build the lighter child, derive the other as
parent - child). Here the equivalents are exercised over data with missing
values and uneven node occupancy.
"""

import pathlib
import re

import numpy as np
import pytest
import jax.numpy as jnp

from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree


def _chip_knobs(**fields):
    """The chip's program on the CPU: every chooser reads ``tpu`` (Pallas
    histogram, one-hot totals, dense bin fetch, select tables), and
    ``pallas_interpret()`` keeps reading the real backend."""
    return hist_mod.resolve_hist_knobs()._replace(backend="tpu", **fields)


@pytest.fixture
def rand_problem():
    rng = np.random.RandomState(7)
    n, d, num_bins = 3000, 9, 33  # num_bins includes the missing slot
    bins = rng.randint(0, num_bins, size=(n, d)).astype(np.int32)
    grad = rng.randn(n).astype(np.float32)
    hess = rng.rand(n).astype(np.float32) + 0.1
    num_cuts = np.full(d, num_bins - 2, np.int32)
    return bins, grad, hess, num_cuts, num_bins


def _build(bins, grad, hess, num_cuts, num_bins, max_depth=5, knobs=None,
           subtract=True):
    with pytest.MonkeyPatch.context() as mp:
        if not subtract:  # a cache over the cap builds both children directly
            mp.setattr(hist_mod, "SUBTRACT_CACHE_MAX_BYTES", 0)
        tree, row_out = build_tree(
            jnp.asarray(bins),
            jnp.asarray(grad),
            jnp.asarray(hess),
            jnp.asarray(num_cuts),
            max_depth=max_depth,
            num_bins=num_bins,
            knobs=knobs,
        )
    return {k: np.asarray(v) for k, v in tree.items()}, np.asarray(row_out)


def _assert_trees_match(ta, ra, tb, rb, atol=2e-4):
    # Structural decisions must agree on reachable internal nodes EXCEPT
    # where two candidate splits have near-identical gains: impls sum in
    # different orders, so argmax ties may flip. At any disagreeing node the
    # stored gains must be within float tolerance (a genuine bug would pick
    # a split with a materially different gain).
    internal = ~ta["is_leaf"] & ~tb["is_leaf"]
    same = (
        (ta["feature"] == tb["feature"])
        & (ta["bin"] == tb["bin"])
        & (ta["default_left"] == tb["default_left"])
    )
    differs = internal & ~same
    if differs.any():
        ga, gb = ta["gain"][differs], tb["gain"][differs]
        np.testing.assert_allclose(ga, gb, rtol=1e-3, atol=1e-4)
        # a tie flip reroutes rows, so the subtree below may differ; the
        # final predictions are only comparable when no tie flipped
        return
    assert np.array_equal(ta["is_leaf"], tb["is_leaf"])
    np.testing.assert_allclose(ta["leaf_value"], tb["leaf_value"], atol=atol)
    np.testing.assert_allclose(ra, rb, atol=atol)


def test_subtraction_matches_direct(rand_problem):
    t_direct, r_direct = _build(*rand_problem, subtract=False)
    t_sub, r_sub = _build(*rand_problem)
    _assert_trees_match(t_direct, r_direct, t_sub, r_sub)


@pytest.mark.parametrize("impl", ["pallas"])
def test_impls_match_flat(rand_problem, impl):
    assert hist_mod.choose_hist_impl("tpu") == impl
    t0, r0 = _build(*rand_problem, subtract=False)
    t1, r1 = _build(*rand_problem, subtract=False, knobs=_chip_knobs())
    _assert_trees_match(t0, r0, t1, r1)


def test_matmul_subtract_combo(rand_problem):
    """The Pallas one-hot matmul with sibling subtraction against the flat
    builder with both children built directly."""
    t0, r0 = _build(*rand_problem, subtract=False)
    t1, r1 = _build(*rand_problem, knobs=_chip_knobs())
    _assert_trees_match(t0, r0, t1, r1)


def test_matmul_precision_modes(rand_problem):
    """The Pallas kernel's two operand precisions against the flat builder:
    the program's (``bf16x2``) and the failing control's (``bf16``)."""
    bins, grad, hess, num_cuts, num_bins = rand_problem
    node = np.zeros(len(grad), np.int32)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(node))
    ref_G, ref_H = hist_mod._hist_flat(*args, 1, num_bins)
    assert hist_mod.HIST_PRECISIONS == ("bf16x2", "bf16")
    for prec, tol in [("bf16x2", 5e-4), ("bf16", 0.3)]:
        G, H = hist_mod._hist_pallas(*args, 1, num_bins, prec=prec)
        assert float(jnp.abs(G - ref_G).max()) < tol, prec
        assert float(jnp.abs(H - ref_H).max()) < tol, prec
    with pytest.raises(ValueError, match="f32"):
        hist_mod._hist_pallas(*args, 1, num_bins, prec="f32")


def test_node_totals_matches_histogram(rand_problem):
    bins, grad, hess, num_cuts, num_bins = rand_problem
    rng = np.random.RandomState(3)
    node = rng.randint(-1, 4, size=len(grad)).astype(np.int32)
    G, H = hist_mod._hist_flat(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(node), 4, num_bins,
    )
    gt, ht = hist_mod.node_totals(
        jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(node), 4
    )
    np.testing.assert_allclose(
        np.asarray(gt), np.asarray(G[:, 0, :].sum(-1)), rtol=1e-5, atol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(ht), np.asarray(H[:, 0, :].sum(-1)), rtol=1e-5, atol=1e-4
    )


def test_lossguide_subtraction_matches_direct(rand_problem):
    from sagemaker_xgboost_container_tpu.ops.lossguide import build_tree_lossguide

    bins, grad, hess, num_cuts, num_bins = rand_problem

    def build(cache_cap=None):
        with pytest.MonkeyPatch.context() as mp:
            if cache_cap is not None:
                mp.setattr(hist_mod, "SUBTRACT_CACHE_MAX_BYTES", cache_cap)
            tree, row_out = build_tree_lossguide(
                jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
                jnp.asarray(num_cuts), max_leaves=16, num_bins=num_bins,
            )
        return {k: np.asarray(v) for k, v in tree.items()}, np.asarray(row_out)

    t0, r0 = build(cache_cap=0)
    t1, r1 = build()
    _assert_trees_match(t0, r0, t1, r1)


def test_lossguide_predict_depth_adaptive():
    """In-training eval of a lossguide tree iterates only to the true depth
    (while_loop early exit), and leaf routing matches the reference direct
    traversal."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(11)
    X = rng.rand(600, 5).astype(np.float32)
    y = (np.sin(6 * X[:, 0]) + X[:, 1]).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    log = {}

    class Rec:
        def after_iteration(self, model, epoch, evals_log):
            log.update(
                {k: {m: list(v) for m, v in d.items()} for k, d in evals_log.items()}
            )
            return False

    forest = train(
        {"grow_policy": "lossguide", "max_leaves": 32, "max_depth": 0, "eta": 0.3},
        dtrain, num_boost_round=5, evals=[(dtrain, "train")], callbacks=[Rec()],
    )
    # in-training eval (predict_binned path) must agree with the forest's
    # own host predict (true-depth traversal)
    final_rmse = log["train"]["rmse"][-1]
    direct = float(np.sqrt(np.mean((forest.predict(X) - y) ** 2)))
    assert abs(final_rmse - direct) < 1e-4, (final_rmse, direct)


def test_colsample_bynode_actually_wired():
    """Regression: colsample_bynode must reach the tree builder through the
    train() path (it was parsed but silently dropped from the builder
    kwargs). An aggressive bynode setting must change the trees."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(17)
    X = rng.rand(800, 8).astype(np.float32)
    y = (X @ rng.rand(8).astype(np.float32)).astype(np.float32)
    dtrain = DataMatrix(X, labels=y)
    base = {"max_depth": 4, "eta": 0.3, "seed": 5}
    full = train(dict(base), dtrain, num_boost_round=3)
    narrow = train(
        dict(base, colsample_bynode=0.15), dtrain, num_boost_round=3
    )
    full_feats = np.concatenate([t.feature[~t.is_leaf] for t in full.trees])
    narrow_feats = np.concatenate([t.feature[~t.is_leaf] for t in narrow.trees])
    assert full_feats.shape != narrow_feats.shape or not np.array_equal(
        full_feats, narrow_feats
    ), "colsample_bynode had no effect on tree structure"


def test_route_impls_equivalent(monkeypatch):
    """train() under the dense bin fetch and the select table lookup must
    build identical trees to the gathers (both levelwise routing and binned
    eval prediction use them, so the logged validation losses must agree
    too). The lowerings follow the session snapshot's backend and the width
    rules; tests/test_row_routing.py has the pieces."""
    from sagemaker_xgboost_container_tpu.ops import tree_build
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(5)
    X = rng.rand(3000, 7).astype(np.float32)
    X[rng.rand(3000, 7) < 0.1] = np.nan
    y = (np.nan_to_num(X[:, 0]) + np.nan_to_num(X[:, 2]) > 1).astype(np.float32)
    d = DataMatrix(X[:2400], labels=y[:2400])
    dval = DataMatrix(X[2400:], labels=y[2400:])
    params = {"objective": "binary:logistic", "max_depth": 5, "_rounds_per_dispatch": 2}

    class KeepLog:
        def after_iteration(self, forest, rnd, evals_log):
            self.evals_log = evals_log
            return False

    forests, logs = {}, {}
    # the chip's program twice: as the width rules pick at this width (dense,
    # select), then with both rules cut to zero (gathers)
    for side, max_width in (("rules", None), ("gathers", 0)):
        if max_width is not None:
            monkeypatch.setattr(tree_build, "ROUTE_DENSE_MAX_WIDTH", max_width)
            monkeypatch.setattr(tree_build, "NODE_TABLE_SELECT_MAX_WIDTH", max_width)
        assert tree_build.choose_route_impl("tpu", 7) == (
            "gather" if side == "gathers" else "dense"
        )
        keep = KeepLog()
        forests[side] = train(
            params, d, num_boost_round=4, evals=[(d, "train"), (dval, "validation")],
            callbacks=[keep], verbose_eval=False, hist_knobs=_chip_knobs(),
        )
        logs[side] = {k: dict(v) for k, v in keep.evals_log.items()}
    np.testing.assert_array_equal(
        np.asarray(forests["rules"].predict_margin(X)),
        np.asarray(forests["gathers"].predict_margin(X)),
    )
    assert logs["rules"] == logs["gathers"]
    assert len(logs["rules"]["validation"]["logloss"]) == 4


@pytest.mark.parametrize("B, split", [(257, True), (129, True), (200, False)])
def test_mxu_aligned_hist_matches_flat(B, split):
    """Whenever B = k*128 + 1 (max_bin=256 -> B=257 pads to 384 MXU lanes
    otherwise) the Pallas kernel splits the missing-bin column out of the
    one-hot dot; any other B keeps it inside. Both must match the flat
    scatter reference bin-for-bin, including the missing column."""
    assert hist_mod._mxu_split_missing(B) is split
    rng = np.random.RandomState(11)
    n, d, W = 4000, 5, 8
    bins = jnp.asarray(rng.randint(0, B, size=(n, d)).astype(np.int32))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray((rng.rand(n) + 0.1).astype(np.float32))
    node = jnp.asarray(rng.randint(-1, W, size=n).astype(np.int32))

    def hist(impl, prec="bf16x2"):
        G, H = hist_mod.level_histogram(
            bins, grad, hess, node, W, B, impl=impl, knobs=_chip_knobs(precision=prec)
        )
        return np.asarray(G), np.asarray(H)

    G0, H0 = hist("flat")
    assert G0[:, :, B - 1].any(), "fixture must exercise the missing bin"
    # bf16x2 (the program) to split-precision tolerance; bf16 (the control)
    # to operand-rounding tolerance
    for prec, atol in (("bf16x2", 5e-3), ("bf16", 0.2)):
        G1, H1 = hist("pallas", prec)
        np.testing.assert_allclose(G1, G0, atol=atol, err_msg=prec)
        np.testing.assert_allclose(H1, H0, atol=atol, err_msg=prec)


def test_node_totals_onehot_matches_segment():
    """``onehot`` (MXU contraction, no sort) must match the segment_sum
    lowering used for last-level leaf weights."""
    rng = np.random.RandomState(12)
    n, W = 70000, 256  # more than one chunk of TOTALS_CHUNK_ROWS
    assert n > hist_mod.TOTALS_CHUNK_ROWS
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray((rng.rand(n) + 0.1).astype(np.float32))
    node = jnp.asarray(rng.randint(-1, W, size=n).astype(np.int32))

    def totals(impl):
        g, h = hist_mod.node_totals(grad, hess, node, W, impl=impl)
        return np.asarray(g), np.asarray(h)

    g0, h0 = totals("segment")
    g1, h1 = totals("onehot")
    np.testing.assert_allclose(g1, g0, rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(h1, h0, rtol=1e-4, atol=1e-3)


def _level_problem(seed, n, d, B, W, dtype=np.uint8):
    rng = np.random.RandomState(seed)
    return (
        jnp.asarray(rng.randint(0, B, size=(n, d)).astype(dtype)),
        jnp.asarray(rng.randn(n).astype(np.float32)),
        jnp.asarray((rng.rand(n) + 0.1).astype(np.float32)),
        jnp.asarray(rng.randint(-1, W, size=n).astype(np.int32)),  # -1: dead
    )


@pytest.mark.parametrize("B", [129, 257])
@pytest.mark.parametrize("d", [5, 28, 40])
@pytest.mark.parametrize("W", [1, 2, 4, 8, 16, 32, 64])
def test_operand_rows_follow_the_level(W, d, B):
    """The kernel's gradient operand holds the level's 2W rows (g of node w
    in row w, h in row W + w), padded once to the bf16 tile, whatever the
    width: a short group (5), a group with a padded tail (28), a full group
    and a padded one (40). Every level against the flat reference, dead rows
    excluded, the aligned missing-bin dot included (B = k*128 + 1)."""
    assert hist_mod._operand_rows(W) == max(16, 2 * W)
    dtype = np.uint8 if B <= 256 else np.uint16
    bins, grad, hess, node = _level_problem(13 + W, 2048, d, B, W, dtype)
    assert (np.asarray(node) < 0).any()
    G0, H0 = hist_mod.level_histogram(bins, grad, hess, node, W, B, impl="flat")
    G1, H1 = hist_mod.level_histogram(bins, grad, hess, node, W, B, impl="pallas")
    assert G1.shape == (W, d, B)
    np.testing.assert_allclose(np.asarray(G1), np.asarray(G0), atol=5e-3)
    np.testing.assert_allclose(np.asarray(H1), np.asarray(H0), atol=5e-3)


@pytest.mark.parametrize(
    "W, B, prec, fold",
    [
        # 256 bin lanes (max_bin 256 with its missing bin split out, or 255)
        (1, 257, "bf16x2", 2), (2, 257, "bf16x2", 2), (4, 257, "bf16x2", 2),
        (8, 257, "bf16x2", 2), (16, 257, "bf16x2", 1), (32, 257, "bf16x2", 1),
        (64, 257, "bf16x2", 1), (8, 256, "bf16x2", 2), (8, 200, "bf16x2", 2),
        # one tile: max_bin <= 127
        (1, 128, "bf16x2", 1), (1, 129, "bf16x2", 1), (8, 64, "bf16x2", 1),
        # u16 bins: two of four tiles at W <= 8, whole tiles only (three: none)
        (1, 513, "bf16x2", 2), (8, 513, "bf16x2", 2), (16, 513, "bf16x2", 1),
        (1, 385, "bf16x2", 1), (8, 1025, "bf16x2", 2),
        # the one-pass control streams half the rows
        (8, 257, "bf16", 2), (16, 257, "bf16", 2), (32, 257, "bf16", 1),
        (8, 513, "bf16", 4), (16, 513, "bf16", 2), (1, 129, "bf16", 1),
    ],
)
def test_bin_fold_follows_the_level_the_bin_lanes_and_the_precision(W, B, prec, fold):
    """``fold`` copies of the stacked operand fit the rows a latch carries
    free (64), and the folded one-hot is whole 128-lane tiles."""
    assert hist_mod.LATCH_FREE_ROWS == 64
    rows, lanes = hist_mod._operand_rows(W), hist_mod._bin_lanes(B)
    got = hist_mod._bin_fold(rows, lanes, prec)
    assert got == fold
    assert got * rows * (2 if prec == "bf16x2" else 1) <= 64 or got == 1
    assert (lanes // 128) % got == 0


def test_a_fold_that_cuts_no_whole_tiles_is_refused():
    with pytest.raises(ValueError, match="whole tiles"):
        hist_mod._pallas_hist_fn(512, 5, 16, 1, 385, 512, "bf16x2", True, True, 16, 1, 2)


@pytest.mark.parametrize(
    "policy, depth, leaves, subtract, levels",
    [
        ("depthwise", 8, 0, True, [(1, 1), (1, 1), (2, 1), (4, 1), (8, 1), (16, 1), (32, 1), (64, 1)]),
        ("depthwise", 3, 0, False, [(1, 1), (2, 1), (4, 1)]),
        ("depthwise", 1, 0, False, [(1, 1)]),
        # PR 43: a loss-guided build's passes hold PASS_SLOTS node slots (eight
        # leaves' left children, or four leaves' two children) and run at most
        # once a split step; fewer slots where the tree has fewer open leaves
        ("lossguide", 0, 31, True, [(1, 1), (8, 30)]),
        ("lossguide", 0, 31, False, [(1, 1), (8, 30)]),
        ("lossguide", 0, 4, True, [(1, 1), (2, 3)]),
        ("lossguide", 0, 2, False, [(1, 1), (2, 1)]),
        ("lossguide", 0, 1, True, [(1, 1)]),
    ],
)
def test_round_hist_levels_are_the_calls_a_build_issues(policy, depth, leaves, subtract, levels):
    from sagemaker_xgboost_container_tpu.ops.lossguide import pass_nodes

    slots = pass_nodes(leaves, subtract) if policy == "lossguide" else 1
    assert hist_mod.round_hist_levels(policy, depth, leaves, subtract, slots) == levels


@pytest.mark.parametrize(
    "n, d, B, prec, depth, pct",
    [
        # higgs-d8: W = 1, 1, 2 at a quarter (two features a tile, PR 47), W = 4, 8 at half
        (8_800_000, 28, 257, "bf16x2", 8, 59.375),
        (2_270_296, 136, 257, "bf16x2", 8, 59.375),   # mslr-ndcg
        # both Criteo cells, a chip: the twentieth tile holds one real feature and is whole
        (16_387_491, 39, 257, "bf16x2", 8, 100.0 * (3 * 20 + 2 * 39 + 3 * 78) / (8 * 78)),
        (8_800_000, 28, 257, "bf16", 8, 50.0),        # the one-pass control: W = 4 packs too
        (8_800_000, 28, 128, "bf16x2", 8, 75.0),      # one bin tile: nothing folds, W <= 4 packs
        (8_800_000, 28, 257, "bf16x2", 4, 31.25),     # a depth-4 tree: every level
    ],
)
def test_onehot_tile_plan_counts_what_the_fold_saves(n, d, B, prec, depth, pct):
    levels = hist_mod.round_hist_levels("depthwise", depth, 0, True)
    latched, unfolded = hist_mod.round_onehot_tiles(levels, n, d, B, prec)
    row_tiles = -(-n // (512 * 32)) * 512 * 32 // 128
    assert unfolded == depth * row_tiles * d * (hist_mod._bin_lanes(B) // 128)
    assert 100.0 * latched / unfolded == pct
    twice = hist_mod.round_onehot_tiles(levels, n, d, B, prec, trees_per_round=2)
    assert twice == (2 * latched, 2 * unfolded)
    assert hist_mod.round_onehot_tiles(levels, 0, d, B, prec) == (0, 0)


def test_tile_plan_packs_the_one_tree_levels_alone():
    """A tile of two features with one real one is a whole tile (39 columns
    are 20 tiles at W <= 2), and the class trees' operand is full at W = 1:
    `mnist8m-mc10`'s ten depth-5 class trees latch the 31,109,120 tiles they
    latched (the number ``tests/benchmark/test_multiclass_cell.py`` and the
    cell hold)."""
    row_tiles = -(-16_387_491 // (512 * 32)) * 512 * 32 // 128
    root = hist_mod.round_onehot_tiles([(1, 1)], 16_387_491, 39, 257, "bf16x2")
    assert root == (20 * row_tiles, 2 * 39 * row_tiles)
    level2 = hist_mod.round_onehot_tiles([(4, 1)], 16_387_491, 39, 257, "bf16x2")
    assert level2 == (39 * row_tiles, 2 * 39 * row_tiles)
    levels = hist_mod.round_hist_levels("depthwise", 5, 0, True)
    assert hist_mod.round_onehot_tiles(
        levels, 506_250, 784, 257, "bf16x2", trees_per_round=10, class_trees=10
    ) == (31_109_120, 311_091_200)


@pytest.mark.parametrize("chip", [True, False])
def test_session_states_its_tile_plan_where_the_kernel_builds(chip):
    """The two gauges the benchmark's ``hist_tiles_latched_pct`` divides: set
    at session build from shapes, 0 where the builder is not the kernel."""
    import json

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    root = pathlib.Path(__file__).parent.parent
    spec = json.loads((root / "benchmark/layer_metrics/hist_tiles_latched_pct.json").read_text())
    assert spec["reader"] == "gauge_ratio" and spec["args"]["scale"] == 100.0
    rng = np.random.RandomState(5)
    X = rng.rand(600, 4).astype(np.float32)
    train(
        {"max_depth": 5, "max_bin": 256}, DataMatrix(X, labels=X[:, 0]), num_boost_round=1,
        hist_knobs=_chip_knobs() if chip else None,
    )
    gauges = {
        name: family[0].value for name, _kind, _help, family in REGISTRY.collect()
        if name in (spec["args"]["over"], spec["args"]["under"])
    }
    over, under = gauges[spec["args"]["over"]], gauges[spec["args"]["under"]]
    if not chip:
        assert (over, under) == (0, 0)
        return
    # 600 rows pad to 1,024: 8 row tiles x 4 features x 2 bin tiles, 5 levels
    assert under == 5 * 8 * 4 * 2
    # W = 1, 1, 2, 4, 8: every level of a depth-5 tree folds, and at the first
    # three the four features are two tiles (PR 47)
    assert over == (3 * 8 * 2 + 2 * 8 * 4)


@pytest.mark.parametrize("d, dtype", [(28, np.uint16), (40, np.uint8), (70, np.uint8)])
def test_padding_features_get_no_dot(d, dtype):
    """The operand block is a whole (fg, block) tile, but a padding feature
    costs neither a one-hot nor a dot. Its bins are all 0, so one dot would
    leave the node totals in bin 0 of its rows of the kernel's RAW output:
    they must stay zero, and so must its missing-bin rows."""
    W, B, n = 2, 129, 1024
    bins, grad, hess, node = _level_problem(5, n, d, B, W, dtype)
    fg = hist_mod._pallas_feature_group(d, dtype)
    d_pad = -(-d // fg) * fg
    assert d_pad > d
    rows = hist_mod._operand_rows(W)
    fn = hist_mod._pallas_hist_fn(
        n, d, fg, W, B, hist_mod.PALLAS_ROW_BLOCK, "bf16x2", True, True, rows, 1
    )
    main, miss = fn(
        jnp.pad(bins.T, [(0, d_pad - d), (0, 0)]),
        jnp.stack([grad, hess]),
        jnp.where(node >= 0, node, W)[None, :],
        hist_mod._live_tiles(None, d, fg, B),   # the unfolded body's flags: every tile
    )
    assert main.shape == (1, d_pad, rows, 128) and miss.shape == (1, d_pad, 2 * rows)
    assert np.asarray(main[0, d - 1, : 2 * W, 0]).any(), "the last real feature's bin 0 is hit"
    assert not np.asarray(main[0, d:]).any()
    assert not np.asarray(miss[0, d:]).any()
    # and the rows of the operand past 2W hold nothing either
    assert not np.asarray(main[0, :, 2 * W:]).any()


def test_row_chunks_keep_the_partial_sums(monkeypatch):
    """A shallow level's rows are accumulated in outer chunks of the row axis,
    each into an output slab of its own, added after the kernel: at the
    cells' sizes 32 over W of them, as many accumulator cells a (feature,
    bin) as the root level has. Rows that are no multiple of chunks x block
    are padded with dead ones."""
    assert hist_mod.HIST_ROW_CHUNKS == 32
    cap = hist_mod._chunk_cap
    # higgs-d8's 17,188 row blocks, mslr-ndcg's 4,435, a small job's 196, a test's 6
    assert [cap(s) for s in (17188, 4435, 196, 63, 6)] == [32, 32, 4, 1, 1]
    chunks = hist_mod._row_chunks
    assert [chunks(W, 32) for W in (1, 2, 3, 4, 8, 16, 32, 64)] == [32, 16, 8, 8, 4, 2, 1, 1]
    assert [chunks(W, 1) for W in (1, 2, 64)] == [1, 1, 1]

    monkeypatch.setattr(hist_mod, "PALLAS_ROW_BLOCK", 128)
    n, d, B = 16500, 5, 129  # 129 blocks of 128: four chunks, padded to 132 blocks
    assert cap(-(-n // 128)) == 4 and n % (4 * 128)
    for W in (1, 2, 8):
        bins, grad, hess, node = _level_problem(17, n, d, B, W)
        G0, H0 = hist_mod.level_histogram(bins, grad, hess, node, W, B, impl="flat")
        G1, H1 = hist_mod.level_histogram(bins, grad, hess, node, W, B, impl="pallas")
        np.testing.assert_allclose(np.asarray(G1), np.asarray(G0), atol=5e-3)
        np.testing.assert_allclose(np.asarray(H1), np.asarray(H0), atol=5e-3)


@pytest.mark.parametrize("impl", ["flat", "pallas"])
def test_empty_input_yields_zero_histograms(impl):
    """n==0 (empty shard / empty eval set) must return zeros from every
    impl — the pallas grid would be (0,) and its step-0 out_ref init never
    runs, so without an explicit guard it returns uninitialized VMEM
    (ADVICE r2)."""
    bins = jnp.zeros((0, 4), jnp.uint8)
    grad = jnp.zeros((0,), jnp.float32)
    hess = jnp.zeros((0,), jnp.float32)
    node = jnp.zeros((0,), jnp.int32)
    G, H = hist_mod.level_histogram(bins, grad, hess, node, 4, 17, impl=impl)
    assert G.shape == (4, 4, 17) and H.shape == (4, 4, 17)
    assert not np.asarray(G).any() and not np.asarray(H).any()


@pytest.mark.parametrize("impl", ["segment", "onehot"])
def test_empty_input_yields_zero_totals(impl):
    grad = jnp.zeros((0,), jnp.float32)
    node = jnp.zeros((0,), jnp.int32)
    g, h = hist_mod.node_totals(grad, grad, node, 8, impl=impl)
    assert g.shape == (8,) and not np.asarray(g).any()
    assert h.shape == (8,) and not np.asarray(h).any()


def test_multiclass_vmap_over_pallas():
    """Multiclass training vmaps the tree builder over classes; the pallas
    histogram kernel must survive the vmap batching rule."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(21)
    X = rng.randn(900, 4).astype(np.float32)
    y = ((X[:, 0] > 0).astype(int) + (X[:, 1] > 0).astype(int)).astype(
        np.float32
    )
    params = {"objective": "multi:softprob", "num_class": 3, "max_depth": 3}
    f1 = train(
        dict(params), DataMatrix(X, labels=y), num_boost_round=2,
        hist_knobs=_chip_knobs(),
    )
    f0 = train(dict(params), DataMatrix(X, labels=y), num_boost_round=2)
    np.testing.assert_allclose(
        np.asarray(f1.predict(X)), np.asarray(f0.predict(X)), atol=1e-4
    )


@pytest.mark.parametrize(
    "backend, hist, totals",
    [("tpu", "pallas", "onehot"), ("cpu", "flat", "segment"), ("gpu", "flat", "segment")],
)
@pytest.mark.parametrize("chooser", ["choose_hist_impl", "choose_totals_impl"])
def test_choosers_pick_one_lowering_a_backend(chooser, backend, hist, totals):
    """The one-hot matmul forms where scatters serialize (the TPU), the
    segment sums elsewhere: the defaults the environment used to override."""
    want = hist if chooser == "choose_hist_impl" else totals
    assert getattr(hist_mod, chooser)(backend) == want


def test_unknown_builder_or_lowering_raises():
    z = jnp.zeros((4,), jnp.float32)
    node = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="matmul"):
        hist_mod.level_histogram(
            jnp.zeros((4, 2), jnp.uint8), z, z, node, 1, 9, impl="matmul"
        )
    with pytest.raises(ValueError, match="pallas"):
        hist_mod.node_totals(z, z, node, 1, impl="pallas")


def test_hist_knobs_is_what_a_session_must_freeze():
    assert hist_mod.HistKnobs._fields == ("backend", "precision")
    knobs = hist_mod.resolve_hist_knobs()
    assert knobs == ("cpu", "bf16x2")


@pytest.mark.parametrize("value", ["f32", "fp8", ""])
def test_unknown_precision_raises_at_session_build(monkeypatch, value):
    """``GRAFT_HIST_MM_PREC`` has two values: the program's and the failing
    control's. Anything else stops the job before it traces a round."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    monkeypatch.setenv("GRAFT_HIST_MM_PREC", value)
    X = np.random.RandomState(0).rand(40, 3).astype(np.float32)
    with pytest.raises(ValueError, match="GRAFT_HIST_MM_PREC"):
        train({"max_depth": 2}, DataMatrix(X, labels=X[:, 0]), num_boost_round=1)


# the nine histogram knobs PR 30 retired, each with a value that took its
# other branch while it lived
RETIRED_KNOBS = {
    "GRAFT_HIST_IMPL": "flat",
    "GRAFT_TOTALS_IMPL": "segment",
    "GRAFT_HIST_CHUNK": "1024",
    "GRAFT_HIST_BLOCK": "256",
    "GRAFT_HIST_ALIGN": "0",
    "GRAFT_HIST_VNODES": "0",
    "GRAFT_VNODE_VMEM": "0",
    "GRAFT_HIST_SUBTRACT": "0",
    "GRAFT_SUBTRACT_MEM": "0",
    "GRAFT_HIST_COMM": "reduce_scatter",  # PR 45: one collective, one schedule
    "GRAFT_HIST_OVERLAP": "0",
}


@pytest.fixture(scope="module")
def chip_program_tree():
    """One tree of the chip's program (interpreted), no retired name set."""
    rng = np.random.RandomState(19)
    n, d, num_bins = 1500, 6, 129  # 129: the aligned missing-bin dot too
    problem = (
        rng.randint(0, num_bins, size=(n, d)).astype(np.uint8),
        rng.randn(n).astype(np.float32),
        rng.rand(n).astype(np.float32) + 0.1,
        np.full(d, num_bins - 2, np.int32),
        num_bins,
    )
    return problem, _build(*problem, max_depth=4, knobs=_chip_knobs())


@pytest.mark.parametrize("name", sorted(RETIRED_KNOBS))
def test_retired_knob_is_dead(monkeypatch, chip_program_tree, name):
    """Setting a retired name changes no bit of a tree, and no module of the
    package reads it."""
    problem, (tree, row_out) = chip_program_tree
    monkeypatch.setenv(name, RETIRED_KNOBS[name])
    tree_set, row_out_set = _build(*problem, max_depth=4, knobs=_chip_knobs())
    for field in tree:
        np.testing.assert_array_equal(tree_set[field], tree[field], err_msg=field)
    np.testing.assert_array_equal(row_out_set, row_out)

    import sagemaker_xgboost_container_tpu as package

    root = pathlib.Path(package.__file__).parent
    word = re.compile(r"\b%s\b" % name)
    readers = [
        str(path.relative_to(root))
        for path in sorted(root.rglob("*.py"))
        if word.search(path.read_text())
    ]
    assert readers == []


@pytest.mark.parametrize(
    "backend, interpreted",
    [("cpu", True), ("tpu", False), ("gpu", False), ("some_new_accelerator", False)],
)
def test_pallas_interpreted_only_on_the_cpu_backend(monkeypatch, backend, interpreted):
    """An accelerator under any name compiles the Pallas kernels or fails
    loudly; only the CPU backend (tests, rehearsals) interprets them."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert hist_mod.pallas_interpret() is interpreted


def test_pallas_feature_group_is_tile_aligned_and_bounded():
    """The accumulator block spans one feature group: whole sublane tiles
    of the bin dtype, at most 32 features, whatever the matrix width."""
    fg = hist_mod._pallas_feature_group
    assert fg(28, np.uint16) == 32 and fg(28, np.uint8) == 32
    assert fg(8, np.uint16) == 16 and fg(8, np.uint8) == 32
    assert fg(9, np.int32) == 16
    assert fg(136, np.uint16) == 32 and fg(5000, np.uint8) == 32


def test_pallas_hist_wider_than_one_feature_group():
    """More features than one group: the grid's feature axis runs several
    accumulator blocks, each equal to the flat reference's columns."""
    rng = np.random.RandomState(3)
    n, d, num_bins, W = 400, 70, 17, 2
    bins = rng.randint(0, num_bins, size=(n, d)).astype(np.uint8)
    grad = rng.randn(n).astype(np.float32)
    hess = rng.rand(n).astype(np.float32)
    node = rng.randint(-1, W, size=n).astype(np.int32)
    args = (jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(node))
    Gp, Hp = hist_mod._hist_pallas(*args, W, num_bins)
    Gf, Hf = hist_mod._hist_flat(*args, W, num_bins)
    np.testing.assert_allclose(np.asarray(Gp), np.asarray(Gf), atol=2e-4)
    np.testing.assert_allclose(np.asarray(Hp), np.asarray(Hf), atol=2e-4)
