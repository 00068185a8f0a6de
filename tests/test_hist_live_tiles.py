"""The one-hot tiles a column's cuts cannot reach (PR 49).

The calls of the level histogram kernel that do not fold take a dot a
128-lane bin tile, and tile ``t`` > 0 of feature ``f`` is live where a bin of
the column can land on it: ``128 * t <= num_cuts[f]``, or the missing bin
lives there. The kernel takes a feature group's live tiles off a list, four
entries a conditional block (``ops/histogram.py::_live_tiles``,
``_pallas_hist_tiles_fn``), so a dead tile is not built unless it fills a
list's last block. The lists are made on the chip from the round program's
own ``num_cuts`` input, so: the same bits as the call that builds every
tile; one program whatever the cuts are; and none of what PR 48's sorted
column groups cost in front of the window (a second copy of the bins, kernel
bodies of their own).
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree

# a constant column, a binary one, the widest that needs one tile, the
# narrowest that needs two, a full one
CUTS = np.array([0, 1, 127, 128, 255, 5, 200], np.int32)


def _chip_knobs():
    return hist_mod.resolve_hist_knobs()._replace(backend="tpu")


def _columns(seed, n, cuts, B, missing):
    """u16 [n, d]: column f uniform over its bins 0 .. cuts[f], a tenth of
    the cells in the missing bin where ``missing``."""
    rng = np.random.RandomState(seed)
    bins = np.stack([rng.randint(0, c + 1, n) for c in cuts], axis=1)
    if missing:
        bins[rng.rand(n, len(cuts)) < 0.1] = B - 1
    return jnp.asarray(bins.astype(np.uint16))


def _same_bits(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))


@pytest.mark.parametrize("missing", [False, True], ids=["dense", "missing"])
@pytest.mark.parametrize(
    "W, trees", [(16, 0), (32, 0), (64, 0), (1, 10), (8, 10)],
    ids=["W16", "W32", "W64", "ten_classes_W1", "ten_classes_W8"],
)
def test_skipped_tiles_leave_the_same_bits(W, trees, missing):
    """Every call that does not fold: one tree at W >= 16 and the ten class
    trees in one operand at any W. The flags from the columns' cuts against
    ``reach=None``, which builds every tile: bit for bit, and both the flat
    reference's histogram."""
    n, B = 3000, 257
    rows = hist_mod._operand_rows(W, max(trees, 1))
    assert hist_mod._bin_fold(rows, 256, "bf16x2") == 1
    assert trees or hist_mod._tile_pack(W, 256, "bf16x2") == 1
    rng = np.random.RandomState(W + trees)
    bins = _columns(W, n, CUTS, B, missing)
    lead = (trees,) if trees else ()
    grad = jnp.asarray(rng.randn(*lead, n).astype(np.float32))
    hess = jnp.asarray((rng.rand(*lead, n) + 0.1).astype(np.float32))
    node = jnp.asarray(rng.randint(-1, W, size=lead + (n,)).astype(np.int32))
    every = hist_mod._hist_pallas(bins, grad, hess, node, W, B)
    live = hist_mod._hist_pallas(bins, grad, hess, node, W, B, reach=jnp.asarray(CUTS))
    assert _same_bits(every, live)
    flat = lambda g, h, nd: hist_mod._hist_flat(bins, g, h, nd, W, B)  # noqa: E731
    want = jax.vmap(flat)(grad, hess, node) if trees else flat(grad, hess, node)
    for got, ref in zip(live, want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=5e-3)


def test_the_list_is_read_and_not_assumed():
    """A reach that understates a column drops the bins above it: its second
    tile is never built, unless it fills the last block of the list. What no
    session does (bins and cuts come from one sketch); here it shows the skip
    is real on the interpreter too. Six columns, the last one live: one block
    of four, the live column and the first three dead ones; the other two
    dead columns' second tiles stay zeros."""
    W, B, n = 16, 257, 2048
    assert hist_mod.LIVE_CHUNK_SLOTS == 4
    bins = _columns(3, n, np.array([255] * 6, np.int32), B, True)
    rng = np.random.RandomState(3)
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.ones(n, jnp.float32)
    node = jnp.asarray(rng.randint(0, W, size=n).astype(np.int32))
    G_all, H_all = hist_mod._hist_pallas(bins, grad, hess, node, W, B)
    G, H = hist_mod._hist_pallas(
        bins, grad, hess, node, W, B, reach=jnp.asarray([127] * 5 + [255], jnp.int32)
    )
    assert np.asarray(H_all[:, 3:5, 128:256]).any(axis=(0, 2)).all()
    assert not np.asarray(H[:, 3:5, 128:256]).any() and not np.asarray(G[:, 3:5, 128:256]).any()
    # their first tile, their missing bin and the block's four columns are whole
    assert np.array_equal(np.asarray(H[:, :, :128]), np.asarray(H_all[:, :, :128]))
    assert np.array_equal(np.asarray(H[:, :, 256]), np.asarray(H_all[:, :, 256]))
    for f in (0, 1, 2, 5):
        assert np.array_equal(np.asarray(H[:, f]), np.asarray(H_all[:, f]))


@pytest.mark.parametrize("B, tile", [(201, 1), (300, 2), (129, None), (257, None)])
def test_the_missing_bins_tile_stays_live(B, tile):
    """Where the missing bin is not split out (``_mxu_split_missing``) it
    lives on the bin axis's last used tile: that tile is live for a column
    of 5 cuts, and the tiles between are not. The kernel's operand: for
    every tile above the first, the group's features with the live ones
    first, then their count; a padding feature is live nowhere."""
    fg, tiles = 16, hist_mod._bin_lanes(B) // 128
    lists = np.asarray(hist_mod._live_tiles(jnp.asarray([5, B - 2], jnp.int32), 2, fg, B))
    if tiles == 1:
        assert lists.shape == (1, 1)           # nothing above the one tile
        return
    lists = lists.reshape(tiles - 1, fg + 1)
    top = (B - 2) // 128                       # the wide column's highest data tile
    for t in range(1, tiles):
        live = [f for f, reach in enumerate((5, B - 2)) if 128 * t <= reach or t == tile]
        assert lists[t - 1, fg] == len(live) == (2 if t == tile else int(t <= top))
        assert list(lists[t - 1, :len(live)]) == live
        assert sorted(lists[t - 1, :fg]) == list(range(fg))
    every = np.asarray(hist_mod._live_tiles(None, 2, fg, B)).reshape(tiles - 1, fg + 1)
    assert (every[:, fg] == 2).all() and (every[:, :2] == [0, 1]).all()
    if tile is None:
        return
    W, n = 16, 2048
    bins = _columns(B, n, np.array([5, B - 2], np.int32), B, True)
    assert (np.asarray(bins[:, 0]) == B - 1).any()
    rng = np.random.RandomState(B)
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.ones(n, jnp.float32)
    node = jnp.asarray(rng.randint(0, W, size=n).astype(np.int32))
    every = hist_mod._hist_pallas(bins, grad, hess, node, W, B)
    live = hist_mod._hist_pallas(
        bins, grad, hess, node, W, B, reach=jnp.asarray([5, B - 2], jnp.int32)
    )
    assert _same_bits(every, live)
    _G, H_flat = hist_mod._hist_flat(bins, grad, hess, node, W, B)
    assert np.asarray(live[1][:, 0, B - 1]).sum() == np.asarray(H_flat[:, 0, B - 1]).sum() > 0


def test_new_cuts_are_new_flags_of_the_one_program():
    """The approx refresh: cuts and bins change between two dispatches of
    the one jitted program. It compiles once and follows the cuts it is
    handed."""
    W, B, n = 16, 257, 2048
    level = jax.jit(
        lambda bins, g, h, nd, cuts: hist_mod.level_histogram(
            bins, g, h, nd, W, B, impl="pallas", reach=cuts
        )
    )
    rng = np.random.RandomState(9)
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.ones(n, jnp.float32)
    node = jnp.asarray(rng.randint(0, W, size=n).astype(np.int32))
    for seed, cuts in enumerate(([3, 255, 127], [255, 90, 128], [0, 0, 0])):
        cuts = np.array(cuts, np.int32)
        bins = _columns(seed, n, cuts, B, True)
        got = level(bins, grad, hess, node, jnp.asarray(cuts))
        assert _same_bits(got, hist_mod._hist_pallas(bins, grad, hess, node, W, B))
    assert level._cache_size() == 1


# --------------------------------------------- what PR 48 was refused for
HIGGS_ROWS, HIGGS_FEATURES, NUM_BINS, DEPTH = 8_800_000, 28, 257, 8


def test_the_round_traces_no_kernel_body_beyond_the_parents(monkeypatch):
    """`higgs-d8`'s tree build lowered for the chip holds the eight
    ``graft_level_histogram`` calls the parent's holds (read off c046af6):
    one a level, no call of its own for the narrow columns. PR 48's sorted
    groups of narrow columns were bodies of their own: 6 to 8 thread-seconds
    of tracing and lowering in front of the window (PERF.md section 6)."""
    knobs = _chip_knobs()
    monkeypatch.setattr(hist_mod, "pallas_interpret", lambda: False)

    def build(bins, grad, hess, num_cuts):
        return build_tree(bins, grad, hess, num_cuts, DEPTH, NUM_BINS, knobs=knobs)[1]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype)

    n, d = HIGGS_ROWS, HIGGS_FEATURES
    lowered = jax.jit(build).trace(
        shape((n, d), jnp.uint16), shape((n,), jnp.float32), shape((n,), jnp.float32),
        shape((d,), jnp.int32),
    ).lower(lowering_platforms=("tpu",))
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == DEPTH
    assert text.count('kernel_name = "graft_level_histogram"') == DEPTH


def _session(x, y):
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models.booster import TrainConfig, _TrainingSession
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    cfg = TrainConfig({"max_depth": 8, "max_bin": 256})
    forest = Forest(objective_name=cfg.objective, objective_params=cfg.objective_params,
                    base_score=cfg.base_score, num_feature=x.shape[1])
    return _TrainingSession(cfg, DataMatrix(x, labels=y), [], forest, hist_knobs=_chip_knobs())


def _narrow_matrix(n, d, narrow, seed=11):
    """f32 [n, d]: the first ``narrow`` columns hold three values (two cuts),
    the others more distinct values than the sketch has cuts."""
    rng = np.random.RandomState(seed)
    x = rng.rand(n, d).astype(np.float32)
    x[:, :narrow] = rng.randint(0, 3, size=(n, narrow))
    return x, (x[:, -1] > 0.5).astype(np.float32)


def test_the_session_holds_no_array_beside_the_bins():
    """The device arrays a session of 28 columns holds, four of them narrow,
    in bytes: what the parent's holds (read off c046af6 with this test's
    matrix). PR 48 kept a second, column-sorted copy of the bins: 2.10 GB in
    `criteo-tb-d8`."""
    gc.collect()
    before = sum(a.nbytes for a in jax.live_arrays())
    x, y = _narrow_matrix(3000, 28, 4)
    session = _session(x, y)
    gc.collect()
    held = sum(a.nbytes for a in jax.live_arrays()) - before
    bins = session.bins.nbytes
    assert bins == 3000 * 28 * 2
    assert held == SESSION_BYTES and held < 2 * bins


SESSION_BYTES = 204_236  # read off c046af6


# ------------------------------------------------------------- the counter
# the cells' columns of at most 127 cuts (PERF.md section 6, PR 49), spread
# over the columns as evenly as they come
CELL_COLUMNS = {
    "criteo-tb-d8": (16_387_491, 39, 17),
    "mslr-ndcg": (2_270_296, 136, 47),
    "higgs-d8": (8_800_000, 28, 4),
}


def _not_built(reach, fg=32, slots=4):
    """Second tiles the kernel leaves out, counted the slow way: a feature
    group builds its live ones in whole blocks of ``slots``."""
    dead = 0
    for first in range(0, len(reach), fg):
        group = reach[first:first + fg]
        live = sum(r >= 128 for r in group)
        dead += len(group) - min(fg, -(-live // slots) * slots)
    return dead


@pytest.mark.parametrize("cell", sorted(CELL_COLUMNS))
def test_tile_plan_takes_the_dead_tiles_off_the_unfolded_levels(cell):
    """A depth-8 tree with subtraction calls at W = 1, 1, 2, 4, 8 (packed,
    folded: one tile a feature or less, nothing to skip) and 16, 32, 64: the
    count is the parent's less row tiles x the tiles not built at those
    three; all the narrow columns' second tiles but a list's last block's
    fill (17 of 17 in `criteo-tb-d8`'s two groups as spread here, 4 of 4 in
    `higgs-d8`)."""
    n, d, narrow = CELL_COLUMNS[cell]
    levels = hist_mod.round_hist_levels("depthwise", 8, 0, True)
    assert [W for W, _count in levels] == [1, 1, 2, 4, 8, 16, 32, 64]
    reach = np.full(d, 255, np.int32)
    reach[np.linspace(0, d - 1, narrow).astype(int)] = 100
    dead = hist_mod.dead_bin_tiles(reach, 257, np.uint16)
    assert dead == _not_built(list(reach)) and narrow - 3 * -(-d // 32) <= dead <= narrow
    parents = hist_mod.round_onehot_tiles(levels, n, d, 257, "bf16x2")
    row_tiles = -(-n // (512 * 32)) * 512 * 32 // 128
    got = hist_mod.round_onehot_tiles(levels, n, d, 257, "bf16x2", dead_tiles=dead)
    assert got == (parents[0] - 3 * row_tiles * dead, parents[1])
    pct = hist_mod.skipped_tiles_pct(levels, n, d, 257, "bf16x2", dead_tiles=dead)
    assert pct == pytest.approx(100.0 * dead / (2 * d))
    assert hist_mod.skipped_tiles_pct(levels, n, d, 257, "bf16x2") == 0.0
    # full-width columns: nothing left out (39 = 32 + 7: the eighth slot of the
    # second group's second block is a padding feature's tile, one more)
    assert hist_mod.dead_bin_tiles(np.full(d, 255), 257, np.uint16) == (-1 if d == 39 else 0)
    # a tree whose every level folds has nothing the rule reads
    shallow = hist_mod.round_hist_levels("depthwise", 5, 0, True)
    assert hist_mod.skipped_tiles_pct(shallow, n, d, 257, "bf16x2", dead_tiles=dead) == 0.0
    assert hist_mod.round_onehot_tiles(
        shallow, n, d, 257, "bf16x2", dead_tiles=dead
    ) == hist_mod.round_onehot_tiles(shallow, n, d, 257, "bf16x2")


def test_a_lists_last_block_is_filled_up():
    """Seven columns, five of them wide: two blocks of four, so the two
    narrow columns' second tiles and a padding feature's are built: one tile
    more than the parent's seven."""
    assert hist_mod.dead_bin_tiles([255] * 5 + [3, 3], 257, np.uint16) == -1
    assert hist_mod.dead_bin_tiles([255] * 4 + [3, 3, 3], 257, np.uint16) == 3
    # three bin tiles (max_bin 384): a tile a list
    assert hist_mod.dead_bin_tiles([383, 200, 100, 3], 385, np.uint16) == 0 + 0
    assert hist_mod.dead_bin_tiles([383] + [3] * 7, 385, np.uint16) == 2 * (8 - 4)


def test_class_trees_skip_once_a_group():
    """`mnist8m-mc10`'s ten depth-5 class trees: every call is unfolded, one
    group of ten at W <= 8, so a dead tile is off the count once a level."""
    levels = hist_mod.round_hist_levels("depthwise", 5, 0, True)
    shape = dict(trees_per_round=10, class_trees=10)
    row_tiles = -(-506_250 // (512 * 32)) * 512 * 32 // 128
    latched, unfolded = hist_mod.round_onehot_tiles(
        levels, 506_250, 784, 257, "bf16x2", dead_tiles=263, **shape
    )
    assert (latched, unfolded) == (31_109_120 - 5 * row_tiles * 263, 311_091_200)
    assert hist_mod.skipped_tiles_pct(
        levels, 506_250, 784, 257, "bf16x2", dead_tiles=263, **shape
    ) == pytest.approx(100.0 * 263 / 1568)


def _gauges():
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    return {
        name: family[0].value for name, _kind, _help, family in REGISTRY.collect()
        if name in ("hist_onehot_tiles_per_round", "hist_onehot_tiles_unfolded_per_round",
                    "hist_tiles_skipped_pct")
    }


@pytest.mark.parametrize("narrow", [0, 4], ids=["full_width", "four_narrow"])
def test_session_counts_the_tiles_its_columns_leave_dead(narrow):
    """The gauges at session build, from the host's copy of the cuts: 28
    columns of 255 cuts skip nothing; with four columns of two cuts the
    depth-8 round's three unfolded levels latch four tiles a row tile
    fewer, 4 of 56."""
    x, y = _narrow_matrix(3000, 28, narrow)
    session = _session(x, y)
    assert sum(len(c) < 128 for c in session.cuts) == narrow
    gauges = _gauges()
    row_tiles = 3072 // 128
    full = row_tiles * (2 * 14 + 14 + 2 * 28 + 3 * 56)   # packed, packed, folded, unfolded
    assert gauges["hist_onehot_tiles_unfolded_per_round"] == 8 * row_tiles * 56
    assert gauges["hist_onehot_tiles_per_round"] == full - 3 * row_tiles * narrow
    assert gauges["hist_tiles_skipped_pct"] == pytest.approx(100.0 * narrow / 56)
