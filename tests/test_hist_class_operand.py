"""The class trees of a round in the level histogram kernel's operand.

Two promises of ``ops/histogram.py``. A one-tree build traces the kernel it
traced before the class operand was there (the numbers below were read off
commit 06c84c3, the parent of PR 41, and are literals: a warm program load is
Python tracing and lowering of exactly these bodies, every job's set-up). And
the class-batched level is the per-class loop over the one-tree call, bit for
bit, whether the call folds, does not, or takes its classes in groups.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod
from sagemaker_xgboost_container_tpu.ops.tree_build import build_tree


def _chip_knobs():
    return hist_mod.resolve_hist_knobs()._replace(backend="tpu")


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for sub in value if isinstance(value, (list, tuple)) else [value]:
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def _count_eqns(jaxpr):
    return sum(1 + sum(_count_eqns(sub) for sub in _sub_jaxprs(e)) for e in jaxpr.eqns)


def _find(jaxpr, primitive):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for sub in _sub_jaxprs(eqn):
            found += _find(sub, primitive)
    return found


# ---------------------------------------------------- the one-tree trace pin
PIN_ROWS, PIN_FEATURES, PIN_BINS = 32768, 28, 257   # 64 row blocks: 2 chunks

# W -> (equations of the kernel body, grid, operand rows), read off 06c84c3;
# the same for u8 and u16 bins (the widening is one convert either way).
# Since PR 47 the levels W <= 2 take the packed body (``_tile_pack``): 16
# tiles of two features where the folded one unrolls 32 features, a slab of
# (feature, copy, k) x (feature', low) a tile, untangled after the call
ONE_TREE_KERNELS = {
    1: (765, (1, 2, 32), 4),       # packed: two features a latched tile
    2: (765, (1, 1, 64), 4),
    4: (970, (1, 1, 64), 16),      # folded: two masked copies a feature
    8: (970, (1, 1, 64), 16),
    # unfolded. Since PR 49 a dot a 128-lane bin tile and a row block, four
    # row blocks of 512 a grid step (``_pallas_hist_tiles_fn``): 28 first
    # tiles straight-line, the tiles above them off a list of the group's
    # live ones, four list entries a conditional block (eight blocks a group
    # of 32), and the missing bin's dot a row block: 244 dots. The body is
    # 179 equations, loads and stores round two jitted helpers (326 with one
    # 256-lane dot a feature and row block, up to PR 47)
    16: (3519, (1, 1, 16), 32),
    64: (3519, (1, 1, 16), 128),
}
ONE_TREE_WRAPPER_EQNS = 39         # _hist_pallas around the call
PACKED_WRAPPER_EQNS = 52           # and the slabs' untangling
UNFOLDED_WRAPPER_EQNS = 73         # and the live-tile lists of a call with no reach


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16], ids=["u8", "u16"])
@pytest.mark.parametrize("W", sorted(ONE_TREE_KERNELS))
def test_one_tree_level_traces_the_kernel_it_always_traced(W, dtype):
    eqns, grid, rows = ONE_TREE_KERNELS[W]
    n, d, B = PIN_ROWS, PIN_FEATURES, PIN_BINS
    packed = hist_mod._tile_pack(W, 256, "bf16x2") > 1
    assert packed == (W <= 2)
    closed = jax.make_jaxpr(
        lambda b, g, h, node: hist_mod._hist_pallas(b, g, h, node, W, B)
    )(jnp.zeros((n, d), dtype), jnp.zeros(n), jnp.zeros(n), jnp.zeros(n, jnp.int32))
    (call,) = _find(closed.jaxpr, "pallas_call")
    kernel = call.params["jaxpr"]
    mapping = call.params["grid_mapping"]
    unfolded = W >= 16
    assert _count_eqns(kernel) == eqns
    assert _count_eqns(closed.jaxpr) - eqns == (
        PACKED_WRAPPER_EQNS if packed
        else UNFOLDED_WRAPPER_EQNS if unfolded else ONE_TREE_WRAPPER_EQNS
    )
    assert tuple(mapping.grid) == grid
    blocks = [
        tuple(getattr(x, "block_size", x) for x in bm.block_shape)
        for bm in mapping.block_mappings
    ]
    # packed: 16 tiles of [2 features x 4 copies x 4 rows, 128 lanes]; the
    # missing bin's dot meets the four slots of both halves
    slab = (16, 32, 128) if packed else (32, rows, 256)
    miss = (32, 32) if packed else (32, 2 * rows)
    # the unfolded body alone takes a fourth operand, the group's list of
    # live second tiles and their count, whole in scalar memory, and four
    # row blocks a step; the folded and the packed call are handed three
    live = [(1, 33)] if unfolded else []
    step = 2048 if unfolded else 512
    assert blocks == [(32, step), (2, step), (1, step)] + live + [(1,) + slab, (1,) + miss]
    if unfolded:
        assert len(kernel.eqns) == 179
        assert len(_find(kernel, "cond")) == 1 + 8
        assert len(_find(kernel, "dot_general")) == (28 + 32) * 4 + 4
    chunks = grid[1]
    assert [tuple(a.shape) for a in call.params["out_avals"]] == [
        (chunks,) + slab, (chunks,) + miss,
    ]
    # nothing wrapped round the call: no custom_vmap, no inner jit
    assert not _find(closed.jaxpr, "custom_vmap_call") and not _find(closed.jaxpr, "pjit")


def _program_counts():
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    counts = {}
    for name, _kind, _help, family in REGISTRY.collect():
        if name == "xla_programs_total":
            for sample in family:
                if "host_dispatch" in sample.labels["phase"]:
                    stage = sample.labels["stage"]
                    counts[stage] = counts.get(stage, 0) + sample.value
    return counts


def test_one_tree_round_program_fires_the_trace_and_lower_events_it_always_fired():
    """``xla_programs_total{stage}`` under the first dispatch of a small
    one-tree job on the chip's program, with jax's caches dropped first so
    that no earlier test's traces are found again: 1,018 ``trace`` events
    (every inner jit of the round program, the interpreter's included; 1,306
    on 06c84c3 and up to PR 46: the three levels of this depth-3 tree take
    the packed body since PR 47, two tiles where the folded one unrolled
    four features) and one ``lower``. A count, so it cannot wander with the
    host; a wrapper that traces the build or the level again moves it."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    X = np.random.RandomState(5).rand(600, 4).astype(np.float32)
    jax.clear_caches()
    before = _program_counts()
    train(
        {"max_depth": 3, "max_bin": 256}, DataMatrix(X, labels=X[:, 0]),
        num_boost_round=2, hist_knobs=_chip_knobs(),
    )
    after = _program_counts()
    fired = {stage: after[stage] - before.get(stage, 0) for stage in after}
    assert fired.get("trace") == 1018
    assert fired.get("lower") == 1


# ------------------------------------------------ equality of the class operand
def _class_problem(seed, n, d, B, W, T, dtype=np.uint16):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, B, size=(n, d)).astype(dtype)
    bins[rng.rand(n, d) < 0.1] = B - 1                     # a live missing bin
    return (
        jnp.asarray(bins),
        jnp.asarray(rng.randn(T, n).astype(np.float32)),
        jnp.asarray((rng.rand(T, n) + 0.1).astype(np.float32)),
        jnp.asarray(rng.randint(-1, W, size=(T, n)).astype(np.int32)),  # -1: dead
    )


@pytest.mark.parametrize("W", [1, 2, 8, 16])
@pytest.mark.parametrize("T", [2, 3, 10])
def test_class_batched_level_is_the_per_class_loop_to_the_bit(T, W):
    """Folded (T = 2, 3 at W <= 2), unfolded and class-grouped (T = 10 at
    W = 16: two groups of five) calls; 1,500 rows pad to 1,536 (whole
    row blocks), a tenth of the cells in the missing bin, dead rows in every
    tree, the trees routed apart."""
    B, d = 257, 5
    bins, grad, hess, node = _class_problem(100 * T + W, 1500, d, B, W, T)
    size, groups = hist_mod._class_groups(W, T)
    rows = hist_mod._operand_rows(W, size)
    assert size * groups >= T and rows <= max(hist_mod.CLASS_OPERAND_MAX_ROWS, 2 * W)
    assert (groups > 1) == (T == 10 and W == 16)
    assert (hist_mod._bin_fold(rows, 256, "bf16x2") == 2) == (T < 10 and W <= 2)
    G, H = hist_mod._hist_pallas(bins, grad, hess, node, W, B)
    assert G.shape == H.shape == (T, W, d, B)
    assert np.asarray(G)[..., B - 1].any()
    for t in range(T):
        G1, H1 = hist_mod._hist_pallas(bins, grad[t], hess[t], node[t], W, B)
        np.testing.assert_array_equal(np.asarray(G[t]), np.asarray(G1))
        np.testing.assert_array_equal(np.asarray(H[t]), np.asarray(H1))


@pytest.mark.parametrize("prec", hist_mod.HIST_PRECISIONS)
def test_class_vmap_hands_the_kernel_one_operand(prec):
    """``level_histogram(class_vmap=True)`` under ``jax.vmap``: one kernel
    call with the class-group axis leading its grid (not Pallas's own rule:
    the class axis on the grid of the one-tree kernel), the root's one row
    of node ids broadcast, the one-pass control included; and without
    ``class_vmap`` the level is the one-tree call under Pallas's rule."""
    T, W, B, d = 3, 1, 257, 5
    bins, grad, hess, _node = _class_problem(7, 1500, d, B, W, T)
    root = jnp.zeros(1500, jnp.int32)
    knobs = _chip_knobs()._replace(precision=prec)

    def level(class_vmap):
        return jax.vmap(
            lambda g, h: hist_mod.level_histogram(
                bins, g, h, root, W, B, knobs=knobs, class_vmap=class_vmap
            )
        )

    (call,) = _find(jax.make_jaxpr(level(True))(grad, hess).jaxpr, "pallas_call")
    assert tuple(call.params["grid_mapping"].grid) == (1, 1, 1, 3)
    assert call.params["out_avals"][0].shape == (1, 1, 16, 16, 256)
    (old,) = _find(jax.make_jaxpr(level(False))(grad, hess).jaxpr, "pallas_call")
    assert tuple(old.params["grid_mapping"].grid) == (T, 1, 1, 3)
    G, H = level(True)(grad, hess)
    G0, H0 = level(False)(grad, hess)
    np.testing.assert_array_equal(np.asarray(G), np.asarray(G0))
    np.testing.assert_array_equal(np.asarray(H), np.asarray(H0))


def test_empty_class_input_yields_zero_histograms():
    z = jnp.zeros((3, 0), jnp.float32)
    G, H = hist_mod._hist_pallas(
        jnp.zeros((0, 4), jnp.uint8), z, z, jnp.zeros((3, 0), jnp.int32), 2, 129
    )
    assert G.shape == H.shape == (3, 2, 4, 129) and not np.asarray(G).any()


def test_a_class_build_reads_its_split_winners_without_a_gather():
    """Mapped over the class trees, the split scan's two ``take_along_axis``
    reads at the winning (feature, bin) become one gather over a [T, W, d *
    bins] operand. XLA's memory-space assignment kept the ``take_left`` mask
    of `mnist8m-mc10`'s W = 8 level in VMEM, and a v5e stopped for good in
    that gather at some indices (PR 41: the same seed at the same dispatch,
    three runs of three; the parent's program had the operand in HBM). The
    class build asks ``find_best_splits(gathers=False)``: the same values bit
    for bit, NaN and -inf rows included, and no gather in the program."""
    from sagemaker_xgboost_container_tpu.ops.split import find_best_splits

    rng = np.random.default_rng(11)
    W, d, B = 8, 6, 33
    G = rng.normal(size=(W, d, B)).astype(np.float32)
    H = rng.uniform(0.5, 3.0, size=(W, d, B)).astype(np.float32)
    G[1], H[1] = 0.0, 0.0                   # an empty node: every gain -inf
    G[2, 3, 4] = np.nan                     # a poisoned cell: NaN wins argmax
    G[3] = np.round(G[3])                   # ties: the first maximum wins
    num_cuts = jnp.asarray(rng.integers(1, B - 1, size=d), jnp.int32)
    kwargs = dict(gamma=0.5, min_child_weight=2.0)
    want = find_best_splits(jnp.asarray(G), jnp.asarray(H), num_cuts, **kwargs)
    got = find_best_splits(jnp.asarray(G), jnp.asarray(H), num_cuts, gathers=False, **kwargs)
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want[key]), err_msg=key)

    def mapped(gathers):
        scan = functools.partial(find_best_splits, num_cuts=num_cuts, gathers=gathers)
        stacked = jnp.zeros((3, W, d, B), jnp.float32)
        return str(jax.make_jaxpr(jax.vmap(scan))(stacked, stacked))

    # which of the two a build asks for: tests/test_chip_compile.py, where
    # the class branch's compiled program holds no gather at all
    assert " gather[" in mapped(True) and " gather[" not in mapped(False)


# ------------------------------------------------------------ whole forests
def _plain_class_vmap(monkeypatch):
    """The parent's program: the class axis left to Pallas's batching rule."""
    monkeypatch.setattr(
        hist_mod, "_class_hist_fn",
        lambda W, B, prec: lambda bins, grad, hess, node, reach: hist_mod._hist_pallas(
            bins, grad, hess, node, W, B, prec=prec, reach=reach
        ),
    )


def _forest_arrays(forest):
    fields = ("feature", "threshold", "default_left", "left", "right", "value",
              "base_weight", "gain", "sum_hess")
    return [np.asarray(getattr(tree, f)) for tree in forest.trees for f in fields]


@pytest.mark.parametrize(
    "params, shards",
    [
        # the ten-class forest of tests/test_split_oracle.py, cut to 96 columns
        ({"objective": "multi:softmax", "num_class": 10, "max_depth": 5, "eta": 0.2,
          "gamma": 0.3, "min_child_weight": 2.0, "max_bin": 64,
          "_rounds_per_dispatch": 2}, 1),
        # tests/test_parallel.py's bagged classes: the class vmap once a tree
        ({"objective": "multi:softprob", "num_class": 3, "max_depth": 4,
          "num_parallel_tree": 2, "subsample": 0.8, "eta": 0.7, "max_bin": 256}, 1),
        # the batching rule under shard_map, the psum around the class call
        ({"objective": "multi:softprob", "num_class": 3, "max_depth": 4, "eta": 0.3}, 4),
    ],
    ids=["ten_classes", "parallel_trees_by_three_classes", "three_classes_on_a_data_mesh"],
)
def test_class_forests_keep_their_trees(monkeypatch, params, shards):
    """Through ``models.train()`` on the chip's program: the forest grown
    with the class trees in one operand is the forest the class axis on the
    kernel's grid grew, field for field."""
    from jax.sharding import Mesh

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(17)
    classes, rounds = params["num_class"], 2
    y = rng.randint(0, classes, 1200).astype(np.float32)
    centres = rng.randn(classes, 96) * 1.5
    X = (centres[y.astype(int)] + rng.randn(1200, 96)).astype(np.float32)
    X = X[:, : 96 if classes == 10 else 6]
    mesh = Mesh(np.array(jax.devices()[:shards]), axis_names=("data",)) if shards > 1 else None

    def grow():
        return train(
            dict(params), DataMatrix(X, labels=y), num_boost_round=rounds,
            hist_knobs=_chip_knobs(), verbose_eval=False, mesh=mesh,
        )

    ours = grow()
    with monkeypatch.context() as mp:
        _plain_class_vmap(mp)
        theirs = grow()
    assert len(ours.trees) == rounds * classes * params.get("num_parallel_tree", 1)
    assert sum(int((np.asarray(t.left) >= 0).sum()) for t in ours.trees) >= 3 * classes
    for a, b in zip(_forest_arrays(ours), _forest_arrays(theirs)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------- the tile counter
def test_mnist8m_round_latches_31_million_tiles():
    """`mnist8m-mc10.train-fused`: ten depth-5 class trees over 506,250 x 784
    with 257 bins: 6,221,824 tiles a call, five calls a round, none folded
    (ten trees at W = 1 are the 64 rows a latch carries free), against ten
    folded calls a level before."""
    levels = hist_mod.round_hist_levels("depthwise", 5, 0, True)
    tiles = functools.partial(
        hist_mod.round_onehot_tiles, levels, 506_250, 784, 257, "bf16x2",
        trees_per_round=10,
    )
    assert tiles(class_trees=10) == (31_109_120, 311_091_200)
    # ten one-tree calls a level: half, and a quarter where two features share a tile (W <= 2, PR 47)
    assert tiles() == (108_881_920, 311_091_200)
    # bagged classes: the class trees of each bagged step share, the steps do not
    assert hist_mod.round_onehot_tiles(
        levels, 506_250, 784, 257, "bf16x2", trees_per_round=20, class_trees=10
    ) == (62_218_240, 622_182_400)


@pytest.mark.parametrize("T, depth", [(10, 5), (3, 7)])
def test_tile_plan_counts_the_tiles_the_calls_latch(monkeypatch, T, depth):
    """``round_onehot_tiles`` against the calls a class-mapped build really
    makes: every ``_pallas_hist_fn`` the trace asks for, class groups x row
    tiles x features x latched bin tiles (depth 7 at T = 3 reaches W = 32:
    two groups)."""
    n, d, B = 3000, 7, 257
    asked = []
    real = hist_mod._pallas_hist_fn

    def recording(n_pad, d_, fg, W, B_, block, prec, interpret, split_missing,
                  rows, chunks, fold=1, class_groups=None):
        # (custom_vmap also traces its unmapped primal, the one-tree call,
        # and drops it: no class groups, nothing lowered, not counted)
        if class_groups is not None:
            asked.append(
                class_groups[1] * (n_pad // 128) * d_
                * (hist_mod._bin_lanes(B_) // 128 // fold)
            )
        return real(n_pad, d_, fg, W, B_, block, prec, interpret, split_missing,
                    rows, chunks, fold, class_groups)

    monkeypatch.setattr(hist_mod, "_pallas_hist_fn", recording)
    knobs = _chip_knobs()

    def build(bins, grad, hess, num_cuts):
        return jax.vmap(
            lambda g, h: build_tree(
                bins, g, h, num_cuts, depth, B, knobs=knobs, class_vmap=True
            )[1]
        )(grad, hess)

    jax.make_jaxpr(build)(
        jnp.zeros((n, d), jnp.uint16), jnp.zeros((T, n)), jnp.zeros((T, n)),
        jnp.full((d,), B - 2, jnp.int32),
    )
    levels = hist_mod.round_hist_levels("depthwise", depth, 0, True)
    latched, _unfolded = hist_mod.round_onehot_tiles(
        levels, n, d, B, "bf16x2", trees_per_round=T, class_trees=T
    )
    assert len(asked) == depth and sum(asked) == latched
