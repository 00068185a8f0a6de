"""The level histogram kernel's tile pack (``ops/histogram.py::_tile_pack``,
PR 47): at the levels whose real operand rows leave room, two features share
every latched one-hot tile. The packed kernel against the same call with the
pack forced to 1 (the folded body, the parent's), bit for bit; the levels the
rule leaves alone; the one-pass control's pack; a loss-guided tree whose root
call packs. Interpreted kernels: a file of their own, as
``tests/test_hist_kernel_fold.py`` is, so that ``--dist loadfile`` gives them
a worker.
"""

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod

from tests.test_hist_impls import _level_problem


PACK_BINS = [(256, np.uint8), (257, np.uint16), (513, np.uint16)]


def _unpacked(monkeypatch):
    """A tile a feature, whatever the level: the parent's kernel."""
    monkeypatch.setattr(hist_mod, "_tile_pack", lambda W, lanes, prec: 1)


@pytest.fixture
def drop_compiled_kernels():
    """As ``tests/test_hist_kernel_fold.py``'s: an interpreted kernel with its
    features unrolled is hundreds of memory mappings, kept for the process's
    life. Dropped in front of the test too: it counts the kernels it
    builds, and another file's tests on this worker may have left theirs."""
    import jax

    def drop():
        hist_mod._pallas_hist_fn.cache_clear()
        hist_mod._pallas_hist_packed_fn.cache_clear()
        jax.clear_caches()

    drop()
    yield
    drop()


def _both(monkeypatch, bins, grad, hess, node, W, B, prec="bf16x2"):
    packed = hist_mod._hist_pallas(bins, grad, hess, node, W, B, prec=prec)
    built = hist_mod._pallas_hist_packed_fn.cache_info().currsize
    _unpacked(monkeypatch)
    plain = hist_mod._hist_pallas(bins, grad, hess, node, W, B, prec=prec)
    assert hist_mod._pallas_hist_packed_fn.cache_info().currsize == built
    return packed, plain, built


@pytest.mark.parametrize("B, dtype", PACK_BINS, ids=lambda v: getattr(v, "__name__", str(v)))
@pytest.mark.parametrize("d", [5, 28, 39])
@pytest.mark.parametrize("W", [1, 2])
def test_packed_kernel_equals_the_unpacked_one_to_the_bit(
    monkeypatch, drop_compiled_kernels, W, d, B, dtype
):
    """Two features' one-hots of ``bin % 64`` side by side on one latched
    tile, ``bin // 64`` picking which of a feature's four slots of real rows
    a row's gradients ride: every kept product lands where it landed, so
    both histograms keep every bit, over rows in the missing bin (B - 1),
    dead rows, a row count that pads (1,100 to three blocks), an odd width
    (a tile with one real feature), a second feature group (39 in u16), and
    four bin tiles, where nothing packs (8 copies x 2 x 8 rows are 128)."""
    bins, grad, hess, node = _level_problem(47 + W, 1100, d, B, W, dtype)
    assert (np.asarray(node) < 0).any() and (np.asarray(bins) == B - 1).any()
    pack = hist_mod._tile_pack(W, hist_mod._bin_lanes(B), "bf16x2")
    assert pack == (2 if B <= 257 else 1)
    (G1, H1), (G0, H0), built = _both(monkeypatch, bins, grad, hess, node, W, B)
    assert built == (1 if pack > 1 else 0)
    np.testing.assert_array_equal(np.asarray(G1), np.asarray(G0))
    np.testing.assert_array_equal(np.asarray(H1), np.asarray(H0))
    assert np.asarray(H1).any() and G1.shape == (W, d, B)


@pytest.mark.parametrize(
    "W, B, pack",
    [(1, 257, 2), (2, 257, 2), (4, 257, 2), (1, 128, 4), (4, 129, 2)],
)
def test_one_pass_control_packs_further_and_keeps_its_bits(
    monkeypatch, drop_compiled_kernels, W, B, pack
):
    """The one-pass control streams one half, so W = 4 packs too, and one
    bin tile takes four features at W = 1: the same rounded sums."""
    dtype = np.uint8 if B <= 256 else np.uint16
    bins, grad, hess, node = _level_problem(53 + W, 1100, 28, B, W, dtype)
    assert hist_mod._tile_pack(W, hist_mod._bin_lanes(B), "bf16") == pack
    (G1, H1), (G0, H0), built = _both(monkeypatch, bins, grad, hess, node, W, B, "bf16")
    assert built == 1
    np.testing.assert_array_equal(np.asarray(G1), np.asarray(G0))
    np.testing.assert_array_equal(np.asarray(H1), np.asarray(H0))
    assert np.asarray(H1).any()


@pytest.mark.parametrize("B", [128, 256, 257, 513])
@pytest.mark.parametrize("W", [4, 8, 16, 64])
def test_wider_levels_keep_the_folded_body(W, B):
    """From W = 4 the real rows of ``fold`` x ``pack`` slots are over what a
    latch carries free (4 x 2 x 16 at 256 bin lanes): pack 1, the body
    ``_bin_fold`` rules, unchanged by construction. One bin tile (128) packs
    W = 4 still: 2 x 2 x 16 rows are 64."""
    lanes = hist_mod._bin_lanes(B)
    pack = hist_mod._tile_pack(W, lanes, "bf16x2")
    assert pack == (2 if (W, lanes) == (4, 128) else 1)
    stacked = 2 * hist_mod._slot_rows(W, lanes, 2 * pack)
    assert (lanes // 128) * (2 * pack) ** 2 * stacked > hist_mod.LATCH_FREE_ROWS
    assert hist_mod._operand_rows(8) == hist_mod._operand_rows(1) == 16


def test_a_tile_of_padding_features_gets_no_dot(drop_compiled_kernels):
    """39 features in groups of 32: the last group's fourth tile holds
    feature 38 beside a padding feature and is a whole tile (the padding
    feature's bins are all 0, so its bin 0 takes the node totals, cut off
    with the padding); the tiles behind it get neither a one-hot nor a dot,
    and a dead row rides no slot."""
    W, B, n, d = 2, 257, 1024, 39
    bins, grad, hess, node = _level_problem(5, n, d, B, W, np.uint16)
    fg = hist_mod._pallas_feature_group(d, np.uint16)
    d_pad = -(-d // fg) * fg
    rows = hist_mod._slot_rows(W, 256, 2)
    assert (fg, d_pad, rows) == (32, 64, 4)
    import jax.numpy as jnp

    fn = hist_mod._pallas_hist_packed_fn(
        n, d, fg, W, B, hist_mod.PALLAS_ROW_BLOCK, "bf16x2", True, True, 1, 2
    )
    main, miss = fn(
        jnp.pad(bins.T, [(0, d_pad - d), (0, 0)]),
        jnp.stack([grad, hess]),
        jnp.where(node >= 0, node, W)[None, :],
    )
    assert main.shape == (1, d_pad, rows, 256) and miss.shape == (1, d_pad, 2 * rows)
    main, miss = np.asarray(main), np.asarray(miss)
    assert main[0, d - 1, :, 0].any() and main[0, d, :, 0].any()
    assert not main[0, d, :, 1:].any()
    assert not main[0, d + 1:].any()
    assert not miss[0, d:].any()
    live = np.asarray(node) >= 0
    np.testing.assert_allclose(
        main[0, 0].sum(axis=1) + miss[0, 0, :rows] + miss[0, 0, rows:],
        [np.asarray(x)[live & (np.asarray(node) == w)].sum() for x in (grad, hess) for w in range(W)],
        atol=5e-3,
    )


def test_loss_guided_tree_keeps_its_digest_where_its_root_call_packs(drop_compiled_kernels):
    """``tests/lossguide_cases.py``'s kernel case: the root's W = 1 call
    packs (17 bins are one bin tile; so do the W = 4 passes of a tree this
    small, where a W = 8 pass would not), and the tree is the parent's by
    sha256."""
    from tests import lossguide_cases
    from tests.test_lossguide_rolled import PARENT_DIGESTS

    from sagemaker_xgboost_container_tpu.ops.lossguide import pass_nodes

    lanes = hist_mod._bin_lanes(lossguide_cases.NUM_BINS)
    slots = pass_nodes(8, True)            # a pass of a tree of 8 leaves: W = 4
    assert hist_mod._tile_pack(1, lanes, "bf16x2") == 2
    assert hist_mod._tile_pack(8, lanes, "bf16x2") == 1
    packed = {W for W in (1, slots) if hist_mod._tile_pack(W, lanes, "bf16x2") > 1}
    hist_mod._pallas_hist_packed_fn.cache_clear()
    tree, row_out = lossguide_cases.run_case(*lossguide_cases.cases()["l8.sub.kernel"])
    assert hist_mod._pallas_hist_packed_fn.cache_info().currsize == len(packed)
    assert lossguide_cases.digest(tree, row_out) == PARENT_DIGESTS["l8.sub.kernel"]


def test_mapped_builds_take_the_packed_body_through_pallas_own_batching_rule(drop_compiled_kernels):
    """DART's class trees and the CV folds map one-tree builds over one bin
    matrix with ``jax.vmap`` and no ``class_vmap``: Pallas's own rule puts
    the mapped axis on the grid of whichever body the shape picks, and every
    member's histograms are its own call's, to the bit."""
    import jax
    import jax.numpy as jnp

    W, B, d = 2, 257, 28
    bins, grad, hess, node = _level_problem(59, 1100, d, B, W, np.uint16)
    grads = jnp.stack([grad, -0.5 * grad, grad + 1.0])
    hesses = jnp.stack([hess, 2.0 * hess, hess])
    nodes = jnp.stack([node, node[::-1], jnp.zeros_like(node)])

    def one(g, h, nd):
        return hist_mod._hist_pallas(bins, g, h, nd, W, B)

    Gm, Hm = jax.vmap(one)(grads, hesses, nodes)
    assert hist_mod._pallas_hist_packed_fn.cache_info().currsize == 1
    assert hist_mod._pallas_hist_fn.cache_info().currsize == 0
    for t in range(3):
        G, H = one(grads[t], hesses[t], nodes[t])
        np.testing.assert_array_equal(np.asarray(Gm[t]), np.asarray(G))
        np.testing.assert_array_equal(np.asarray(Hm[t]), np.asarray(H))
