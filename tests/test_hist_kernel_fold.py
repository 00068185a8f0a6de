"""The level histogram kernel's bin fold (``ops/histogram.py::_bin_fold``,
PR 35): the folded kernel against the unfolded one, bit for bit, at every
level width, feature width and bin count the fold's table names, and the
one-pass control's fold. 112 interpreted-kernel cases, 309 s alone on one
worker (583 under the six workers' load in the driver's run of PR 44): a
file of their own since PR 45, so that ``--dist loadfile`` gives them a
worker beside ``tests/test_hist_impls.py``'s, where they were two thirds of
the suite's longest file.
"""

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.ops import histogram as hist_mod

from tests.test_hist_impls import _level_problem


FOLD_BINS = [
    (128, np.uint8), (129, np.uint8), (256, np.uint8), (256, np.uint16),
    (257, np.uint16), (513, np.uint16),
]


def _unfolded(monkeypatch):
    """The kernel with the whole bin axis in its one-hot, a feature a tile:
    PR 35's parent's."""
    monkeypatch.setattr(hist_mod, "_bin_fold", lambda rows, lanes, prec: 1)
    monkeypatch.setattr(hist_mod, "_tile_pack", lambda W, lanes, prec: 1)


@pytest.fixture
def drop_compiled_kernels():
    """An interpreted kernel with its features unrolled is some 650 memory
    mappings of compiled code, kept for the process's life: a hundred cases
    of them would bring a worker to the kernel's limit of 65,530."""
    yield
    import jax

    hist_mod._pallas_hist_fn.cache_clear()
    hist_mod._pallas_hist_packed_fn.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("B, dtype", FOLD_BINS, ids=lambda v: getattr(v, "__name__", str(v)))
@pytest.mark.parametrize("d", [5, 28, 39])
@pytest.mark.parametrize("W", [1, 2, 4, 8, 16, 64])
def test_folded_kernel_equals_the_unfolded_one_to_the_bit(
    monkeypatch, drop_compiled_kernels, W, d, B, dtype
):
    """Where the latch has free rows the kernel latches the one-hot of a
    bin's low part alone and the high part picks the operand's copy: every
    product lands where it landed, so both histograms keep every bit, over
    rows in the missing bin (B - 1), dead rows, a row count that pads (1,100
    to three blocks), one bin tile (no fold), two and four. Where two
    features share a tile (W <= 2 at two bin tiles, W <= 4 at one) the
    shipped kernel is the packed one."""
    bins, grad, hess, node = _level_problem(41 + W, 1100, d, B, W, dtype)
    assert (np.asarray(node) < 0).any() and (np.asarray(bins) == B - 1).any()
    lanes = hist_mod._bin_lanes(B)
    fold = hist_mod._bin_fold(hist_mod._operand_rows(W), lanes, "bf16x2")
    assert fold == (2 if W <= 8 and lanes >= 256 else 1)
    # since PR 47 the narrowest levels take the packed body (``_tile_pack``),
    # held to the same unfolded kernel
    pack = hist_mod._tile_pack(W, lanes, "bf16x2")
    assert pack == (2 if lanes <= 256 and W * lanes <= 512 else 1)
    G1, H1 = hist_mod._hist_pallas(bins, grad, hess, node, W, B)
    _unfolded(monkeypatch)
    G0, H0 = hist_mod._hist_pallas(bins, grad, hess, node, W, B)
    np.testing.assert_array_equal(np.asarray(G1), np.asarray(G0))
    np.testing.assert_array_equal(np.asarray(H1), np.asarray(H0))
    assert np.asarray(H1).any() and G1.shape == (W, d, B)


@pytest.mark.parametrize("W, B, fold", [(1, 257, 2), (16, 257, 2), (32, 257, 1), (8, 513, 4)])
def test_one_pass_control_folds_too_and_keeps_its_bits(monkeypatch, drop_compiled_kernels, W, B, fold):
    """The one-pass control streams half the rows, so it folds one level
    further (and four tiles of u16 bins into one): the same rounded sums."""
    bins, grad, hess, node = _level_problem(43 + W, 1100, 28, B, W, np.uint16)
    assert hist_mod._bin_fold(hist_mod._operand_rows(W), hist_mod._bin_lanes(B), "bf16") == fold
    G1, H1 = hist_mod._hist_pallas(bins, grad, hess, node, W, B, prec="bf16")
    _unfolded(monkeypatch)
    G0, H0 = hist_mod._hist_pallas(bins, grad, hess, node, W, B, prec="bf16")
    np.testing.assert_array_equal(np.asarray(G1), np.asarray(G0))
    np.testing.assert_array_equal(np.asarray(H1), np.asarray(H0))
