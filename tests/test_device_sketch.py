"""Device-side weighted quantile sketch vs the host numpy reference.

The reference's binning runs in native code inside libxgboost (weighted
quantile sketch, SURVEY.md §2.2); our host path is a numpy argsort loop
(~14s for 1M x 28 on one core). GRAFT_SKETCH_IMPL=device lowers the whole
sketch (stable sort, run-end cumulative weights, quantile-target pick,
midpoint cuts) to one vmapped XLA program. Cut positions may differ from
the host path by one distinct-value neighbor on razor-edge quantile
targets (f32 cumsum associativity), which is below binning resolution —
tolerances here reflect that.
"""

import os

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data import binning


def _cuts(X, weights, max_bin, impl):
    old = os.environ.get("GRAFT_SKETCH_IMPL")
    os.environ["GRAFT_SKETCH_IMPL"] = impl
    try:
        return binning.compute_cut_points(X, weights, max_bin)
    finally:
        if old is None:
            os.environ.pop("GRAFT_SKETCH_IMPL", None)
        else:
            os.environ["GRAFT_SKETCH_IMPL"] = old


def _case(name):
    rng = np.random.RandomState(0)
    if name == "random":
        return rng.randn(20000, 6).astype(np.float32)
    if name == "few_distinct":
        return rng.randint(0, 9, size=(5000, 4)).astype(np.float32)
    if name == "heavy_ties":
        return np.round(rng.randn(8000, 3), 1).astype(np.float32)
    if name == "with_nan":
        X = rng.randn(20000, 6).astype(np.float32)
        X[rng.rand(*X.shape) < 0.15] = np.nan
        return X
    if name == "const_and_allnan":
        X = rng.randn(3000, 3).astype(np.float32)
        X[:, 1] = 7.0      # single distinct value -> one cut above it
        X[:, 2] = np.nan   # all missing -> no cuts
        return X
    raise KeyError(name)


@pytest.mark.parametrize(
    "case", ["random", "few_distinct", "heavy_ties", "with_nan", "const_and_allnan"]
)
@pytest.mark.parametrize("weighted", [False, True])
def test_device_sketch_matches_host(case, weighted):
    X = _case(case)
    rng = np.random.RandomState(1)
    w = (rng.rand(X.shape[0]) + 0.2).astype(np.float32) if weighted else None
    host = _cuts(X, w, 32, "host")
    dev = _cuts(X, w, 32, "device")
    assert len(host) == len(dev)
    for f, (a, b) in enumerate(zip(host, dev)):
        assert a.shape == b.shape, (case, f, a.shape, b.shape)
        np.testing.assert_allclose(
            a, b, rtol=1e-3, atol=1e-3, err_msg="{} f={}".format(case, f)
        )


def test_device_sketch_trains_equivalently():
    """End to end: trees built from device-sketch cuts match host-sketch
    model quality (cut flips at quantile boundaries are noise-level)."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train

    rng = np.random.RandomState(4)
    X = rng.rand(4000, 5).astype(np.float32)
    y = (np.sin(5 * X[:, 0]) + X[:, 1] ** 2 + 0.05 * rng.randn(4000)).astype(
        np.float32
    )
    preds = {}
    for impl in ("host", "device"):
        old = os.environ.get("GRAFT_SKETCH_IMPL")
        os.environ["GRAFT_SKETCH_IMPL"] = impl
        try:
            f = train({"max_depth": 4}, DataMatrix(X, labels=y), num_boost_round=8)
        finally:
            if old is None:
                os.environ.pop("GRAFT_SKETCH_IMPL", None)
            else:
                os.environ["GRAFT_SKETCH_IMPL"] = old
        preds[impl] = np.asarray(f.predict(X))
    rmse_h = float(np.sqrt(np.mean((preds["host"] - y) ** 2)))
    rmse_d = float(np.sqrt(np.mean((preds["device"] - y) ** 2)))
    assert abs(rmse_h - rmse_d) < 0.02 * max(rmse_h, 1e-6), (rmse_h, rmse_d)


def test_device_apply_matches_host():
    """Device binning (vmapped searchsorted) == numpy apply_cut_points,
    including NaN -> missing bin, +/-inf values, and empty cut lists."""
    rng = np.random.RandomState(7)
    X = rng.randn(6000, 4).astype(np.float32)
    X[rng.rand(6000, 4) < 0.1] = np.nan
    X[0, 0] = np.inf
    X[1, 1] = -np.inf
    X[:, 3] = np.nan  # all-missing feature -> empty cuts
    cuts = _cuts(X, None, 32, "host")
    host_bins = None
    for impl in ("host", "device"):
        old = os.environ.get("GRAFT_SKETCH_IMPL")
        os.environ["GRAFT_SKETCH_IMPL"] = impl
        try:
            b = binning.apply_cut_points(X, cuts, 32)
        finally:
            if old is None:
                os.environ.pop("GRAFT_SKETCH_IMPL", None)
            else:
                os.environ["GRAFT_SKETCH_IMPL"] = old
        if host_bins is None:
            host_bins = b
        else:
            assert b.dtype == host_bins.dtype
            np.testing.assert_array_equal(b, host_bins)


def test_device_sketch_small_n_and_infinities():
    """Regression (r2 review): (a) fewer rows than max_cuts must not crash
    the static-shape select (100 rows at max_bin=256); (b) +inf feature
    values are ordinary distinct reps on the host path and must be on the
    device path too (NaN alone is the missing sentinel)."""
    rng = np.random.RandomState(0)
    Xs = rng.randn(100, 3).astype(np.float32)
    for a, b in zip(_cuts(Xs, None, 256, "host"), _cuts(Xs, None, 256, "device")):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    Xi = np.array(
        [[0.0], [1.0], [2.0], [np.inf], [np.inf], [np.nan], [1.0], [0.0]],
        np.float32,
    )
    h = _cuts(Xi, None, 16, "host")[0]
    d = _cuts(Xi, None, 16, "device")[0]
    assert np.isinf(h[-1])  # host keeps the inf rep -> inf cut
    assert h.shape == d.shape
    np.testing.assert_allclose(h, d)


def test_approx_resketch_device_impl(monkeypatch):
    """r5: tree_method=approx with the on-device sketch lowering (the TPU
    default) — the per-dispatch re-sketch keeps features device-resident
    (no per-round [n, d] re-upload) and hessian weights never leave the
    device. Quality must stay in the host-impl band and the cuts must
    actually refresh between dispatches."""
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import train
    from sagemaker_xgboost_container_tpu.models.booster import (
        TrainConfig, _TrainingSession,
    )
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    rng = np.random.RandomState(6)
    X = rng.rand(3000, 5).astype(np.float32)
    y = (np.sin(5 * X[:, 0]) + X[:, 1] ** 2 + 0.05 * rng.randn(3000)).astype(
        np.float32
    )
    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "device")
    f_dev = train(
        {"tree_method": "approx", "max_bin": 64, "max_depth": 4},
        DataMatrix(X, labels=y),
        num_boost_round=8,
    )
    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "host")
    f_host = train(
        {"tree_method": "approx", "max_bin": 64, "max_depth": 4},
        DataMatrix(X, labels=y),
        num_boost_round=8,
    )
    rmse_d = float(np.sqrt(np.mean((np.asarray(f_dev.predict(X)) - y) ** 2)))
    rmse_h = float(np.sqrt(np.mean((np.asarray(f_host.predict(X)) - y) ** 2)))
    assert abs(rmse_d - rmse_h) < 0.05 * max(rmse_h, 1e-6), (rmse_d, rmse_h)

    # the device features are staged once and the cuts refresh in place
    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "device")
    yb = (X[:, 0] > 0.5).astype(np.float32)
    cfg = TrainConfig(
        {"tree_method": "approx", "max_bin": 32,
         "objective": "binary:logistic", "max_depth": 3}
    )
    session = _TrainingSession(
        cfg, DataMatrix(X, labels=yb), [],
        Forest(objective_name=cfg.objective, base_score=cfg.base_score,
               num_feature=X.shape[1]),
    )
    session.run_rounds()
    staged = session._train_floats  # a block a shard: one, on one device
    assert staged and not any(isinstance(block, np.ndarray) for block in staged)
    cuts0 = [np.asarray(c).copy() for c in session.cuts]
    session.run_rounds()
    session.end_turnaround()
    assert session._train_floats is staged, "features must stage exactly once"
    assert any(
        a.shape != np.asarray(b).shape or not np.allclose(a, np.asarray(b))
        for a, b in zip(cuts0, session.cuts)
    )


def test_device_kernels_do_not_recompile_across_calls(monkeypatch):
    """ADVICE r5 regression: the sketch/apply jit kernels were fresh
    closures, so the per-dispatch approx re-sketch recompiled both every
    boosting round. Hoisted + cached (binning._cut_points_kernel /
    _apply_kernel), two calls with the same static config must reuse ONE
    compiled executable (jit cache size stays 1)."""
    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "device")
    rng = np.random.RandomState(11)
    X1 = rng.randn(257, 6).astype(np.float32)
    X2 = rng.randn(257, 6).astype(np.float32)  # same shape, new contents
    w = np.ones(257, np.float32)

    binning._cut_points_kernel.cache_clear()
    binning._apply_kernel.cache_clear()

    cuts1 = binning.compute_cut_points(X1, w, 32)
    kernel = binning._cut_points_kernel(31, max(257, 31))
    size_after_first = kernel._cache_size()
    cuts2 = binning.compute_cut_points(X2, w, 32)
    assert binning._cut_points_kernel(31, max(257, 31)) is kernel
    assert kernel._cache_size() == size_after_first == 1

    binning.apply_cut_points(X1, cuts1, 32)
    akernel = binning._apply_kernel(32)
    a_size = akernel._cache_size()
    binning.apply_cut_points(X2, cuts2, 32)
    assert binning._apply_kernel(32) is akernel
    assert akernel._cache_size() == a_size == 1


def test_approx_resketch_forces_single_round_dispatch(monkeypatch, caplog):
    """ADVICE r5: with _rounds_per_dispatch > 1 the approx re-sketch would
    refresh candidates once per K-round dispatch, not once per boosting
    iteration as libxgboost's approx does. The session forces K=1, WARNED
    ONCE per process (a CV fold / elastic rebuild must not re-log);
    GRAFT_APPROX_RESKETCH=0 restores batched dispatches."""
    import logging

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import booster as booster_mod
    from sagemaker_xgboost_container_tpu.models.booster import (
        TrainConfig, _TrainingSession,
    )
    from sagemaker_xgboost_container_tpu.models.forest import Forest

    monkeypatch.setattr(booster_mod, "_approx_k_forcing_warned", False)

    rng = np.random.RandomState(3)
    X = rng.randn(256, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)

    def _session():
        cfg = TrainConfig(
            {"tree_method": "approx", "max_bin": 16,
             "objective": "binary:logistic", "max_depth": 3,
             "_rounds_per_dispatch": 4}
        )
        return _TrainingSession(
            cfg, DataMatrix(X, labels=y), [],
            Forest(objective_name=cfg.objective, base_score=cfg.base_score,
                   num_feature=X.shape[1]),
        )

    with caplog.at_level(logging.INFO):
        session = _session()
    assert session.approx_resketch
    assert session.rounds_per_dispatch == 1
    forcing_logs = [
        r for r in caplog.records if "_rounds_per_dispatch" in r.message
    ]
    assert len(forcing_logs) == 1
    assert forcing_logs[0].levelno == logging.WARNING

    # warn-once: a rebuilt session (CV fold / elastic reform) still forces
    # K=1 but adds no second log line
    with caplog.at_level(logging.INFO):
        again = _session()
    assert again.rounds_per_dispatch == 1
    assert (
        len([r for r in caplog.records if "_rounds_per_dispatch" in r.message])
        == 1
    )

    monkeypatch.setenv("GRAFT_APPROX_RESKETCH", "0")
    session2 = _session()
    assert not session2.approx_resketch
    assert session2.rounds_per_dispatch == 4


# ------------------------------------------------ blocks (DEVICE_BLOCK_BYTES)
def _criteo_like_columns(n=6000):
    """The column shapes a click log has: all missing, 77 % missing, one
    value, three values, counts spiked at 0 with a heavy tail, all distinct."""
    rng = np.random.RandomState(7)
    heavy = np.floor(3.0 * (rng.rand(n) ** -0.8 - 1.0))
    cols = [
        np.full(n, np.nan),
        np.where(rng.rand(n) < 0.77, np.nan, np.floor(0.4 * (rng.rand(n) ** -0.7 - 1.0))),
        np.full(n, 7.0),
        rng.randint(0, 3, n).astype(np.float64),
        heavy,
        rng.permutation(n).astype(np.float64) * 0.37,
        np.where(rng.rand(n) < 0.45, np.nan, heavy),
    ]
    return np.stack(cols, axis=1).astype(np.float32)


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("block_columns", [1, 2, 3, 4, 6])
def test_blocked_sketch_gives_the_bits_of_the_unblocked_one(
    monkeypatch, weighted, block_columns
):
    """Columns are independent under the kernel's vmap: cut for cut, bit for
    bit, a sketch run in blocks of any width (the last overlapping its
    neighbour) is the sketch run in one."""
    X = _criteo_like_columns()
    n, d = X.shape
    w = (np.random.RandomState(3).rand(n) + 0.2).astype(np.float32) if weighted else None
    whole = _cuts(X, w, 32, "device")
    assert [len(c) for c in whole[:4]] == [0, whole[1].size, 1, 2]
    # a budget that holds `block_columns` columns of n rows and no more
    monkeypatch.setattr(
        binning, "DEVICE_BLOCK_BYTES", binning.DEVICE_BYTES_PER_VALUE * n * block_columns
    )
    assert binning._equal_blocks(d, block_columns)[0] > 1
    blocked = _cuts(X, w, 32, "device")
    assert len(blocked) == d
    for f, (a, b) in enumerate(zip(whole, blocked)):
        assert a.dtype == b.dtype == np.float32
        assert a.tobytes() == b.tobytes(), (f, a, b)


@pytest.mark.parametrize("block_rows", [1, 7, 2999, 3000, 5999])
def test_blocked_apply_gives_the_bins_of_the_unblocked_one(monkeypatch, block_rows):
    X = _criteo_like_columns()
    n, d = X.shape
    cuts = _cuts(X, None, 32, "device")
    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "device")
    whole = binning.apply_cut_points(X, cuts, 32)
    monkeypatch.setattr(
        binning, "DEVICE_BLOCK_BYTES", binning.DEVICE_BYTES_PER_VALUE * d * block_rows
    )
    blocked = binning.apply_cut_points(X, cuts, 32)
    assert blocked.dtype == whole.dtype == np.uint8
    np.testing.assert_array_equal(whole, blocked)
    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "host")
    np.testing.assert_array_equal(binning.apply_cut_points(X, cuts, 32), whole)
    assert (whole[:, 0] == 32).all() and abs((whole[:, 1] == 32).mean() - 0.77) < 0.02


def test_equal_blocks_cover_everything_in_one_shape():
    for total in (1, 5, 28, 39, 136, 16387491):
        for most in (0, 1, 3, 8, 13, 19, 39, 10**9):
            blocks, size = binning._equal_blocks(total, most)
            assert 1 <= size <= max(most, 1) or blocks == 1
            starts = [min(b * size, total - size) for b in range(blocks)]
            covered = set()
            for s in starts:
                assert 0 <= s and s + size <= total
                covered.update(range(s, s + size) if total < 1000 else (s, s + size - 1))
            if total < 1000:
                assert covered == set(range(total))
            assert (blocks - 1) * size < total <= blocks * size
    # the cells the benchmark has keep one block: their programs do not move
    cap = binning.DEVICE_BLOCK_BYTES // binning.DEVICE_BYTES_PER_VALUE
    assert binning._equal_blocks(28, cap // 8800000) == (1, 28)
    assert binning._equal_blocks(136, cap // 2270296) == (1, 136)
    assert binning._equal_blocks(39, cap // 16387491) == (3, 13)
    assert binning._equal_blocks(8800000, cap // 28)[0] == 1
    assert binning._equal_blocks(2270296, cap // 136)[0] == 1
    assert binning._equal_blocks(16387491, cap // 39) == (2, 8193746)


def _shard_data(seed=17, rows=257, shards=4):
    rng = np.random.RandomState(seed)
    blocks = []
    for s in range(shards):
        x = rng.randn(rows, 6).astype(np.float32) + 0.3 * s  # the shares differ
        x[rng.rand(rows) < 0.2, 1] = np.nan
        x[:, 4] = rng.randint(0, 5, rows)
        blocks.append(x)
    return blocks


def test_each_shard_is_sketched_on_its_own_device(monkeypatch):
    """The device lowering a shard on the device it is given: the four
    shards' candidates are what each gives alone, merged by the one rule;
    the shards run side by side (one thread a shard)."""
    import threading

    import jax

    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "device")
    blocks = _shard_data()
    devices = jax.devices()[:4]
    placed, threads = [], set()
    real = binning._float_block

    def recording(block, device):
        out = real(block, device)
        placed.append((device, next(iter(out.devices()))))
        threads.add(threading.get_ident())
        return out

    monkeypatch.setattr(binning, "_float_block", recording)
    weights = [np.full(len(b), 1.0 + s, np.float32) for s, b in enumerate(blocks)]
    merged = binning.sketch_shards(blocks, weights, 32, devices)
    assert placed and all(asked == held for asked, held in placed)
    assert {asked for asked, _held in placed} == set(devices) and len(threads) == 4
    alone = [binning.compute_cut_points(b, w, 32) for b, w in zip(blocks, weights)]
    want = binning.merge_cut_candidates(alone, 32)
    assert [c.tobytes() for c in merged] == [c.tobytes() for c in want]
    assert all(len(c) <= 31 for c in merged)
    # one shard on the default device: its own sketch, untouched by the merge
    assert [c.tobytes() for c in binning.sketch_shards(blocks[:1], weights[:1], 32)] == [
        c.tobytes() for c in alone[0]
    ]


def test_each_shard_is_binned_on_its_own_device_and_left_there(monkeypatch):
    import jax

    blocks = _shard_data(seed=18)
    cuts = binning.compute_cut_points(np.concatenate(blocks), None, 32)
    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "host")
    want = [binning.apply_cut_points(b, cuts, 32) for b in blocks]
    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "device")
    devices = jax.devices()[:4]
    binned = binning.apply_shards(blocks, cuts, 32, devices, name="train")
    for out, dev, expect in zip(binned, devices, want):
        assert isinstance(out, jax.Array) and out.devices() == {dev}
        assert out.dtype == np.uint8  # narrowed where it lies: no int32 comes back
        assert np.asarray(out).tobytes() == expect.tobytes()
    back = binning.apply_shards(blocks, cuts, 32, devices, to_host=True)
    assert all(isinstance(b, np.ndarray) and b.tobytes() == w.tobytes() for b, w in zip(back, want))


def test_row_blocks_of_a_device_apply_join_on_the_device(monkeypatch):
    """A matrix over the block budget goes through in equal blocks of rows,
    the last overlapping its neighbour, and comes out whole, on the device."""
    import jax

    monkeypatch.setenv("GRAFT_SKETCH_IMPL", "device")
    rng = np.random.RandomState(19)
    X = rng.randn(1001, 5).astype(np.float32)
    X[::9, 2] = np.nan
    cuts = binning.compute_cut_points(X, None, 300)  # uint16 bins
    whole = binning.apply_shards([X], cuts, 300)[0]
    monkeypatch.setattr(binning, "DEVICE_BLOCK_BYTES", 30 * 5 * 334)  # 3 blocks of 334 rows
    blocked = binning.apply_shards([X], cuts, 300)[0]
    assert isinstance(blocked, jax.Array) and blocked.shape == (1001, 5)
    assert blocked.dtype == np.uint16
    assert np.asarray(blocked).tobytes() == np.asarray(whole).tobytes()
