"""Row routing's bin fetch: ``row_bin_lookup``'s two lowerings give the same
integers, the tree builder and the binned traversal give the same trees and
margins under either, and the chooser reads a backend and a width and nothing
else. Everything runs on the CPU with the lowering forced: through ``impl=`` /
``route_impl=`` where the function takes it, through the session snapshot's
``route_backend`` where it takes ``knobs``.
"""

import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from sagemaker_xgboost_container_tpu.data.binning import (
    apply_cut_points,
    compute_cut_points,
)
from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs
from sagemaker_xgboost_container_tpu.ops.tree_build import (
    ROUTE_DENSE_MAX_WIDTH,
    build_tree,
    choose_route_impl,
    pack_tree,
    predict_binned,
    row_bin_lookup,
    tree_from_packed,
)

# the backend whose chooser picks each lowering at the widths used here
BACKEND_OF = {"dense": "tpu", "gather": "cpu"}


@pytest.mark.parametrize("d", [1, 7, 28, 130])
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
def test_row_bin_lookup_dense_equals_gather(dtype, d):
    num_bins = 256 if dtype == np.uint8 else 257
    n = 1000 + 37  # not a multiple of the 128 lanes
    rng = np.random.RandomState(d)
    bins = rng.randint(0, num_bins, size=(n, d)).astype(dtype)
    bins[rng.rand(n, d) < 0.1] = num_bins - 1  # the missing bin
    feat = rng.randint(0, d, size=n).astype(np.int32)
    feat[:4] = [0, d - 1, 0, d - 1]
    bins[0, 0] = bins[1, d - 1] = num_bins - 1
    want = bins[np.arange(n), feat].astype(np.int32)
    for impl in ("gather", "dense"):
        got = row_bin_lookup(jnp.asarray(bins), jnp.asarray(feat), impl=impl)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), want, err_msg=impl)


def test_row_bin_lookup_rejects_unknown_lowering():
    with pytest.raises(ValueError, match="onehot"):
        row_bin_lookup(jnp.zeros((4, 2), jnp.uint8), jnp.zeros(4, jnp.int32), impl="onehot")


def _nan_problem(n=3000, d=8, max_bin=32, seed=5):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, d).astype(np.float32)
    x[rng.rand(n, d) < 0.1] = np.nan
    y = (np.nan_to_num(x[:, 0]) + np.sin(6 * np.nan_to_num(x[:, 2])) > 1).astype(np.float32)
    cuts = compute_cut_points(x, None, max_bin)
    bins = apply_cut_points(x, cuts, max_bin).astype(np.uint8)
    num_cuts = np.asarray([len(c) for c in cuts], np.int32)
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)
    assert (bins == max_bin).any()  # NaNs sit in the missing bin
    return bins, grad, hess, num_cuts, max_bin + 1


def _build_plain(problem, knobs):
    bins, grad, hess, num_cuts, num_bins = problem

    @jax.jit
    def build(b, g, h, nc):
        tree, row_out = build_tree(
            b, g, h, nc, max_depth=5, num_bins=num_bins, eta=0.3, knobs=knobs
        )
        return pack_tree(tree), row_out

    packed, row_out = build(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(num_cuts)
    )
    return np.asarray(packed), np.asarray(row_out)


def _build_feature_sharded(problem, knobs):
    bins, grad, hess, num_cuts, num_bins = problem
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), axis_names=("data", "feature"))

    def build(b, g, h, nc):
        tree, row_out = build_tree(
            b, g, h, nc, max_depth=5, num_bins=num_bins, eta=0.3, knobs=knobs,
            axis_name="data", feature_axis_name="feature",
        )
        return pack_tree(tree), row_out

    mapped = jax.jit(
        jax.shard_map(
            build,
            mesh=mesh,
            in_specs=(P("data", "feature"), P("data"), P("data"), P("feature")),
            out_specs=(P(), P("data")),
            check_vma=False,
        )
    )
    packed, row_out = mapped(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(num_cuts)
    )
    return np.asarray(packed), np.asarray(row_out)


@pytest.mark.multichip
@pytest.mark.parametrize(
    "build", [_build_plain, _build_feature_sharded], ids=["plain", "feature_sharded"]
)
def test_build_tree_identical_under_each_lowering(build):
    """Tree arrays and ``row_out`` bit for bit; on the feature axis the width
    the chooser sees is the shard's own two columns."""
    problem = _nan_problem()
    results = {}
    for impl, backend in BACKEND_OF.items():
        knobs = resolve_hist_knobs()._replace(route_backend=backend)
        assert choose_route_impl(knobs.route_backend, 2) == impl
        results[impl] = build(problem, knobs)
    packed, row_out = results["gather"]
    assert (packed[3] < 0.5).sum() > 10  # is_leaf row: a tree with real splits
    np.testing.assert_array_equal(results["dense"][0], packed)
    np.testing.assert_array_equal(results["dense"][1], row_out)


def test_predict_binned_identical_under_each_lowering():
    problem = _nan_problem()
    bins, num_bins = problem[0], problem[-1]
    packed, row_out = _build_plain(problem, None)
    tree = tree_from_packed(jnp.asarray(packed))
    unseen = _nan_problem(n=1111, seed=9)[0]
    for rows in (unseen, bins):
        got = {
            impl: np.asarray(
                predict_binned(tree, jnp.asarray(rows), 5, num_bins, route_impl=impl)
            )
            for impl in ("gather", "dense")
        }
        np.testing.assert_array_equal(got["dense"], got["gather"])
    # the traversal lands every train row on the leaf the build routed it to
    np.testing.assert_array_equal(got["dense"], row_out)
    assert np.unique(got["dense"]).size > 4


@pytest.mark.parametrize(
    "backend,width,want",
    [
        ("tpu", 1, "dense"),
        ("tpu", 28, "dense"),
        ("tpu", ROUTE_DENSE_MAX_WIDTH, "dense"),
        ("tpu", ROUTE_DENSE_MAX_WIDTH + 1, "gather"),
        ("tpu", 1 << 20, "gather"),
        ("cpu", 1, "gather"),
        ("cpu", 28, "gather"),
        ("cpu", ROUTE_DENSE_MAX_WIDTH + 1, "gather"),
        ("gpu", 28, "gather"),
    ],
)
def test_chooser_reads_backend_and_width_only(monkeypatch, backend, width, want):
    class NoEnviron:
        def __getattr__(self, name):
            raise AssertionError("the chooser read the environment")

        __getitem__ = __contains__ = __getattr__

    with monkeypatch.context() as during_the_call:
        during_the_call.setattr(os, "environ", NoEnviron())
        got = choose_route_impl(backend, width)
    assert got == want


def test_session_snapshot_holds_the_backend():
    knobs = resolve_hist_knobs()
    assert knobs.route_backend == jax.default_backend() == "cpu"


@pytest.mark.parametrize(
    "backend,width,want", [("tpu", 28, "dense"), ("cpu", 28, "gather"), ("tpu", None, None)]
)
def test_device_runtime_line_names_the_resolved_lowering(
    monkeypatch, caplog, backend, width, want
):
    from sagemaker_xgboost_container_tpu.utils import device_runtime

    monkeypatch.setattr(device_runtime, "enable_compile_cache", lambda: None)
    knobs = resolve_hist_knobs()._replace(route_backend=backend)
    with caplog.at_level(logging.INFO, logger=device_runtime.__name__):
        fields = device_runtime.start_device_runtime("train", knobs=knobs, route_width=width)
    assert (fields["route_impl"], fields["route_width"]) == (want, width)
    line = [r.getMessage() for r in caplog.records if "device runtime: " in r.getMessage()][-1]
    logged = json.loads(line.split("device runtime: ", 1)[1])
    assert (logged["route_impl"], logged["route_width"]) == (want, width)
