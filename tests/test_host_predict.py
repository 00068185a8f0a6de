"""Host (numpy) small-payload predictor == compiled device predictor.

Serving's small-batch strategy (the reference C++ predictor at
serve_utils.py:244-250 has no dispatch floor): payloads at or
below GRAFT_HOST_PREDICT_ROWS run a vectorized numpy traversal that must be
bit-identical to the XLA kernel on every routing rule — numeric splits,
NaN-missing default directions, categorical set-membership, invalid
categories, multi-class tree grouping.
"""

import numpy as np
import pytest

from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
from sagemaker_xgboost_container_tpu.models import train
from sagemaker_xgboost_container_tpu.ops.predict import (
    forest_predict_margin,
    host_predict_margin,
)

from tests.test_categorical import _categorical_forest, CASES


def _trained_forest(objective="reg:squarederror", num_class=None, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(600, 6).astype(np.float32)
    X[rng.rand(600, 6) < 0.1] = np.nan  # exercise default directions
    if num_class:
        y = rng.randint(0, num_class, 600).astype(np.float32)
    elif objective == "binary:logistic":
        y = (np.nan_to_num(X[:, 0]) > 0.5).astype(np.float32)
    else:
        y = (np.nan_to_num(X) @ rng.rand(6)).astype(np.float32)
    params = {"max_depth": 4, "objective": objective}
    if num_class:
        params["num_class"] = num_class
    return train(params, DataMatrix(X, labels=y), num_boost_round=8)


@pytest.mark.parametrize("n_rows", [1, 7, 32])
@pytest.mark.parametrize(
    "objective,num_class",
    [("reg:squarederror", None), ("binary:logistic", None), ("multi:softprob", 3)],
)
def test_host_matches_device(n_rows, objective, num_class, monkeypatch):
    forest = _trained_forest(objective, num_class)
    rng = np.random.RandomState(7)
    X = rng.rand(n_rows, 6).astype(np.float32)
    X[rng.rand(n_rows, 6) < 0.2] = np.nan

    monkeypatch.setenv("GRAFT_HOST_PREDICT_ROWS", "0")
    device = forest.predict_margin(X)
    monkeypatch.setenv("GRAFT_HOST_PREDICT_ROWS", "64")
    host = forest.predict_margin(X)
    np.testing.assert_allclose(host, device, rtol=1e-6, atol=1e-6)


def test_host_matches_device_categorical():
    forest = _categorical_forest()
    stacked = forest._stack(slice(0, 1))
    X = np.array([[f0, f1] for (f0, f1), _ in CASES], np.float32)
    host = host_predict_margin(stacked, X)
    device = forest_predict_margin(stacked, X)
    np.testing.assert_allclose(host, device, rtol=1e-6)
    np.testing.assert_allclose(host, [exp for _, exp in CASES], rtol=1e-6)


@pytest.mark.parametrize(
    "objective,num_class",
    [("reg:squarederror", None), ("binary:logistic", None), ("multi:softprob", 3)],
)
def test_native_host_matches_numpy_host(objective, num_class, monkeypatch):
    """r5: the C++ traversal (fastdata.cpp::forest_leaf_values) must be
    BIT-identical to the numpy twin on every routing rule — both produce
    per-tree leaf values, and the group summing is shared numpy."""
    from sagemaker_xgboost_container_tpu.data.native import forest_predictor_available

    if not forest_predictor_available():
        pytest.skip("no native forest traversal on this host")
    forest = _trained_forest(objective, num_class, seed=5)
    rng = np.random.RandomState(11)
    X = rng.rand(9, 6).astype(np.float32)
    X[rng.rand(9, 6) < 0.25] = np.nan
    stacked = forest._stack(slice(0, len(forest.trees)))
    info = forest.tree_info
    kw = dict(num_output_group=forest.num_output_group, tree_info=info)

    monkeypatch.setenv("GRAFT_HOST_PREDICT_IMPL", "numpy")
    a = host_predict_margin(stacked, X, **kw)
    monkeypatch.delenv("GRAFT_HOST_PREDICT_IMPL")
    b = host_predict_margin(stacked, X, **kw)
    np.testing.assert_array_equal(a, b)


def test_native_host_matches_numpy_host_categorical(monkeypatch):
    """Category bitmask membership, invalid categories (negative /
    out-of-range floats), and NaN-missing agree between C++ and numpy."""
    from sagemaker_xgboost_container_tpu.data.native import forest_predictor_available

    if not forest_predictor_available():
        pytest.skip("no native forest traversal on this host")
    forest = _categorical_forest()
    stacked = forest._stack(slice(0, 1))
    X = np.array([[f0, f1] for (f0, f1), _ in CASES], np.float32)

    monkeypatch.setenv("GRAFT_HOST_PREDICT_IMPL", "numpy")
    a = host_predict_margin(stacked, X)
    monkeypatch.delenv("GRAFT_HOST_PREDICT_IMPL")
    b = host_predict_margin(stacked, X)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(b, [exp for _, exp in CASES], rtol=1e-6)


def test_native_refuses_corrupt_indices():
    """A corrupt BYO model with out-of-range node/feature ids must never
    reach the C++ loop (OOB read); the native wrapper refuses ONCE per
    stacked forest and callers fall back to numpy, which fails loudly."""
    from sagemaker_xgboost_container_tpu.data.native import (
        forest_leaf_values_native, forest_predictor_available,
    )

    if not forest_predictor_available():
        pytest.skip("no native forest traversal on this host")
    forest = _trained_forest(seed=2)
    X = np.random.RandomState(0).rand(3, 6).astype(np.float32)

    bad = dict(forest._stack(slice(0, len(forest.trees))))
    bad.pop("_native_args", None)  # fresh validation on the mutated copy
    bad["left"] = np.asarray(bad["left"]).copy()
    bad["left"][0, 0] = 10**6  # node id far past N
    assert forest_leaf_values_native(bad, X) is None
    assert forest_leaf_values_native(bad, X) is None  # cached refusal

    wide = dict(forest._stack(slice(0, len(forest.trees))))
    wide.pop("_native_args", None)
    wide["feature"] = np.asarray(wide["feature"]).copy()
    wide["feature"][0, 0] = 99  # feature id beyond the payload width
    assert forest_leaf_values_native(wide, X) is None


def test_threshold_respected(monkeypatch):
    """Above the cutover the device path must still be used (power-of-2
    padded), below it the host path — outputs agree either way."""
    forest = _trained_forest()
    X = np.random.RandomState(3).rand(33, 6).astype(np.float32)
    monkeypatch.setenv("GRAFT_HOST_PREDICT_ROWS", "32")
    above = forest.predict_margin(X)      # 33 rows -> device
    below = forest.predict_margin(X[:32])  # 32 rows -> host
    np.testing.assert_allclose(above[:32], below, rtol=1e-6, atol=1e-6)
