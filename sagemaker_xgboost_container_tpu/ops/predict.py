"""Compiled forest inference kernel.

The serving-side replacement for libxgboost's C++ predictor (reference hot
loop: serve_utils.py:244-250 ``booster.predict``). The whole forest is laid
out as stacked per-tree node arrays in HBM; traversal is ``depth`` rounds of
vectorized gather/compare over [rows x trees] — no per-tree Python, one XLA
program, jit-cached per (num_rows bucket, forest version).

Works on explicit child indices (not the padded full-binary layout) so
imported xgboost-JSON models of any shape run through the same kernel.
Missing values (NaN) follow ``default_left``.
"""

import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _leaf_nodes_impl(
    xp, feature, threshold, default_left, left, right, is_leaf, x, depth,
    cat_split=None, cat_mask=None,
):
    """The ONE traversal implementation, parameterized by array namespace
    (``xp`` = jnp for the jitted device kernels, np for the host small-payload
    path) so the routing rules cannot diverge between them.

    Rules (xgboost semantics): NaN-missing follows ``default_left``;
    numerical nodes go right when ``v >= threshold``; categorical nodes
    (cat_split/cat_mask given; xgboost common::Decision) go right when the
    int category is in the node's bitmask, while an invalid category
    (negative float / out-of-range) goes LEFT unconditionally — negativity
    is checked on the FLOAT value: -0.5 truncates to int 0 but is still
    invalid. Leaves self-loop via left/right == own index.
    """
    n = x.shape[0]
    T = feature.shape[0]
    node = xp.zeros((n, T), xp.int32)
    t_idx = xp.broadcast_to(xp.arange(T)[None, :], (n, T))
    if cat_mask is not None:
        max_cat = cat_mask.shape[2] * 32

    for _ in range(depth):
        feat = feature[t_idx, node]            # [n, T]
        thr = threshold[t_idx, node]
        v = xp.take_along_axis(x, feat.reshape(n, -1), axis=1).reshape(n, T)
        miss = xp.isnan(v)
        dfl = default_left[t_idx, node]
        go_right = xp.where(miss, ~dfl, v >= thr)
        if cat_mask is not None:
            # range checks on the FLOAT value: float->int32 of values >= 2^31
            # wraps on numpy but saturates on XLA:TPU, so an int-side
            # comparison would diverge between the host and device paths
            invalid = (v < 0) | (v >= max_cat)
            # clip the FLOAT before the int cast: inf / >=2^31 values would
            # otherwise warn on numpy (and saturate on XLA); the `invalid`
            # flag above already captured out-of-range on the float side
            cat = xp.clip(
                xp.nan_to_num(v, nan=-1.0), -1.0, float(max_cat)
            ).astype(xp.int32)
            safe_cat = xp.clip(cat, 0, max_cat - 1)
            word = cat_mask[t_idx, node, safe_cat >> 5]
            in_set = ((word >> (safe_cat & 31).astype(xp.uint32)) & 1) == 1
            go_right_cat = xp.where(miss, ~dfl, xp.where(invalid, False, in_set))
            go_right = xp.where(cat_split[t_idx, node], go_right_cat, go_right)
        nxt = xp.where(go_right, right[t_idx, node], left[t_idx, node])
        node = xp.where(is_leaf[t_idx, node], node, nxt)
    return node


@partial(jax.jit, static_argnames=("depth",))
def _forest_leaf_nodes(feature, threshold, default_left, left, right, is_leaf, x, depth):
    """x: f32 [n, d] (NaN = missing) -> leaf node index per (row, tree)."""
    return _leaf_nodes_impl(
        jnp, feature, threshold, default_left, left, right, is_leaf, x, depth
    )


@partial(jax.jit, static_argnames=("depth",))
def _forest_leaf_nodes_cat(
    feature, threshold, default_left, left, right, is_leaf,
    cat_split, cat_mask, x, depth,
):
    """Traversal with partition-based categorical nodes (BYO xgboost models)."""
    return _leaf_nodes_impl(
        jnp, feature, threshold, default_left, left, right, is_leaf, x, depth,
        cat_split=cat_split, cat_mask=cat_mask,
    )


def _stacked_args(stacked, *extra_keys):
    """Common [T, N] traversal arrays (+ extras) as device arrays."""
    keys = ("feature", "threshold", "default_left", "left", "right", "is_leaf")
    return tuple(jnp.asarray(stacked[k]) for k in keys + extra_keys)


def forest_leaf_nodes(stacked, x):
    """Dispatch: the plain numerical kernel, or the categorical-aware one
    when the stacked forest carries category bitmasks."""
    x = jnp.asarray(x, jnp.float32)
    if "cat_split" in stacked:
        return _forest_leaf_nodes_cat(
            *_stacked_args(stacked, "cat_split", "cat_mask"), x, stacked["depth"]
        )
    return _forest_leaf_nodes(*_stacked_args(stacked), x, stacked["depth"])


@partial(jax.jit, static_argnames=("depth",))
def _forest_margin(feature, threshold, default_left, left, right, is_leaf, leaf_value, x, depth):
    """x: f32 [n, d] (NaN = missing) -> per-tree-group margins [n].

    Tree arrays: [T, N] stacked; leaves self-loop via left/right == own index.
    """
    T = feature.shape[0]
    t_idx = jnp.arange(T)[None, :]
    node = _forest_leaf_nodes(
        feature, threshold, default_left, left, right, is_leaf, x, depth
    )
    return leaf_value[t_idx, node]             # [n, T]


@partial(jax.jit, static_argnames=("depth",))
def _forest_margin_cat(
    feature, threshold, default_left, left, right, is_leaf,
    cat_split, cat_mask, leaf_value, x, depth,
):
    T = feature.shape[0]
    t_idx = jnp.arange(T)[None, :]
    node = _forest_leaf_nodes_cat(
        feature, threshold, default_left, left, right, is_leaf,
        cat_split, cat_mask, x, depth,
    )
    return leaf_value[t_idx, node]             # [n, T]


def forest_leaf_margins(stacked, x):
    """Per-tree leaf contributions [n, T]; one cached XLA program either way
    (categorical-aware when the stacked forest carries category bitmasks)."""
    x = jnp.asarray(x, jnp.float32)
    if "cat_split" in stacked:
        return _forest_margin_cat(
            *_stacked_args(stacked, "cat_split", "cat_mask", "leaf_value"),
            x,
            stacked["depth"],
        )
    return _forest_margin(
        *_stacked_args(stacked, "leaf_value"), x, stacked["depth"]
    )


def forest_predict_margin(stacked, x, num_output_group=1, base_margin=0.0, tree_info=None):
    """Sum per-tree leaf outputs into per-group margins.

    stacked: dict of [T, N] numpy/jnp arrays + "depth" int.
    Returns [n] (single group) or [n, num_output_group].
    """
    leaf = forest_leaf_margins(stacked, x)
    if num_output_group == 1:
        return np.asarray(leaf.sum(axis=1)) + base_margin
    # group trees by class id (tree_info) — static host-side partition
    out = np.zeros((x.shape[0], num_output_group), np.float32)
    leaf_np = np.asarray(leaf)
    info = np.asarray(tree_info)
    for c in range(num_output_group):
        out[:, c] = leaf_np[:, info == c].sum(axis=1) + base_margin
    return out


# ------------------------------------------------------------- host predictor


def host_leaf_nodes(stacked, x):
    """Numpy twin of the XLA traversal for tiny serving payloads.

    A 1-row `/invocations` on TPU pays the full host->device->host dispatch
    for microseconds of compute; the reference's C++ predictor
    (serve_utils.py:244-250) has no such floor. Rows below ``Forest``'s host-path threshold therefore run
    ``_leaf_nodes_impl`` with xp=np — the same code the jitted kernels run,
    so the routing rules cannot diverge.
    """
    x = np.asarray(x, np.float32)
    keys = ("feature", "threshold", "default_left", "left", "right", "is_leaf")
    arrays = tuple(np.asarray(stacked[k]) for k in keys)
    cat = {}
    if "cat_split" in stacked:
        cat = {
            "cat_split": np.asarray(stacked["cat_split"]),
            "cat_mask": np.asarray(stacked["cat_mask"]),
        }
    return _leaf_nodes_impl(np, *arrays, x, int(stacked["depth"]), **cat)


def _host_leaf_values(stacked, x):
    """[n, T] per-tree leaf values on the host: the C++ traversal
    (native/fastdata.cpp::forest_leaf_values — the reference's libxgboost
    C++ predictor analog, ~2 us vs ~0.3 ms of numpy per-op overhead for a
    100-tree single-row request) with the numpy twin as fallback.
    GRAFT_HOST_PREDICT_IMPL=numpy forces the fallback for A/Bs."""
    x = np.asarray(x, np.float32)
    if os.environ.get("GRAFT_HOST_PREDICT_IMPL", "native") != "numpy":
        from ..data.native import forest_leaf_values_native

        leaf = forest_leaf_values_native(stacked, x)
        if leaf is not None:
            return leaf
    node = host_leaf_nodes(stacked, x)
    leaf_value = np.asarray(stacked["leaf_value"])
    T = leaf_value.shape[0]
    return leaf_value[np.arange(T)[None, :], node]       # [n, T]


def host_predict_margin(stacked, x, num_output_group=1, base_margin=0.0, tree_info=None):
    """Host forest margin for tiny payloads (same contract as
    ``forest_predict_margin``, no device dispatch, no padding needed)."""
    leaf = _host_leaf_values(stacked, x)
    if num_output_group == 1:
        return leaf.sum(axis=1) + base_margin
    out = np.zeros((x.shape[0], num_output_group), np.float32)
    info = np.asarray(tree_info)
    for c in range(num_output_group):
        out[:, c] = leaf[:, info == c].sum(axis=1) + base_margin
    return out
