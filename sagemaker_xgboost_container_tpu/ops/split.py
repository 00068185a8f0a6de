"""Split finding: gain scan over level histograms.

XGBoost-exact split semantics in pure XLA (replacing libxgboost's
EnumerateSplit): L1 thresholding (alpha), L2 smoothing (lambda), gamma
complexity penalty, min_child_weight pruning, and **sparsity-aware missing
direction** — both placements of the missing bucket are scored and the argmax
decides ``default_left``, reproducing the reference's default-direction
behavior for sparse libsvm data.

All shapes static: histograms are [W, d, B] with B = max_bin + 1 (last slot =
missing); the scan considers splits at bins 0..B-3 masked by each feature's
true cut count.
"""

import jax
import jax.numpy as jnp

_EPS = 1e-6  # xgboost kRtEps: minimum loss change to accept a split


def combine_splits_across_shards(splits, feat_shard, d_local, feature_axis_name):
    """Merge per-shard best splits along a mesh axis carrying feature slices.

    Each column shard proposes its best (gain, local feature, bin,
    default_left) per node; the winner is the max gain with ties broken
    toward the lowest global feature id (matching the single-device argmax
    over the concatenated column order), and the winning shard's bin /
    default_left are psum-broadcast so every shard ends with identical
    global split decisions.

    The *feature* mesh axis (column-sharded data — the reference's
    vestigial dsplit=col done as SPMD) is this merge's only caller, from
    both the depthwise (ops/tree_build.py) and leaf-wise (ops/lossguide.py)
    builders. ``g_total``/``h_total`` are already identical on every shard
    (every row lands in exactly one bin of every feature), so they pass
    through.
    """
    global_feat = splits["feature"] + feat_shard * d_local
    gain = splits["gain"]
    best_gain = jax.lax.pmax(gain, feature_axis_name)
    is_tied_winner = gain == best_gain
    cand = jnp.where(is_tied_winner, global_feat, jnp.int32(2**30))
    win_feat = jax.lax.pmin(cand, feature_axis_name)
    i_own = is_tied_winner & (global_feat == win_feat)

    def _sel(x):
        return jax.lax.psum(
            jnp.where(i_own, x, jnp.zeros_like(x)), feature_axis_name
        )

    return {
        "gain": best_gain,
        "feature": _sel(global_feat),
        "bin": _sel(splits["bin"]),
        "default_left": _sel(splits["default_left"].astype(jnp.int32)) > 0,
        "g_total": splits["g_total"],
        "h_total": splits["h_total"],
    }


def column_shard_helpers(feat_shard, d_local, n_feature_shards, d_global):
    """Shared cross-shard column-draw convention for both tree builders.

    Column-subset draws (colsample_bylevel/bynode, interaction masks) are
    made over the REAL global feature count ``d_draw`` with the replicated
    rng — an identical threefry stream to the single-device build, which
    never pads — then zero-padded to the padded global width and sliced to
    this shard's segment. A per-shard draw would silently decorrelate split
    choices across shards.

    Returns ``(d_draw, pad_cols, local_cols)`` where ``pad_cols`` zero-pads
    a [..., d_draw] mask to [..., d_total] and ``local_cols`` slices a
    global-width mask down to this shard's [..., d_local] columns (identity
    when there is no feature axis, i.e. ``feat_shard is None``).
    """
    d_total = d_local * n_feature_shards
    d_draw = int(d_global) if d_global is not None else d_total

    def pad_cols(mask_real):
        if d_draw == d_total:
            return mask_real
        pad = [(0, 0)] * (mask_real.ndim - 1) + [(0, d_total - d_draw)]
        return jnp.pad(mask_real, pad)

    def local_cols(mask_global):
        if feat_shard is None:
            return mask_global
        start = (0,) * (mask_global.ndim - 1) + (feat_shard * d_local,)
        sizes = mask_global.shape[:-1] + (d_local,)
        return jax.lax.dynamic_slice(mask_global, start, sizes)

    return d_draw, pad_cols, local_cols


def _threshold_l1(g, alpha):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - alpha, 0.0)


def _score(g, h, reg_lambda, alpha):
    t = _threshold_l1(g, alpha)
    return (t * t) / (h + reg_lambda)


def find_best_splits(
    G,
    H,
    num_cuts,
    reg_lambda=1.0,
    alpha=0.0,
    gamma=0.0,
    min_child_weight=1.0,
    feature_mask=None,
    monotone=None,
    gathers=True,
):
    """Best (feature, bin, default_dir, gain) per node at one level.

    Args:
      G, H: f32 [W, d, B] level histograms (B includes the missing slot).
      num_cuts: i32 [d] — number of real cut thresholds per feature; splits
        are only legal at bin < num_cuts[f].
      feature_mask: optional f32/bool [d] colsample mask, or [W, d] per-node
        mask (interaction constraints); 1 = usable.
      monotone: optional i32 [d] in {-1, 0, 1} monotone constraints.
      gathers: static; how the winner's gain and default direction are read
        at ``best_idx``. True: two ``take_along_axis`` gathers, a node long.
        False, for a build mapped over the class trees of a round: a maximum
        and a compare-select-reduce, the same values bit for bit. Mapped, the
        gathers become one over a [T, W, d * nbins] operand that XLA's
        memory-space assignment may keep in VMEM on the chip, and a v5e stops
        for good in such a gather of the ``take_left`` mask at some indices
        (the program it was found in: PERF.md section 6, PR 41).

    Returns dict of per-node arrays (length W): gain f32, feature i32,
    bin i32, default_left bool, plus node totals g_total/h_total f32.
    """
    W, d, B = G.shape
    nbins = B - 1  # data bins
    # node totals: every row lands in exactly one bin of feature 0
    g_total = G[:, 0, :].sum(axis=-1)
    h_total = H[:, 0, :].sum(axis=-1)

    g_miss = G[:, :, nbins]  # [W, d]
    h_miss = H[:, :, nbins]

    # cumulative over data bins: CL[w, f, b] = sum_{b' <= b}
    g_cum = jnp.cumsum(G[:, :, :nbins], axis=-1)
    h_cum = jnp.cumsum(H[:, :, :nbins], axis=-1)

    parent = _score(g_total, h_total, reg_lambda, alpha)[:, None, None]

    def _gain(gl, hl):
        gr = g_total[:, None, None] - gl
        hr = h_total[:, None, None] - hl
        ok = (hl >= min_child_weight) & (hr >= min_child_weight)
        raw = 0.5 * (
            _score(gl, hl, reg_lambda, alpha)
            + _score(gr, hr, reg_lambda, alpha)
            - parent
        ) - gamma
        if monotone is not None:
            wl = -_threshold_l1(gl, alpha) / (hl + reg_lambda)
            wr = -_threshold_l1(gr, alpha) / (hr + reg_lambda)
            mono = monotone[None, :, None]
            ok = ok & jnp.where(
                mono == 0, True, jnp.where(mono > 0, wl <= wr, wl >= wr)
            )
        return jnp.where(ok, raw, -jnp.inf)

    gain_right = _gain(g_cum, h_cum)                       # missing -> right
    gain_left = _gain(g_cum + g_miss[:, :, None], h_cum + h_miss[:, :, None])

    # mask: split bin must be a real cut of this feature
    bin_ids = jnp.arange(nbins, dtype=jnp.int32)[None, :]
    legal = bin_ids < num_cuts[:, None]                    # [d, nbins]
    legal = legal[None, :, :]
    if feature_mask is not None:
        if feature_mask.ndim == 2:  # [W, d] per-node mask
            legal = legal & (feature_mask[:, :, None] > 0)
        else:
            legal = legal & (feature_mask[None, :, None] > 0)
    gain_right = jnp.where(legal, gain_right, -jnp.inf)
    gain_left = jnp.where(legal, gain_left, -jnp.inf)

    take_left = gain_left > gain_right
    gain = jnp.where(take_left, gain_left, gain_right)     # [W, d, nbins]

    flat = gain.reshape(W, d * nbins)
    best_idx = jnp.argmax(flat, axis=1)
    best_feature = (best_idx // nbins).astype(jnp.int32)
    best_bin = (best_idx % nbins).astype(jnp.int32)
    if gathers:
        best_gain = jnp.take_along_axis(flat, best_idx[:, None], axis=1)[:, 0]
        best_default_left = jnp.take_along_axis(
            take_left.reshape(W, d * nbins), best_idx[:, None], axis=1
        )[:, 0]
    else:
        # argmax is the first maximum (a NaN counts as one), so the value
        # there is the maximum itself
        best_gain = flat.max(axis=1)
        at_best = jnp.arange(d * nbins, dtype=best_idx.dtype)[None, :] == best_idx[:, None]
        best_default_left = (take_left.reshape(W, d * nbins) & at_best).any(axis=1)

    return {
        "gain": jnp.where(jnp.isfinite(best_gain), best_gain, -jnp.inf),
        "feature": best_feature,
        "bin": best_bin,
        "default_left": best_default_left,
        "g_total": g_total,
        "h_total": h_total,
    }


def leaf_weight(g, h, reg_lambda=1.0, alpha=0.0, max_delta_step=0.0):
    """Optimal leaf weight -T(g)/(h+lambda), clipped by max_delta_step."""
    w = -_threshold_l1(g, alpha) / (h + reg_lambda)
    if max_delta_step > 0:
        w = jnp.clip(w, -max_delta_step, max_delta_step)
    return w
