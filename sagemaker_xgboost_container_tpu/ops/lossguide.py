"""Leaf-wise (lossguide) tree growth: best-gain-first splitting to max_leaves.

The reference validates grow_policy=lossguide + max_leaves
(hyperparameter_validation.py:259-260) and delegates to libxgboost's
lossguide updater (LightGBM-style growth). Static-shape XLA formulation:

* node slots are allocated sequentially (root=0; split t creates 2t+1, 2t+2),
  explicit child indices — the shared tree layout of ops/tree_build;
* the ``max_leaves - 1`` split steps are ONE rolled ``lax.fori_loop`` over
  the build's state (tree arrays, candidate store, node sums and depths, each
  row's node, the per-node histogram cache, the alive constraint sets): the
  program holds one kernel call site, one split scan and one routing step
  whatever ``max_leaves`` (95,708 equations unrolled at 255 leaves, where a
  depth-8 ``build_tree`` has 1,892). Each step picks the global best-gain
  leaf (argmax over the candidate store), routes its rows, and histograms
  the fresh children (the left one alone with sibling subtraction);
* every leaf keeps a precomputed best-split candidate, so step selection is
  O(nodes), not O(n);
* every instruction lies under a stage of the round program
  (``telemetry/device.py::STAGES``): ``hist``, ``split_scan``, ``route_rows``,
  ``leaf_margin``, and ``step_pick`` for the pick, the tree and store
  updates, the cache's slot writes and the loop itself.

Cost note: each step rescans all n rows for the 2-child histogram, so a tree
costs O(max_leaves * n * d) versus depthwise's O(max_depth * n * d); this is
inherent to static-shape leaf-wise growth without dynamic row partitions.
"""

import jax
import jax.numpy as jnp

from .histogram import (
    _comm_overlap,
    apply_hist_collective,
    level_histogram,
    overlap_node_batches,
    padded_feature_width,
    subtraction_enabled,
)
from ..telemetry.device import (
    STAGE_HIST,
    STAGE_LEAF_MARGIN,
    STAGE_ROUTE_ROWS,
    STAGE_SPLIT_SCAN,
    STAGE_STEP_PICK,
    stage,
)
from .split import (
    broadcast_node_totals,
    column_shard_helpers,
    combine_splits_across_shards,
    concat_node_splits,
    find_best_splits,
    leaf_weight,
    shard_feature_slice,
)

MIN_SPLIT_LOSS = 1e-6


def _subtraction_enabled(max_leaves, d_hist, num_bins):
    """Sibling subtraction for leaf-wise growth: every split step histograms
    only the LEFT fresh child (W=1 scan over rows) and derives the right one
    from the parent's cached histogram — halving per-step histogram work.
    Needs a [2*max_leaves-1, d_hist, B] f32 cache x2, so gated by the shared
    cap. Callers pass the FULL feature width regardless of the
    GRAFT_HIST_COMM lowering (same-decision-both-lowerings bit-identity
    contract — see ops.tree_build._subtraction_enabled); under
    reduce_scatter the resident cache is only the d/axis_size slice."""
    return subtraction_enabled(2 * (2 * max_leaves - 1) * d_hist * num_bins * 4)


def build_tree_lossguide(
    bins,
    grad,
    hess,
    num_cuts,
    max_leaves,
    num_bins,
    max_depth=0,
    reg_lambda=1.0,
    alpha=0.0,
    gamma=0.0,
    min_child_weight=1.0,
    eta=0.3,
    max_delta_step=0.0,
    feature_mask=None,
    monotone=None,
    axis_name=None,
    rng=None,
    colsample_bylevel=1.0,
    colsample_bynode=1.0,
    interaction_sets=None,
    feature_axis_name=None,
    n_feature_shards=1,
    d_global=None,
    hist_comm="psum",
    n_data_shards=1,
    knobs=None,
):
    """Grow one leaf-wise tree. Returns (tree arrays dict, row_out [n]).

    Same output layout as ops.tree_build.build_tree; max_depth=0 means
    unbounded depth (bounded by max_leaves - 1). ``hist_comm`` selects the
    data-axis collective (see ops.tree_build.build_tree): reduce_scatter
    scans only this shard's feature slice per step and merges winners into
    the candidate store with bit-identical tie-breaking. ``knobs``: the
    session's ``ops.histogram.HistKnobs`` snapshot (trace-safety; None,
    for direct unit-test/probe callers, chooses from the process's backend).
    """
    n, d = bins.shape
    max_nodes = 2 * max_leaves - 1
    depth_cap = max_depth if max_depth > 0 else max_leaves
    reduce_scatter = hist_comm == "reduce_scatter" and axis_name is not None
    # ``d`` is the feature-shard-LOCAL width on a 2-D (data x feature)
    # mesh, so the reduce_scatter slicing composes with the feature axis —
    # see ops.tree_build.build_tree: each device scans a doubly-sharded
    # d_local/n_data_shards block and winners merge hierarchically.
    d_scan = padded_feature_width(d, n_data_shards) // n_data_shards if reduce_scatter else d
    data_shard = jax.lax.axis_index(axis_name) if reduce_scatter else None

    def _scan_slice(arr):
        """Per-feature scan input -> this shard's slice (reduce_scatter)."""
        if not reduce_scatter or arr is None:
            return arr
        return shard_feature_slice(arr, data_shard, d_scan, n_data_shards)

    # feature-axis sharding: this shard holds columns [feat_shard*d,
    # (feat_shard+1)*d) of the global matrix; candidate splits are combined
    # across shards (combine_splits_across_shards) so the candidate store —
    # and therefore every step's argmax — is identical on all shards, and
    # feature ids in the store/tree are GLOBAL.
    feat_shard = (
        jax.lax.axis_index(feature_axis_name) if feature_axis_name is not None else None
    )
    # shared column-draw convention (ops/split.py), so depthwise and
    # lossguide shards agree on every mask stream
    d_draw, _pad_cols, _local_cols = column_shard_helpers(
        feat_shard, d, n_feature_shards, d_global
    )

    def _combine(splits):
        if reduce_scatter:
            # data-axis winner merge (shared with the feature-axis path);
            # totals were broadcast from data-shard 0 before the scan. On a
            # 2-D mesh this yields feature-shard-local ids, globalized by
            # the feature-axis merge below (hierarchical two-axis merge).
            splits = combine_splits_across_shards(
                splits, data_shard, d_scan, axis_name
            )
        if feature_axis_name is None:
            return splits
        return combine_splits_across_shards(splits, feat_shard, d, feature_axis_name)

    def _scan_totals(G, H):
        """Pre-scan node totals under reduce_scatter (bit-identical to the
        psum lowering's feature-0 derivation); None otherwise."""
        if not reduce_scatter:
            return None
        return broadcast_node_totals(G, H, data_shard, axis_name)

    # colsample_bylevel: one Bernoulli feature mask per DEPTH, shared by all
    # nodes at that depth (the leaf-wise analog of tree_build's per-level
    # draw; same fold_in(rng, depth) stream so depthwise and lossguide agree
    # on the sampling convention). Depths are traced here, so the masks are
    # precomputed for every reachable depth and indexed dynamically.
    level_masks = None
    if colsample_bylevel < 1.0 and rng is not None:
        draws = jax.vmap(
            lambda i: jax.random.uniform(jax.random.fold_in(rng, i), (d_draw,))
        )(jnp.arange(depth_cap + 1))
        level_masks = _local_cols(
            _pad_cols((draws < colsample_bylevel).astype(jnp.float32))
        )

    def _with_level_mask(mask, depth):
        """Fold the depth's bylevel draw into a [d] or [2, d] mask."""
        if level_masks is None:
            return mask
        lm = level_masks[jnp.minimum(depth, depth_cap)]
        if mask is None:
            return lm
        return mask * lm if mask.ndim == 1 else mask * lm[None, :]

    tree = {
        "feature": jnp.zeros(max_nodes, jnp.int32),
        "bin": jnp.zeros(max_nodes, jnp.int32),
        "default_left": jnp.zeros(max_nodes, jnp.bool_),
        "is_leaf": jnp.ones(max_nodes, jnp.bool_),
        "leaf_value": jnp.zeros(max_nodes, jnp.float32),
        "base_weight": jnp.zeros(max_nodes, jnp.float32),
        "gain": jnp.zeros(max_nodes, jnp.float32),
        "sum_hess": jnp.zeros(max_nodes, jnp.float32),
        "left": jnp.arange(max_nodes, dtype=jnp.int32),
        "right": jnp.arange(max_nodes, dtype=jnp.int32),
    }
    # per-leaf best-split candidate store
    cand = {
        "gain": jnp.full(max_nodes, -jnp.inf, jnp.float32),
        "feature": jnp.zeros(max_nodes, jnp.int32),
        "bin": jnp.zeros(max_nodes, jnp.int32),
        "default_left": jnp.zeros(max_nodes, jnp.bool_),
    }
    node_g = jnp.zeros(max_nodes, jnp.float32)
    node_h = jnp.zeros(max_nodes, jnp.float32)
    node_depth = jnp.zeros(max_nodes, jnp.int32)

    # interaction constraints: per-node alive constraint sets, the leaf-wise
    # form of tree_build's level-synchronous update. A feature is usable in a
    # node iff some still-alive set contains it; splitting on f keeps alive
    # only the sets containing f (xgboost semantics). ``interaction_sets``
    # spans GLOBAL columns; per-node masks are sliced to this shard's segment.
    alive_sets = None
    if interaction_sets is not None:
        num_sets = interaction_sets.shape[0]
        alive_sets = jnp.zeros((max_nodes, num_sets), jnp.bool_)
        alive_sets = alive_sets.at[0].set(True)

    def _allowed_cols(alive_row):
        """[S] alive-set row -> local [d] allowed-feature mask (f32)."""
        allowed = (
            alive_row.astype(jnp.float32) @ interaction_sets.astype(jnp.float32)
        ) > 0
        return _local_cols(allowed.astype(jnp.float32))

    node_of_row = jnp.zeros(n, jnp.int32)

    # pipelined step collectives (GRAFT_HIST_OVERLAP): without subtraction a
    # split step reduces both fresh children's histograms — issuing one
    # collective per child lets the second child's psum/psum_scatter fly
    # while the first child's gain scan runs (the leaf-wise form of the
    # depthwise level pipeline). The subtraction path has one collective
    # per step (left child only) — nothing to overlap there.
    overlap = (
        (knobs.comm_overlap if knobs is not None else _comm_overlap())
        and axis_name is not None
    )

    def _scan_nodes(Gb, Hb, mask_b):
        """Gain-scan + cross-shard combine for one node batch."""
        s = find_best_splits(
            Gb,
            Hb,
            _scan_slice(num_cuts),
            reg_lambda=reg_lambda,
            alpha=alpha,
            gamma=gamma,
            min_child_weight=min_child_weight,
            feature_mask=_scan_slice(mask_b),
            monotone=_scan_slice(monotone),
            totals=_scan_totals(Gb, Hb),
        )
        # cross-shard combine: the candidate store (and therefore every
        # step's argmax) must be identical on all shards, with GLOBAL ids
        return _combine(s)

    def _child_splits(batches, mask, depth_ab):
        """Candidates of the two fresh children from their (reduced)
        histograms, a node batch at a time; the depth cap folded into the
        gains (children at ``depth_cap`` can never split)."""
        with stage(STAGE_SPLIT_SCAN):
            splits = concat_node_splits(
                [
                    _scan_nodes(
                        Gb, Hb,
                        mask[nsl] if mask is not None and mask.ndim == 2 else mask,
                    )
                    for nsl, Gb, Hb in batches
                ]
            )
            gains = jnp.where(depth_ab < depth_cap, splits["gain"], -jnp.inf)
        return splits, gains

    # full-width gate under both lowerings (bit-identity: same build path)
    subtract = _subtraction_enabled(max_leaves, d, num_bins)
    hist_cache = None
    if subtract:
        # per-node histogram cache (filled as leaves are created); stores
        # only this shard's feature slice under reduce_scatter
        hist_cache = (
            jnp.zeros((max_nodes, d_scan, num_bins), jnp.float32),
            jnp.zeros((max_nodes, d_scan, num_bins), jnp.float32),
        )

    # root candidate
    with stage(STAGE_HIST):
        G, H = level_histogram(
            bins, grad, hess, jnp.zeros(n, jnp.int32), 1, num_bins,
            axis_name=axis_name, comm=hist_comm, axis_size=n_data_shards,
            knobs=knobs,
        )
        if subtract:
            hist_cache = (hist_cache[0].at[0].set(G[0]), hist_cache[1].at[0].set(H[0]))
    with stage(STAGE_SPLIT_SCAN):
        root_mask = _with_level_mask(feature_mask, jnp.int32(0))
        if alive_sets is not None:
            allowed0 = _allowed_cols(alive_sets[0])
            root_mask = allowed0 if root_mask is None else root_mask * allowed0
        root_splits = _scan_nodes(G, H, root_mask)
    with stage(STAGE_STEP_PICK):
        for field in cand:
            cand[field] = cand[field].at[0].set(root_splits[field][0])
        node_g = node_g.at[0].set(root_splits["g_total"][0])
        node_h = node_h.at[0].set(root_splits["h_total"][0])

    def _pair(table, values, id_a):
        """``values`` [2, ...] into the fresh children's slots ``id_a`` and
        ``id_a + 1`` of a per-node ``table``."""
        return jax.lax.dynamic_update_slice(
            table, values.astype(table.dtype), (id_a,) + (0,) * (table.ndim - 1)
        )

    def split_step(t, state):
        """One split step: pick the best leaf, route its rows, score its two
        fresh children (slots ``2t + 1``, ``2t + 2``). What lies under no
        stage of its own here is ``step_pick``'s (the scope round the loop)."""
        tree, cand, node_g, node_h, node_depth, node_of_row, hist_cache, alive_sets = state
        tree, cand = dict(tree), dict(cand)
        id_a, id_b = 2 * t + 1, 2 * t + 2
        gains = jnp.where(tree["is_leaf"], cand["gain"], -jnp.inf)
        l = jnp.argmax(gains).astype(jnp.int32)
        can = gains[l] > MIN_SPLIT_LOSS

        f_l = cand["feature"][l]
        b_l = cand["bin"][l]
        dl_l = cand["default_left"][l]

        # mark split; stored is the split's own loss change
        # (ops/tree_build.py::build_tree)
        won = gains[l] + gamma if gamma else gains[l]
        for field, value in (
            ("feature", f_l), ("bin", b_l), ("default_left", dl_l), ("is_leaf", False),
            ("gain", won), ("left", id_a), ("right", id_b),
        ):
            tree[field] = tree[field].at[l].set(jnp.where(can, value, tree[field][l]))
        # exhausted leaves can't be re-picked
        cand["gain"] = cand["gain"].at[l].set(-jnp.inf)

        with stage(STAGE_ROUTE_ROWS):
            # route rows of l: one scalar feature for every row, so a dynamic
            # column slice, not a per-row gather
            in_l = node_of_row == l
            if feature_axis_name is None:
                row_bin = jax.lax.dynamic_slice(bins, (0, f_l), (n, 1))[:, 0]
                is_missing = row_bin == (num_bins - 1)
                go_right = jnp.where(is_missing, ~dl_l, row_bin > b_l)
            else:
                # only the shard owning the winning (global) feature can decide
                # the rows; decisions psum-broadcast along the feature axis —
                # same convention as tree_build's level routing
                owner = (f_l // d) == feat_shard
                f_local = jnp.clip(f_l - feat_shard * d, 0, d - 1)
                row_bin = jax.lax.dynamic_slice(bins, (0, f_local), (n, 1))[:, 0]
                is_missing = row_bin == (num_bins - 1)
                decision = jnp.where(is_missing, ~dl_l, row_bin > b_l)
                go_right = (
                    jax.lax.psum(
                        jnp.where(owner, decision, False).astype(jnp.int32),
                        feature_axis_name,
                    )
                    > 0
                )
            new_node = jnp.where(go_right, id_b, id_a)
            node_of_row = jnp.where(in_l & can, new_node, node_of_row)

        # children depth + candidates
        depth_ab = node_depth[l] + 1
        node_depth = _pair(node_depth, jnp.stack([depth_ab, depth_ab]), id_a)
        with stage(STAGE_SPLIT_SCAN):
            node_mask = feature_mask
            if colsample_bynode < 1.0 and rng is not None:
                # drawn over GLOBAL columns (identical stream to single-device),
                # each shard slicing its own segment — see the bylevel comment
                draw = jax.random.uniform(jax.random.fold_in(rng, 7919 + t), (2, d_draw))
                sampled = _local_cols(
                    _pad_cols((draw < colsample_bynode).astype(jnp.float32))
                )
                node_mask = sampled if node_mask is None else sampled * node_mask[None, :]
            # the children being scored sit at depth_ab: their candidate splits
            # (executed at that depth) draw that depth's bylevel subset
            node_mask = _with_level_mask(node_mask, depth_ab)
            if alive_sets is not None:
                # both fresh children inherit alive-sets = parent's ∩ {sets
                # containing the split feature}; inert when the step can't split
                # (their candidate gains are forced to -inf below)
                child_alive = alive_sets[l] & interaction_sets[:, f_l]
                alive_sets = _pair(alive_sets, jnp.stack([child_alive, child_alive]), id_a)
                allowed = _allowed_cols(child_alive)
                if node_mask is None:
                    node_mask = allowed
                elif node_mask.ndim == 1:
                    node_mask = node_mask * allowed
                else:
                    node_mask = node_mask * allowed[None, :]
        with stage(STAGE_HIST):
            if subtract:
                # histogram only the LEFT child; right = cached parent - left.
                # When the step can't split, no rows were routed: left is all
                # zeros and the right side is forced to zero too.
                left_local = jnp.where(can & (node_of_row == id_a), 0, -1)
                Ga, Ha = level_histogram(
                    bins, grad, hess, left_local, 1, num_bins,
                    axis_name=axis_name, comm=hist_comm, axis_size=n_data_shards,
                    knobs=knobs,
                )
                Gb = jnp.where(can, hist_cache[0][l] - Ga[0], 0.0)
                Hb = jnp.where(can, hist_cache[1][l] - Ha[0], 0.0)
                # already reduced, one batch
                batches = [(slice(0, 2), jnp.stack([Ga[0], Gb]), jnp.stack([Ha[0], Hb]))]
            else:
                child_local = jnp.where(
                    can & (node_of_row == id_a),
                    0,
                    jnp.where(can & (node_of_row == id_b), 1, -1),
                )
                G_loc, H_loc = level_histogram(
                    bins, grad, hess, child_local, 2, num_bins, knobs=knobs,
                )
                batches = [
                    (nsl,)
                    + apply_hist_collective(
                        G_loc[nsl], H_loc[nsl], axis_name, hist_comm, n_data_shards,
                    )
                    for nsl in overlap_node_batches(2, overlap)
                ]
        if subtract:
            hist_cache = (
                _pair(hist_cache[0], batches[0][1], id_a),
                _pair(hist_cache[1], batches[0][2], id_a),
            )
        splits, child_gains = _child_splits(
            batches, node_mask, jnp.stack([depth_ab, depth_ab])
        )
        # children of a non-split never get rows, so their -inf gains + zero
        # totals are inert
        cand["gain"] = _pair(cand["gain"], jnp.where(can, child_gains, -jnp.inf), id_a)
        for field in ("feature", "bin", "default_left"):
            cand[field] = _pair(cand[field], splits[field], id_a)
        node_g = _pair(node_g, splits["g_total"], id_a)
        node_h = _pair(node_h, splits["h_total"], id_a)
        return tree, cand, node_g, node_h, node_depth, node_of_row, hist_cache, alive_sets

    # ONE rolled loop over the split steps: the program holds one kernel call
    # site, one split scan and one routing step whatever ``max_leaves``
    state = (tree, cand, node_g, node_h, node_depth, node_of_row, hist_cache, alive_sets)
    with stage(STAGE_STEP_PICK):
        state = jax.lax.fori_loop(0, max_leaves - 1, split_step, state)
    tree, _cand, node_g, node_h, _depth, node_of_row, _cache, _alive = state

    # finalize leaf values for every (reachable) leaf slot
    with stage(STAGE_LEAF_MARGIN):
        weight = leaf_weight(node_g, node_h, reg_lambda=reg_lambda, alpha=alpha,
                             max_delta_step=max_delta_step)
        tree["base_weight"] = weight
        tree["sum_hess"] = node_h
        tree["leaf_value"] = jnp.where(tree["is_leaf"], eta * weight, 0.0)
        row_out = tree["leaf_value"][node_of_row]
    return tree, row_out
