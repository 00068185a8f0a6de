"""Level-wise tree growth: one whole tree as a single XLA program.

Replaces libxgboost's depthwise hist updater. Shapes are fully static: a tree
with ``max_depth`` grows into a padded full-binary layout of
``2**(max_depth+1) - 1`` node slots (children of i at 2i+1 / 2i+2), with the
level loop unrolled in Python (max_depth is a compile-time constant), so XLA
sees straight-line code of segment-sums, scans, and node-table lookups — no
data-dependent control flow (SURVEY.md §7 "static shapes" risk).

Per level: histogram -> (psum over the data axis when distributed) -> split
scan -> finalize leaves -> route rows to children. Rows carry their node id;
finalized rows hold -1 and accumulate their leaf value into ``row_out``, so
the booster updates margins without re-predicting the train set.
"""

import jax
import jax.numpy as jnp

from .histogram import (
    apply_hist_collective,
    level_histogram,
    node_totals,
    subtraction_enabled,
)
from ..telemetry.device import (
    STAGE_HIST,
    STAGE_LEAF_MARGIN,
    STAGE_NODE_TOTALS,
    STAGE_PACK,
    STAGE_ROUTE_ROWS,
    STAGE_SPLIT_SCAN,
    stage,
)
from .split import (
    column_shard_helpers,
    combine_splits_across_shards,
    find_best_splits,
    leaf_weight,
)

MIN_SPLIT_LOSS = 1e-6  # xgboost kRtEps


# Widest bins matrix whose split bins the TPU fetches with the dense pass: the
# widest probed at which dense beats gather for both storage dtypes. ns a row
# and call on one v5e (scripts/dissect.py --route-widths, 1,048,576 rows up to
# width 1,024, then 2**30 / width; PR 27, PERF.md section 5):
#
#   width    gather u8 / u16    dense u8 / u16
#      28     10.80 / 10.82      0.21 /  0.21
#      54     10.81 / 12.50      0.19 /  0.20
#     136     12.76 / 13.47      0.26 /  0.44
#     512     12.40 / 12.56      0.98 /  1.44
#   1,024     12.35 / 12.46      1.60 /  2.80
#   2,048     12.39 / 12.48      2.87 /  5.59
#   4,096     12.56 / 12.64      5.75 / 11.17
#   8,192     12.87 / 12.88     11.46 / 22.36   <- u16 loses
#
# The gather costs the same at every width; the dense pass grows with the
# bytes it reads (1.4 ns a row per 1,000 u8 columns, 2.7 per 1,000 u16).
ROUTE_DENSE_MAX_WIDTH = 4096


def choose_route_impl(backend, width):
    """The lowering ``row_bin_lookup`` takes for a bins matrix of ``width``
    columns (static at trace time; the shard-local width on a feature-sharded
    mesh) on ``backend``. The dense pass costs n * width and the gather n
    times a constant, so dense wins up to a width, and only where gathers
    serialize: on the CPU the gather is the fast one."""
    if backend == "tpu" and width <= ROUTE_DENSE_MAX_WIDTH:
        return "dense"
    return "gather"


def row_bin_lookup(bins, feat_idx, impl=None):
    """Per-row bin of a per-row feature: ``bins[i, feat_idx[i]]``.

    Two lowerings, the same integers bit for bit:

    * ``gather``: ``take_along_axis`` — one dynamic gather per row.
    * ``dense``: compare ``feat_idx`` with an iota over the feature axis,
      select, reduce over features — one fusion that reads every bin once in
      its storage dtype and writes no [n, d] intermediate.

    Used by level routing here and by binned eval prediction. ``impl``: a
    lowering by name (traced callers resolve it from the session's
    ``HistKnobs.backend`` through ``choose_route_impl``); None chooses
    from the process's backend, for direct callers only.
    """
    if impl is None:
        impl = choose_route_impl(jax.default_backend(), bins.shape[1])
    if impl == "dense":
        d = bins.shape[1]
        wanted = feat_idx[:, None] == jnp.arange(d, dtype=jnp.int32)[None, :]
        return jnp.sum(jnp.where(wanted, bins, 0).astype(jnp.int32), axis=1)
    if impl != "gather":
        raise ValueError("unknown row_bin_lookup lowering: {!r}".format(impl))
    return jnp.take_along_axis(bins, feat_idx[:, None], axis=1)[:, 0].astype(jnp.int32)


# Widest node table the TPU looks up with the select pass: the widest probed
# at which select beats gather by half in every dtype. ns a row and lookup on
# one v5e (scripts/dissect.py --node-table-widths, 2,200,000 rows, every pair
# bit-equal; PR 29, PERF.md section 5):
#
#   width    gather i32 / bool / f32    select i32 / bool / f32
#    1-32     0.09 / 0.09 / 0.09         0.09-0.11 (every dtype)
#      64     0.09 / 0.10 / 0.09         0.19 / 0.18 / 0.19
#     128     8.27 / 8.19 / 8.27         0.84 / 0.84 / 0.84
#     256     8.61 / 8.19 / 8.61         0.84 / 0.84 / 0.84
#     512     8.61 / 8.19 / 8.61         0.90 / 0.92 / 0.90
#   1,024     5.64 / 8.55 / 5.64         0.98 / 1.14 / 0.98
#   2,048     7.53 / 8.56 / 7.53         1.63 / 1.99 / 1.63
#   4,096     7.53 / 8.56 / 7.52         3.17 / 3.93 / 3.17
#   8,192     7.53 / 8.56 / 7.52         6.34 / 7.88 / 6.25   <- a tenth apart
#
# Up to 64 entries XLA expands the gather into a select itself; from 128 it
# is a real gather, 8 ns a row whatever it fetches. The select pass costs per
# lookup and not per byte up to 1,024 entries, then 3 operations an entry at
# the VPU's rate (0.77 ns a row per 1,000 entries).
#
# What a depth-8 build reads at its two widest levels (the four split fields
# and the leaf weight by one index at 128 entries, the leaf weight at 256;
# the same probe's build read set, every variant bit-equal; PR 33), ns a row:
#
#   rows          six gathers    one select a field    packed word + 2 weights
#    8,800,000      42.26            5.14                   2.56
#   16,387,491      42.27            4.85                   2.44
#
# and a whole tree over 8,800,000 x 28: 998.8 / 611.0 / 581.6 ms, which is
# why the build packs (``split_word_bin_bits``).
NODE_TABLE_SELECT_MAX_WIDTH = 4096


def choose_table_impl(backend, width):
    """The lowering ``node_table_lookup`` takes for a node table ``width``
    entries long (static at trace time: a level of a depth-wise tree is
    ``2**level`` wide) on ``backend``, chosen as ``choose_route_impl``
    chooses: the select pass costs n * width and the gather n times a
    constant, and only the TPU serializes gathers."""
    if backend == "tpu" and width <= NODE_TABLE_SELECT_MAX_WIDTH:
        return "select"
    return "gather"


def node_table_lookup(table, idx, impl):
    """Per-row entry of a short per-node table: ``table[idx[i]]``, ``idx``
    inside the table.

    Two lowerings, the same bits (a float travels as its 32 bits, so a
    ``-0.0`` leaf stays ``-0.0``):

    * ``gather``: the indexed gather.
    * ``select``: compare ``idx`` with an iota over the table, select, reduce
      over the table's axis — ``row_bin_lookup``'s dense pass with the table
      broadcast over the rows, one fusion and no [n, width] intermediate.

    ``impl``: a lowering by name, as ``choose_table_impl`` gives it. Read by
    the build (each level's split word and leaf weight, by the row's node)
    and by the evaluation walk.
    """
    if impl == "gather":
        return table[idx]
    if impl != "select":
        raise ValueError("unknown node_table_lookup lowering: {!r}".format(impl))
    wanted = idx[:, None] == jnp.arange(table.shape[0], dtype=jnp.int32)[None, :]
    if table.dtype == jnp.bool_:
        return jnp.any(wanted & table[None, :], axis=1)
    if table.dtype == jnp.float32:
        bits = jax.lax.bitcast_convert_type(table, jnp.int32)
        return jax.lax.bitcast_convert_type(
            jnp.sum(jnp.where(wanted, bits[None, :], 0), axis=1), jnp.float32
        )
    return jnp.sum(jnp.where(wanted, table[None, :], 0), axis=1)


# A level's split fields share one index, so the build reads them as one int32
# a node where they fit: feature | bin | default_left | becomes_leaf, low bits
# last. The word stays non-negative (bit 31 clear), so shifts unpack it.
SPLIT_WORD_BITS = 31


def split_word_bin_bits(feature_ids, num_bins):
    """Bits of the packed split word's bin field, or None where ``feature_ids``
    feature ids and ``num_bins`` bins do not fit ``SPLIT_WORD_BITS`` beside the
    two flags (both static at trace time): the fields are then read one by one."""
    bin_bits = int(num_bins).bit_length()
    if int(feature_ids).bit_length() + bin_bits + 2 <= SPLIT_WORD_BITS:
        return bin_bits
    return None


def pack_split_word(feature, split_bin, default_left, becomes_leaf, bin_bits):
    return (
        (feature << (bin_bits + 2))
        | (split_bin << 2)
        | (default_left.astype(jnp.int32) << 1)
        | becomes_leaf.astype(jnp.int32)
    )


def unpack_split_word(word, bin_bits):
    """(feature, bin, default_left, becomes_leaf) of ``pack_split_word``."""
    return (
        word >> (bin_bits + 2),
        (word >> 2) & ((1 << bin_bits) - 1),
        (word & 2) != 0,
        (word & 1) != 0,
    )


def choose_eval_traversal(grow_policy):
    """How an evaluation row finds its leaf in a tree this session's builder
    made: ``level`` (``predict_binned_levels``) for the heap ``build_tree``
    lays out, ``replay`` (``predict_binned_steps``) for loss-guided trees,
    whose nodes are numbered in the order they were split."""
    return "replay" if grow_policy == "lossguide" else "level"


def max_nodes_for_depth(max_depth):
    return 2 ** (max_depth + 1) - 1


def _subtraction_enabled(max_depth, d_hist, num_bins):
    """Histogram subtraction: build only left children, derive right ones as
    parent - left (libxgboost's standard sibling trick) — halves histogram
    work per level. Needs the previous level's histograms cached
    ([2**(L-1), d_hist, B] f32 x2); gated by the shared memory cap."""
    if max_depth < 2:
        return False
    return subtraction_enabled(2 * (2 ** (max_depth - 1)) * d_hist * num_bins * 4)


def build_tree(
    bins,
    grad,
    hess,
    num_cuts,
    max_depth,
    num_bins,
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
    knobs=None,
    class_vmap=False,
    bundle=None,
    cat=None,
):
    """Grow one tree. Returns (tree arrays dict, row_out f32 [n]).

    Tree arrays (length ``max_nodes_for_depth(max_depth)``):
      feature, bin (i32), default_left (bool), is_leaf (bool),
      leaf_value (f32, eta already applied), base_weight (f32, pre-eta),
      gain (f32: a split's loss change without ``gamma`` taken off, 0 at a
      leaf), sum_hess (f32).

    feature_axis_name: optional second mesh axis carrying a *column* shard
    (the reference's vestigial dsplit=col, done properly): ``bins`` holds only
    this shard's feature columns; candidate splits combine across the axis by
    max-gain, and row routing decisions (which need the winning feature's
    bins) are computed by the owning shard and psum-broadcast. Emitted
    feature ids are global.

    knobs: the session's ``ops.histogram.HistKnobs`` snapshot (trace-safety:
    the traced build must not read env; None, for direct unit-test/probe
    callers, chooses every lowering from the process's backend).

    class_vmap: static; True where the caller maps this build over the class
    trees of a round with ``jax.vmap`` (``models/booster.py``, the class
    branch): the level histogram kernel then takes the trees' gradients as
    one operand over the one bin matrix (``ops.histogram._class_hist_fn``),
    and the split scan reads its winners without a gather
    (``ops.split.find_best_splits``, ``gathers``).

    bundle: static; the session's ``ops.bundle.BundleTables`` where ``bins``
    is a bundled matrix (``data/bundling.py``: a bin column holds several
    mutually exclusive original columns, each in its own range of positions).
    The scan then judges original columns over positions and a row absent
    from its node's split column is told by a range test, a second word read
    of the node's table; ``feature`` and ``bin`` of the tree are then a bundle
    and a position. None, a dense session, traces none of it.

    cat: static; the session's ``ops.categorical.CatTables`` where some
    columns are given as categories (``data/categorical.py``: a categorical
    column's codes are positions of its own bin columns). The scan then also
    tries set-membership splits, a row reads its value of the split column
    over the column's chunks and, at a set split, its bit of the node's set;
    the tree holds the original column in ``feature``, ``num_bins - 1`` in
    ``bin`` at a set split, and the sets in ``cat_words`` (i32 ``[nodes,
    words]``). None traces none of it.

    Every per-row read of a level's per-node table is ``node_table_lookup`` in
    the lowering ``choose_table_impl(backend, 2**level)`` picks: the four
    fields a row reads of its node's split (feature, bin, default_left,
    becomes_leaf) as one packed word where ``split_word_bin_bits`` fits them,
    one by one otherwise, and the leaf weight as a lookup of its own. The
    same bits under either lowering, packed or not.
    """
    n, d = bins.shape
    max_nodes = max_nodes_for_depth(max_depth)
    route_impl = (
        choose_route_impl(knobs.backend, d) if knobs is not None else None
    )
    table_backend = knobs.backend if knobs is not None else jax.default_backend()

    def at_node(table, local_safe):
        """The level's per-node ``table`` read by each row's own node."""
        return node_table_lookup(
            table, local_safe, impl=choose_table_impl(table_backend, table.shape[0])
        )

    # every id a merged split's feature field can hold: the local width
    # times the feature shards
    feature_ids = d
    if feature_axis_name is not None:
        feature_ids *= jax.lax.axis_size(feature_axis_name)
    word_bin_bits = split_word_bin_bits(feature_ids, num_bins)
    # bins stay in their storage dtype (u8/u16 from binning) end to end:
    # every consumer widens inside a fused op, so no [n, d] i32 copy is ever
    # materialized in HBM and the hot-loop bin reads move half the bytes

    tree = {
        "feature": jnp.zeros(max_nodes, jnp.int32),
        "bin": jnp.zeros(max_nodes, jnp.int32),
        "default_left": jnp.zeros(max_nodes, jnp.bool_),
        "is_leaf": jnp.zeros(max_nodes, jnp.bool_),
        "leaf_value": jnp.zeros(max_nodes, jnp.float32),
        "base_weight": jnp.zeros(max_nodes, jnp.float32),
        "gain": jnp.zeros(max_nodes, jnp.float32),
        "sum_hess": jnp.zeros(max_nodes, jnp.float32),
    }
    if cat is not None:
        tree["cat_words"] = jnp.zeros((max_nodes, cat.words), jnp.int32)

    node_of_row = jnp.zeros(n, jnp.int32)
    row_out = jnp.zeros(n, jnp.float32)

    # interaction constraints: per-node alive constraint sets. A feature is
    # usable in a node iff some still-alive set contains it; splitting on f
    # keeps alive only the sets containing f (xgboost semantics). With a
    # feature axis, ``interaction_sets`` spans GLOBAL columns (split ids are
    # global after cross-shard combination) and per-node masks are sliced to
    # this shard's column segment.
    alive_sets = None
    if interaction_sets is not None:
        num_sets = interaction_sets.shape[0]
        alive_sets = jnp.ones((1, num_sets), jnp.bool_)

    feat_shard = (
        jax.lax.axis_index(feature_axis_name) if feature_axis_name is not None else None
    )

    # the highest bin a row of each column can sit in: the level histogram
    # builds no one-hot tile above it (ops.histogram._live_tiles)
    reach = num_cuts if bundle is None else jnp.asarray(bundle.reach)
    if cat is not None:  # a chunk's reach is its last category, whatever the data
        reach = num_cuts + jnp.asarray(cat.chunk_reach)
    subtract = _subtraction_enabled(max_depth, d, num_bins)
    G_cache = H_cache = None      # previous level's [W/2, d, B] histograms
    parent_leaf = None            # previous level's becomes_leaf [W/2]

    for level in range(max_depth + 1):
        first = 2**level - 1
        width = 2**level
        node_local = node_of_row - first  # negative for finalized rows

        if level == max_depth:
            # Last level: every surviving node becomes a leaf, and leaf
            # weights only need per-node g/h totals — skip the full (widest,
            # most expensive) [W, d, B] histogram of the tree entirely.
            with stage(STAGE_NODE_TOTALS):
                g_tot, h_tot = node_totals(
                    grad, hess, node_local, width, axis_name=axis_name, knobs=knobs
                )
            with stage(STAGE_LEAF_MARGIN):
                weight = leaf_weight(
                    g_tot, h_tot,
                    reg_lambda=reg_lambda, alpha=alpha, max_delta_step=max_delta_step,
                )
                sl = slice(first, first + width)
                tree["is_leaf"] = tree["is_leaf"].at[sl].set(True)
                tree["leaf_value"] = tree["leaf_value"].at[sl].set(eta * weight)
                tree["base_weight"] = tree["base_weight"].at[sl].set(weight)
                tree["sum_hess"] = tree["sum_hess"].at[sl].set(h_tot)
                at_level = node_local >= 0
                local_safe = jnp.clip(node_local, 0, width - 1)
                row_out = jnp.where(at_level, eta * at_node(weight, local_safe), row_out)
            break

        with stage(STAGE_HIST):
            if subtract and level > 0:
                # histogram only the LEFT child of each sibling pair; the right
                # one is parent - left. Parents that leafed routed no rows to
                # their children, so their pair contribution is zeroed.
                active = node_local >= 0
                is_left = (node_local % 2) == 0
                left_local = jnp.where(active & is_left, node_local // 2, -1)
                Gl, Hl = level_histogram(
                    bins, grad, hess, left_local, width // 2, num_bins,
                    knobs=knobs, class_vmap=class_vmap, reach=reach,
                )
                # (after the kernel call, before the collective: the order the
                # pinned one-tree program and the mesh program trace)
                keep = ~parent_leaf
                Gl, Hl = apply_hist_collective(Gl, Hl, axis_name)
                Gp = jnp.where(keep[:, None, None], G_cache, 0.0)
                Hp = jnp.where(keep[:, None, None], H_cache, 0.0)
                Gr = Gp - Gl
                Hr = Hp - Hl
                # level nodes interleaved: left child 2i, right child 2i + 1
                G = jnp.stack([Gl, Gr], axis=1).reshape(width, Gl.shape[1], -1)
                H = jnp.stack([Hl, Hr], axis=1).reshape(width, Hl.shape[1], -1)
            else:
                G, H = apply_hist_collective(
                    *level_histogram(
                        bins, grad, hess, node_local, width, num_bins, knobs=knobs,
                        class_vmap=class_vmap, reach=reach,
                    ),
                    axis_name,
                )
            if subtract:
                G_cache, H_cache = G, H
        with stage(STAGE_SPLIT_SCAN):
            # shared column-draw convention (ops/split.py): draws over the REAL
            # global feature count, padded then sliced per shard
            d_draw, _pad_cols, _local_cols = column_shard_helpers(
                feat_shard, d, n_feature_shards, d_global
            )

            level_mask = feature_mask
            if colsample_bylevel < 1.0 and rng is not None:
                draw = jax.random.uniform(jax.random.fold_in(rng, level), (d_draw,))
                sampled = _local_cols(
                    _pad_cols((draw < colsample_bylevel).astype(jnp.float32))
                )
                level_mask = sampled if level_mask is None else level_mask * sampled
            if colsample_bynode < 1.0 and rng is not None:
                # fresh per-node feature subset (xgboost colsample_bynode)
                node_draw = jax.random.uniform(
                    jax.random.fold_in(rng, 7919 + level), (width, d_draw)
                )
                node_mask = _local_cols(
                    _pad_cols((node_draw < colsample_bynode).astype(jnp.float32))
                )
                if level_mask is None:
                    level_mask = node_mask
                elif level_mask.ndim == 1:
                    level_mask = node_mask * level_mask[None, :]
                else:
                    level_mask = node_mask * level_mask
            if alive_sets is not None:
                # [W, S] @ [S, d_total] -> per-node allowed-feature mask over
                # global columns, sliced to this shard
                node_allowed = (
                    alive_sets.astype(jnp.float32) @ interaction_sets.astype(jnp.float32)
                ) > 0
                per_node = _local_cols(node_allowed.astype(jnp.float32))
                level_mask = per_node if level_mask is None else per_node * level_mask[None, :]
            scan = find_best_splits if bundle is None else bundle.find_best_splits
            if cat is not None:
                scan = cat.find_best_splits
            splits = scan(
                G,
                H,
                num_cuts,
                reg_lambda=reg_lambda,
                alpha=alpha,
                gamma=gamma,
                min_child_weight=min_child_weight,
                feature_mask=level_mask,
                monotone=monotone,
                gathers=not class_vmap,
            )
            if feature_axis_name is not None:
                splits = combine_splits_across_shards(
                    splits, feat_shard, d, feature_axis_name
                )

            g_tot, h_tot = splits["g_total"], splits["h_total"]
            weight = leaf_weight(
                g_tot, h_tot, reg_lambda=reg_lambda, alpha=alpha, max_delta_step=max_delta_step
            )

            can_split = splits["gain"] > MIN_SPLIT_LOSS
            becomes_leaf = ~can_split
            parent_leaf = becomes_leaf

        # --- route rows ----------------------------------------------------
        with stage(STAGE_ROUTE_ROWS):
            # what each row reads of its node: one index, so one pass where
            # the fields pack into a word
            at_level = node_local >= 0
            local_safe = jnp.clip(node_local, 0, width - 1)
            fields = (
                splits["feature"], splits["bin"], splits["default_left"], becomes_leaf
            )
            if word_bin_bits is None:
                split_feat, split_bin, default_left, row_at_leaf = (
                    at_node(field, local_safe) for field in fields
                )
            else:
                split_feat, split_bin, default_left, row_at_leaf = unpack_split_word(
                    at_node(pack_split_word(*fields, word_bin_bits), local_safe),
                    word_bin_bits,
                )
            row_leafed = at_level & row_at_leaf
            if cat is not None:  # the row's value over the column's chunks, then its set's bit
                value = cat.row_value(bins, split_feat)
                go_right = cat.go_right(
                    value, split_bin, default_left,
                    cat.set_word(splits["cat_words"], local_safe, value, table_backend),
                )
            elif feature_axis_name is None:
                row_bin = row_bin_lookup(bins, split_feat, impl=route_impl)
                if bundle is None:
                    is_missing = row_bin == (num_bins - 1)
                    go_right = jnp.where(is_missing, ~default_left, row_bin > split_bin)
                else:  # absent: outside the split column's range
                    go_right = bundle.go_right(
                        row_bin, at_node(splits["range"], local_safe), split_bin, default_left
                    )
            else:
                # only the shard owning a node's split feature can decide its
                # rows; decisions psum-broadcast along the feature axis
                owner = (split_feat // d) == feat_shard
                local_idx = jnp.clip(split_feat - feat_shard * d, 0, d - 1)
                row_bin = row_bin_lookup(bins, local_idx, impl=route_impl)
                is_missing = row_bin == (num_bins - 1)
                decision = jnp.where(is_missing, ~default_left, row_bin > split_bin)
                go_right = (
                    jax.lax.psum(
                        jnp.where(owner, decision, False).astype(jnp.int32),
                        feature_axis_name,
                    )
                    > 0
                )
            child = node_of_row * 2 + 1 + go_right.astype(jnp.int32)
            node_of_row = jnp.where(
                row_leafed, -1, jnp.where(at_level, child, node_of_row)
            )

        with stage(STAGE_LEAF_MARGIN):
            sl = slice(first, first + width)
            tree["feature"] = tree["feature"].at[sl].set(splits["feature"])
            tree["bin"] = tree["bin"].at[sl].set(splits["bin"])
            tree["default_left"] = tree["default_left"].at[sl].set(splits["default_left"])
            tree["is_leaf"] = tree["is_leaf"].at[sl].set(becomes_leaf)
            tree["leaf_value"] = tree["leaf_value"].at[sl].set(
                jnp.where(becomes_leaf, eta * weight, 0.0)
            )
            tree["base_weight"] = tree["base_weight"].at[sl].set(weight)
            # stored is the split's own loss change, as xgboost keeps
            # loss_chg: gamma decides whether a split is kept and is no part
            # of what it won (a static branch: no gamma, the same program)
            won = splits["gain"] + gamma if gamma else splits["gain"]
            tree["gain"] = tree["gain"].at[sl].set(jnp.where(can_split, won, 0.0))
            tree["sum_hess"] = tree["sum_hess"].at[sl].set(h_tot)
            if cat is not None:
                tree["cat_words"] = tree["cat_words"].at[sl].set(splits["cat_words"])
            row_out = jnp.where(row_leafed, eta * at_node(weight, local_safe), row_out)

        if alive_sets is not None and level < max_depth:
            feat_sets = interaction_sets[:, splits["feature"]].T  # [W, S]
            child_alive = alive_sets & feat_sets
            alive_sets = jnp.repeat(child_alive, 2, axis=0)       # [2W, S]

    # explicit child indices (leaves self-loop), so depthwise and lossguide
    # trees share one predict/compact layout
    with stage(STAGE_PACK):
        ids = jnp.arange(max_nodes, dtype=jnp.int32)
        tree["left"] = jnp.where(tree["is_leaf"], ids, 2 * ids + 1)
        tree["right"] = jnp.where(tree["is_leaf"], ids, 2 * ids + 2)
    return tree, row_out


_TREE_FIELDS = (
    "feature",
    "bin",
    "default_left",
    "is_leaf",
    "leaf_value",
    "base_weight",
    "gain",
    "sum_hess",
    "left",
    "right",
)


def pack_tree(tree):
    """Tree dict -> one f32 [8, max_nodes] array (single D2H transfer)."""
    return jnp.stack([tree[k].astype(jnp.float32) for k in _TREE_FIELDS])


def tree_from_packed(packed):
    """Packed device array -> device tree dict (cheap casts, no transfer)."""
    return {
        "feature": packed[0].astype(jnp.int32),
        "bin": packed[1].astype(jnp.int32),
        "default_left": packed[2] > 0.5,
        "is_leaf": packed[3] > 0.5,
        "leaf_value": packed[4],
        "base_weight": packed[5],
        "gain": packed[6],
        "sum_hess": packed[7],
        "left": packed[8].astype(jnp.int32),
        "right": packed[9].astype(jnp.int32),
    }


def unpack_tree(packed):
    """Packed numpy array -> host tree dict with proper dtypes."""
    import numpy as np

    out = {}
    for i, key in enumerate(_TREE_FIELDS):
        arr = np.asarray(packed[i])
        if key in ("feature", "bin", "left", "right"):
            out[key] = arr.astype(np.int32)
        elif key in ("default_left", "is_leaf"):
            out[key] = arr.astype(bool)
        else:
            out[key] = arr.astype(np.float32)
    return out


def predict_binned(tree, bins, max_depth, num_bins, route_impl=None):
    """Apply one trained tree to binned rows -> margins.

    Traverses explicit child indices (leaves self-loop) under a
    ``lax.while_loop`` that stops as soon as every row sits on a leaf;
    ``max_depth`` is only the static upper bound (max root->leaf distance for
    depthwise trees, max_leaves-1 for lossguide), so a 256-leaf lossguide
    tree of actual depth ~8 costs ~8 gather rounds, not 255. Used for
    validation-set evaluation during training (validation is binned with the
    training cuts, so bin comparison == float comparison). ``route_impl``:
    ``row_bin_lookup``'s lowering — traced callers resolve it from the
    session's ``HistKnobs.backend`` (trace-safety; None chooses from
    the process's backend, for direct callers only).
    """
    n = bins.shape[0]

    def cond(state):
        i, node = state
        return (i < max_depth) & jnp.any(~tree["is_leaf"][node])

    def body(state):
        i, node = state
        feat = tree["feature"][node]
        split_bin = tree["bin"][node]
        row_bin = row_bin_lookup(bins, feat, impl=route_impl)
        is_missing = row_bin == (num_bins - 1)
        go_right = jnp.where(is_missing, ~tree["default_left"][node], row_bin > split_bin)
        child = jnp.where(go_right, tree["right"][node], tree["left"][node])
        node = jnp.where(tree["is_leaf"][node], node, child)
        return i + 1, node

    _, node = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.zeros(n, jnp.int32))
    )
    return tree["leaf_value"][node]


def predict_binned_levels(tree, bins, max_depth, num_bins, route_impl=None,
                          table_backend=None, bundle=None, gathers=True, cat=None):
    """``predict_binned`` for a tree ``build_tree`` made, bit for bit.

    Such a tree is a heap (children of i at 2i+1 / 2i+2, level L the static
    slice [2**L - 1, 2**(L+1) - 1)), so rows walk it as the build routes its
    own: the level loop unrolled at trace time, each level's split fields
    looked up in that level's own ``2**L``-entry slices, rows that met a leaf
    above staying on it. No ``while_loop``, no reduction over the rows, no
    ``left`` / ``right``: no data-dependent control flow, so under ``vmap``
    over a stack of trees it is the same program with a leading axis. The
    leaf value is one lookup at the end, where the build reads its leaf weight
    once a level, both through ``node_table_lookup`` (15.5 ms a tree over 2.2M
    rows on the chip; PERF.md section 6, PR 33).
    ``table_backend`` chooses each ``node_table_lookup``'s lowering through
    ``choose_table_impl`` (traced callers pass the session's
    ``HistKnobs.backend``; None reads the process's backend, for direct
    callers only). ``bundle``: the session's ``ops.bundle.BundleTables`` where
    tree and bins are bundled: a row absent from a split's column is told by
    the range test, as the build tells it (``gathers``: how the nodes' range
    words are read, ``BundleTables.node_ranges``). ``cat``: the session's
    ``ops.categorical.CatTables`` where the tree holds set splits: a row reads
    its value over the split column's chunks and its bit of the node's set
    (``tree["cat_words"]``), as the build routes its own.
    """
    if table_backend is None:
        table_backend = jax.default_backend()
    if bundle is not None:
        tree = dict(tree, range=bundle.node_ranges(tree["feature"], tree["bin"], gathers))
    node = jnp.zeros(bins.shape[0], jnp.int32)
    for level in range(max_depth):
        first = 2**level - 1
        width = 2**level
        impl = choose_table_impl(table_backend, width)
        node_local = node - first  # negative: the row met its leaf above
        local_safe = jnp.clip(node_local, 0, width - 1)

        def at_node(field):
            return node_table_lookup(
                tree[field][first:first + width], local_safe, impl=impl
            )

        if cat is not None:
            value = cat.row_value(bins, at_node("feature"))
            go_right = cat.go_right(
                value, at_node("bin"), at_node("default_left"),
                cat.set_word(
                    tree["cat_words"][first:first + width], local_safe, value, table_backend
                ),
            )
        else:
            row_bin = row_bin_lookup(bins, at_node("feature"), impl=route_impl)
            if bundle is None:
                go_right = jnp.where(
                    row_bin == (num_bins - 1), ~at_node("default_left"), row_bin > at_node("bin")
                )
            else:
                go_right = bundle.go_right(
                    row_bin, at_node("range"), at_node("bin"), at_node("default_left")
                )
        child = node * 2 + 1 + go_right.astype(jnp.int32)
        node = jnp.where((node_local >= 0) & ~at_node("is_leaf"), child, node)
    leaves = tree["leaf_value"]
    return node_table_lookup(
        leaves, node, impl=choose_table_impl(table_backend, leaves.shape[0])
    )


# What follows stands at the end of the file because the lines of the traced
# code above are part of the compile cache's key (PERF.md section 6, PR 30).

#: a loss-guided build's pass counters (ops/lossguide.py: passes over the
#: rows, node slots filled, slots used), the one field a tree dict may hold
#: beside _TREE_FIELDS
PASS_COUNTS_FIELD = "hist_passes"


#: a categorical build's sets (ops/categorical.py: i32 [nodes, words]), the
#: other field a tree dict may hold beside _TREE_FIELDS
SET_WORDS_FIELD = "cat_words"


def pack_round_trees(tree):
    """``pack_tree`` for the round program: a loss-guided tree's pass
    counters ride the one array as the first entries of an eleventh row, so
    they cost no transfer of their own, and a categorical build's sets as
    ``2 * words`` rows more (``ops.categorical.pack_set_words``); a
    depth-wise tree without sets packs as ever."""
    packed = pack_tree(tree)
    if SET_WORDS_FIELD in tree:
        from .categorical import pack_set_words

        return jnp.concatenate([packed, pack_set_words(tree[SET_WORDS_FIELD])])
    if PASS_COUNTS_FIELD not in tree:
        return packed
    counts = tree[PASS_COUNTS_FIELD].astype(jnp.float32)
    spare = packed.shape[-1] - counts.shape[-1]
    row = jnp.pad(counts, [(0, 0)] * (counts.ndim - 1) + [(0, spare)])
    return jnp.concatenate([packed, row[None]])


def round_tree_from_packed(packed, set_words=0):
    """``tree_from_packed``, and the sets where the session's trees carry
    ``set_words`` words of them a node."""
    tree = tree_from_packed(packed)
    if set_words:
        from .categorical import unpack_set_words

        tree[SET_WORDS_FIELD] = unpack_set_words(packed[len(_TREE_FIELDS):])
    return tree


def unpack_round_trees(packed, set_words=0):
    """``unpack_tree``, and the pass counters or the sets (``set_words``
    words a node: a categorical session's) where the array carries them."""
    import numpy as np

    out = unpack_tree(packed)
    if set_words:
        from .categorical import unpack_set_words

        out[SET_WORDS_FIELD] = unpack_set_words(np.asarray(packed[len(_TREE_FIELDS):]), np)
    elif len(packed) > len(_TREE_FIELDS):
        counts = np.asarray(packed[len(_TREE_FIELDS)])
        out[PASS_COUNTS_FIELD] = counts[..., :3].astype(np.int64)
    return out


def predict_binned_steps(tree, bins, num_bins, table_backend=None):
    """``predict_binned`` for a tree ``build_tree_lossguide`` made, bit for bit.

    Such a tree numbers its nodes in the order they were split (split step t
    made nodes ``2t + 1`` and ``2t + 2``; ``ops/lossguide.py``), so the tree
    is the list of its builder's steps and rows replay them the way the build
    routed its own: ONE rolled ``fori_loop`` over the ``(nodes - 1) // 2``
    steps, a step's node and split read as scalars, that one column sliced
    out of ``bins`` and the rows that sit on the node moved by one compare
    and one select. No ``while_loop``, no reduction over the rows and no
    per-row gather inside the loop (on the chip the pointer walk's five
    row-length gathers a level cost 37.6 ns a row and level, a step here
    30 us over 500,000 rows: PERF.md section 6, PR 44); under ``vmap`` over
    a stack of trees the scalar column is one column a tree.
    The per-step table is derived here from ``left`` and ``is_leaf`` by a
    ``[steps, nodes]`` compare: a step that could not split has no node
    (-1, which no row sits on), and nothing is added to the packed tree. The
    leaf value is one ``node_table_lookup`` at the end, in the lowering
    ``choose_table_impl(table_backend, nodes)`` picks (traced callers pass
    the session's ``HistKnobs.backend``; None reads the process's backend,
    for direct callers only).
    """
    if table_backend is None:
        table_backend = jax.default_backend()
    n = bins.shape[0]
    nodes = tree["left"].shape[0]
    steps = (nodes - 1) // 2
    node_ids = jnp.arange(nodes, dtype=jnp.int32)
    first_child = 2 * jnp.arange(steps, dtype=jnp.int32) + 1
    # [steps, nodes]: the internal node whose children step t made; a step
    # that could not split reads -1 in every field, and no row sits on node -1
    made_by = ~tree["is_leaf"][None, :] & (tree["left"][None, :] == first_child[:, None])
    table = jnp.stack(
        [
            jnp.max(jnp.where(made_by, values.astype(jnp.int32)[None, :], -1), axis=1)
            for values in (node_ids, tree["feature"], tree["bin"], tree["default_left"])
        ],
        axis=1,
    )

    def step(t, node):
        parent, feature, split_bin, default_left = jax.lax.dynamic_index_in_dim(
            table, t, keepdims=False
        )
        row_bin = jax.lax.dynamic_slice(bins, (0, feature), (n, 1))[:, 0]
        go_right = jnp.where(
            row_bin == (num_bins - 1), default_left == 0, row_bin > split_bin
        )
        child = 2 * t + 1 + go_right.astype(jnp.int32)
        return jnp.where(node == parent, child, node)

    node = jax.lax.fori_loop(0, steps, step, jnp.zeros(n, jnp.int32))
    return node_table_lookup(
        tree["leaf_value"], node, impl=choose_table_impl(table_backend, nodes)
    )
