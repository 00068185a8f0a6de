"""Leaf-wise (lossguide) tree growth: best-gain-first splitting to max_leaves.

The reference validates grow_policy=lossguide + max_leaves
(hyperparameter_validation.py:259-260) and delegates to libxgboost's
lossguide updater (LightGBM-style growth). Static-shape XLA formulation:

* node slots are allocated sequentially (root=0; split t creates 2t+1, 2t+2),
  explicit child indices — the shared tree layout of ops/tree_build;
* the ``max_leaves - 1`` split steps are ONE rolled ``lax.fori_loop`` over
  the build's state (tree arrays, candidate store, node sums and depths, each
  row's node, the per-node histogram cache, the store of children's
  histograms, the alive constraint sets): the program holds two kernel call
  sites (the root's, a pass's), one split scan and one routing step whatever
  ``max_leaves``. Each step picks the global best-gain leaf (argmax over the
  candidate store), routes its rows, takes its children's histograms from
  the store and scans them;
* every leaf keeps a precomputed best-split candidate, so step selection is
  O(nodes), not O(n);
* **a pass over the rows histograms several open leaves at once.** A leaf's
  children's histograms depend on its rows and its stored candidate alone,
  both fixed from the moment the leaf exists, and not on the order in which
  other leaves are split. So a step whose pick has no entry in the store
  runs one pass (under a ``lax.cond``): the pick and the next-best open
  leaves without an entry take the ``PASS_SLOTS`` node slots of ONE
  ``level_histogram`` call, every row labelled with the child it WILL go to
  when its leaf is committed, and the results go into the store. The steps
  after it commit leaf after leaf from the store, in exactly the order a
  pass a step would, until a pick is not there. A step that cannot split
  runs no pass. ``PASS_SLOTS`` is read off the kernel's shapes: the widest
  call whose gradient operand is still W = 1's one bf16 tile, so a pass
  costs what a one-child call costs (``ops/histogram.py::_operand_rows``,
  ``_bin_fold``'s table). A leaf that holds more than a slot's share of the
  root's hessian sum is dealt round as many slots as it holds shares, and
  takes them from the leaves behind it (``_leaf_slots``): the kernel sums a
  W = 8 call in an eighth of a W = 1 call's row chunks, on the premise that
  a level's nodes share the rows, and a sum's error follows the length of
  its chain of additions;
* every instruction lies under a stage of the round program
  (``telemetry/device.py::STAGES``): ``hist`` (a pass's kernel call, its
  operands, the store's writes), ``route_rows`` (the committed leaf's rows,
  and a pass's go-left decisions for its leaves), ``split_scan``,
  ``leaf_margin``, and ``step_pick`` for the pick, a pass's choice of
  leaves, the tree and store updates, the cache's slot writes and the loop
  itself.

Cost note: a tree reads all n rows once a pass, and a tree of 255 leaves
over millions of rows takes 45 to 75 passes where it took 254 (PERF.md
section 6, PR 43), against depthwise's ``max_depth``. What is left is
chains (a fresh child that is at once the best leaf has no entry yet) and
that a pass still reads every row for leaves that hold a few thousand: rows
grouped by node would read 8.8 n a tree. Under a ``vmap`` (the class trees of
a loss-guided multi-class job) the ``cond`` becomes a select and a pass runs
every step, at the price of the one-child call it replaced: nothing gained
there and nothing lost.
"""

import jax
import jax.numpy as jnp

from .histogram import (
    _operand_rows,
    apply_hist_collective,
    level_histogram,
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
    column_shard_helpers,
    combine_splits_across_shards,
    find_best_splits,
    leaf_weight,
)
from .tree_build import choose_route_impl, row_bin_lookup

MIN_SPLIT_LOSS = 1e-6


def _pass_slots():
    """Node slots of a pass (``PASS_SLOTS``): the widest ``level_histogram``
    call whose gradient operand is still the one bf16 tile a W = 1 call
    streams, so that a pass costs what a one-child call costs wherever the
    kernel is the builder (``ops/histogram.py::_bin_fold``'s table: W = 1, 2,
    4 and 8 take 25.5 to 25.7 ms a call, W = 16 takes 44.6). Past it nothing
    is gained either: what is left is chains, a fresh child that is at once
    the best leaf (PERF.md section 6, PR 43)."""
    W = 1
    while _operand_rows(2 * W) == _operand_rows(1):
        W *= 2
    return W


PASS_SLOTS = _pass_slots()  # 8


def pass_leaves(max_leaves, subtract):
    """Leaves a pass histograms: with sibling subtraction a leaf takes one
    node slot of the ``PASS_SLOTS`` (its left child; the right one is the
    cached parent less it), without it two; a power of two, and never more
    than the open leaves a tree of ``max_leaves`` can hold."""
    leaves = PASS_SLOTS // (1 if subtract else 2)
    while leaves > max(1, max_leaves - 1):
        leaves //= 2
    return leaves


def pass_nodes(max_leaves, subtract):
    """Node slots of a pass's ``level_histogram`` call (its W, and its
    collective's on a mesh): ``PASS_SLOTS`` but for the smallest trees."""
    return pass_leaves(max_leaves, subtract) * (1 if subtract else 2)


def _subtraction_enabled(max_leaves, d_hist, num_bins):
    """Sibling subtraction for leaf-wise growth: a pass histograms only the
    LEFT child of each of its leaves and a split step derives the right one
    from the parent's cached histogram, so a pass holds twice the leaves.
    Needs a [2*max_leaves-1, d_hist, B] f32 cache x2 and, beside it, the
    store of left children in the same shape, so gated by the shared cap
    on both."""
    return subtraction_enabled(2 * 2 * (2 * max_leaves - 1) * d_hist * num_bins * 4)


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
    knobs=None,
):
    """Grow one leaf-wise tree. Returns (tree arrays dict, row_out [n]).

    Same output layout as ops.tree_build.build_tree, and one field more,
    ``hist_passes``: int32 ``[passes, slots filled, slots used]`` of the
    step loop (the root's call apart), which ``pack_tree`` carries to the
    host in the round's one array; max_depth=0 means
    unbounded depth (bounded by max_leaves - 1). ``knobs``: the
    session's ``ops.histogram.HistKnobs`` snapshot (trace-safety; None,
    for direct unit-test/probe callers, chooses from the process's backend).
    """
    n, d = bins.shape
    max_nodes = 2 * max_leaves - 1
    depth_cap = max_depth if max_depth > 0 else max_leaves
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

    def _scan_nodes(Gb, Hb, mask_b):
        """Gain-scan + cross-shard combine for one node batch."""
        s = find_best_splits(
            Gb,
            Hb,
            num_cuts,
            reg_lambda=reg_lambda,
            alpha=alpha,
            gamma=gamma,
            min_child_weight=min_child_weight,
            feature_mask=mask_b,
            monotone=monotone,
        )
        # cross-shard combine: the candidate store (and therefore every
        # step's argmax) must be identical on all shards, with GLOBAL ids
        if feature_axis_name is None:
            return s
        return combine_splits_across_shards(s, feat_shard, d, feature_axis_name)

    def _child_splits(G_ab, H_ab, mask, depth_ab):
        """Candidates of the two fresh children from their (reduced)
        histograms; the depth cap folded into the gains (children at
        ``depth_cap`` can never split)."""
        with stage(STAGE_SPLIT_SCAN):
            splits = _scan_nodes(G_ab, H_ab, mask)
            gains = jnp.where(depth_ab < depth_cap, splits["gain"], -jnp.inf)
        return splits, gains

    subtract = _subtraction_enabled(max_leaves, d, num_bins)
    hist_cache = None
    if subtract:
        # per-node histogram cache (filled as leaves are created)
        hist_cache = (
            jnp.zeros((max_nodes, d, num_bins), jnp.float32),
            jnp.zeros((max_nodes, d, num_bins), jnp.float32),
        )
    # the store of children's histograms a pass fills and the split steps
    # read. With subtraction a leaf takes one node slot of a pass (its left
    # child; the right one is the cached parent less it) and the store is
    # the cache's shape, an entry a leaf, kept until the leaf is committed.
    # Without it (the cache is over the gate, so a store of that shape is
    # too) a leaf takes two slots, and the store holds the leaves of the
    # last pass alone.
    kids = 1 if subtract else 2
    in_pass = pass_leaves(max_leaves, subtract)
    store_leaves = max_nodes if subtract else in_pass
    store = (
        jnp.zeros((store_leaves * kids, d, num_bins), jnp.float32),
        jnp.zeros((store_leaves * kids, d, num_bins), jnp.float32),
    )
    # the store entry that holds a leaf's children, -1 where none does
    entry = jnp.full(max_nodes, -1, jnp.int32)
    # [passes, slots filled, slots used] of the step loop
    pass_counts = jnp.zeros(3, jnp.int32)

    # root candidate
    with stage(STAGE_HIST):
        G, H = level_histogram(
            bins, grad, hess, jnp.zeros(n, jnp.int32), 1, num_bins,
            axis_name=axis_name, knobs=knobs, reach=num_cuts,
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
        ``id_a + 1`` of a per-node ``table`` (and a leaf's children into their
        entry of the store)."""
        return jax.lax.dynamic_update_slice(
            table, values.astype(table.dtype), (id_a,) + (0,) * (table.ndim - 1)
        )

    def _rows_at(table, start, count):
        """``table[start : start + count]`` for a traced ``start``."""
        return jax.lax.dynamic_slice(
            table, (start,) + (0,) * (table.ndim - 1), (count,) + table.shape[1:]
        )

    route_impl = choose_route_impl(knobs.backend, d) if knobs is not None else None

    def _goes_right(f, b, default_left):
        """Which way every row goes at a split on (global) feature ``f``, bin
        ``b``. Scalars (a split step: one leaf's split for all rows): a
        dynamic column slice, not a per-row gather. A value a row (a pass:
        each row under its own leaf's candidate): ``row_bin_lookup``, as a
        depth-wise level routes. Under feature sharding only the shard owning
        ``f`` can decide: its decisions as int32, every other shard's zeros,
        for ``_across_feature_shards``."""
        owner = True
        if feature_axis_name is not None:
            owner = (f // d) == feat_shard
            f = jnp.clip(f - feat_shard * d, 0, d - 1)
        if f.ndim == 0:
            row_bin = jax.lax.dynamic_slice(bins, (0, f), (n, 1))[:, 0]
        else:
            row_bin = row_bin_lookup(bins, f, impl=route_impl)
        decision = jnp.where(row_bin == (num_bins - 1), ~default_left, row_bin > b)
        if feature_axis_name is None:
            return decision
        return jnp.where(owner, decision, False).astype(jnp.int32)

    def _across_feature_shards(decisions):
        """The owners' decisions psum-broadcast along the feature axis —
        same convention as tree_build's level routing."""
        if feature_axis_name is None:
            return decisions
        return jax.lax.psum(decisions, feature_axis_name) > 0

    def _leaf_slots(h_leaf, h_root):
        """Node slots a leaf's child is spread over in a pass (times ``kids``
        without subtraction): its share of the root's hessian sum in
        ``in_pass``-ths, rounded up to a power of two. The kernel sums a
        W = 8 call in an eighth of the row chunks of a W = 1 call
        (``ops/histogram.py::_row_chunks``), which keeps a sum's chain of
        additions as long as the root's where a level's nodes share the rows;
        a pass's leaves do not, so a leaf that holds more than a slot's share
        takes as many slots as it holds shares, its rows dealt round them,
        and the slots are added up afterwards. By hessian and not by rows:
        a node's sums are the build's own state, the same on every shard."""
        share = h_leaf * in_pass / h_root
        slots = jnp.ones_like(share, dtype=jnp.int32)
        width = 1
        while width < in_pass:
            slots = jnp.where(share > width, 2 * width, slots)
            width *= 2
        return slots

    def hist_pass(l, gains, cand, node_h, node_of_row, store, entry, pass_counts):
        """One pass over the rows: the children's histograms of the step's
        pick ``l`` and, behind it, of the best open leaves that have none
        yet, in the ``PASS_SLOTS`` node slots of one kernel call, into the
        store. Every shard holds the same candidate store, so every shard
        runs the same passes and the collective inside is uniform."""
        if not subtract:
            entry = jnp.full_like(entry, -1)  # the store is overwritten whole
        # the pick first whatever picked it: the step reads its entry next
        open_gains = jnp.where(entry < 0, gains, -jnp.inf).at[l].set(jnp.inf)
        top_gains, leaves = jax.lax.top_k(open_gains, in_pass)
        # a leaf that cannot split (at a depth cap its gain is -inf) is never
        # committed: it takes no slot; nor does a leaf that no longer fits
        wants = _leaf_slots(node_h[leaves], node_h[0])
        live, first, taken = [], [], jnp.int32(0)
        for j in range(in_pass):
            live.append((top_gains[j] > MIN_SPLIT_LOSS) & (taken + wants[j] <= in_pass))
            first.append(taken)
            taken = taken + jnp.where(live[j], wants[j], 0)
        with stage(STAGE_ROUTE_ROWS):
            # where a leaf's rows WILL go when it is committed: its stored
            # candidate is the split the step then writes into the tree. One
            # pass over the bins for all the leaves, each row under its own
            # leaf's candidate
            slot_of_row = jnp.full(n, -1, jnp.int32)  # the row's leaf's first slot
            spread = jnp.ones(n, jnp.int32)           # and how many it is dealt round
            f_row = jnp.zeros(n, jnp.int32)
            b_row = jnp.zeros(n, jnp.int32)
            dl_row = jnp.zeros(n, jnp.bool_)
            for j in range(in_pass):
                in_leaf = live[j] & (node_of_row == leaves[j])
                slot_of_row = jnp.where(in_leaf, first[j], slot_of_row)
                spread = jnp.where(in_leaf, wants[j], spread)
                f_row = jnp.where(in_leaf, cand["feature"][leaves[j]], f_row)
                b_row = jnp.where(in_leaf, cand["bin"][leaves[j]], b_row)
                dl_row = jnp.where(in_leaf, cand["default_left"][leaves[j]], dl_row)
            go_right = _across_feature_shards(_goes_right(f_row, b_row, dl_row))
            dealt = slot_of_row + (jnp.arange(n, dtype=jnp.int32) & (spread - 1))
            if subtract:  # the left child alone
                slot_of_row = jnp.where((slot_of_row < 0) | go_right, -1, dealt)
            else:
                slot_of_row = jnp.where(
                    slot_of_row < 0, -1, 2 * dealt + go_right.astype(jnp.int32)
                )
        with stage(STAGE_HIST):
            G, H = apply_hist_collective(
                *level_histogram(
                    bins, grad, hess, slot_of_row, in_pass * kids, num_bins, knobs=knobs,
                    reach=num_cuts,
                ),
                axis_name,
            )
            held = leaves if subtract else jnp.arange(in_pass, dtype=jnp.int32)
            by_slot = [built.reshape((in_pass, kids) + built.shape[1:]) for built in (G, H)]
            slots = jnp.arange(in_pass, dtype=jnp.int32)[:, None, None, None]
            for j in range(in_pass):
                at = held[j] * kids
                mine = (slots >= first[j]) & (slots < first[j] + wants[j])
                store = tuple(
                    _pair(
                        table,
                        jnp.where(
                            live[j],
                            jnp.sum(jnp.where(mine, parts, 0.0), axis=0),  # its slots added up
                            _rows_at(table, at, kids),
                        ),
                        at,
                    )
                    for table, parts in zip(store, by_slot)
                )
        live = jnp.stack(live)
        entry = entry.at[leaves].set(jnp.where(live, held, entry[leaves]))
        return store, entry, pass_counts + jnp.stack([1, kids * taken, 0])

    def split_step(t, state):
        """One split step: pick the best leaf, run a pass if the store does
        not hold its children yet, route its rows, score its two fresh
        children (slots ``2t + 1``, ``2t + 2``). What lies under no stage of
        its own here is ``step_pick``'s (the scope round the loop)."""
        (tree, cand, node_g, node_h, node_depth, node_of_row, hist_cache, alive_sets,
         store, entry, pass_counts) = state
        tree, cand = dict(tree), dict(cand)
        id_a, id_b = 2 * t + 1, 2 * t + 2
        gains = jnp.where(tree["is_leaf"], cand["gain"], -jnp.inf)
        l = jnp.argmax(gains).astype(jnp.int32)
        can = gains[l] > MIN_SPLIT_LOSS

        # before the pick's rows move: a pass labels every row by the leaf
        # it sits in. A ready pick, or a step that cannot split, runs none
        store, entry, pass_counts = jax.lax.cond(
            can & (entry[l] < 0),
            lambda: hist_pass(l, gains, cand, node_h, node_of_row, store, entry, pass_counts),
            lambda: (store, entry, pass_counts),
        )

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
            # route rows of l
            in_l = node_of_row == l
            go_right = _across_feature_shards(_goes_right(f_l, b_l, dl_l))
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
            # the children's histograms, already reduced, from the store: the
            # left one as a pass built it; the right one the cached parent
            # less it, or (no subtraction) the pass's too. When the step
            # can't split, no rows were routed: both are zeros.
            at = jnp.maximum(entry[l], 0) * kids
            G_ab, H_ab = (
                jnp.where(can, _rows_at(table, at, kids), 0.0) for table in store
            )
            if subtract:
                G_ab = jnp.stack([G_ab[0], jnp.where(can, hist_cache[0][l] - G_ab[0], 0.0)])
                H_ab = jnp.stack([H_ab[0], jnp.where(can, hist_cache[1][l] - H_ab[0], 0.0)])
                hist_cache = (_pair(hist_cache[0], G_ab, id_a), _pair(hist_cache[1], H_ab, id_a))
        pass_counts = pass_counts.at[2].add(
            jnp.where(can, kids * _leaf_slots(node_h[l], node_h[0]), 0)
        )
        splits, child_gains = _child_splits(
            G_ab, H_ab, node_mask, jnp.stack([depth_ab, depth_ab])
        )
        # children of a non-split never get rows, so their -inf gains + zero
        # totals are inert
        cand["gain"] = _pair(cand["gain"], jnp.where(can, child_gains, -jnp.inf), id_a)
        for field in ("feature", "bin", "default_left"):
            cand[field] = _pair(cand[field], splits[field], id_a)
        node_g = _pair(node_g, splits["g_total"], id_a)
        node_h = _pair(node_h, splits["h_total"], id_a)
        return (tree, cand, node_g, node_h, node_depth, node_of_row, hist_cache, alive_sets,
                store, entry, pass_counts)

    # ONE rolled loop over the split steps: the program holds one pass (its
    # kernel call site), one split scan and one routing step whatever
    # ``max_leaves``
    state = (tree, cand, node_g, node_h, node_depth, node_of_row, hist_cache, alive_sets,
             store, entry, pass_counts)
    with stage(STAGE_STEP_PICK):
        state = jax.lax.fori_loop(0, max_leaves - 1, split_step, state)
    tree, _cand, node_g, node_h, _depth, node_of_row = state[:6]
    tree["hist_passes"] = state[-1]

    # finalize leaf values for every (reachable) leaf slot
    with stage(STAGE_LEAF_MARGIN):
        weight = leaf_weight(node_g, node_h, reg_lambda=reg_lambda, alpha=alpha,
                             max_delta_step=max_delta_step)
        tree["base_weight"] = weight
        tree["sum_hess"] = node_h
        tree["leaf_value"] = jnp.where(tree["is_leaf"], eta * weight, 0.0)
        row_out = tree["leaf_value"][node_of_row]
    return tree, row_out
