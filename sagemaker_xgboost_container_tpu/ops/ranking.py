"""LambdaMART gradients for rank:pairwise / rank:ndcg / rank:map.

The reference delegates ranking to libxgboost's LambdaRank objective (group
layout carried by the DMatrix). Here query groups are laid out once on the
host as dense ``[G, M]`` row indices with -1 padding, and each round computes
all intra-group pairwise RankNet gradients as one XLA program: sigmoid on the
score-difference matrix, masked by label ordering, optionally weighted by
|delta NDCG| (LambdaMART), then handed back in row order.

Group sizes are skewed (MSLR-WEB30K: 1 to 1,251 documents a query, mean
120), so one layout padded to the largest group would compute fifty times
the real pairs. ``build_group_layout`` therefore sorts groups into buckets
by size (widths doubling from 32 to 512, then every 256 up to the largest
group rounded up to the lane width), each bucket an index of its own, and
``lambdarank_grad_hess`` runs the pair pass per bucket, ``lax.map`` over
chunks of groups sized so that one ``[chunk, M, M]`` pair tensor stays
under ``PAIR_SLOTS_PER_STEP`` elements. No group is truncated. A single
``[G, M]`` index is the one-bucket case; the mesh path keeps one index a
shard (``build_sharded_group_layout``).

Every row lies in exactly one slot, so the way back from slots to rows is a
gather through the inverse index (``row_slot``), not a scatter-add.

Which columns are whose. A round changes the margins and nothing else, so a
round gathers the margins and nothing else (``gather_groups`` under
``rank_gather``, once a bucket and caller). Labels, weights and all that
follows from them and the index alone (``valid``, the DCG gains, each group's
ideal DCG at every cutoff a caller asks for) are the layout's: gathered and
computed once, where the layout goes to the device (``with_slot_columns``,
the same helper and the same expressions the round used to run), and carried
beside the index they belong to as one ``SlotColumns`` a bucket. Both callers,
``lambdarank_grad_hess`` and ``models/device_metrics.py::grouped_ndcg``, read
them there.
"""

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry.device import (
    STAGE_RANK_GATHER,
    STAGE_RANK_PAIRS,
    STAGE_RANK_SCATTER,
    stage,
)

_SIGMA = 1.0

#: bucket widths: doubling up to 512, then every 256 (``bucket_widths``)
_FIRST_WIDTH = 32
_LAST_DOUBLED_WIDTH = 512
_WIDTH_STEP = 256
_LANES = 128
_SUBLANES = 8
#: elements of one ``[chunk, M, M]`` pair tensor a ``lax.map`` step may hold
#: (64 MiB in float32; the pair pass keeps a handful of them alive)
PAIR_SLOTS_PER_STEP = 1 << 24


class GroupLayout(NamedTuple):
    """Query groups as the round program takes them (a pytree of arrays).

    indices: tuple of int32 ``[G_b, M_b]`` row indices, -1 padding, one a
      bucket (on a mesh: one ``[shards, G, M]`` array, a ``[1, G, M]`` slice
      a shard, in shard-local row coordinates).
    row_slot: int32 ``[n]``, each row's position in the concatenation of the
      flattened ``indices``; -1 for a row in no group (mesh padding).
    empty_groups: float32 scalar, the groups that hold no row: they are in
      no bucket, and a per-group metric counts them as the host does.
    slots: tuple of ``SlotColumns``, one a bucket, in the shapes of
      ``indices``; empty on a layout as the host builds it, filled where it
      goes to the device (``with_slot_columns``).
    """

    indices: tuple
    row_slot: object
    empty_groups: object
    slots: tuple = ()


class SlotColumns(NamedTuple):
    """What one bucket's slots hold that no round changes, ``[G, M]`` each
    (``[shards, G, M]`` on a mesh).

    labels: float32, -inf in the padding (padding is never "preferred").
    weights: float32, 0 in the padding.
    valid: bool, the slots that hold a row.
    gains: float32, ``dcg_gain(labels, valid)``.
    ideal_dcg: ``{cutoff: float32 [G]}``, ``ideal_dcg`` at every cutoff asked
      for; cutoff 0 is the whole group (the gradient's, and plain ``ndcg``'s).
    """

    labels: object
    weights: object
    valid: object
    gains: object
    ideal_dcg: dict


def map_exchange_delta(S, Y, valid):
    """Exact |delta AP| for every intra-group pair swap (binary relevance).

    S, Y, valid: [G, M] scores / labels / validity. For a pair with the
    relevant doc at rank p above the irrelevant at rank q:
    |dAP| = (C(p)/p - C(q)/q + Sum_{k in (p,q)} rel_k/k) / R, with the
    symmetric +1/r_u correction when the relevant doc is the lower one;
    C(k) = #relevant in top-k. Verified against brute-force AP recomputation
    in tests/test_map_delta.py.
    """
    G, M = S.shape
    rel = jnp.where(valid, (Y > 0).astype(jnp.float32), 0.0)
    order_key = jnp.where(valid, -S, jnp.inf)
    order = jnp.argsort(order_key, axis=1)
    ranks = jnp.argsort(order, axis=1) + 1                      # [G, M]
    rel_sorted = jnp.take_along_axis(rel, order, axis=1)
    C_sorted = jnp.cumsum(rel_sorted, axis=1)
    k_pos = jnp.arange(1, M + 1, dtype=jnp.float32)[None, :]
    S_sorted = jnp.cumsum(rel_sorted / k_pos, axis=1)
    inv_order = ranks - 1                                       # inverse perm
    C_i = jnp.take_along_axis(C_sorted, inv_order, axis=1)      # C(r_i)
    S_i = jnp.take_along_axis(S_sorted, inv_order, axis=1)      # S(r_i)
    r_f = ranks.astype(jnp.float32)
    R_total = jnp.maximum(rel.sum(axis=1), 1.0)[:, None, None]
    upper_is_i = (ranks[:, :, None] < ranks[:, None, :]).astype(jnp.float32)

    def pick(a):
        ai, aj = a[:, :, None], a[:, None, :]
        return upper_is_i * ai + (1 - upper_is_i) * aj, (
            upper_is_i * aj + (1 - upper_is_i) * ai
        )

    r_u, r_l = pick(r_f)
    C_u, C_l = pick(C_i)
    S_u, S_l = pick(S_i)
    rel_u, rel_l = pick(rel)
    core = (
        C_u / r_u + (1.0 - rel_u) / r_u - C_l / r_l + (S_l - rel_l / r_l) - S_u
    )
    differs = jnp.abs(rel[:, :, None] - rel[:, None, :])
    return jnp.abs(core) * differs / R_total


def _round_up(value, multiple):
    return -(-int(value) // multiple) * multiple


def bucket_widths(max_size):
    """Bucket widths for groups of up to ``max_size`` rows, read from the
    sizes alone: 32, 64, ..., 512, then 768, 1024, ... and last the largest
    group rounded up to the lane width (to the sublane width where it is
    under a lane's)."""
    top = _round_up(max(max_size, 1), _LANES if max_size > _LANES else _SUBLANES)
    widths, w = [], _FIRST_WIDTH
    while w < top:
        widths.append(w)
        w = w * 2 if w < _LAST_DOUBLED_WIDTH else w + _WIDTH_STEP
    return widths + [top]


def _dense_index(starts, sizes, width):
    """int32 ``[G, width]``: row ``starts[g] + j`` where ``j < sizes[g]``, else -1."""
    j = np.arange(width, dtype=np.int64)[None, :]
    index = np.where(j < sizes[:, None], starts[:, None] + j, -1)
    return index.astype(np.int32)


def _row_slots(indices, n_rows):
    """Inverse of the concatenated flat ``indices``: row -> slot, -1 for none."""
    row_slot = np.full(n_rows, -1, np.int32)
    offset = 0
    for index in indices:
        flat = index.reshape(-1)
        at = np.flatnonzero(flat >= 0)
        row_slot[flat[at]] = offset + at
        offset += flat.size
    return row_slot


def build_group_layout(groups, widths=None):
    """Group-size array -> ``GroupLayout`` (numpy), groups bucketed by size.

    Host-side, once per dataset. Rows of a group are contiguous, in the
    order of ``groups``. ``widths`` overrides the bucket rule (ascending, the
    last at least the largest group): the tests compare one bucket against
    several.
    """
    sizes = np.asarray(groups, np.int64)
    starts = np.cumsum(sizes) - sizes
    if widths is None:
        widths = bucket_widths(int(sizes.max()) if len(sizes) else 1)
    widths = np.asarray(widths, np.int64)
    if len(sizes) and sizes.max() > widths[-1]:
        raise ValueError("the widest bucket is smaller than the largest group")
    bucket = np.searchsorted(widths, sizes)  # the narrowest that holds the group
    indices = []
    for b, width in enumerate(widths):
        held = np.flatnonzero((bucket == b) & (sizes > 0))
        if len(held):
            indices.append(_dense_index(starts[held], sizes[held], int(width)))
    if not indices:
        indices.append(np.full((1, int(widths[0])), -1, np.int32))
    return GroupLayout(
        tuple(indices),
        _row_slots(indices, int(sizes.sum())),
        np.float32(np.count_nonzero(sizes == 0)),
    )


def _chunking(n_groups, width, pair_slots_per_step):
    """(steps, groups a step) for one bucket: as few steps as keep a
    ``[chunk, width, width]`` tensor under the budget, the groups spread
    evenly over them so that at most ``steps - 1`` all-padding groups are added."""
    steps = max(1, -(-n_groups * width * width // pair_slots_per_step))
    steps = min(steps, n_groups)
    return steps, -(-n_groups // steps)


def pair_slots(layout, pair_slots_per_step=PAIR_SLOTS_PER_STEP):
    """Pair slots a round computes over ``layout``, chunk padding included
    (a mesh's ``[shards, G, M]`` index: over all its shards)."""
    total = 0
    for index in layout.indices:
        n_groups, width = index.shape[-2:]
        steps, chunk = _chunking(n_groups, width, pair_slots_per_step)
        total += int(np.prod(index.shape[:-2])) * steps * chunk * width * width
    return total


def build_sharded_group_layout(groups, n_shards, max_group_size=None,
                               rows_per_shard=None, max_groups_per_shard=None):
    """Partition query groups across data shards for distributed LambdaMART.

    Groups never straddle shards (pairwise gradients are intra-group, so
    shard-local gradients stay exact — the reference's Rabit path likewise
    keeps each worker's groups whole). Greedy longest-processing-time
    assignment balances row counts; every shard pads to the same
    ``rows_per_shard`` with -1 (weight-0) rows.

    Returns (perm, layout, rows_per_shard):
      perm: int64 [n_shards * rows_per_shard] — device-order position ->
        original row id, -1 for padding.
      layout: ``GroupLayout`` of one index, int32 [n_shards, G_max, M] — the
        per-shard groups in SHARD-LOCAL row coordinates, -1 padding — and
        ``row_slot`` [n_shards * rows_per_shard], each shard's rows into its
        own flattened [G_max, M] (feed one shard's slices to
        lambdarank_grad_hess inside shard_map). One bucket a shard: the
        shards must agree on every shape, and a shard's groups are few.
    The ``rows_per_shard`` / ``max_groups_per_shard`` / ``max_group_size``
    overrides let multi-host runs agree on global maxima; a
    ``max_group_size`` under the largest local group is refused, never a
    truncation.
    """
    sizes = np.asarray(groups, np.int64)
    G = len(sizes)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    order = np.argsort(-sizes, kind="stable")
    assign = [[] for _ in range(n_shards)]
    loads = np.zeros(n_shards, np.int64)
    for g in order:
        s = int(np.argmin(loads))
        assign[s].append(int(g))
        loads[s] += sizes[g]
    rps = int(rows_per_shard if rows_per_shard is not None else loads.max())
    if loads.max() > rps:
        raise ValueError("rows_per_shard too small for group assignment")
    G_max = max((len(a) for a in assign), default=1) or 1
    if max_groups_per_shard is not None:
        G_max = max(G_max, int(max_groups_per_shard))
    M = int(max_group_size if max_group_size is not None else sizes.max())
    if M < sizes.max():
        raise ValueError("max_group_size is smaller than the largest group")
    perm = np.full(n_shards * rps, -1, np.int64)
    row_index = np.full((n_shards, G_max, M), -1, np.int32)
    row_slot = np.full((n_shards, rps), -1, np.int32)
    for s, group_list in enumerate(assign):
        pos = 0
        for gi, g in enumerate(sorted(group_list)):
            size = int(sizes[g])
            rows = np.arange(starts[g], starts[g] + size, dtype=np.int64)
            perm[s * rps + pos : s * rps + pos + size] = rows
            row_index[s, gi, :size] = np.arange(pos, pos + size, dtype=np.int32)
            pos += size
        row_slot[s] = _row_slots([row_index[s]], rps)
    layout = GroupLayout(
        (row_index,), row_slot.reshape(-1), np.float32(np.count_nonzero(sizes == 0))
    )
    return perm, layout, rps



def _pad_groups(array, n_groups, fill):
    extra = n_groups - array.shape[0]
    if not extra:
        return array
    pad = jnp.full((extra,) + array.shape[1:], fill, array.dtype)
    return jnp.concatenate([array, pad], axis=0)


def map_group_chunks(fn, arrays, pair_slots_per_step=PAIR_SLOTS_PER_STEP, fills=None):
    """``fn(*arrays)`` over the groups of one bucket, ``lax.map`` over chunks
    of groups sized by ``_chunking`` from the bucket's width (the second
    axis of ``arrays[0]``, ``[G, M]``). ``fn`` maps ``[g, M]`` arrays to a
    tuple of arrays with ``g`` leading; groups added to fill the last chunk
    hold ``fills`` and are cut off the result."""
    n_groups, width = arrays[0].shape
    steps, chunk = _chunking(n_groups, width, pair_slots_per_step)
    if steps == 1:
        return fn(*arrays)
    fills = fills or (0,) * len(arrays)
    chunked = tuple(
        _pad_groups(a, steps * chunk, fill).reshape((steps, chunk) + a.shape[1:])
        for a, fill in zip(arrays, fills)
    )
    outs = jax.lax.map(lambda xs: fn(*xs), chunked)
    return tuple(o.reshape((steps * chunk,) + o.shape[2:])[:n_groups] for o in outs)


def gather_groups(index, columns, fills):
    """Rows -> slots: each of ``columns`` ([n]) at ``index`` ([G, M]), the
    padding slots holding the column's fill. Returns (valid, *gathered).

    The one way from rows to slots, for every caller. A round passes the
    margins alone, the one column it changes; labels and weights pass through
    here once, at set-up (``with_slot_columns``), and stay on the layout."""
    valid = index >= 0
    safe = jnp.where(valid, index, 0)
    return (valid,) + tuple(
        jnp.where(valid, col[safe], fill) for col, fill in zip(columns, fills)
    )


def slots_to_rows(slot_values, row_slot):
    """Slots -> rows: ``slot_values`` (the flattened buckets, concatenated)
    read through the inverse index; a row in no slot gets 0."""
    return jnp.where(row_slot >= 0, slot_values[jnp.maximum(row_slot, 0)], 0.0)


def rank_descending(key, valid):
    """1-based rank of each slot within its group by ``key`` descending,
    ties broken by position, padding last: what ``argsort(argsort(-key))``
    with a stable sort gives, counted from the ``[G, M, M]`` comparison the
    pair pass forms anyway (a sort along 1,251 lanes costs far more)."""
    k = jnp.where(valid, -key, jnp.inf)
    ki, kj = k[:, :, None], k[:, None, :]
    pos = jnp.arange(key.shape[1])
    ahead = (kj < ki) | ((kj == ki) & (pos[None, None, :] < pos[None, :, None]))
    return ahead.sum(axis=2, dtype=jnp.int32) + 1


def dcg_gain(labels, valid):
    return jnp.where(valid, jnp.exp2(labels) - 1.0, 0.0)


def dcg_discount(ranks):
    return 1.0 / jnp.log2(1.0 + ranks.astype(jnp.float32))


def ideal_dcg(labels, gains, valid, cutoffs):
    """DCG of each group's documents in the order of their labels, at each of
    ``cutoffs`` (0: the whole group): a tuple of ``[G]``."""
    ideal_ranks = rank_descending(labels, valid)
    terms = gains * dcg_discount(ideal_ranks)
    return tuple(
        (jnp.where(ideal_ranks <= k, terms, 0.0) if k else terms).sum(axis=1)
        for k in cutoffs
    )


def _slot_columns(index, labels, weights, cutoffs, pair_slots_per_step):
    """One bucket's ``SlotColumns`` from its ``[G, M]`` index."""
    # padding is never "preferred": its label is -inf
    valid, Y, W = gather_groups(index, (labels, weights), (-jnp.inf, 0.0))
    gains = dcg_gain(Y, valid)
    ideal = map_group_chunks(
        partial(ideal_dcg, cutoffs=cutoffs),
        (Y, gains, valid),
        pair_slots_per_step,
        fills=(-jnp.inf, 0.0, False),
    )
    return SlotColumns(Y, W, valid, gains, dict(zip(cutoffs, ideal)))


@partial(jax.jit, static_argnames=("cutoffs", "pair_slots_per_step"))
def with_slot_columns(layout, labels, weights, cutoffs=(0,),
                      pair_slots_per_step=PAIR_SLOTS_PER_STEP):
    """``layout`` with its ``slots`` filled from the rows' ``labels`` and
    ``weights`` ([n]): once, where the layout goes to the device. A shard's
    ``[1, G, M]`` slice of a mesh's index (under ``shard_map``) gives columns
    with the same leading axis. ``cutoffs``: the ``ideal_dcg`` entries."""
    slots = []
    for index in layout.indices:
        lead = index.shape[:-2]
        columns = _slot_columns(
            index.reshape(index.shape[-2:]), labels, weights, cutoffs, pair_slots_per_step
        )
        slots.append(
            jax.tree_util.tree_map(lambda a: a.reshape(lead + a.shape), columns)
        )
    return layout._replace(slots=tuple(slots))


def lambdarank_grad_hess(
    margins, layout, scheme="pairwise", pair_slots_per_step=PAIR_SLOTS_PER_STEP,
):
    """Per-row (grad, hess) for LambdaMART.

    margins: [n]; layout: a ``GroupLayout`` with its ``slots`` (labels,
    weights and what follows from them; cutoff 0 among ``ideal_dcg`` for
    "ndcg");
    scheme: "pairwise" (delta = 1) | "ndcg" (|delta NDCG|) | "map" (exact
    |delta AP| exchange weights, binary relevance = label > 0).

    Three stages (``telemetry/device.py::STAGES``): ``rank_gather`` (the
    margins from rows to slots: the one gather a bucket), ``rank_pairs``
    (ranks, the O(M^2) pair pass and its sums over slots, per bucket, chunks
    of groups at a time) and ``rank_scatter`` (slots back to rows).
    """
    grads, hesses = [], []
    for index, slots in zip(layout.indices, layout.slots):
        if index.ndim == 3:  # a shard's [1, G, M] slice
            index, slots = jax.tree_util.tree_map(lambda a: a[0], (index, slots))
        with stage(STAGE_RANK_GATHER):
            _valid, S = gather_groups(index, (margins,), (0.0,))
        with stage(STAGE_RANK_PAIRS):
            columns = (S, slots.labels, slots.weights, slots.valid)
            fills = (0.0, -jnp.inf, 0.0, False)
            if scheme == "ndcg":
                columns += (slots.gains, jnp.maximum(slots.ideal_dcg[0], 1e-12))
                fills += (0.0, 1e-12)
            g_mat, h_mat = map_group_chunks(
                partial(_lambdarank_block, scheme=scheme),
                columns,
                pair_slots_per_step,
                fills=fills,
            )
        grads.append(g_mat.reshape(-1))
        hesses.append(h_mat.reshape(-1))
    with stage(STAGE_RANK_SCATTER):
        row_slot = layout.row_slot
        return (
            slots_to_rows(jnp.concatenate(grads), row_slot),
            slots_to_rows(jnp.concatenate(hesses), row_slot),
        )


def _lambdarank_block(S, Y, W, valid, gains=None, max_dcg=None, scheme="pairwise"):
    """(g, h) of every slot of ``[G, M]`` groups: all intra-group pairs.
    ``gains`` ([G, M]) and ``max_dcg`` ([G]) are the layout's, for "ndcg"."""
    s_diff = S[:, :, None] - S[:, None, :]             # [G, M, M]
    rho = 1.0 / (1.0 + jnp.exp(_SIGMA * s_diff))       # P(swap needed | i>j)
    prefer = (Y[:, :, None] > Y[:, None, :]) & valid[:, :, None] & valid[:, None, :]

    if scheme == "ndcg":
        ranks = rank_descending(S, valid)              # by score, 1-based
        discount = dcg_discount(ranks)
        delta = (
            jnp.abs(gains[:, :, None] - gains[:, None, :])
            * jnp.abs(discount[:, :, None] - discount[:, None, :])
            / max_dcg[:, None, None]
        )
    elif scheme == "map":
        delta = map_exchange_delta(S, Y, valid)
    else:
        delta = 1.0

    lam = _SIGMA * rho * delta
    lam = jnp.where(prefer, lam, 0.0)
    hess_pair = _SIGMA * _SIGMA * rho * (1.0 - rho) * delta
    hess_pair = jnp.where(prefer, hess_pair, 0.0)

    # i preferred over j: i pulled up (negative grad), j pushed down
    g_mat = -lam.sum(axis=2) + lam.sum(axis=1)         # [G, M]
    h_mat = hess_pair.sum(axis=2) + hess_pair.sum(axis=1)
    g_mat = g_mat * W
    h_mat = jnp.maximum(h_mat, 1e-16) * W
    return jnp.where(valid, g_mat, 0.0), jnp.where(valid, h_mat, 0.0)
