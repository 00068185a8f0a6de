"""Per-node gradient histograms on TPU.

The hot op of the whole framework: for each tree level, accumulate
(grad, hess) into a (node x feature x bin) tensor. This replaces libxgboost's
OpenMP hist builder + Rabit allreduce (reference hot loop at
algorithm_mode/train.py:367-376 -> C++), followed by an optional
``lax.psum`` over the data-parallel mesh axis, which is the entire
multi-host story (SURVEY.md §2.3 row 1).

Two builders, one a backend (``choose_hist_impl``, as
``ops/tree_build.choose_route_impl`` picks the bin fetch):

* ``pallas`` (the TPU): the one-hot matmul as a Pallas TPU kernel — per-block
  bin one-hots live only in VMEM (never HBM), accumulator resident in VMEM
  across the row-block grid. bf16x2 split-precision operands (hi/lo
  decomposition of f32 grads) keep MXU rate with ~f16-mantissa accuracy,
  accumulated in f32. The gradient operand holds the level's 2W rows
  (``_operand_rows``), and a padding feature gets no dot. Where that operand
  leaves free rows on a latched one-hot tile (W <= 8 at 256 bin lanes), the
  bin axis is folded into it (``_bin_fold``): the kernel latches the one-hot
  of a bin's low part alone, half the tiles, and the bin's high part picks
  which copy of the operand a row's gradients ride. At the narrowest levels
  (W <= 2) the rows that really hold a gradient are so few that two features
  share every latched tile (``_tile_pack``): half the tiles again. The calls
  that do not fold (W >= 16, the class trees') take a dot a 128-lane bin
  tile, and a tile no bin of its column can land on is not built: the tiles
  above a feature's first come off a list made on the chip from the
  columns' cut counts (``_live_tiles``, ``_pallas_hist_tiles_fn``).
  The class trees of a round read one bin matrix, so they share a call and
  its latched tiles: their gradients are rows of one operand
  (``_class_groups``). Interpreted on the CPU backend (tests, rehearsals).
* ``flat`` (everything else, and the tests' reference): one
  ``jax.ops.segment_sum`` over n*d flattened (node, feature, bin) ids. XLA
  lowers it to a sorted scatter-add — correct everywhere, fast on CPU,
  scatter-bound on TPU (0.265 rounds/s against the kernel's 3.1 at 1M x 28:
  builders' probe, one v5e, round 2).

and two node-total lowerings (``choose_totals_impl``): ``onehot`` on the TPU,
``segment`` elsewhere (2.3 against 16.8 ms a call on the chip, same probe).
"""

import collections
import functools
import math
import os

import jax
import jax.numpy as jnp

from ..telemetry.device import STAGE_HIST_ALLREDUCE, stage

# What a training session freezes, host-side, when it builds its round
# closure (models/booster.py), so that every shard and every re-trace sees
# the same values for the session's life. Trace-safety contract (graftlint
# trace-env-read, docs/static-analysis.md): the jitted round path reads no
# env; the builders take this snapshot as ``knobs``.
HistKnobs = collections.namedtuple(
    "HistKnobs",
    [
        "backend",       # jax.default_backend(): what the four choosers read
        "precision",     # GRAFT_HIST_MM_PREC
    ],
)

# operand precision of the Pallas histogram: ``bf16x2`` (hi + lo bf16 pair,
# two MXU passes) is the program; ``bf16`` (one pass) is the failing control
# that proves a benchmark configuration's check_limits (PERF.md section 2)
HIST_PRECISIONS = ("bf16x2", "bf16")


def resolve_hist_knobs():
    """The session's :class:`HistKnobs`, resolved ONCE, host-side.

    Call at session build time (models/booster.py), never from code that
    can run under trace.
    """
    precision = os.environ.get("GRAFT_HIST_MM_PREC", HIST_PRECISIONS[0])
    if precision not in HIST_PRECISIONS:
        raise ValueError(
            "Unknown GRAFT_HIST_MM_PREC=%r; expected %s"
            % (precision, "|".join(HIST_PRECISIONS))
        )
    return HistKnobs(
        backend=jax.default_backend(),
        precision=precision,
    )


def _backend(knobs):
    """The backend the choosers read: the session snapshot's, or the
    process's for direct callers (unit tests, probes) with no snapshot."""
    return knobs.backend if knobs is not None else jax.default_backend()


def choose_hist_impl(backend):
    """The builder ``level_histogram`` takes on ``backend``: the Pallas
    one-hot matmul kernel where scatters serialize (the TPU), the flat
    segment-sum elsewhere."""
    return "pallas" if backend == "tpu" else "flat"


def choose_totals_impl(backend):
    """The lowering ``node_totals`` takes on ``backend``, chosen as
    ``choose_hist_impl`` chooses: no sort on the TPU."""
    return "onehot" if backend == "tpu" else "segment"


# rows a step of the node-totals scan (_totals_onehot), before balancing
TOTALS_CHUNK_ROWS = 65536


def _balanced_chunks(n, chunk_rows):
    """(chunk, steps) for scanning n rows in ~chunk_rows-row chunks.

    Balanced: caps padding waste at steps-1 rows instead of a nearly full
    chunk when n slightly exceeds a multiple of the configured size.
    Requires n >= 1.
    """
    steps_wanted = -(-n // min(chunk_rows, n))
    chunk = -(-n // steps_wanted)
    return chunk, -(-n // chunk)


def apply_hist_collective(G, H, axis_name):
    """Reduce (G, H) level histograms across the data axis: a ``psum``,
    the one collective the level histograms have, once a level (or a
    loss-guided pass). The collective tail of :func:`level_histogram`, and
    what a builder that holds local histograms calls itself. No-op when
    ``axis_name`` is None.
    """
    if axis_name is None:
        return G, H
    with stage(STAGE_HIST_ALLREDUCE):
        return jax.lax.psum(G, axis_name), jax.lax.psum(H, axis_name)


def padded_feature_width(d, axis_size):
    """Features padded up to a multiple of the `feature` mesh axis's size,
    so that every column shard owns an equal contiguous slice. The padded
    columns carry all-zero histograms and zero cut counts, so they can
    never win a split."""
    return -(-d // axis_size) * axis_size


def round_hist_levels(grow_policy, max_depth, max_leaves, subtract, pass_slots=1):
    """``(W, count)`` of the ``level_histogram`` calls one tree build issues:
    W nodes built a call, ``count`` such calls; for a loss-guided build's passes
    (``pass_slots`` nodes each, ops/lossguide.py) AT MOST: one a split step. With
    sibling subtraction a depth-wise level builds its left children alone."""
    if grow_policy == "lossguide":
        levels = [(1, 1)]                                # root
        if max_leaves > 1:
            levels.append((pass_slots, max_leaves - 1))  # a pass: the data's count
        return levels
    return [(1, 1)] + [                                  # level 0, then 1 ..
        (2 ** (level - 1) if subtract else 2**level, 1)
        for level in range(1, max_depth)
    ]


def round_comm_plan(
    grow_policy,
    max_depth,
    max_leaves,
    d,
    num_bins,
    axis_size,
    subtract,
    trees_per_round=1, pass_slots=1,
):
    """Static per-round collective plan for the data axis.

    Returns ``(entries, bytes_per_round)`` where each entry is
    ``{"kind": "hist"|"totals", "shape": local payload shape,
    "count": n, "bytes": wire bytes for all n collectives}``.
    ``bytes_per_round`` feeds the ``hist_comm_bytes_total`` counter; the
    entry list feeds the latency calibration (one timing per distinct
    shape). ``hist`` entries carry the G and H f32 histogram pair; wire
    bytes are the payload times a ring allreduce's 2(p-1)/p (reduce-scatter
    + all-gather; docs/DESIGN.md §Communication). ``d`` is the width each
    data shard histograms: the feature-shard-LOCAL width on a 2-D mesh.
    """
    if axis_size <= 1:
        return [], 0
    ratio = 2.0 * (axis_size - 1) / axis_size
    entries = []
    total_bytes = 0.0
    for W, count in round_hist_levels(grow_policy, max_depth, max_leaves, subtract, pass_slots):
        count *= trees_per_round
        b = 2 * W * d * num_bins * 4 * ratio * count     # G + H, f32
        entries.append(
            {"kind": "hist", "shape": (W, d, num_bins), "count": count,
             "bytes": b}
        )
        total_bytes += b
    if grow_policy != "lossguide":
        W = 2**max_depth                                 # last-level node totals
        b = 2 * W * 4 * ratio * trees_per_round
        entries.append(
            {"kind": "totals", "shape": (W,), "count": trees_per_round, "bytes": b}
        )
        total_bytes += b
    return entries, int(total_bytes)


# most bytes of level histograms a builder keeps alive for sibling subtraction
SUBTRACT_CACHE_MAX_BYTES = 512 * 1024 * 1024


def subtraction_enabled(cache_bytes):
    """Shared gate for sibling-subtraction paths (both growers): the
    histogram cache the caller would have to keep alive fits under
    SUBTRACT_CACHE_MAX_BYTES. Over it, both children are built directly."""
    return cache_bytes <= SUBTRACT_CACHE_MAX_BYTES


def level_histogram(
    bins,
    grad,
    hess,
    node_local,
    num_nodes,
    num_bins,
    axis_name=None,
    knobs=None,
    impl=None,
    class_vmap=False,
    reach=None,
):
    """Build (G, H) histograms for one tree level.

    Args:
      bins: i32 [n, d] bin indices (missing bin included in num_bins).
      grad, hess: f32 [n].
      node_local: i32 [n]; position of the row's node within this level,
        or negative when the row no longer participates.
      num_nodes: static int — number of nodes at this level (2**level).
      num_bins: static int — histogram width per feature (max_bin + 1).
      axis_name: mesh axis to psum over, or None on a single device.
      knobs: the session's :class:`HistKnobs` snapshot; traced production
        code must thread it (trace-safety). None, for direct callers (unit
        tests, probes): the process's backend and ``bf16x2``.
      impl: a builder by name (``flat`` | ``pallas``), for direct callers;
        None chooses from the backend through ``choose_hist_impl``.
      class_vmap: static; True where the caller's build is mapped over a
        round's class trees with ``jax.vmap`` (the class branch of the round
        program): the kernel then takes the trees' gradients as one operand
        (``_class_hist_fn``). A one-tree build leaves it False and traces
        what it always traced.
      reach: i32 [d], traced: the highest bin a row of each column can sit
        in, the missing bin apart (a column's count of cuts; a bundle's
        highest position). The kernel's unfolded calls skip, feature by
        feature, the one-hot tiles above it (``_live_tiles``); None, for
        direct callers, builds every tile. The same bits either way.

    Returns:
      (G, H): f32 [num_nodes, d, num_bins].
    """
    if impl is None:
        impl = choose_hist_impl(_backend(knobs))
    if impl == "pallas":
        prec = knobs.precision if knobs is not None else HIST_PRECISIONS[0]
        if class_vmap:
            G, H = _class_hist_fn(num_nodes, num_bins, prec)(
                bins, grad, hess, node_local, reach
            )
        else:
            G, H = _hist_pallas(
                bins, grad, hess, node_local, num_nodes, num_bins, prec=prec,
                reach=reach,
            )
    elif impl == "flat":
        G, H = _hist_flat(bins, grad, hess, node_local, num_nodes, num_bins)
    else:
        raise ValueError(
            "unknown level_histogram builder: {!r}; expected flat|pallas".format(impl)
        )
    return apply_hist_collective(G, H, axis_name)


def node_totals(grad, hess, node_local, num_nodes, axis_name=None, knobs=None,
                impl=None):
    """Per-node (sum g, sum h) without the full histogram.

    The last tree level only needs leaf weights -> node totals; skipping the
    [W, d, B] histogram there removes the widest (most expensive) level from
    every tree build.

    Two lowerings: ``segment`` uses segment_sum (a sorted scatter-add on TPU
    — sorts all n rows by node id; fast on CPU); ``onehot`` scans row chunks
    and contracts a node one-hot on the MXU, avoiding the sort entirely.
    ``knobs`` and ``impl`` as for :func:`level_histogram`: None chooses from
    the backend through ``choose_totals_impl``.
    """
    if impl is None:
        impl = choose_totals_impl(_backend(knobs))
    if impl == "onehot":
        g_tot, h_tot = _totals_onehot(grad, hess, node_local, num_nodes)
    elif impl != "segment":
        raise ValueError(
            "unknown node_totals lowering: {!r}; expected segment|onehot".format(impl)
        )
    else:
        active = node_local >= 0
        safe = jnp.where(active, node_local, num_nodes)
        g_tot = jax.ops.segment_sum(
            jnp.where(active, grad, 0.0), safe, num_segments=num_nodes + 1
        )[:num_nodes]
        h_tot = jax.ops.segment_sum(
            jnp.where(active, hess, 0.0), safe, num_segments=num_nodes + 1
        )[:num_nodes]
    if axis_name is not None:
        g_tot = jax.lax.psum(g_tot, axis_name)
        h_tot = jax.lax.psum(h_tot, axis_name)
    return g_tot, h_tot


def _totals_onehot(grad, hess, node_local, num_nodes):
    """[2, c] @ node-one-hot[c, W] per row chunk, f32 accumulated — no sort,
    no scatter; the one-hot never leaves registers/VMEM after fusion."""
    n = grad.shape[0]
    W = num_nodes
    if n == 0:
        z = jnp.zeros(W, jnp.float32)
        return z, z
    active = node_local >= 0
    g = jnp.where(active, grad, 0.0)
    h = jnp.where(active, hess, 0.0)
    node = jnp.where(active, node_local, W)  # dead slot -> one-hot 0

    chunk, steps = _balanced_chunks(n, TOTALS_CHUNK_ROWS)
    n_pad = steps * chunk
    if n_pad != n:
        pad = [(0, n_pad - n)]
        g = jnp.pad(g, pad)
        h = jnp.pad(h, pad)
        node = jnp.pad(node, pad, constant_values=W)

    iota_w = jnp.arange(W, dtype=jnp.int32)

    def body(carry, i):
        sl = i * chunk
        node_c = jax.lax.dynamic_slice(node, (sl,), (chunk,))
        g_c = jax.lax.dynamic_slice(g, (sl,), (chunk,))
        h_c = jax.lax.dynamic_slice(h, (sl,), (chunk,))
        oh = (node_c[:, None] == iota_w[None, :]).astype(jnp.float32)  # [c, W]
        gh = jnp.stack([g_c, h_c])  # [2, c]
        # HIGHEST: a TPU runs a default-precision f32 dot as one bf16 pass,
        # which rounds every gradient to 8 mantissa bits before it is summed
        # into a leaf weight; the dot is tiny ([2, c] @ [c, W])
        P = jax.lax.dot_general(
            gh, oh, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        return carry + P, None

    init = jnp.zeros((2, W), jnp.float32)
    if steps == 1:
        GH, _ = body(init, jnp.int32(0))
    else:
        GH, _ = jax.lax.scan(body, init, jnp.arange(steps, dtype=jnp.int32))
    return GH[0], GH[1]


# --------------------------------------------------------------------- flat


def _hist_flat(bins, grad, hess, node_local, num_nodes, num_bins):
    n, d = bins.shape
    active = node_local >= 0
    safe_node = jnp.where(active, node_local, num_nodes)
    seg = (safe_node[:, None] * d + jnp.arange(d, dtype=jnp.int32)[None, :]) * num_bins + bins
    seg = jnp.where(active[:, None], seg, num_nodes * d * num_bins)
    num_segments = num_nodes * d * num_bins + 1

    flat_seg = seg.reshape(-1)
    # two 1-D passes: the fused [n*d, 2] segment_sum variant compiles
    # pathologically on the TPU toolchain (multi-minute hang), so G and H go
    # through separate scatter-adds
    g_flat = jnp.broadcast_to(grad[:, None], (n, d)).reshape(-1)
    h_flat = jnp.broadcast_to(hess[:, None], (n, d)).reshape(-1)
    G = jax.ops.segment_sum(g_flat, flat_seg, num_segments=num_segments)
    H = jax.ops.segment_sum(h_flat, flat_seg, num_segments=num_segments)
    G = G[:-1].reshape(num_nodes, d, num_bins)
    H = H[:-1].reshape(num_nodes, d, num_bins)
    return G, H


# ------------------------------------------------------------------- pallas


def _split_bf16(x):
    """f32 -> (hi, lo) bf16 pair with hi + lo ~= x to ~16 mantissa bits."""
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def _mxu_split_missing(B):
    """When B = k*128 + 1 (the usual max_bin=256 -> 257 with the missing bin
    last), the one-hot dot's N dimension pads to the next lane multiple
    (257 -> 384 on the MXU, +50% wasted FLOPs). Splitting the missing column
    out — one [2W, d] dot over the (bins == B-1) mask — keeps the per-feature
    dots at an exact lane multiple."""
    return B > 128 and (B - 1) % 128 == 0


def _bin_lanes(B):
    """Lanes of the kernel's bin axis (Bp): the bins the main dots hold,
    without the missing bin where it is split out, padded to whole tiles."""
    return _round_up(B - 1 if _mxu_split_missing(B) else B, 128)


# rows a grid step of the Pallas histogram (on the lane axis: whole 128-lane
# tiles)
PALLAS_ROW_BLOCK = 512

# most partial sums (outer chunks of the row axis, each with an output slab of
# its own, added after the kernel) a level's histogram is accumulated in. One
# f32 cell a (node, feature, bin) over every row block of 8.8M rows is a chain
# of 17,188 adds, and its error grows with its length and with the sum it
# carries. At the root of higgs-d8's matrix, cell error over the root-sum-square
# of the cell's hessians, median / p90 (.chipwork probe, one v5e, PR 31): one
# chain 2.5e-4 / 5.6e-4, 32 chunks 1.2e-5 / 3.0e-5; W = 8: 3.5e-5 / 8.6e-5
# against 8.9e-6 / 2.2e-5 with 4. The chunks cost the MXU nothing (44.55
# against 44.45 ms a call), so the levels whose nodes hold the most rows get
# the most (_row_chunks).
HIST_ROW_CHUNKS = 32


def _operand_rows(W, trees=1):
    """Rows of the kernel's gradient operand for a level of W nodes: g of
    node w in row w, h in row W + w, padded ONCE to the bf16 operand tile (16
    sublanes). The operand is the side the MXU streams against each latched
    [128, 128] tile of a bin one-hot, and a level issues the rows it holds.
    With ``trees`` class trees in one call (``_class_groups``) tree t's 2W
    rows follow tree t - 1's, and the ``2 * W * trees`` rows are padded once.

    ms a call by the rows streamed against one latched tile (hi and lo halves
    of the bf16x2 operand together), W = 1, padding features dotted
    (scripts/dissect.py --hist-levels re-reads it; one v5e, jax 0.9.0, PR 31):

        rows a latch             16     32     64    128    256
        8.8M x 28, one dot      50.2   50.4   51.0   97.8  192.2  (stacked)
        8.8M x 28, two dots     99.5   97.9   98.8  192.4         (hi | lo)
        2.27M x 136, one dot    65.1   65.3   66.0  126.6  248.6

    Linear down to 64 rows a latch, flat below: a latch costs what 64 rows
    cost (about 67 cycles), whatever rides it. Hence ONE dot with the halves
    stacked on the row axis (two dots are two latches: W <= 16 would cost
    what W = 32 does), and no operand narrower than the bf16 tile: under 64
    rows nothing more is to be had from this side of the dot, so the rows a
    latch still carries free take a part of the bin axis (``_bin_fold``)."""
    return _round_up(2 * W * trees, 16)


# rows of the streamed operand one latched one-hot tile carries at the
# latch's own cost (_operand_rows' table: flat to 64 rows, linear from there)
LATCH_FREE_ROWS = 64


def _bin_fold(rows, bin_lanes, prec):
    """Bin tiles folded into the streamed operand: the largest power of two
    that cuts ``bin_lanes`` (the padded bin axis, Bp) into whole 128-lane
    tiles and keeps ``fold`` copies of the stacked operand (``rows``, twice
    for bf16x2) within LATCH_FREE_ROWS. From shapes alone, as ``rows`` and
    the row chunks are; 1 is the unfolded kernel.

    A bin is ``L * top + low`` with ``L = bin_lanes / fold``. The kernel
    latches the one-hot of ``low`` alone ([L, blk]: 1/fold of the tiles) and
    streams ``fold`` copies of the operand against it, copy t masked to the
    rows whose ``top`` is t; copy t's product lands on lanes [t * L,
    (t + 1) * L) of the same accumulator slab. The same products reach the
    same cells in the same position of the same contraction, beside zeros.

    ms a call (scripts/dissect.py --hist-levels re-reads it; 257 bins in u16:
    256 bin lanes and the missing bin's own dot; one v5e, jax 0.9.0, PR 35),
    by the level's node count, the shipped fold beside ``fold`` 1:

        W (operand rows)      1 (16)  2 (16)  4 (16)  8 (16)  16 (32)  32 (64)  64 (128)
        8.8M x 28, fold 1      45.5    45.4    45.4    45.5    46.2     86.5    169.2
        8.8M x 28, fold 2      25.5    25.7    25.6    25.7
        2.27M x 136, fold 1    61.7    61.4    61.6    61.3    63.0    114.7    219.0
        2.27M x 136, fold 2    37.2    37.3    37.2    37.3

    Since PR 49 the ``fold`` 1 rows are ``_pallas_hist_tiles_fn``'s (a dot a
    bin tile and row block, four row blocks a grid step, the tiles above a
    feature's first off a list of the live ones). ``scripts/dissect.py
    --hist-levels`` and ``--narrow-every 3`` (every third column at 127
    cuts, one bin tile; 8 of 56 and 32 of 272 tiles left out) re-read it,
    one v5e, jax 0.9.0, PR 49; the body it replaced beside it from a scratch
    script of the same calls, the same bits:

        W (operand rows)                16 (32)  32 (64)  64 (128)
        8.8M x 28, as it was             46.0     86.8    169.3
        8.8M x 28, all at 255 cuts       48.6     89.6    172.9
        8.8M x 28, every third at 127    41.5     76.7    148.1
        2.27M x 136, as it was           63.2    114.5    218.9
        2.27M x 136, all at 255 cuts     62.0    113.5    217.9
        2.27M x 136, every third         54.4     99.9    192.1

    and the one-pass control at W = 1 (its operand is one half: 16 streamed
    rows a copy): 44.8 -> 24.4 and 60.7 -> 35.5. Half the tiles at 64
    streamed rows cost 56 to 61 % of all of them at 32 (3.86M -> 1.93M tiles
    a call at 8.8M x 28): the floor was the latch, and the masked copies (two
    selects on a [32, blk] bf16 operand a feature) cost nothing that shows,
    whether selected as bf16, as 32-bit words or not at all (25.6 / 25.5 /
    25.4). Past LATCH_FREE_ROWS a fold buys nothing: W = 16 at 64 rows
    against all tiles (46.2) is what 128 rows against half would stream."""
    stacked = rows * (2 if prec == "bf16x2" else 1)
    tiles = bin_lanes // 128
    fold = 1
    while tiles % (2 * fold) == 0 and 2 * fold * stacked <= LATCH_FREE_ROWS:
        fold *= 2
    return fold


# least rows of a packed tile's slot: g and h of up to two nodes, so that the
# W = 1 and W = 2 calls of a tree are one shape of the packed body
PACK_SLOT_ROWS = 4


def _slot_rows(W, bin_lanes, pack):
    """Rows of a slot of the packed body's operand: the 2W of the level that
    hold a gradient, in a power of two (g of node w in row w, h in row W +
    w), not padded to the bf16 tile as ``_operand_rows`` pads the unpacked
    operand; and no fewer than make a feature's ``fold`` = bin_lanes / (128 /
    pack) slots whole bf16 tiles of 16 rows, which the body masks as 32-bit
    words."""
    fold = bin_lanes * pack // 128
    return max(PACK_SLOT_ROWS, 1 << (2 * W - 1).bit_length(), 16 // fold)


def _tile_pack(W, bin_lanes, prec):
    """Features that share one latched one-hot tile: the largest power of two
    ``pack`` whose ``fold`` = bin_lanes / (128 / pack) masked copies of every
    one of the ``pack`` features' stacked REAL rows (``_slot_rows``, twice
    for bf16x2) stay within LATCH_FREE_ROWS; 1 is the body ``_bin_fold``
    rules. From shapes alone. With 256 bin lanes and bf16x2 that is 2 at
    W <= 2 (4 copies x 2 features x 8 rows = 64); the one-pass control
    streams half the rows and packs W = 4 too.

    ``_bin_fold`` stops where ``fold`` x the stacked operand reaches 64 rows,
    but ``_operand_rows`` has padded 2W to the 16 sublanes of a bf16 tile
    first: at W = 1 only 8 of those 64 rows hold a gradient, at W = 2 only
    16. The packed body builds its operand from the real rows, so the bin
    axis folds down to L = 128 / pack lanes a feature, and a latched tile
    holds the one-hots of ``pack`` features side by side: half the tiles
    again (``_pallas_hist_packed_fn``).

    ms a call (scripts/dissect.py --hist-levels re-reads it; 257 bins in u16:
    256 bin lanes and the missing bin's own dot; one v5e, jax 0.9.0, PR 47),
    by the level's node count, the shipped pack beside ``pack`` 1 at the
    shipped fold (``_bin_fold``'s second rows, read again):

        W (slot rows)                  1 (4)   2 (4)   4 (16)  8 (16)
        8.8M x 28, pack 1, fold 2      25.7    25.7    25.6    25.5
        8.8M x 28, pack 2, fold 4      15.0    15.1
        2.27M x 136, pack 1, fold 2    37.5    37.3    37.3    37.3
        2.27M x 136, pack 2, fold 4    23.9    23.9

    and the one-pass control at W = 1 (32 streamed rows a tile): 24.5 -> 14.2
    and 35.7 -> 22.6; inside `higgs-d8`'s round a call went 24.2 -> 13.85 ms
    (PERF.md section 5). Half the tiles again (1.93M -> 0.96M a call at 8.8M x
    28) cost 58 to 64 % of the folded call, as the fold's halving cost 56 to
    61 % of the unfolded one: 62 ns a row block and tile of two features,
    where the folded body takes 53 a feature. Not so with the folded body's
    way of building its operands: selects over
    the whole [64, blk] bf16 operand and a [128, blk] compare converted to
    bf16 ran 21.5 / 32.0 ms a call (0.85 of the folded call: at a quarter of
    the tiles the VPU's work a tile binds, not the latch), the operand
    masked as 32-bit words 17.6 / 26.6, the one-hot built as words too 15.0 /
    23.9 (``_pallas_hist_packed_fn``). What a grid step costs whatever it
    latches (``_bin_fold``'s 2.4 to 5.7 ms a call) is still there."""
    halves = 2 if prec == "bf16x2" else 1
    pack = 1
    while (bin_lanes // 128) * (2 * pack) ** 2 * halves * _slot_rows(
        W, bin_lanes, 2 * pack
    ) <= LATCH_FREE_ROWS:
        pack *= 2
    return pack


# most rows of the streamed operand one call carries for the class trees of
# a round: ten classes at W = 8 (160 rows, the widest call the probe beside
# ``_class_groups`` ran in one piece). From 64 streamed rows a call costs
# what its rows cost, so wider levels lose nothing by going in groups
CLASS_OPERAND_MAX_ROWS = 160


def _class_groups(W, trees):
    """``(size, groups)``: how the ``trees`` class trees of a round ride a
    level of W nodes. A latched one-hot tile of a row tile, feature and bin
    tile is the same for every tree that reads the one bin matrix; only the
    streamed gradient operand differs. So ``size`` trees share one operand
    (``_operand_rows(W, size)`` rows, within CLASS_OPERAND_MAX_ROWS) and one
    latch of every tile, and the level takes ``groups`` such operands, one a
    step of the kernel's outermost grid axis (equal sizes: the last group is
    filled up with trees of dead rows). From shapes alone.

    ms a call of ten class trees (scripts/dissect.py --hist-levels --trees
    10 re-reads it; 506,250 x 784, 257 bins in u16, bf16x2: 6,221,824 tiles a
    group; one v5e, jax 0.9.0, PR 41; PR 40's probe read the same to 0.5
    ms), beside ten times the one-tree call (45.2 ms folded: what the class
    axis on the kernel's grid cost):

        W                       1      2      4      8     16     32     64
        operand rows           32     48     80    160    160    128    128
        class groups            1      1      1      1      2      5     10
        ms a call            83.0  117.3  186.9  359.9  702.8  1,397  2,787
        ten one-tree calls    452    452    452    452    798  1,460  2,798
        MXU share of peak    0.80   0.85   0.89   0.92   0.94   0.95   0.95

    Ten trees at W = 1 are 64 stacked rows, what a latch carries free
    (LATCH_FREE_ROWS), so nothing is left to fold; from W = 2 the call is
    bound by the rows it streams, and by W = 64 (a group a tree) nothing is
    shared and nothing lost."""
    size = min(trees, max(1, CLASS_OPERAND_MAX_ROWS // (2 * W)))
    groups = -(-trees // size)
    return -(-trees // groups), groups


def round_onehot_tiles(levels, n, d, num_bins, prec, trees_per_round=1,
                       class_trees=1, dead_tiles=0):
    """``(latched, unfolded)``: the [128, 128] one-hot tiles the Pallas kernel
    latches a round over ``n`` rows x ``d`` features (a shard's), summed over
    ``levels`` (``round_hist_levels``): row tiles x features x bin tiles
    after the fold, or row tiles x tiles of ``pack`` features where a
    one-tree level packs (``_tile_pack``; a tile with one real feature is a
    whole tile), and the same with ``fold`` and ``pack`` 1. Of the round's
    ``trees_per_round`` trees, ``class_trees`` at a time share their latches
    (the class trees of one bagged step: ``_class_groups``), so a level
    latches once a class group, not once a tree; ``unfolded`` counts every
    tree. ``dead_tiles``: the bin tiles, summed over the ``d`` features, that
    a call which neither folds nor packs does not build because no bin of
    their column can land on them (``dead_bin_tiles``). From shapes and the
    columns' cut counts alone: what the fold, the pack, the class operand
    and the live-tile rule engage on, stated before a round runs."""
    latched, unfolded, _skipped, _plain = _round_tiles(
        levels, n, d, num_bins, prec, trees_per_round, class_trees, dead_tiles
    )
    return latched, unfolded


def skipped_tiles_pct(levels, n, d, num_bins, prec, trees_per_round=1,
                      class_trees=1, dead_tiles=0):
    """Share (%) of the one-hot tiles of a round's calls that neither fold
    nor pack which the live-tile rule leaves unbuilt (``round_onehot_tiles``'s
    arguments); 0 where every call folds."""
    _latched, _unfolded, skipped, plain = _round_tiles(
        levels, n, d, num_bins, prec, trees_per_round, class_trees, dead_tiles
    )
    return 100.0 * skipped / plain if plain else 0.0


def _round_tiles(levels, n, d, num_bins, prec, trees_per_round, class_trees,
                 dead_tiles):
    """``(latched, unfolded, skipped, plain)``: ``round_onehot_tiles``'s two
    counts, the tiles the live-tile rule skips, and all the tiles of the
    calls it reads (``fold`` and ``pack`` 1)."""
    block = PALLAS_ROW_BLOCK
    row_tiles = _round_up(n, block * _chunk_cap(-(-n // block))) // 128
    lanes = _bin_lanes(num_bins)
    latched = unfolded = skipped = plain = 0
    for W, count in levels:
        tiles = count * row_tiles * d * (lanes // 128)
        unfolded += tiles * trees_per_round
        size, groups = _class_groups(W, class_trees)       # (1, 1) for one tree
        calls = trees_per_round // class_trees * groups
        pack = _tile_pack(W, lanes, prec) if class_trees == 1 else 1
        fold = _bin_fold(_operand_rows(W, size), lanes, prec)
        if pack > 1:
            latched += count * row_tiles * -(-d // pack) * calls
        elif fold > 1:
            latched += tiles * calls // fold
        else:
            dead = count * row_tiles * dead_tiles * calls
            latched += tiles * calls - dead
            skipped += dead
            plain += tiles * calls
    return latched, unfolded, skipped, plain


def dead_bin_tiles(reach, num_bins, bins_dtype):
    """Bin tiles the unfolded kernel does not build over the columns of
    ``reach`` (host-side: a numpy int array, a column's count of cuts or a
    bundle's highest position; bins stored as ``bins_dtype``, which sets the
    kernel's feature groups), summed: ``round_onehot_tiles``'s
    ``dead_tiles``. Of a group's tiles t > 0 the kernel builds the live ones
    in whole blocks of LIVE_CHUNK_SLOTS (``_pallas_hist_tiles_fn``); the
    last block's fill may be a padding feature's, which counts against."""
    import numpy as np

    reached = _tile_reached(np.asarray(reach), num_bins, np)[:, 1:]
    fg = _pallas_feature_group(len(reached), bins_dtype)
    dead = 0
    for first in range(0, len(reached), fg):
        live = reached[first:first + fg].sum(axis=0)               # a tile
        built = np.minimum(fg, -(-live // LIVE_CHUNK_SLOTS) * LIVE_CHUNK_SLOTS)
        dead += int((len(reached[first:first + fg]) - built).sum())
    return dead


def _tile_reached(reach, B, xp):
    """bool [d, tiles]: a bin of column f can land on the 128-lane bin tile t
    of the unfolded kernel's one-hot: ``128 * t <= reach[f]`` (a column of k
    cuts holds bins 0 .. k), or the missing bin lives on it (where it is not
    split out, ``_mxu_split_missing``). ``xp``: numpy on the host, jax.numpy
    under trace."""
    t = xp.arange(_bin_lanes(B) // 128, dtype=xp.int32)
    on = 128 * t[None, :] <= reach[:, None].astype(xp.int32)
    if not _mxu_split_missing(B):
        on = on | (t == (B - 1) // 128)[None, :]
    return on


def _floor_pow2(x):
    return 1 << (max(1, x).bit_length() - 1)


def _chunk_cap(steps):
    """Most row chunks a matrix of ``steps`` row blocks is cut into: a power
    of two up to HIST_ROW_CHUNKS, and small enough that padding the rows to
    whole chunks adds under a thirty-second of them. Every level of a tree
    pads to this one multiple, so its transposed operands are one array."""
    return _floor_pow2(min(HIST_ROW_CHUNKS, steps // 32))


def _row_chunks(W, cap):
    """Partial sums of a level of W nodes: ``cap`` over W, a power of two, so
    that chunks x nodes, the accumulator cells a feature's bin is spread
    over, stays what the root level has."""
    return _floor_pow2(cap // W)


def pallas_interpret():
    """Pallas kernels are interpreted on the CPU backend only (tests, CPU
    rehearsals). Any accelerator, whatever its platform is called, compiles
    them or fails loudly — never a silent interpreter run on a device."""
    return jax.default_backend() == "cpu"


def _round_up(x, m):
    return -(-x // m) * m


# most feature rows per grid step of the pallas histogram: one packed
# sublane tile of the narrowest bin dtype (u8 tiles are 32 x 128), so a
# block of transposed bins is tile-aligned for u8, u16 and i32 alike
_PALLAS_FEATURE_GROUP = 32


def _pallas_feature_group(d, bins_dtype):
    """Feature rows per grid step: _PALLAS_FEATURE_GROUP, or for a narrower
    matrix its width rounded up to whole sublane tiles of the bin dtype
    (32 rows of u8, 16 of u16) and of the bf16 missing-bin operand (16) —
    narrow data is not padded to 32 features of MXU work."""
    tile = max(16, 32 // jnp.dtype(bins_dtype).itemsize)
    return min(_PALLAS_FEATURE_GROUP, _round_up(d, tile))


def _level_rows(node, gh_ref, W, row):
    """A block of the one-tree kernels' f32 operand: g of the row's node
    where ``row`` (i32 [rows, blk]) is the node's id (``node`` i32 [1, blk]),
    h where it is W + that, else 0. A dead row (node >= W) must stay out of
    BOTH halves, not land in h's first rows."""
    dead = node >= W
    g_row = jnp.where(dead, -1, node)
    h_row = jnp.where(dead, -1, node + W)
    return jnp.where(
        row == g_row, gh_ref[0:1, :],
        jnp.where(row == h_row, gh_ref[1:2, :], 0.0),
    )


@functools.lru_cache(maxsize=None)
def _pallas_hist_fn(n, d, fg, W, B, block, prec, interpret, split_missing,
                    rows, chunks, fold=1, class_groups=None):
    """Compiled pallas histogram over ROW-ON-LANES operands: (bins int
    [d_pad, n] — any integer storage dtype, widened per block in VMEM so
    u8/u16 bins move fewer HBM bytes — gh f32 [2, n], node i32 [1, n]) ->
    (main f32 [chunks, d_pad, rows, Bp], miss f32 [chunks, d_pad, rows or
    2*rows]), d_pad = d rounded up to whole groups of fg. Of the ``rows``
    (>= 2W, see _operand_rows; the probe scripts/dissect.py --hist-levels
    passes more) row w holds g of node w and row W + w its h; ``chunks``
    outer slices of the row axis accumulate into slabs of their own (see
    HIST_ROW_CHUNKS; the caller adds them), n = chunks * whole blocks.

    bf16x2: the hi and lo halves of the operand ride ONE dot, stacked on the
    row axis ([2*rows, blk] against each latched one-hot tile), and the two
    halves of the product are added; the missing-bin product keeps both
    halves on its lane axis for the caller to add.

    ``fold`` > 1 (see _bin_fold; 1 is the unfolded kernel, which
    ``_pallas_hist_tiles_fn`` builds and this function hands out): the latched
    one-hot is that of ``bin % L``, L = Bp / fold lanes, and the streamed
    operand is ``fold`` copies of the stacked one, copy t zeroed (a select on
    the block's bf16 operand) in the rows whose ``bin // L`` is not t; copy
    t's rows of the product are added into lanes [t * L, (t + 1) * L) of the
    feature's slab. The missing bin (B - 1 = fold * L where it is split out)
    matches no copy, as it matches no lane of the unfolded one-hot. Output
    shapes and the kernel's name do not depend on ``fold``.

    ``class_groups`` = (size, groups) (see _class_groups; None is the
    one-tree kernel, traced as it always was): the class trees of a round
    over the one bin matrix. gh is f32 [groups, 2 * size, n] (a group's g
    rows, then its h rows), node i32 [groups, size, n] (the trees route
    their rows apart), and tree t of a group holds rows [2W * t, 2W * (t +
    1)) of the operand, g then h. One latched one-hot tile then serves the
    ``size`` trees of a group; the groups are the outermost grid axis and
    the outputs' leading one: (main f32 [groups, chunks, d_pad, rows, Bp],
    miss f32 [groups, chunks, d_pad, rows or 2*rows]). The fold, the chunks,
    the missing bin and the padding features as for one tree.

    Every operand keeps rows on the lane axis, so the kernel has no
    lane-sparse [block, 1] blocks, no in-kernel transposes and no strided
    stores: the node and bin one-hots are sublane-broadcast compares, both
    dots contract the lane axis of both operands (the A @ B^T form the MXU
    takes natively), and each feature's [rows, Bp] product lands on a whole
    tile-aligned slab of the accumulator. Bp is the bin axis padded to a
    lane multiple; split_missing (see _mxu_split_missing) moves the missing
    bin out of it into the second output. Grid = (feature groups of fg
    rows, row chunks, row blocks of a chunk): the accumulator block for one
    feature group stays resident in VMEM across the row blocks, so VMEM use
    is bounded by the group size, not by the matrix width. The operand block
    is a whole (fg, block) tile whatever d is, but a padding feature (only
    the last group has any) gets neither a one-hot nor a dot."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if fold == 1:
        return _pallas_hist_tiles_fn(
            n, d, fg, W, B, block, prec, interpret, split_missing, rows, chunks,
            class_groups,
        )
    Bp = _round_up(B - 1 if split_missing else B, 128)
    d_pad = _round_up(d, fg)
    groups = d_pad // fg
    real_in_last = d - (groups - 1) * fg   # features of the last group
    per = n // (block * chunks)            # row blocks a chunk
    stacked = prec == "bf16x2"
    miss_rows = 2 * rows if stacked else rows
    L = Bp // fold                         # lanes of the latched one-hot
    if L * fold != Bp or L % 128:
        raise ValueError("fold {} does not cut {} bin lanes into whole tiles".format(fold, Bp))

    # the class groups lead the grid, the gh, node and output blocks (one
    # group a step) and the outputs' shapes; one tree has no such axis, and
    # nothing is traced for it
    size, tree_groups = class_groups or (1, None)
    lead = () if class_groups is None else (0,)
    axis0 = len(lead)

    def kernel(bins_ref, gh_ref, node_ref, out_ref, miss_ref):
        @pl.when(pl.program_id(axis0 + 2) == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)
            miss_ref[...] = jnp.zeros_like(miss_ref)

        if class_groups is None:
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0)
            A = _level_rows(node_ref[...], gh_ref, W, row)     # [rows, blk]
        else:
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0)
            A = jnp.zeros((rows, block), jnp.float32)
            for t in range(size):                      # tree t: rows 2W * t ..
                node = node_ref[0, t:t + 1, :]         # [1, blk]
                dead = node >= W
                g_row = jnp.where(dead, -1, node + 2 * W * t)
                h_row = jnp.where(dead, -1, node + 2 * W * t + W)
                A = jnp.where(
                    row == g_row, gh_ref[0, t:t + 1, :],
                    jnp.where(row == h_row, gh_ref[0, size + t:size + t + 1, :], A),
                )
        if stacked:
            A = jnp.concatenate(_split_bf16(A), axis=0)    # [2*rows, blk]
        else:  # "bf16": one rounded half, the failing control
            A = A.astype(jnp.bfloat16)
        lanes = (((1,), (1,)), ((), ()))               # contract rows

        bw = bins_ref[...].astype(jnp.int32)           # widen in VMEM
        iota_b = jax.lax.broadcasted_iota(jnp.int32, (L, block), 0)
        S = A.shape[0]                                 # streamed rows a copy

        def feature(f):
            b = bw[f:f + 1, :]                         # [1, blk]
            # bin = L * top + low: the one-hot of ``low`` is latched,
            # ``top`` picks the copy of the operand a row's g and h ride
            top = sum((b >= t * L).astype(jnp.int32) for t in range(1, fold))
            low = b - L * top
            zero = jnp.zeros_like(A)
            Af = jnp.concatenate(
                [jnp.where(top == t, A, zero) for t in range(fold)], axis=0
            )                                          # [fold * S, blk]
            ob = (iota_b == low).astype(jnp.bfloat16)  # [L, blk]
            P = jax.lax.dot_general(
                Af, ob, lanes, preferred_element_type=jnp.float32
            )
            for t in range(fold):
                Pt = P[t * S:(t + 1) * S]
                out_ref[lead + (0, f, slice(None), slice(t * L, (t + 1) * L))] += (
                    (Pt[:rows] + Pt[rows:]) if stacked else Pt
                )

        for f in range(real_in_last):                  # real in every group
            feature(f)
        if real_in_last < fg and groups > 1:
            @pl.when(pl.program_id(axis0) < groups - 1)
            def _():
                for f in range(real_in_last, fg):
                    feature(f)

        if split_missing:
            miss = (bw == (B - 1)).astype(jnp.bfloat16)    # [fg, blk]
            miss_ref[lead + (0,)] += jax.lax.dot_general(
                miss, A, lanes, preferred_element_type=jnp.float32
            )

    # accumulator blocks (main + the lane-padded missing-bin block) are
    # double-buffered by the pipeline; operand blocks and the per-feature
    # one-hot temporaries are small next to them
    acc_bytes = fg * rows * (Bp + 128) * 4
    vmem_limit = min(2 * acc_bytes + 16 * 1024 * 1024, 100 * 1024 * 1024)
    q, qs = (1,) * axis0, (tree_groups,) * axis0

    def behind_group(index):
        return lambda *ids: ids[:-3] + index(*ids[-3:])

    return pl.pallas_call(
        kernel,
        grid=qs + (groups, chunks, per),
        in_specs=[
            pl.BlockSpec((fg, block), lambda *ids: (ids[-3], ids[-2] * per + ids[-1])),
            pl.BlockSpec(q + (2 * size, block), behind_group(lambda j, c, i: (0, c * per + i))),
            pl.BlockSpec(q + (size, block), behind_group(lambda j, c, i: (0, c * per + i))),
        ],
        out_specs=[
            pl.BlockSpec(q + (1, fg, rows, Bp), behind_group(lambda j, c, i: (c, j, 0, 0))),
            pl.BlockSpec(q + (1, fg, miss_rows), behind_group(lambda j, c, i: (c, j, 0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qs + (chunks, d_pad, rows, Bp), jnp.float32),
            jax.ShapeDtypeStruct(qs + (chunks, d_pad, miss_rows), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(q) + 2) + ("arbitrary",),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
        name="graft_level_histogram",
    )


# second-tile slots of one conditional block of the unfolded kernel, and the
# 512-row blocks one grid step of it covers (``_pallas_hist_tiles_fn``)
LIVE_CHUNK_SLOTS = 4
TILE_STEP_BLOCKS = 4


def _pallas_hist_tiles_fn(n, d, fg, W, B, block, prec, interpret, split_missing,
                          rows, chunks, class_groups):
    """``_pallas_hist_fn`` at ``fold`` 1, the kernel of the calls that do not
    fold (one tree at W >= 16, the class trees'): same operands and one more,
    ``live`` i32 [feature groups, lists] whole in scalar memory
    (``_live_tiles``), same results, same name.

    A feature's dot over the Bp bin lanes is one dot a 128-lane bin tile.
    Tile 0 of every feature is straight-line code, as the whole dot was.
    The tiles t > 0 are taken from the group's list of the features whose
    column can reach tile t (live ones first), LIVE_CHUNK_SLOTS list entries
    a conditional block, a block run only where its first entry is live: so
    a tile no bin of its column can land on is not built, but for the up to
    LIVE_CHUNK_SLOTS - 1 that fill the last block of a list (their one-hot
    is all zeros: the column holds no bin there). A tile that is skipped
    leaves its lanes of the slab at the zeros the first grid step wrote,
    which is what its dot against an all-zero one-hot added: the same bits.

    Why blocks and not a branch a feature: a conditional block is scheduled
    alone, so the latency of its first latch and its last accumulate is not
    hidden behind another feature's work; one v5e, ms a call at 8.8M x 28,
    all 28 columns at 255 cuts, W = 16 / 32 / 64 (PR 49's probe; one
    256-lane dot a feature: 46.2 / 86.8 / 169.5, two 128-lane dots the
    same): a branch round every second tile 112.9 / 153.9 / 236.9 (138 ns a
    taken branch and row block, whatever W; 17 ns one not taken), a branch
    between every two tiles 175.4 / 216.7 / 299.9. So a block holds several
    tiles, and a grid step TILE_STEP_BLOCKS row blocks of 512 (each a dot of
    its own, accumulated in row order: the same bits), where the rows divide
    so.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Bp = _round_up(B - 1 if split_missing else B, 128)
    tiles = Bp // 128
    d_pad = _round_up(d, fg)
    groups = d_pad // fg
    real_in_last = d - (groups - 1) * fg   # features of the last group
    blocks = n // (block * chunks)         # 512-row blocks a chunk
    sub = math.gcd(blocks, TILE_STEP_BLOCKS)
    per = blocks // sub                    # grid steps a chunk
    step = sub * block                     # rows a grid step
    stacked = prec == "bf16x2"
    miss_rows = 2 * rows if stacked else rows
    U = LIVE_CHUNK_SLOTS

    size, tree_groups = class_groups or (1, None)
    lead = () if class_groups is None else (0,)
    axis0 = len(lead)
    lanes = (((1,), (1,)), ((), ()))                   # contract rows

    # The kernel's two pieces of arithmetic as jitted helpers: each is traced
    # once and bound a row block, or a tile and a feature; the body itself is
    # loads, stores and those calls, so what a job waits for in front of its
    # first dispatch is little more than their lowering

    @jax.jit
    def operand_of(node, gh):
        """The streamed operand of a row block ([S, blk] bf16) from its node
        ids (i32 [size, blk]) and gradients (f32 [2 * size, blk])."""
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0)
        A = jnp.zeros((rows, block), jnp.float32)
        for t in range(size):                      # tree t: rows 2W * t ..
            at = node[t:t + 1]                     # [1, blk]
            dead = at >= W                         # out of BOTH halves
            g_row = jnp.where(dead, -1, at + 2 * W * t)
            h_row = jnp.where(dead, -1, at + 2 * W * t + W)
            A = jnp.where(
                row == g_row, gh[t:t + 1], jnp.where(row == h_row, gh[size + t:size + t + 1], A)
            )
        if stacked:
            return jnp.concatenate(_split_bf16(A), axis=0)     # [2*rows, blk]
        return A.astype(jnp.bfloat16)  # "bf16": one rounded half, the failing control

    cut = [slice(k * block, (k + 1) * block) for k in range(sub)]  # a step's row blocks

    @functools.partial(jax.jit, static_argnums=3)
    def tile_of(slab, operand, b, t):
        """A feature's slab lanes of bin tile t ([1, rows, 128]) with a grid
        step's rows added: a dot a row block, the operand ([S, blk]) against
        the one-hot of tile t of the feature's bins (b i32 [1, step]), the
        halves of bf16x2 added, the row blocks' products in row order."""
        lane_bin = jax.lax.broadcasted_iota(jnp.int32, (128, block), 0)
        one = jnp.ones((128, block), jnp.float32)
        zero = jnp.zeros((128, block), jnp.float32)
        for k in range(sub):
            at = jax.lax.slice_in_dim(b, cut[k].start, cut[k].stop, axis=1) - 128 * t
            # (a 32-bit select, then f32 -> bf16: Mosaic lowers bool -> bf16
            # through a helper traced at every use, a third of this body's
            # lowering time, into bool -> i32 -> f32 -> bf16)
            ob = jax.lax.select(
                jax.lax.eq(lane_bin, jax.lax.broadcast_in_dim(at, (128, block), (0, 1))),
                one, zero,
            ).astype(jnp.bfloat16)                     # [128, blk]
            P = jax.lax.dot_general(operand[k], ob, lanes, preferred_element_type=jnp.float32)
            if stacked:
                P = jax.lax.slice_in_dim(P, 0, rows) + jax.lax.slice_in_dim(P, rows, 2 * rows)
            slab = slab + P[None]
        return slab

    def kernel(bins_ref, gh_ref, node_ref, live_ref, out_ref, miss_ref, bw_ref):
        @pl.when(pl.program_id(axis0 + 2) == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)
            miss_ref[...] = jnp.zeros_like(miss_ref)

        group = pl.program_id(axis0)
        bw = bins_ref[...].astype(jnp.int32)           # widen in VMEM
        if tiles > 1:
            bw_ref[...] = bw                           # rows read by a traced index
        operand = tuple(
            operand_of(node_ref[lead + (slice(None), at)], gh_ref[lead + (slice(None), at)])
            for at in cut
        )

        def tile(f, t, b):
            # bin tile t of feature f (an int, or a traced index off a list)
            at = lead + (0, pl.ds(f, 1), slice(None), slice(128 * t, 128 * (t + 1)))
            out_ref[at] = tile_of(out_ref[at], operand, b, t)

        def first_tiles(features):
            for f in features:
                tile(f, 0, jax.lax.slice_in_dim(bw, f, f + 1, axis=0))

        first_tiles(range(real_in_last))               # real in every group
        if real_in_last < fg and groups > 1:
            @pl.when(group < groups - 1)
            def _():
                first_tiles(range(real_in_last, fg))

        # the tiles above: LIVE_CHUNK_SLOTS entries of the group's list a
        # block (a padding feature is on no list's live part)
        for t in range(1, tiles):
            base = (t - 1) * (fg + 1)
            count = live_ref[group, base + fg]
            for c in range(fg // U):
                @pl.when(count > c * U)
                def _(t=t, base=base, c=c):
                    for u in range(U):
                        f = live_ref[group, base + c * U + u]
                        tile(f, t, bw_ref[pl.ds(f, 1), :])

        if split_missing:
            miss = (bw == (B - 1)).astype(jnp.bfloat16)    # [fg, step]
            for k in range(sub):
                miss_ref[lead + (0,)] += jax.lax.dot_general(
                    miss[:, cut[k]], operand[k], lanes, preferred_element_type=jnp.float32
                )

    # accumulator blocks (main + the lane-padded missing-bin block) are
    # double-buffered by the pipeline; operand blocks and the per-feature
    # one-hot temporaries are small next to them
    acc_bytes = fg * rows * (Bp + 128) * 4
    vmem_limit = min(2 * acc_bytes + 16 * 1024 * 1024, 100 * 1024 * 1024)
    q, qs = (1,) * axis0, (tree_groups,) * axis0

    def behind_group(index):
        return lambda *ids: ids[:-3] + index(*ids[-3:])

    return pl.pallas_call(
        kernel,
        grid=qs + (groups, chunks, per),
        in_specs=[
            pl.BlockSpec((fg, step), lambda *ids: (ids[-3], ids[-2] * per + ids[-1])),
            pl.BlockSpec(q + (2 * size, step), behind_group(lambda j, c, i: (0, c * per + i))),
            pl.BlockSpec(q + (size, step), behind_group(lambda j, c, i: (0, c * per + i))),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec(q + (1, fg, rows, Bp), behind_group(lambda j, c, i: (c, j, 0, 0))),
            pl.BlockSpec(q + (1, fg, miss_rows), behind_group(lambda j, c, i: (c, j, 0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qs + (chunks, d_pad, rows, Bp), jnp.float32),
            jax.ShapeDtypeStruct(qs + (chunks, d_pad, miss_rows), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((fg, step), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (len(q) + 2) + ("arbitrary",),
            vmem_limit_bytes=vmem_limit,
        ),
        interpret=interpret,
        name="graft_level_histogram",
    )


@functools.lru_cache(maxsize=None)
def _pallas_hist_packed_fn(n, d, fg, W, B, block, prec, interpret, split_missing,
                           chunks, pack):
    """The one-tree kernel of a level narrow enough that ``pack`` features
    share every latched one-hot tile (see _tile_pack): same operands as
    ``_pallas_hist_fn`` (bins [d_pad, n], gh f32 [2, n], node i32 [1, n]),
    same results with ``rows`` = ``_slot_rows`` (main f32 [chunks, d_pad,
    rows, Bp], miss f32 [chunks, d_pad, rows or 2 * rows]), the same kernel
    name, grid, row chunks, missing-bin dot and padding-feature rule.

    A bin is ``L * top + low`` with L = 128 / pack lanes and ``fold`` = Bp /
    L values of ``top``. A tile of ``pack`` adjacent features latches ONE
    [128, blk] one-hot: lanes [j * L, (j + 1) * L) hold that of feature j's
    ``low``. The streamed operand holds, for each half of the bf16x2 split,
    a slot of the level's ``rows`` real rows for every (feature j of the
    tile, copy t): row (j, t, k) is row k of the level's operand where
    feature j's ``top`` is t, else 0. So the [S, 128] product a tile and
    block (S = pack * fold * rows, halves added as in the unpacked body)
    holds feature j's histogram in rows (j, t, k) x lanes [j * L, (j + 1) *
    L), bin L * t + lane, and the products of feature j's rows with another
    feature's lanes in the other blocks, which ``untangle`` drops after the
    kernel. Every product that is kept reaches its cell at the same position
    of the same 512-row contraction as in the unpacked body, beside zeros:
    the same bits. A tile with a padding feature beside a real one is a
    whole tile; a tile of padding features alone gets no dot.

    With half the tiles again the VPU's work a tile shows (``_tile_pack``'s
    table), so operand and one-hot are built as 32-bit words, two bf16 rows
    a word (``pltpu.bitcast``: word r of a column holds rows 2r and 2r + 1).
    A feature's ``fold`` slots are whole bf16 tiles (``_slot_rows``): one
    compare and one select a half over [fold * rows / 2, blk] words mask
    them, and a feature's one-hot is one compare and one select over [L / 2,
    blk] words (lanes 2r and 2r + 1 the halves of word r); the features'
    pieces sit side by side without a copy."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Bp = _round_up(B - 1 if split_missing else B, 128)
    d_pad = _round_up(d, fg)
    groups = d_pad // fg
    tiles_in_last = -(-(d - (groups - 1) * fg) // pack)   # with a real feature
    per = n // (block * chunks)            # row blocks a chunk
    stacked = prec == "bf16x2"
    rows = _slot_rows(W, Bp, pack)
    L = 128 // pack                        # lanes a feature of the latched tile
    fold = Bp // L
    F = fold * rows                        # rows of a feature's slots: (copy, k)
    S = pack * F                           # rows of a half: (feature, copy, k)
    halves = 2 if stacked else 1
    shift_w, shift_l = (rows // 2).bit_length() - 1, L.bit_length() - 1

    def kernel(bins_ref, gh_ref, node_ref, out_ref, miss_ref):
        @pl.when(pl.program_id(2) == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)
            miss_ref[...] = jnp.zeros_like(miss_ref)

        # every copy's slot holds the level's real rows
        k = jax.lax.broadcasted_iota(jnp.int32, (F, block), 0) & (rows - 1)
        A = _level_rows(node_ref[...], gh_ref, W, k)   # [F, blk]
        # "bf16": one rounded half, the failing control
        A = _split_bf16(A) if stacked else (A.astype(jnp.bfloat16),)
        # two rows of a slot a 32-bit word: [F / 2, blk], whole sublane tiles
        words = [pltpu.bitcast(half, jnp.uint32) for half in A]
        copy_of = jax.lax.broadcasted_iota(jnp.int32, (F // 2, block), 0) >> shift_w
        word_of = jax.lax.broadcasted_iota(jnp.int32, (L // 2, block), 0)
        lanes = (((1,), (1,)), ((), ()))               # contract rows

        bw = bins_ref[...].astype(jnp.int32)           # widen in VMEM

        def tile(p):
            b = [bw[pack * p + j:pack * p + j + 1, :] for j in range(pack)]  # [1, blk]
            # the missing bin (fold * L where it is split out) is copy
            # ``fold``: it rides no slot
            rides = [(bj >> shift_l) == copy_of for bj in b]
            Af = pltpu.bitcast(
                jnp.concatenate(
                    [jnp.where(m, half, jnp.uint32(0)) for half in words for m in rides], axis=0
                ),
                jnp.bfloat16,
            )                                          # [halves * S, blk]
            # the one-hot as words too: lanes 2r and 2r + 1 of a feature are
            # the halves of its word r, and 0x3F80 is bf16's 1.0
            ob = pltpu.bitcast(
                jnp.concatenate(
                    [
                        jnp.where(
                            ((bj & (L - 1)) >> 1) == word_of,
                            jnp.uint32(0x3F80) << ((bj & 1) << 4).astype(jnp.uint32),
                            jnp.uint32(0),
                        )
                        for bj in b
                    ],
                    axis=0,
                ),
                jnp.bfloat16,
            )                                          # [128, blk]
            P = jax.lax.dot_general(
                Af, ob, lanes, preferred_element_type=jnp.float32
            )
            out_ref[0, p] += (P[:S] + P[S:]) if stacked else P

        for p in range(tiles_in_last):                 # real in every group
            tile(p)
        if tiles_in_last < fg // pack and groups > 1:
            @pl.when(pl.program_id(0) < groups - 1)
            def _():
                for p in range(tiles_in_last, fg // pack):
                    tile(p)

        if split_missing:
            miss = (bw == (B - 1)).astype(jnp.bfloat16)    # [fg, blk]
            miss_ref[0] += jax.lax.dot_general(
                miss, jnp.concatenate(A, axis=0), lanes,
                preferred_element_type=jnp.float32,
            )

    call = pl.pallas_call(
        kernel,
        grid=(groups, chunks, per),
        in_specs=[
            pl.BlockSpec((fg, block), lambda j, c, i: (j, c * per + i)),
            pl.BlockSpec((2, block), lambda j, c, i: (0, c * per + i)),
            pl.BlockSpec((1, block), lambda j, c, i: (0, c * per + i)),
        ],
        out_specs=[
            pl.BlockSpec((1, fg // pack, S, 128), lambda j, c, i: (c, j, 0, 0)),
            pl.BlockSpec((1, fg, halves * F), lambda j, c, i: (c, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((chunks, d_pad // pack, S, 128), jnp.float32),
            jax.ShapeDtypeStruct((chunks, d_pad, halves * F), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="graft_level_histogram",
    )

    def untangle(main, miss):
        # a tile's slab is (feature, copy, k) x (feature', low): keep feature
        # == feature', bins back to L * copy + low
        main = main.reshape(chunks, d_pad // pack, pack, fold, rows, pack, L)
        main = jnp.stack([main[:, :, j, :, :, j] for j in range(pack)], axis=2)
        main = main.transpose(0, 1, 2, 4, 3, 5).reshape(chunks, d_pad, rows, Bp)
        # the missing bin's dot met every copy's slot: the first has it
        miss = jnp.concatenate(
            [miss[..., h * F:h * F + rows] for h in range(halves)], axis=-1
        )
        return main, miss

    return lambda bins_t, gh, node: untangle(*call(bins_t, gh, node))


def _live_tiles(reach, d, fg, B):
    """i32 [feature groups, (tiles - 1) * (fg + 1)], the unfolded kernel's
    scalar operand: for every bin tile t > 0, the group's fg features in the
    order the kernel takes them, those whose column can reach tile t first
    (``_tile_reached``; ``reach`` i32 [d], traced; in their own order), then
    how many those are. ``reach`` None: every real feature reaches every
    tile. A padding feature reaches none. No sort and no gather: a feature's
    place is a running count, and the list a compare-select-reduce over
    [fg, fg]."""
    tiles = _bin_lanes(B) // 128
    d_pad = _round_up(d, fg)
    if tiles == 1:                       # nothing above the one tile: no list
        return jnp.zeros((d_pad // fg, 1), jnp.int32)
    if reach is None:
        on = jnp.ones((d, tiles), jnp.bool_)
    else:
        on = _tile_reached(reach, B, jnp)
    on = jnp.pad(on[:, 1:], [(0, d_pad - d), (0, 0)])      # [d_pad, tiles - 1]
    on = on.reshape(d_pad // fg, fg, tiles - 1).transpose(0, 2, 1).astype(jnp.int32)
    count = on.sum(axis=-1, keepdims=True)                  # [groups, tiles - 1, 1]
    place = jnp.where(
        on > 0, jnp.cumsum(on, axis=-1) - 1, count + jnp.cumsum(1 - on, axis=-1) - 1
    )
    slot = jnp.arange(fg, dtype=jnp.int32)
    listed = jnp.sum(
        jnp.where(place[..., None, :] == slot[:, None], slot, 0), axis=-1
    )                                                       # [groups, tiles - 1, fg]
    lists = jnp.concatenate([listed, count], axis=-1).astype(jnp.int32)
    return lists.reshape(d_pad // fg, (tiles - 1) * (fg + 1))


def _hist_pallas(bins, grad, hess, node_local, num_nodes, num_bins,
                 prec=HIST_PRECISIONS[0], reach=None):
    """One tree (``grad``, ``hess``, ``node_local`` f32 / i32 [n]) ->
    (G, H) f32 [W, d, B]; or the T class trees of a round over the one bin
    matrix (each [T, n]: the gradients' rank decides) -> [T, W, d, B], the
    trees in groups that share their latched one-hot tiles
    (``_class_groups``)."""
    if prec not in HIST_PRECISIONS:
        raise ValueError("unknown histogram precision: {!r}".format(prec))
    n, d = bins.shape
    W = num_nodes
    B = num_bins
    trees = grad.shape[:-1]                            # () or (T,)
    if n == 0:
        # grid would be (.., 0): the step-0 out_ref init never runs and the
        # kernel would return an uninitialized buffer
        zeros = jnp.zeros(trees + (W, d, B), jnp.float32)
        return zeros, zeros
    block = PALLAS_ROW_BLOCK

    active = node_local >= 0
    g = jnp.where(active, grad, 0.0)
    h = jnp.where(active, hess, 0.0)
    node = jnp.where(active, node_local, jnp.int32(W))

    cap = _chunk_cap(-(-n // block))
    n_pad = _round_up(n, block * cap)
    fg = _pallas_feature_group(d, bins.dtype)
    d_pad = _round_up(d, fg)
    # rows onto the lane axis (see _pallas_hist_fn); the padding rows are
    # dead (node == W) and the padding features are sliced off below
    bins_t = jnp.pad(bins.T, [(0, d_pad - d), (0, n_pad - n)])
    split_missing = _mxu_split_missing(B)
    interpret = pallas_interpret()
    chunks = _row_chunks(W, cap)
    lanes = _bin_lanes(B)
    if not trees:
        gh = jnp.pad(jnp.stack([g, h]), [(0, 0), (0, n_pad - n)])
        node = jnp.pad(node, [(0, n_pad - n)], constant_values=W)

        live = ()                  # the unfolded body's operand alone
        pack = _tile_pack(W, lanes, prec)
        if pack > 1:
            rows = _slot_rows(W, lanes, pack)
            fn = _pallas_hist_packed_fn(
                n_pad, d, fg, W, B, block, prec, interpret, split_missing,
                chunks, pack,
            )
        else:
            rows = _operand_rows(W)
            fold = _bin_fold(rows, lanes, prec)
            fn = _pallas_hist_fn(
                n_pad, d, fg, W, B, block, prec, interpret, split_missing,
                rows, chunks, fold,
            )
            if fold == 1:
                live = (_live_tiles(reach, d, fg, B),)
        GH = _sum_row_chunks(
            *fn(bins_t, gh, node[None, :].astype(jnp.int32), *live),
            d, rows, B, split_missing,
        )
        GH = GH.transpose(1, 0, 2)                         # [rows, d, B]
        return GH[:W], GH[W:2 * W]

    T = trees[0]
    size, groups = _class_groups(W, T)
    # whole groups: a tree that fills up the last one has dead rows alone
    fill = [(0, size * groups - T), (0, n_pad - n)]
    g, h = (jnp.pad(x, fill).reshape(groups, size, n_pad) for x in (g, h))
    node = jnp.pad(node, fill, constant_values=W).reshape(groups, size, n_pad)
    rows = _operand_rows(W, size)
    fold = _bin_fold(rows, lanes, prec)
    fn = _pallas_hist_fn(
        n_pad, d, fg, W, B, block, prec, interpret, split_missing,
        rows, chunks, fold, class_groups=(size, groups),
    )
    live = (_live_tiles(reach, d, fg, B),) if fold == 1 else ()
    GH = _sum_row_chunks(
        *fn(bins_t, jnp.concatenate([g, h], axis=1), node.astype(jnp.int32), *live),
        d, rows, B, split_missing,
    )                                                      # [groups, d, rows, B]
    # a group's rows are (tree, g | h, node): trees to the front
    GH = GH[:, :, :size * 2 * W].reshape(groups, d, size, 2, W, B)
    GH = GH.transpose(0, 2, 3, 4, 1, 5).reshape(groups * size, 2, W, d, B)[:T]
    return GH[:, 0], GH[:, 1]


def _sum_row_chunks(main, miss, d, rows, B, split_missing):
    """The kernel's two results (``_pallas_hist_fn``: main [.., chunks,
    d_pad, rows, Bp], miss [.., chunks, d_pad, rows or 2 * rows], a leading
    class-group axis or none) -> [.., d, rows, B]: the row chunks' partial
    sums added, the padding features and bin lanes cut off, and the missing
    bin, where it was split out, put back as the last bin."""
    GH = main.sum(axis=-4)[..., :d, :, :B - 1 if split_missing else B]
    if split_missing:
        miss = miss.sum(axis=-3)[..., :d, :]
        if miss.shape[-1] != rows:                         # hi and lo halves
            miss = miss[..., :rows] + miss[..., rows:]
        GH = jnp.concatenate([GH, miss[..., None]], axis=-1)
    return GH


@functools.lru_cache(maxsize=None)
def _class_hist_fn(num_nodes, num_bins, prec):
    """``_hist_pallas`` for the builds of a round's class trees, which
    ``models/booster.py`` maps over the class axis with ``jax.vmap``: its
    batching rule hands the kernel the [T, n] gradients as ONE operand over
    the one bin matrix, where Pallas's own rule would put the class axis on
    the kernel's grid and latch every one-hot tile once a class. Only the
    class branch of the round program asks for it (``level_histogram``'s
    ``class_vmap``): a one-tree build calls ``_hist_pallas`` itself, with
    nothing wrapped around it."""

    def call(bins, grad, hess, node_local, reach):  # one tree's [n], or [T, n]
        return _hist_pallas(
            bins, grad, hess, node_local, num_nodes, num_bins, prec=prec, reach=reach
        )

    hist = jax.custom_batching.custom_vmap(call)

    @hist.def_vmap
    def _(axis_size, in_batched, bins, grad, hess, node_local, reach):
        # the bins are the one matrix and the columns' reach theirs, never
        # mapped; the root's node ids (all rows in node 0) are one row for
        # every tree
        grad, hess, node_local = (
            x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
            for x, batched in zip((grad, hess, node_local), in_batched[1:4])
        )
        return call(bins, grad, hess, node_local, reach), (True, True)

    return hist
