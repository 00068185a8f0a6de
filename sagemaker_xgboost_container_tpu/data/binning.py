"""Weighted quantile binning: DataMatrix -> BinnedMatrix.

This is the TPU replacement for XGBoost's weighted quantile sketch + gradient
index (`tree_method=hist`'s binning stage inside libxgboost). The trainer
never touches raw floats: it consumes a compact uint8/uint16 matrix of
per-feature bin indices resident in HBM, which makes the per-round histogram
build a pure integer scatter-add that XLA maps well, and bounds per-round
collective traffic to O(features x bins x nodes) independent of row count
(the same communication-compression role sketching plays in the reference —
SURVEY.md §5 long-context analog).

Design choices:
* Cut points are **midpoints between adjacent selected quantile values**, so
  the binned decision ``bin(v) <= b`` is exactly equivalent to the float
  decision ``v < cut[b]`` — trained trees serialize to xgboost-style
  ``split_condition`` thresholds with no train/serve skew.
* One shared *missing* bin at index ``max_bin`` (values 0..max_bin-1 are real
  bins). Histograms carry the missing bucket explicitly, and the split scan
  chooses the default direction by comparing both placements, reproducing
  XGBoost's sparsity-aware split finding.
* When a feature has <= max_bin distinct values the cuts are exact (every
  adjacent midpoint), matching `exact`-method fidelity on small data.
"""

import concurrent.futures
import functools
import os

import numpy as np

from ..telemetry.spans import span
from ..toolkit import exceptions as exc


class BinnedMatrix:
    """Bin-index features + cut points + labels/weights/groups.

    Also accepted directly by ``models/booster.train`` as a *pre-binned*
    training/eval input (the streaming-ingest plane in ``data/streaming.py``
    produces one without ever materializing the float32 channel): the
    session then skips its own sketch+bin stage and trusts these cuts.
    Pre-binned matrices deliberately have no ``.features`` — anything that
    genuinely needs floats goes through ``rep_block`` (bounded blocks of
    representative values whose tree routing is bit-identical to the
    original floats) so no code path can silently rehydrate the whole
    dataset.
    """

    def __init__(self, bins, cut_points, max_bin, labels=None, weights=None,
                 groups=None, feature_names=None, shape=None):
        # uint8/uint16 [n, d]; max_bin == missing. A training session leaves
        # its bins on the device and hands a function that pulls them, with
        # their ``shape``: whoever reads ``.bins`` on the host pays the trip.
        self._bins = bins
        self._shape = tuple(shape) if shape is not None else None
        self.cut_points = cut_points      # list of d float32 ascending arrays
        self.max_bin = int(max_bin)       # missing-bin index; num_bins = max_bin + 1
        self.labels = labels
        self.weights = weights
        self.groups = groups
        self.feature_names = list(feature_names) if feature_names is not None else None

    @property
    def bins(self):
        if callable(self._bins):
            self._bins = self._bins()
        return self._bins

    @property
    def shape(self):
        return self._shape if self._shape is not None else self._bins.shape

    @property
    def num_row(self):
        return self.shape[0]

    @property
    def num_col(self):
        return self.shape[1]

    @property
    def num_bins(self):
        return self.max_bin + 1

    def get_label(self):
        return self.labels if self.labels is not None else np.empty(0, dtype=np.float32)

    def get_weight(self):
        if self.weights is None:
            return np.ones(self.num_row, dtype=np.float32)
        return self.weights

    @property
    def features(self):
        # loud guard: a pre-binned matrix reaching a float-features consumer
        # is a wiring bug (the caller should be gated off the chunked path
        # or use rep_block) — never silently hand out representative values
        # where code expects the original floats
        raise exc.AlgorithmError(
            "BinnedMatrix has no float features (chunked ingest never "
            "materializes the channel); use rep_block() for routing-exact "
            "representative values or gate this path off pre-binned input"
        )

    def rep_block(self, start, end):
        """Representative float rows ``[start:end)`` (routing-exact).

        Every committed split threshold is drawn from ``cut_points`` (cuts
        ARE the serialized ``split_condition`` values), and for any value v
        in bin b the decision ``v < cut[i]`` holds iff ``b <= i``. The
        representative for bin b >= 1 is ``cut[b-1]`` (and just below
        ``cut[0]`` for bin 0, NaN for the missing bin), which satisfies the
        same equivalence — so predictions computed from representative
        blocks are bit-identical to predictions from the original floats
        (leaf routing identical, identical leaf values summed in the same
        order). Used for warm-start margins and host-side eval on
        pre-binned matrices, one bounded block at a time.
        """
        bins = self.bins[start:end]
        out = np.empty(bins.shape, np.float32)
        for f in range(self.num_col):
            cuts = np.asarray(self.cut_points[f], np.float32)
            lookup = np.full(self.max_bin + 1, np.nan, np.float32)
            if cuts.size:
                # both args float32: nextafter(f32, python-float) promotes to
                # float64 on pre-NEP50 numpy and rounds back to cuts[0] when
                # stored, putting bin 0 on the wrong side of `v < cut[0]`
                lookup[0] = np.nextafter(cuts[0], np.float32(-np.inf))
                lookup[1 : cuts.size + 1] = cuts
            else:
                lookup[0] = 0.0  # no cuts -> never split on; value is inert
            out[:, f] = lookup[bins[:, f]]
        return out


def _select_cuts(sorted_values, sorted_weights, max_cuts):
    """Pick <= max_cuts cut thresholds from one feature's non-missing values.

    sorted_values: ascending, may contain duplicates. Returns midpoints
    between adjacent *distinct* representative values.
    """
    if sorted_values.size == 0:
        return np.empty(0, dtype=np.float32)
    distinct, start_idx = np.unique(sorted_values, return_index=True)
    if distinct.size <= max_cuts:
        reps = distinct
    else:
        # weighted quantiles: cumulative weight at the *end* of each distinct
        # value's run, evaluated at evenly spaced targets
        cum = np.cumsum(sorted_weights)
        total = cum[-1]
        run_end = np.append(start_idx[1:], len(sorted_values)) - 1
        cum_at_distinct = cum[run_end]
        targets = total * (np.arange(1, max_cuts + 1) / (max_cuts + 1))
        picks = np.searchsorted(cum_at_distinct, targets, side="left")
        picks = np.unique(np.clip(picks, 0, distinct.size - 1))
        reps = distinct[picks]
    if reps.size < 2:
        # one distinct value -> no informative split; place one cut above it
        # so "value present" vs "missing" can still separate
        return np.asarray([reps[0] + 1.0 if reps.size else 0.0], dtype=np.float32)
    mids = (reps[:-1] + reps[1:]) / 2.0
    return mids.astype(np.float32)


def _sketch_impl():
    """host | device sketch lowering (GRAFT_SKETCH_IMPL; auto = device on
    TPU). The host path is a per-feature numpy argsort loop — ~14s for
    1M x 28 on one core; the device path sorts/scans all features on-chip
    in one vmapped XLA program (the reference's sketch likewise runs in
    native code inside libxgboost)."""
    v = os.environ.get("GRAFT_SKETCH_IMPL", "auto")
    if v == "auto":
        import jax

        return "device" if jax.default_backend() == "tpu" else "host"
    if v not in ("host", "device"):
        raise ValueError("GRAFT_SKETCH_IMPL must be auto|host|device")
    return v


# What one call of a device kernel below may ask of the chip, and what a value
# costs it. The sort behind the sketch needs 27 to 28 bytes of scratch a value
# at the tightest schedule the chip's compiler finds (17.58 GB refused at
# 16.39M x 40, 8.65 GB held at 2.27M x 136) and takes up to 76 where there is
# room (9.96 GB at 16.39M x 8, 11.5 GB at x 16); the bin-apply's search takes
# 28 to 30 with its float input and int32 output (7.6 GB at 8.8M x 28, 17.7 GB
# at 16.39M x 39, more than the chip has). A matrix over the budget goes
# through the same compiled kernel in equal blocks: of columns in the sketch,
# of rows in the bin-apply. Both are independent along the axis they are cut
# on, so the results are the same bits for any block size.
DEVICE_BLOCK_BYTES = 9 << 30
DEVICE_BYTES_PER_VALUE = 30


def _equal_blocks(total, most):
    """(blocks, size): the fewest equal blocks of at most ``most`` that cover
    ``total``. Block ``b`` starts at ``min(b * size, total - size)``: the last
    one overlaps its neighbour rather than change shape."""
    blocks = max(1, -(-total // max(most, 1)))
    return blocks, -(-total // blocks)


@functools.lru_cache(maxsize=32)
def _cut_points_kernel(max_cuts, L):
    """Jitted device-sketch kernel, cached per (max_cuts, L).

    Bounded: L tracks the dataset row count, so a long-lived process
    sketching many differently-sized datasets would otherwise pin one
    compiled executable per size forever; LRU eviction lets stale kernels
    be collected while any single training job (constant shapes) still
    always hits.

    Hoisted out of _device_cut_points (ADVICE r5): a fresh-closure
    ``@jax.jit`` per call created a new jit wrapper each time, so the approx
    re-sketch — which calls this EVERY dispatch — paid a full retrace +
    compile per boosting round. Cached here, repeated calls with the same
    static config hit the jit cache (tests/test_device_sketch.py asserts no
    recompile via ``_cache_size``).
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(feats, wv):
        # transpose INSIDE the program: XLA folds it into layout assignment
        # instead of materializing an eager [d, n] copy per call (the approx
        # re-sketch calls this every dispatch on staged device features)
        cols = feats.T
        def one(col):
            nanm = jnp.isnan(col)
            # two-key sort: primary = value (NaN mapped to +inf), secondary =
            # missing flag — so real +inf values (kept by the host path as
            # ordinary distinct reps) sort strictly BEFORE the missing tail
            # instead of interleaving with it
            key = jnp.where(nanm, jnp.inf, col)
            sv, snan, sw = jax.lax.sort(
                (key, nanm.astype(jnp.int32), jnp.where(nanm, 0.0, wv)),
                num_keys=2,
            )
            valid = snan == 0
            cw = jnp.cumsum(sw)  # missing rows carry weight 0 at the tail
            nxt = jnp.concatenate([sv[1:], jnp.full((1,), jnp.inf, sv.dtype)])
            nxt_invalid = jnp.concatenate(
                [snan[1:] != 0, jnp.ones((1,), bool)]
            )
            is_end = valid & ((sv != nxt) | nxt_invalid)
            pos = jnp.cumsum(is_end.astype(jnp.int32)) - 1
            n_distinct = jnp.maximum(pos[-1] + 1, 0)
            scatter_idx = jnp.where(is_end, pos, L)
            distinct = (
                jnp.full(L + 1, jnp.inf, sv.dtype)
                .at[scatter_idx].set(sv, mode="drop")[:L]
            )
            cum_at = (
                jnp.full(L + 1, jnp.inf, jnp.float32)
                .at[scatter_idx].set(cw, mode="drop")[:L]
            )
            total = cw[-1]
            targets = total * (
                jnp.arange(1, max_cuts + 1, dtype=jnp.float32) / (max_cuts + 1)
            )
            picks = jnp.searchsorted(cum_at, targets, side="left")
            picks = jnp.clip(picks, 0, jnp.maximum(n_distinct - 1, 0))
            uniq = jnp.concatenate(
                [jnp.ones((1,), bool), picks[1:] != picks[:-1]]
            )
            upos = jnp.cumsum(uniq.astype(jnp.int32)) - 1
            reps_b = (
                jnp.full(max_cuts + 1, jnp.inf, sv.dtype)
                .at[jnp.where(uniq, upos, max_cuts + 1)]
                .set(distinct[picks], mode="drop")[:max_cuts]
            )
            n_b = jnp.sum(uniq.astype(jnp.int32))
            use_all = n_distinct <= max_cuts
            reps = jnp.where(use_all, distinct[:max_cuts], reps_b)
            n_reps = jnp.where(use_all, n_distinct, n_b)
            mids = jnp.concatenate(
                [(reps[:-1] + reps[1:]) * 0.5, jnp.zeros((1,), sv.dtype)]
            )
            single = n_reps == 1
            cut0 = jnp.where(single, reps[0] + 1.0, mids[0])
            mids = mids.at[0].set(cut0)
            n_cuts = jnp.where(
                n_reps == 0, 0, jnp.where(single, 1, n_reps - 1)
            )
            return mids, n_cuts

        return jax.vmap(one)(cols)

    return kernel


def _map_shards(fn, count):
    """``fn(s)`` for every shard, side by side: one thread a shard, so that
    every chip is given its work before any is waited for (a transfer, a
    kernel and a compile all release the interpreter). One shard runs here."""
    if count == 1:
        return [fn(0)]
    with concurrent.futures.ThreadPoolExecutor(max_workers=count) as pool:
        return list(pool.map(fn, range(count)))


def _float_block(block, device):
    """A float32 block for a device kernel, on ``device`` (None: where jax
    puts an array by default). What is on a device already stays there."""
    import jax
    import jax.numpy as jnp

    if device is None or not isinstance(block, np.ndarray):
        return jnp.asarray(block, jnp.float32)
    return jax.device_put(np.asarray(block, np.float32), device)


def _staged_block(block, device, phase, attributes):
    """``_float_block`` for one block of a set-up phase, split where the work
    changes hands: ``<phase>.stage`` is the host's own (the conversion where
    the matrix is not float32 already; a float32 block stays the view it is:
    handed a strided column block, jax gathers it into the transfer many
    times faster than numpy copies it, PERF.md section 6, PR 36),
    ``<phase>.transfer`` the link's (the put, until the block is on the
    chip; ``bytes_up`` what it moved). The wait delays nothing: the kernel
    cannot start before its block is there. What is on a device already is
    staged by that device, inside the transfer span."""
    with span(phase + ".stage", covering=True, attributes=attributes):
        if isinstance(block, np.ndarray):
            block = np.asarray(block, np.float32)
    moved = dict(attributes, bytes_up=_host_bytes(block))
    with span(phase + ".transfer", covering=True, attributes=moved):
        return _float_block(block, device).block_until_ready()


def _device_cut_points(features, w, max_cuts, blocks, block_columns, device=None, shard=0):
    """compute_cut_points's selection semantics as one vmapped XLA program,
    run on ``device`` over ``blocks`` blocks of ``block_columns`` columns
    (``sketch_shards`` sizes them by ``DEVICE_BLOCK_BYTES``), one block after
    another: each is staged, put, sketched and fetched under covering spans
    ``setup.sketch.stage`` / ``.transfer`` / ``.kernel`` / ``.fetch``
    (attributes ``shard``, ``block``), which say whether the host, the link
    or the chip had the work.

    Mirrors the _select_cuts ALGORITHM step for step: stable sort, cumulative
    weight at each distinct value's run end, evenly spaced weighted-quantile
    targets, left-searchsorted picks deduped, adjacent-rep midpoints;
    all-distinct shortcut when a feature has <= max_cuts distinct values; one
    cut above the value for single-valued columns; none for all-missing
    columns. Static shapes: outputs padded to [d, max_cuts] + true counts.
    The jitted kernel is cached per (max_cuts, L) in _cut_points_kernel so
    the per-dispatch approx re-sketch reuses the compiled program.

    NOT bitwise-identical to the host path: cumulative weights accumulate in
    f32 via XLA's tree-structured scan and the quantile targets are f32,
    while the host path does a sequential numpy f32 cumsum against f64
    targets — on large n a razor-edge target can shift a searchsorted pick
    by one distinct value, moving one cut by one value-midpoint (below
    binning resolution; quality parity tested in tests/test_device_sketch.py).
    A training job uses one lowering throughout (GRAFT_SKETCH_IMPL resolves
    once per sketch), so within-job determinism is unaffected; retraining
    with the other lowering may produce slightly different (equally valid)
    cuts. TPU has no native f64, so exact host parity would need a
    compensated scan — not worth it for a one-bin boundary shift.
    """
    n, d = features.shape
    # scatter buffers sized so distinct[:max_cuts] is well-defined even when
    # the dataset has fewer rows than max_cuts (n=100, max_bin=256)
    L = max(n, max_cuts)
    kernel = _cut_points_kernel(max_cuts, L)
    wv = _staged_block(w, device, "setup.sketch", {"shard": shard, "block": "weights"})
    cuts = []
    for b in range(blocks):
        attributes = {"shard": shard, "block": b}
        lo = min(b * block_columns, d - block_columns)
        block = _staged_block(
            features[:, lo : lo + block_columns], device, "setup.sketch", attributes
        )
        # dispatch (and, the first time, the kernel's load) until the result is ready
        with span("setup.sketch.kernel", covering=True, attributes=attributes):
            mids, counts = kernel(block, wv)
            del block
            counts.block_until_ready()
        with span("setup.sketch.fetch", covering=True, attributes=attributes):
            mids = np.asarray(mids, np.float32)
            counts = np.asarray(counts)
            # an overlapping last block repeats columns the one before gave
            cuts += [
                mids[f - lo, : int(counts[f - lo])].copy()
                for f in range(len(cuts), lo + block_columns)
            ]
    return cuts


def _host_cut_points(features, w, max_cuts):
    cuts = []
    order = np.argsort(features, axis=0, kind="stable")
    for f in range(features.shape[1]):
        col = features[order[:, f], f]
        colw = w[order[:, f]]
        valid = ~np.isnan(col)
        cuts.append(_select_cuts(col[valid], colw[valid], max_cuts))
    return cuts


def _host_bytes(*arrays):
    """Bytes of those arrays that are host (numpy) arrays: what handing them
    to a device kernel uploads, as float32."""
    return sum(
        int(a.size) * 4 for a in arrays if isinstance(a, np.ndarray)
    )


def merge_cut_candidates(candidate_sets, max_bin):
    """The one rule by which row shards agree on their cuts: a column's
    candidates from every shard, sorted and deduplicated, and where they are
    more than ``max_bin - 1`` that many of them, evenly spaced by rank. The
    shards are the chips of a one-process mesh, the processes of a job, or
    both (``models/booster.py``). Deterministic, so every process that merges
    the same sets holds the same cuts; one set is returned as it is, so one
    device's cuts are its own sketch's."""
    candidate_sets = list(candidate_sets)
    if len(candidate_sets) == 1:
        return list(candidate_sets[0])
    width = max_bin - 1
    merged = []
    for column in zip(*candidate_sets):
        cands = np.concatenate([np.asarray(c, np.float32) for c in column])
        cands = np.unique(cands[np.isfinite(cands)])
        if len(cands) > width:
            picks = np.linspace(0, len(cands) - 1, width).round().astype(int)
            cands = cands[np.unique(picks)]
        merged.append(cands.astype(np.float32))
    return merged


def sketch_shards(features, weights, max_bin, devices=(None,), merge=None):
    """Cut thresholds of a matrix held as row shards: ``features[s]`` (rows
    of shard ``s`` x all columns; NaN = missing, a row of NaN is padding) is
    sketched with ``weights[s]`` (None: unit weights) on ``devices[s]``, the
    shards side by side, and ``merge`` (default ``merge_cut_candidates``)
    makes one set of the shards' candidates. One ``setup.sketch`` span covers
    the phase; inside it a ``setup.sketch.shard`` span a shard (attribute
    ``shard``) and ``setup.sketch_merge``.

    ``max_bin=None`` selects EVERY adjacent-distinct midpoint (no quantile
    subsetting) — the candidate set and thresholds of xgboost's exact greedy
    enumeration (reference tree_method=exact, schema
    hyperparameter_validation.py:22-24), made static-shape by binning. It
    is no sketch and takes the matrix whole: one shard.
    """
    if max_bin is not None and max_bin < 2:
        raise exc.UserError("max_bin must be at least 2")
    shards = len(features)
    n, d = features[0].shape
    max_cuts = n if max_bin is None else max_bin - 1
    on_device = max_bin is not None and n > 0 and _sketch_impl() == "device"
    blocks, block_columns = (
        _equal_blocks(d, DEVICE_BLOCK_BYTES // DEVICE_BYTES_PER_VALUE // max(n, max_cuts))
        if on_device
        else (1, d)
    )
    weights = [
        np.ones(f.shape[0], dtype=np.float32) if w is None else w
        for f, w in zip(features, weights)
    ]

    # the device kernel takes the float matrix and the weights; what is on
    # the device already (the approx re-sketch stages it) moves nothing
    shard_attributes = [
        {"shard": s, "rows": f.shape[0], "bytes_up": _host_bytes(f, w) if on_device else 0}
        for s, (f, w) in enumerate(zip(features, weights))
    ]

    def sketch(s):
        # a part of `setup.sketch`: kept out of the round record's phases
        with span("setup.sketch.shard", covering=True, attributes=shard_attributes[s]):
            if on_device:
                return _device_cut_points(
                    features[s], weights[s], max_cuts, blocks, block_columns, devices[s], s
                )
            return _host_cut_points(features[s], weights[s], max_cuts)

    attributes = {
        "rows": sum(f.shape[0] for f in features),
        "columns": d,
        "impl": "device" if on_device else "host",
        "shards": shards,
        "column_blocks": blocks,
        "block_columns": block_columns,
        "bytes_up": sum(a["bytes_up"] for a in shard_attributes),
    }
    # the span ends where the merged cuts are on the host
    with span("setup.sketch", attributes=attributes):
        candidates = _map_shards(sketch, shards)
        with span("setup.sketch_merge", covering=True, attributes={"sets": shards}):
            return (merge or functools.partial(merge_cut_candidates, max_bin=max_bin))(
                candidates
            )


def compute_cut_points(features, weights=None, max_bin=256):
    """Per-feature cut thresholds via weighted quantiles. NaN = missing. The
    one-shard case of ``sketch_shards``."""
    return sketch_shards([features], [weights], max_bin)


def cuts_from_summaries(summaries, max_bin):
    """Per-feature cuts from merged (distinct values, weight sums) summaries.

    ``summaries``: one ``(values, weights)`` pair per feature — values
    strictly ascending f32 distinct feature values, weights the total sketch
    weight observed at each value (the streaming-ingest sketch merge,
    ``data/streaming.py``). Runs the exact ``_select_cuts`` host kernel:
    ``np.unique`` over already-distinct values is the identity, so the
    cumulative weight at each distinct run end equals ``cumsum(weights)``
    — for unit (and integer, up to f32-exact range) row weights the
    selected cuts are **bitwise identical** to ``compute_cut_points`` over
    the flat float channel. Arbitrary float row weights can differ in the
    last ulp of a cumulative sum (chunk-partitioned summation order), which
    can shift a razor-edge quantile pick by one distinct value — the same
    class (and magnitude) of caveat the device sketch lowering documents.
    """
    if max_bin is None:
        raise exc.UserError(
            "tree_method='exact' (max_bin=None) is not supported by chunked "
            "ingest; use tree_method='hist' or SM_INGEST_MODE=whole."
        )
    max_cuts = max_bin - 1
    return [
        _select_cuts(
            np.asarray(values, np.float32), np.asarray(weights, np.float32), max_cuts
        )
        for values, weights in summaries
    ]


def bin_dtype(max_bin):
    return np.uint8 if max_bin + 1 <= 256 else np.uint16


def _host_apply(features, cut_points, max_bin, dtype):
    n, d = features.shape
    bins = np.empty((n, d), dtype=dtype)
    for f in range(d):
        col = features[:, f]
        idx = np.searchsorted(cut_points[f], col, side="right")
        idx[np.isnan(col)] = max_bin
        bins[:, f] = idx.astype(dtype)
    return bins


def apply_shards(features, cut_points, max_bin, devices=(None,), name=None, to_host=False):
    """Bin indices of a matrix held as row shards (``features[s]`` as
    ``sketch_shards`` takes them; NaN -> the missing bin, == ``max_bin``):
    shard ``s`` is binned on ``devices[s]`` and, under the device lowering,
    left there, in the bin matrix's narrow dtype; the shards side by side.
    ``to_host`` brings each back as numpy (the host lowering gives numpy
    anyway). ``name`` says which matrix it is (``train``, an evaluation
    set's name) on the one ``setup.bin_apply`` span that covers the phase;
    inside it a ``setup.bin_apply.shard`` span a shard, which ends where the
    shard's bins are ready."""
    shards = len(features)
    n, d = features[0].shape
    dtype = bin_dtype(max_bin)
    on_device = n > 0 and d > 0 and _sketch_impl() == "device"
    if on_device:
        L = max(1, max((len(c) for c in cut_points), default=1))
        padded = np.full((d, L), np.inf, np.float32)
        counts = np.zeros(d, np.int32)
        for f, c in enumerate(cut_points):
            padded[f, : len(c)] = c
            counts[f] = len(c)

    # up: the float matrix; down: the bin indices, where asked for
    down = d * np.dtype(dtype).itemsize if on_device and to_host else 0
    shard_attributes = [
        {
            "shard": s,
            "rows": f.shape[0],
            "bytes_up": _host_bytes(f) if on_device else 0,
            "bytes_down": f.shape[0] * down,
        }
        for s, f in enumerate(features)
    ]

    def apply(s):
        with span("setup.bin_apply.shard", covering=True, attributes=shard_attributes[s]):
            if not on_device:
                return _host_apply(np.asarray(features[s]), cut_points, max_bin, dtype)
            bins = _device_apply(features[s], padded, counts, max_bin, devices[s], s)
            return np.asarray(bins) if to_host else bins.block_until_ready()

    attributes = {
        "rows": sum(f.shape[0] for f in features),
        "columns": d,
        "set": name or "",
        "impl": "device" if on_device else "host",
        "shards": shards,
        "bytes_up": sum(a["bytes_up"] for a in shard_attributes),
        "bytes_down": sum(a["bytes_down"] for a in shard_attributes),
    }
    with span("setup.bin_apply", attributes=attributes):
        return _map_shards(apply, shards)


def apply_cut_points(features, cut_points, max_bin, name=None):
    """Map float features to bin indices on the host; NaN -> missing bin
    (== max_bin). The one-shard case of ``apply_shards``."""
    return apply_shards([features], cut_points, max_bin, name=name, to_host=True)[0]


@functools.lru_cache(maxsize=None)
def _apply_kernel(max_bin):
    """Jitted bin-apply kernel, cached per max_bin (hoisted like
    _cut_points_kernel — the approx re-sketch re-bins train + eval sets
    every dispatch and must hit the jit cache, not recompile). Its output
    is the bin matrix's own narrow dtype, so it can stay on the device."""
    import jax
    import jax.numpy as jnp

    dtype = bin_dtype(max_bin)

    @jax.jit
    def kernel(feats, cuts, cnts):
        cols = feats.T  # folded into the program (see _device_cut_points)
        def one(col, cf, kf):
            # every cut compared and counted, one fused compare-and-sum a
            # column: the default bisection is a serial gather a step, 8 steps
            # a value at 255 cuts (PERF.md section 6, PR 34); the same counts
            idx = jnp.searchsorted(cf, col, side="right", method="compare_all")
            idx = jnp.minimum(idx, kf)          # +inf values -> n_cuts
            return jnp.where(jnp.isnan(col), max_bin, idx).astype(dtype)

        return jax.vmap(one)(cols, cuts, cnts).T

    return kernel


def _device_apply(features, padded, counts, max_bin, device=None, shard=0):
    """Binning as one vmapped on-device searchsorted (the binning stage's
    other host loop, ~5s for 1M x 28), on ``device``. Cuts pad to [d, L]
    with +inf (finite values never land in the pad; +inf values clip to the
    feature's true cut count, matching numpy searchsorted semantics). A
    matrix over ``DEVICE_BLOCK_BYTES`` goes through in equal blocks of rows,
    joined on the device; each block under covering spans
    ``setup.bin_apply.stage`` / ``.transfer`` / ``.kernel`` (attributes
    ``shard``, ``block``), the join under the last block's kernel span."""
    import jax
    import jax.numpy as jnp

    n, d = features.shape
    kernel = _apply_kernel(max_bin)
    moved = {"shard": shard, "block": "cuts", "bytes_up": padded.nbytes + counts.nbytes}
    with span("setup.bin_apply.transfer", covering=True, attributes=moved):
        cuts_dev, counts_dev = jax.device_put((padded, counts), device)
        counts_dev.block_until_ready()
    blocks, rows = _equal_blocks(n, DEVICE_BLOCK_BYTES // DEVICE_BYTES_PER_VALUE // d)
    parts = []
    for b in range(blocks):
        attributes = {"shard": shard, "block": b}
        lo = min(b * rows, n - rows)
        block = _staged_block(features[lo : lo + rows], device, "setup.bin_apply", attributes)
        with span("setup.bin_apply.kernel", covering=True, attributes=attributes):
            part = kernel(block, cuts_dev, counts_dev)
            del block
            # an overlapping last block repeats rows the one before gave
            parts.append(part[b * rows - lo :])
            if b == blocks - 1 and blocks > 1:
                parts = [jnp.concatenate(parts, axis=0)]
            parts[-1].block_until_ready()
    return parts[0]


def resolve_max_bin(cut_points, max_bin, exact_cap=None):
    """The bin width the cuts need: ``max_bin`` itself, or with
    ``max_bin=None`` (exact-greedy binning: cuts at every adjacent-distinct
    midpoint) the width sized by the data. ``exact_cap`` bounds that
    data-driven width: per-node histograms are O(nodes x features x bins),
    so pathologically many distinct values must fail loudly rather than
    exhaust HBM."""
    longest = max((len(c) for c in cut_points), default=0)
    if max_bin is None:
        max_bin = longest + 1
        if exact_cap is not None and max_bin > exact_cap:
            raise exc.UserError(
                "tree_method='exact' needs {} bins for this data (one per "
                "distinct feature value), above the TPU exact cap of {}. Use "
                "tree_method='hist' (quantile binning), or raise "
                "GRAFT_EXACT_BIN_CAP if the memory cost is acceptable.".format(
                    max_bin, exact_cap
                )
            )
        if max_bin + 1 > 65536:
            raise exc.AlgorithmError(
                "exact binning needs {} bins; the uint16 bin matrix holds "
                "at most 65535".format(max_bin)
            )
    elif longest + 1 > max_bin:
        raise exc.AlgorithmError(
            "cut selection produced {} cuts for max_bin {}".format(longest, max_bin)
        )
    return max_bin


def bin_matrix(dmatrix, max_bin=256, cut_points=None, exact_cap=None, name=None):
    """DataMatrix -> BinnedMatrix on the host (computing cuts unless
    provided). ``name`` labels the matrix on its ``setup.bin_apply`` span."""
    if cut_points is None:
        cut_points = compute_cut_points(dmatrix.features, dmatrix.weights, max_bin)
    max_bin = resolve_max_bin(cut_points, max_bin, exact_cap)
    bins = apply_cut_points(dmatrix.features, cut_points, max_bin, name=name)
    return BinnedMatrix(
        bins,
        cut_points,
        max_bin,
        labels=dmatrix.labels,
        weights=dmatrix.weights,
        groups=dmatrix.groups,
    )
