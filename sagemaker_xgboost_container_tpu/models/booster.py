"""The boosting engine: ``train()`` — the TPU replacement for ``xgb.train``.

Reference hot loop (algorithm_mode/train.py:367-376) calls into libxgboost;
here each boosting round is one jitted XLA program: objective grad/hess ->
level-wise tree build (ops/tree_build) -> margin updates for train and every
eval set — the only host work per round is pulling the tree's small node
arrays (O(2^max_depth)) for the Forest and the eval scalars for callbacks.

Distribution: with a mesh, every round runs under ``shard_map`` with rows
sharded over the "data" axis; the single ``lax.psum`` inside the histogram op
is the entire cross-host protocol (replacing Rabit allreduce + tracker
topology — SURVEY.md §5). Trees come out bitwise identical on every shard, so
the "master saves the model" contract is trivially consistent. Rows are
zero-weight padded to a multiple of the shard count.

Ranking objectives route through ops/ranking's LambdaMART gradients over
query groups bucketed by size.

Callback protocol mirrors xgboost's (before_training / after_iteration ->
bool stop / after_training) so the orchestration layer's checkpoint, early
stop, and monitor callbacks port naturally.
"""

import contextlib
import functools
import logging
import os
import sys
import typing
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..data.binning import (
    BinnedMatrix,
    apply_shards,
    merge_cut_candidates,
    resolve_max_bin,
    sketch_shards,
)
from ..ops.histogram import (
    choose_hist_impl,
    dead_bin_tiles,
    padded_feature_width,
    resolve_hist_knobs,
    round_comm_plan,
    round_hist_levels,
    round_onehot_tiles,
    skipped_tiles_pct,
)
from ..ops.ranking import (
    GroupLayout,
    SlotColumns,
    build_group_layout,
    lambdarank_grad_hess,
    pair_slots,
    with_slot_columns,
)
from ..ops.tree_build import (
    round_tree_from_packed,
    build_tree,
    choose_eval_traversal,
    choose_route_impl,
    pack_round_trees,
    predict_binned_levels,
    predict_binned_steps,
    tree_from_packed,
    unpack_round_trees,
)
from ..telemetry import device as device_telemetry
from ..telemetry.cluster import compile_stats, install_program_listener
from ..telemetry.device import (
    STAGE_EVAL_APPLY,
    STAGE_EVAL_METRIC,
    STAGE_GRAD,
    STAGE_LEAF_MARGIN,
    STAGE_PACK,
    stage,
)
from ..telemetry.spans import (
    active_recorder,
    begin_span,
    end_span,
    note_phase_memory,
    record_startup,
    span,
)
from ..toolkit import exceptions as exc
from ..utils.faults import fault_point
from . import eval_metrics
from . import objectives as objectives_mod
from .forest import Forest, compact_padded_tree

logger = logging.getLogger(__name__)

# the one jax.monitoring listener, from the import on: what a caller loads
# in front of its first session is counted too (under no phase)
install_program_listener()

# objective hyperparameters carried into the saved model / objective
# construction (shared by train() and the fold-parallel CV path)
OBJECTIVE_PARAM_KEYS = (
    "scale_pos_weight",
    "tweedie_variance_power",
    "huber_slope",
    "max_delta_step",
    "num_class",
    "aft_loss_distribution",
    "aft_loss_distribution_scale",
)


class TrainConfig:
    """Parsed + defaulted booster parameters (static across rounds)."""

    def __init__(self, params):
        p = dict(params or {})
        self.eta = float(p.get("eta", 0.3))
        max_depth = p.get("max_depth", 6)
        self.max_depth = int(max_depth) if max_depth is not None else 6
        self.grow_policy = p.get("grow_policy", "depthwise")
        self.max_leaves = int(p.get("max_leaves", 0) or 0)
        if self.grow_policy == "lossguide" and self.max_leaves <= 0:
            # xgboost's 0 means unlimited; static shapes need a bound
            raise exc.UserError(
                "grow_policy='lossguide' requires max_leaves >= 2 in the TPU "
                "container (static-shape tree builder)."
            )
        if self.max_depth == 0 and self.grow_policy != "lossguide":
            raise exc.UserError(
                "max_depth=0 (unlimited depth) is not supported by the TPU static-shape "
                "tree builder with grow_policy='depthwise'; set max_depth >= 1 or use "
                "grow_policy='lossguide' with max_leaves."
            )
        self.reg_lambda = float(p.get("lambda", 1.0))
        self.alpha = float(p.get("alpha", 0.0))
        self.gamma = float(p.get("gamma", 0.0))
        self.min_child_weight = float(p.get("min_child_weight", 1.0))
        self.max_delta_step = float(p.get("max_delta_step", 0.0))
        self.exact_binning = p.get("tree_method") == "exact"
        self.exact_bin_cap = None
        if self.exact_binning:
            # True exact-greedy parity: hist with cuts at EVERY adjacent
            # distinct-value midpoint is the same candidate-split set and the
            # same midpoint thresholds as libxgboost's exact enumeration
            # (reference schema hyperparameter_validation.py:22-24), but
            # static-shape. max_bin is sized by the data at binning time
            # (bin_matrix(max_bin=None)), bounded by the cap below; xgboost
            # likewise ignores max_bin for exact.
            self.max_bin = None
            self.exact_bin_cap = int(os.environ.get("GRAFT_EXACT_BIN_CAP", 8192))
        elif p.get("max_bin") is not None:
            self.max_bin = int(p["max_bin"])
        elif p.get("sketch_eps"):
            # approx-method users control sketch granularity via sketch_eps;
            # bins ~ 1/eps is xgboost's own guidance for the hist equivalent
            self.max_bin = int(min(max(1.0 / float(p["sketch_eps"]), 2), 1024))
        else:
            self.max_bin = 256
        if p.get("tree_method") == "approx":
            # r5: approx now matches libxgboost's candidate
            # refresh — a hessian-weighted re-sketch before every dispatch
            # (_TrainingSession._resketch_bins). GRAFT_APPROX_RESKETCH=0
            # restores the single global sketch (hist semantics) for A/Bs.
            logger.info(
                "tree_method='approx': TPU hist engine at max_bin=%d "
                "(~1/sketch_eps) with per-dispatch hessian-weighted "
                "re-sketch (disable via GRAFT_APPROX_RESKETCH=0).",
                self.max_bin,
            )
        self.subsample = float(p.get("subsample", 1.0))
        self.colsample_bytree = float(p.get("colsample_bytree", 1.0))
        self.colsample_bylevel = float(p.get("colsample_bylevel", 1.0))
        self.colsample_bynode = float(p.get("colsample_bynode", 1.0))
        self.seed = int(p.get("seed", 0))
        self.objective = p.get("objective", "reg:squarederror")
        self.num_class = int(p.get("num_class", 0) or 0)
        self.base_score = float(p.get("base_score", 0.5))
        self.tree_method = p.get("tree_method", "auto")
        self.monotone_constraints = p.get("monotone_constraints")
        self.interaction_constraints = p.get("interaction_constraints")
        self.eval_metric = p.get("eval_metric")
        self.num_parallel_tree = int(p.get("num_parallel_tree", 1) or 1)
        self.booster = p.get("booster", "gbtree")
        # the partition scan of a column given as categories (xgboost's names
        # and defaults; ops/categorical.py): fewer categories than the first,
        # one against the rest; at most the second in a scanned set
        to_onehot, threshold = p.get("max_cat_to_onehot"), p.get("max_cat_threshold")
        self.max_cat_to_onehot = 4 if to_onehot is None else int(to_onehot)
        self.max_cat_threshold = 64 if threshold is None else int(threshold)
        if self.max_cat_to_onehot < 1 or self.max_cat_threshold < 1:
            raise exc.UserError(
                "max_cat_to_onehot and max_cat_threshold must be at least 1, got {} and "
                "{}".format(self.max_cat_to_onehot, self.max_cat_threshold)
            )
        # internal: build K trees per device dispatch (with eval sets the
        # per-round metrics ride back as device-computed stats inside the
        # scan; falls back to 1 when a metric can't — see _TrainingSession)
        self.rounds_per_dispatch = int(p.get("_rounds_per_dispatch", 1) or 1)
        self.objective_params = p
        if self.objective == "count:poisson" and "max_delta_step" not in p:
            self.max_delta_step = 0.7
        if self.tree_method == "gpu_hist":
            raise exc.UserError(
                "tree_method 'gpu_hist' is not available in the TPU container; use 'hist'."
            )
        self.predict_depth = (
            (self.max_depth if self.max_depth > 0 else self.max_leaves - 1)
            if self.grow_policy == "lossguide"
            else self.max_depth
        )
        self.eval_traversal = choose_eval_traversal(self.grow_policy)
        self.process_type = p.get("process_type", "default")
        if self.process_type not in ("default", "update"):
            raise exc.UserError(
                "process_type must be 'default' or 'update', got {!r}".format(
                    self.process_type
                )
            )


def _eval_metric_names(config, objective):
    metrics = config.eval_metric
    if metrics is None:
        metrics = [objective.default_metric]
    elif isinstance(metrics, str):
        metrics = [metrics]
    return list(metrics)


def _predict_margin_rows(forest, dm, block_rows=1 << 16):
    """``forest.predict_margin`` over a data/eval matrix's rows.

    DataMatrix inputs predict from their float features as always. Pre-binned
    inputs (chunked streaming ingest — the float channel was never
    materialized) predict from bounded blocks of *representative* values
    (``BinnedMatrix.rep_block``): every committed threshold is a cut value of
    the same cut set, so leaf routing — and therefore the margins — is
    bit-identical to predicting from the original floats, at O(block) peak
    memory instead of O(dataset).
    """
    if not isinstance(dm, BinnedMatrix) and not dm.is_sparse:
        return np.asarray(forest.predict_margin(dm.features), np.float32)
    if dm.num_row == 0:
        return np.zeros((0,), np.float32)
    # a sparse matrix gives its floats a block at a time (NaN = absent)
    block = dm.rep_block if isinstance(dm, BinnedMatrix) else dm.float_block
    parts = [
        np.asarray(
            forest.predict_margin(block(s, min(s + block_rows, dm.num_row))),
            np.float32,
        )
        for s in range(0, dm.num_row, block_rows)
    ]
    return np.concatenate(parts, axis=0)


def _merge_cuts_across_processes(local_sets, max_bin):
    """Every row shard of the job agrees on its cuts: this process's shards'
    candidate sets are allgathered and all of them (processes x local shards)
    merged by the one rule (``data/binning.py::merge_cut_candidates``).
    Deterministic: every process merges the same sets, so identical cuts
    everywhere. The TPU analog of xgboost's allreduced quantile sketch."""
    from jax.experimental import multihost_utils

    width = max_bin - 1
    columns = [c for cuts in local_sets for c in cuts]  # local shards x d
    mat = np.full((len(columns), width), np.nan, np.float32)
    counts = np.zeros(len(columns), np.int32)
    for f, c in enumerate(columns):
        mat[f, : len(c)] = c
        counts[f] = len(c)
    all_mats = np.asarray(multihost_utils.process_allgather(mat))       # [P, S*d, W]
    all_counts = np.asarray(multihost_utils.process_allgather(counts))  # [P, S*d]
    d = len(local_sets[0])
    return merge_cut_candidates(
        [
            [all_mats[p, f, : all_counts[p, f]] for f in range(lo, lo + d)]
            for p in range(all_mats.shape[0])
            for lo in range(0, all_mats.shape[1], d)
        ],
        max_bin,
    )


def _apply_packed_tree(packed, bins, margins, num_group, num_parallel, depth,
                       num_bins, backend, traversal, bundle=None, cat=None):
    """margins += the packed tree's (or tree stack's) outputs on ``bins``.

    Runs under trace (the round fn and the session apply fn), so the
    backend that decides the lowering of the bin fetch and of the node-table
    lookups must arrive as ``backend`` — the session's
    ``hist_knobs.backend`` snapshot, never a trace-time read.
    ``traversal``: the layout of the trees this session's builder makes
    (``TrainConfig.eval_traversal``): ``level`` walks the depth-wise heap
    level by level, ``replay`` takes the rows through a loss-guided tree's
    splits in the order they were made (``depth`` is the level walk's alone).
    ``bundle``: a bundled session's ``ops.bundle.BundleTables`` (the level
    walk's range test), None for every other. ``cat``: a categorical
    session's ``ops.categorical.CatTables`` (the level walk's set test; the
    packed trees then carry their sets), None for every other.
    """
    route_impl = choose_route_impl(backend, bins.shape[1])

    def one(t):
        if traversal == "level":
            return predict_binned_levels(
                t, bins, depth, num_bins, route_impl=route_impl,
                table_backend=backend, bundle=bundle, gathers=num_group == 1, cat=cat,
            )
        return predict_binned_steps(t, bins, num_bins, table_backend=backend)

    with stage(STAGE_EVAL_APPLY):
        tree = (
            tree_from_packed(packed) if cat is None
            else round_tree_from_packed(packed, cat.words)
        )
        if num_group == 1:
            if num_parallel > 1:
                delta = jax.vmap(one)(tree).sum(axis=0)
            else:
                delta = one(tree)
            return margins + delta
        if num_parallel > 1:
            # packed [P, C, ...]: sum the bagged parallel trees per class
            deltas = jax.vmap(jax.vmap(one))(tree).sum(axis=0)
        else:
            deltas = jax.vmap(one)(tree)
        return margins + deltas.T


@functools.lru_cache(maxsize=None)
def _calibrated_comm_ms(mesh, plan_key):
    """Standalone timing of one round's data-axis collectives (ms).

    lru_cached module factory: one calibration per (mesh, plan shapes)
    per PROCESS, not per session — a CV fold rebuild or an elastic
    reform that lands on an identical plan skips the compile + timing
    dispatches entirely (jax Meshes hash by device assignment + axis
    names, so a genuinely different topology still re-calibrates).

    Each DISTINCT payload shape in ``plan_key`` (tuples of
    ``(kind, shape, count)`` from ``round_comm_plan``) is timed as a
    standalone jitted ``psum`` on zeros (min of 3 reps after a warmup)
    and the per-round estimate is the count-weighted sum: what a host pays
    to dispatch the round's collectives one by one, an upper bound on the
    comm share and not the time they take inside the round program (the
    ``hist_allreduce`` stage reads that). Raises on failure — lru_cache does NOT
    memoize raising calls, so a transient failure (device momentarily
    busy) is retried by the next session rebuild instead of pinning the
    gauge to a cached 0.0 for the rest of the process; the caller
    (_calibrate_hist_comm_ms) catches and degrades to 0.0 for ITS session.
    """
    import time

    def psum_fn(x):
        return jax.lax.psum(x, "data")

    total_s = 0.0
    timed = {}
    for kind, shape, count in plan_key:
        key = (kind, shape)
        if key not in timed:
            # graftlint: disable=trace-uncached-jit — calibration-scope: lru_cached module factory, one standalone collective timing per distinct (mesh, plan shape) per process, off the round path
            mapped = jax.jit(
                jax.shard_map(
                    psum_fn,
                    mesh=mesh,
                    in_specs=(P(),),
                    out_specs=P(),
                    check_vma=False,
                )
            )
            x = jnp.zeros(shape, jnp.float32)
            jax.block_until_ready(mapped(x))  # compile + warm
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(mapped(x))
                best = min(best, time.perf_counter() - t0)
            timed[key] = best
        # one timing covers one tensor: hist and totals move G and H
        total_s += timed[key] * 2 * count
    return total_s * 1000.0


_approx_k_forcing_warned = False


def _warn_approx_k_forcing_once(requested):
    """Warn (once per process) that the approx re-sketch forces K -> 1.

    libxgboost's approx refreshes split candidates every ITERATION; a
    K-round dispatch would re-sketch only once per K rounds — a silent
    semantic weakening (ADVICE r5). GRAFT_APPROX_RESKETCH=0 restores
    batched dispatches (single global sketch, hist semantics) —
    docs/MIGRATION.md. Every CV fold / elastic generation rebuilds the
    session, so the log is deduplicated here rather than spamming one
    line per rebuild.
    """
    global _approx_k_forcing_warned
    if _approx_k_forcing_warned:
        return
    _approx_k_forcing_warned = True
    logger.warning(
        "tree_method='approx' re-sketches candidates before every "
        "boosting iteration; forcing _rounds_per_dispatch=%d -> 1 "
        "(set GRAFT_APPROX_RESKETCH=0 to keep batched dispatches "
        "with a single global sketch).",
        requested,
    )


def _pad_rows(array, target_rows, fill):
    n = array.shape[0]
    if n == target_rows:
        return array
    pad_shape = (target_rows - n,) + array.shape[1:]
    return np.concatenate([array, np.full(pad_shape, fill, array.dtype)], axis=0)


class _RowShard(typing.NamedTuple):
    """One data shard's rows of a matrix, in this process's padded layout."""

    lo: int
    hi: int
    device: typing.Any   # sketches and bins the shard (None: jax's default device)
    placements: tuple    # ((device, column slice), ...): who holds which of its columns


class _RowLayout(typing.NamedTuple):
    """How a matrix's rows lie over the devices: this process's row shards,
    and the sharding and global shape of the array they are assembled into
    (sharding None: one device)."""

    shards: tuple
    sharding: typing.Any
    shape: tuple

    @property
    def devices(self):
        return [shard.device for shard in self.shards]


class _TrainingSession:
    """Device state for one training run (bins, margins, jitted round fns)."""

    def __init__(
        self,
        config,
        dtrain,
        evals,
        forest,
        mesh=None,
        metric_names=None,
        has_feval=False,
        hist_knobs=None,
        bundles=False,
    ):
        # the one jax.monitoring listener: program loads (trace, lower,
        # compile, cache load) count under the span that is open, whoever
        # called train() (algorithm_train and serve install it as well)
        install_program_listener()
        self.config = config
        self.objective = forest.objective()
        self.num_group = self.objective.num_output_group
        self.mesh = mesh
        self.n_shards = int(np.prod(mesh.devices.shape)) if mesh is not None else 1
        # optional second mesh axis: column sharding for wide data
        self.has_feature_axis = mesh is not None and "feature" in mesh.axis_names
        self.n_feature_shards = (
            int(mesh.shape["feature"]) if self.has_feature_axis else 1
        )
        self.n_data_shards = (
            int(mesh.shape["data"]) if mesh is not None else 1
        )
        # the backend every kernel chooser reads and the histogram's operand
        # precision, snapshotted host-side ONCE per session (trace-safety:
        # graftlint trace-env-read forbids env reads in the traced build
        # path; flipping the env mid-job cannot desynchronize shards) and
        # threaded into the builders.
        # Callers may inject a snapshot: an elastic membership reform rebuilds
        # the session on a smaller mesh but MUST train under the same knobs
        # as the generation it resumes (no mid-job env drift).
        self.hist_knobs = hist_knobs if hist_knobs is not None else resolve_hist_knobs()
        # multi-host: every process holds its own row shard; device arrays are
        # assembled into global arrays over the whole mesh
        self.is_multiprocess = mesh is not None and jax.process_count() > 1
        if self.is_multiprocess and self.has_feature_axis:
            # every process must own whole rows (all columns of its row
            # shard) so host-local arrays assemble into the global 2-D
            # layout; the feature axis therefore has to live within a host
            local_feat = int(mesh.local_mesh.shape["feature"])
            if local_feat != self.n_feature_shards:
                raise exc.UserError(
                    "The 'feature' mesh axis must not span processes: build "
                    "the mesh with the data axis across hosts and the "
                    "feature axis over each host's local devices."
                )
        if self.is_multiprocess:
            # local rows pad to a multiple of the *local* data shards; the
            # global array is the concatenation over processes
            self.pad_unit = max(1, int(mesh.local_mesh.shape["data"]))
        else:
            self.pad_unit = self.n_data_shards

        labels = dtrain.labels
        self.objective.validate_labels(labels)

        self.is_ranking = getattr(self.objective, "needs_groups", False)
        # survival:cox multi-host watchlists are exact: the partial
        # likelihood does not decompose across hosts, so cox-nloglik rides
        # a dedicated global-rows path — all_gather over the data axis on
        # device (device_metrics needs_global_rows) or process_allgather on
        # the host evaluate() path — the same way the Cox gradients gather
        # global risk sets (r3 parity debt).
        # ranking layouts: single device keeps the [G, M] global layout;
        # on a mesh, rows are re-partitioned BY GROUP (groups never straddle
        # shards, so intra-group pairwise gradients stay shard-exact — the
        # reference's Rabit ranking path keeps worker groups whole the same
        # way, hyperparameter_validation.py:283-309 trains them under Rabit)
        self.rank_layout = None        # GroupLayout (numpy) of the train rows
        self.rank_perm = None          # device-order position -> original row
        self.rank_pos = None           # original (local) row -> device position
        if self.is_ranking:
            # ranking composes with a feature axis: the group-partitioned
            # row layout permutes ROWS only, so bins shard P("data",
            # "feature") as usual, rank_index replicates over the feature
            # axis, and the builder's cross-shard split combine + owner/psum
            # routing (ops/tree_build, ops/lossguide) do the column work
            # (r3 parity debt)
            if dtrain.groups is None:
                # xgboost convention: absent group info = one group per dataset
                groups = np.asarray([dtrain.num_row], np.int64)
            else:
                groups = np.asarray(dtrain.groups, np.int64)
            with span("setup.group_layout", attributes={"what": "train"}):
                self._build_rank_layout(groups, dtrain.num_row)

        pre_binned = isinstance(dtrain, BinnedMatrix)
        # columns given as categories (data/categorical.py): the bin matrix
        # holds a categorical column's codes as positions of its own bin
        # columns, so it is `cat.num_bin_columns` wide; None traces none of it
        self.cat = None
        if getattr(dtrain, "has_categorical", False):
            from ..data.categorical import CatLayout

            from ..ops.categorical import SET_TABLE_SELECT_MAX_ENTRIES, set_table_fits

            with span("setup.cat_encode", attributes={"what": "layout"}):
                self.cat = CatLayout.of(dtrain, config.max_bin)
            if not set_table_fits(self.cat.set_words, config.max_depth, self.hist_knobs.backend):
                raise exc.UserError(
                    "Categorical columns (feature_types 'c'): a column of {} categories at "
                    "max_depth={} needs a set table of {} x {} words a level, over the {} "
                    "the device reads; lower max_depth or group the rare categories.".format(
                        max(self.cat.cardinalities), config.max_depth,
                        1 << (config.max_depth - 1), self.cat.set_words,
                        SET_TABLE_SELECT_MAX_ENTRIES,
                    )
                )
        if self.is_multiprocess and config.max_bin is None:
            # libxgboost's exact updater is likewise single-machine only
            raise exc.UserError(
                "tree_method='exact' does not support distributed "
                "training (it doesn't in XGBoost either); use "
                "tree_method='hist'."
            )
        if pre_binned and (config.max_bin is None or int(config.max_bin) != dtrain.max_bin):
            # chunked streaming ingest: the sketch+bin stage already ran at
            # ingest time (with rank-agreed cuts); trust the matrix, but
            # fail loudly on a config/ingest max_bin drift — a silently
            # re-interpreted bin width would corrupt every histogram
            raise exc.UserError(
                "Pre-binned training data was ingested with max_bin={} "
                "but the training config resolves max_bin={}; re-ingest "
                "or align the hyperparameters.".format(
                    dtrain.max_bin, config.max_bin
                )
            )

        def _agreed_pad(num_row):
            """Local padded row count, agreed across processes. Hosts may
            hold UNEVEN row counts (ShardedByS3Key): every process must pad
            to the same local size or any global row gather (cox risk sets /
            cox-nloglik metric) hits a cross-host collective size mismatch
            (gloo: "402 vs 400") — equal device shards also keep the mesh
            layout uniform. Applies to the train rows AND every eval set;
            ranking agrees via its own maxima allgather above."""
            pad = -(-num_row // self.pad_unit) * self.pad_unit
            if not self.is_multiprocess:
                return pad
            from jax.experimental import multihost_utils

            return int(
                np.asarray(
                    multihost_utils.process_allgather(np.asarray([pad], np.int64))
                ).max()
            )

        self.n = dtrain.num_row
        if self.rank_perm is not None:
            n_pad = len(self.rank_perm)   # local_shards * rows_per_shard
        else:
            n_pad = _agreed_pad(self.n)

        def _layout_rows(arr, fill):
            """Original-order rows -> device layout (tail padding, or the
            group-partitioned permutation for distributed ranking)."""
            if self.rank_perm is None:
                return _pad_rows(arr, n_pad, fill)
            out = np.full((n_pad,) + arr.shape[1:], fill, arr.dtype)
            m = self.rank_perm >= 0
            out[m] = arr[self.rank_perm[m]]
            return out

        # column padding: features pad to a multiple of the feature shards
        # with always-missing columns (zero cuts -> never split on)
        d_real = dtrain.num_col if self.cat is None else self.cat.num_bin_columns
        d_pad = padded_feature_width(d_real, self.n_feature_shards)
        self.d_pad = d_pad

        self._bytes_put = 0  # host bytes handed to the device by _put

        def _put(local_np, spec):
            """Local host array -> placed device array (global across procs)."""
            self._bytes_put += int(local_np.nbytes)
            if self.mesh is None:
                return jnp.asarray(local_np)
            from jax.sharding import NamedSharding

            sharding = NamedSharding(self.mesh, spec)
            if self.is_multiprocess:
                return jax.make_array_from_process_local_data(sharding, local_np)
            return jax.device_put(local_np, sharding)

        self.bins_spec = (
            P("data", "feature") if self.has_feature_axis else P("data", None)
        )
        self.feat_spec = P("feature") if self.has_feature_axis else P()
        margin_spec = P("data") if self.num_group == 1 else P("data", None)

        self._put = _put
        self._layout_rows = _layout_rows
        self._d_real = d_real
        self._n_pad = n_pad

        # approx re-sketch state (see _resketch_bins)
        self._grad_fn = None
        self.approx_resketch = (
            config.tree_method == "approx"
            and os.environ.get("GRAFT_APPROX_RESKETCH", "1") != "0"
        )
        if self.approx_resketch and pre_binned:
            # the per-round re-sketch needs the float channel resident —
            # exactly what chunked ingest exists to avoid. The ingest gating
            # refuses approx up front; this is the defense for direct API
            # callers handing a BinnedMatrix to an approx config.
            logger.warning(
                "tree_method='approx' with pre-binned input keeps the "
                "ingest-time sketch (no per-iteration re-binning)."
            )
            self.approx_resketch = False
        if self.approx_resketch and self.rank_perm is not None:
            logger.warning(
                "tree_method='approx' with distributed ranking keeps the "
                "initial sketch (the group-partitioned row layout does not "
                "support per-iteration re-binning)."
            )
            self.approx_resketch = False
        # A matrix is set up shard by shard, each on the chip that will hold
        # it (one device: one shard): sketched there, the shards' candidates
        # merged into one set of cuts (every host must bin with identical
        # thresholds or the psum'd histograms are meaningless — the TPU
        # analog of xgboost's allreduced weighted quantile sketch), binned
        # there against the merged cuts, and the bins left there. Pre-binned
        # input (chunked streaming ingest) already agreed its cuts cross-rank
        # through the ingest sketch allgather and is only dealt out.
        self._dtrain = dtrain
        self._train_floats = None    # the train rows' float blocks, a shard each
        # a sparse input the shape rule picks is bundled (data/bundling.py):
        # its bin matrix is `bundled.plan.num_bundles` wide, not d_pad
        self.bundle = None
        bundled = (
            self._bundle_inputs(dtrain, evals)
            if bundles and self._takes_bundles(dtrain, evals)
            else None
        )
        self._train_rows = self._row_shards(
            n_pad, d_pad if bundled is None else bundled.plan.num_bundles, self.bins_spec
        )
        if bundled is not None:
            self.bundle = bundled.plan
            cuts, max_bin = bundled.plan.cut_points, bundled.plan.max_bin
            shard_bins = [bundled.bins[0]]
        elif pre_binned:
            cuts, max_bin = dtrain.cut_points, dtrain.max_bin
            shard_bins = [
                self._shard_rows(dtrain.bins, shard, max_bin, self.rank_perm)
                for shard in self._train_rows.shards
            ]
        else:
            self._train_floats = self._float_rows(
                self._bin_column_floats(dtrain, "train"), self._train_rows, self.rank_perm
            )
            weights = dtrain.weights
            cuts = self._sketch(
                None if weights is None else [
                    self._shard_rows(weights, shard, 0.0, self.rank_perm)
                    for shard in self._train_rows.shards
                ]
            )
            self._note_setup_memory("setup.sketch")
            max_bin = resolve_max_bin(cuts, config.max_bin, config.exact_bin_cap)
            shard_bins = apply_shards(
                self._train_floats, cuts, max_bin, self._train_rows.devices, name="train"
            )
        self._stage_train_bins(shard_bins, cuts, max_bin)
        if self.cat is not None:
            self._note_categorical_shape(shard_bins)
        elif bundled is None:
            self._note_binned_shape(shard_bins)
        else:
            self._note_bundled_shape(bundled)
        del shard_bins
        if not self.approx_resketch:
            self._train_floats = None  # only the re-sketch reads them again
        self.eval_sets = []
        self._eval_rows = []    # per eval set: its row layout (None = shared)
        self._eval_floats = {}  # eval-set index -> its float blocks (re-sketch)
        staged = []  # per eval set: its bins a shard, placed under `setup.upload` below
        for i, (dm, name) in enumerate(evals):
            if dm is dtrain:
                self.eval_sets.append((name, dm, self.train_binned))
                self._eval_rows.append(None)
                staged.append(None)
                continue
            rows = self._row_shards(
                _agreed_pad(dm.num_row),
                d_real if bundled is None else bundled.plan.num_bundles,
                P("data", None),
            )
            if isinstance(dm, BinnedMatrix):
                # pre-binned eval set: must carry the training channel's
                # bin edges (streaming ingest bins validation with the
                # train cuts) or its bin indices mean different thresholds
                if dm.max_bin != max_bin or not (
                    dm.cut_points is cuts
                    or (
                        len(dm.cut_points) == len(cuts)
                        and all(
                            np.array_equal(a, b)
                            for a, b in zip(dm.cut_points, cuts)
                        )
                    )
                ):
                    raise exc.AlgorithmError(
                        "pre-binned eval set {!r} was binned with different "
                        "cut points than the training data".format(name)
                    )
                binned = dm
                shard_bins = [
                    self._shard_rows(dm.bins, shard, max_bin) for shard in rows.shards
                ]
            else:
                binned = BinnedMatrix(
                    partial(self._eval_bins_to_host, i), cuts, max_bin,
                    labels=dm.labels, weights=dm.weights, groups=dm.groups,
                    shape=(dm.num_row, d_real),
                )
                if bundled is None:
                    shard_bins = self._bin_eval_rows(i, name, dm, rows, cuts, max_bin)
                else:
                    shard_bins = [bundled.bins[bundled.index_of[id(dm)]]]
            self.eval_sets.append((name, dm, binned))
            self._eval_rows.append(rows)
            staged.append(shard_bins)
        self._note_setup_memory("setup.bin_apply")
        with self._upload_span("labels_weights_margins"):
            self.labels = _put(_layout_rows(labels, 0.0), P("data"))
            self.weights = _put(_layout_rows(dtrain.get_weight(), 0.0), P("data"))
            self.groups = dtrain.groups
            # what a ranking round reads in slot order and never changes goes
            # to the device with the index it belongs to (`_put_layout`): a
            # round gathers the margins alone
            from .device_metrics import ndcg_cutoffs

            self._slot_cutoffs = tuple(sorted({0, *ndcg_cutoffs(metric_names or ())}))
            if self.rank_layout is None:
                self.rank_index_dev = jnp.zeros((1, 1), jnp.int32)  # inert dummy
            else:
                with span("setup.group_layout", attributes={"what": "train_slots"}):
                    self.rank_index_dev = self._put_layout(
                        self.rank_layout, self.labels, self.weights, self._rank_specs()
                    )

            base = self.objective.base_margin(forest.base_score)
            shape = (n_pad,) if self.num_group == 1 else (n_pad, self.num_group)
            if forest.trees:
                margin = _predict_margin_rows(forest, dtrain).reshape(
                    (self.n,) if self.num_group == 1 else (self.n, self.num_group)
                )
                self.margins = _put(
                    _layout_rows(margin.astype(np.float32), base), margin_spec
                )
            else:
                self.margins = _put(np.full(shape, base, np.float32), margin_spec)

        # eval-set device state: bins cached once, margins incremental;
        # labels/weights kept on device for batched device-side metrics
        self.eval_bins = []
        self.eval_margins = []
        self.eval_labels = []
        self.eval_weights = []
        with self._upload_span("eval_sets"):
            for i, (name, dm, binned) in enumerate(self.eval_sets):
                if binned is self.train_binned:
                    self.eval_bins.append(None)     # shares training margins
                    self.eval_margins.append(None)
                    self.eval_labels.append(self.labels)
                    self.eval_weights.append(self.weights)
                    continue
                m_pad = self._eval_rows[i].shards[-1].hi
                self.eval_bins.append(self._place_rows(staged[i], self._eval_rows[i]))
                self.eval_labels.append(_put(_pad_rows(dm.labels, m_pad, 0.0), P("data")))
                self.eval_weights.append(
                    _put(_pad_rows(dm.get_weight(), m_pad, 0.0), P("data"))
                )
                eshape = (m_pad,) if self.num_group == 1 else (m_pad, self.num_group)
                if forest.trees:
                    em = _predict_margin_rows(forest, dm).reshape(
                        (dm.num_row,) if self.num_group == 1 else (dm.num_row, self.num_group)
                    )
                    self.eval_margins.append(
                        _put(_pad_rows(em.astype(np.float32), m_pad, base), margin_spec)
                    )
                else:
                    self.eval_margins.append(_put(np.full(eshape, base, np.float32), margin_spec))
        self._note_setup_memory("setup.upload")

        self.rng = jax.random.PRNGKey(config.seed)

        self.rounds_per_dispatch = max(1, config.rounds_per_dispatch)
        if self.approx_resketch and self.rounds_per_dispatch > 1:
            _warn_approx_k_forcing_once(self.rounds_per_dispatch)
            self.rounds_per_dispatch = 1
        self.device_metric_fns = None
        # Device metrics decompose into psum-able partial stats
        # (device_metrics.py), so they work on any mesh: K-round batching
        # psums per-round stat vectors over the "data" axis inside the
        # jitted scan, and multi-process runs get globally exact metric
        # lines (reference semantics: metrics allreduced under the
        # communicator, distributed.py:219). They activate when batching is
        # requested (K > 1) or when multi-process exactness needs them.
        # A ranking job's metrics are per query group (ndcg): on one device
        # they ride the scan over each evaluation set's bucketed group layout
        # (device_metrics.grouped_ndcg), so K rounds log K metric lines and
        # the host pulls no margins. On a mesh the evaluation rows are not
        # partitioned by group, and a set without groups is one group of all
        # its rows: both stay on the host path below, as `map` does.
        grouped_metrics = (
            self.is_ranking
            and mesh is None
            and all(dm.groups is not None for _name, dm, _b in self.eval_sets)
        )
        want_device_metrics = (
            self.eval_sets
            and metric_names
            and not has_feval
            and (not self.is_ranking or grouped_metrics)
            and (self.rounds_per_dispatch > 1 or self.is_multiprocess)
        )
        self.eval_layouts = ()  # a GroupLayout per non-shared evaluation set
        if want_device_metrics:
            from .device_metrics import all_supported

            self.device_metric_fns = all_supported(
                metric_names,
                self.objective.name,
                self.num_group,
                config.objective_params,
                grouped=grouped_metrics,
            )
            if self.device_metric_fns is not None:
                self.device_metric_names = list(metric_names)
                if any(fn.needs_groups for fn in self.device_metric_fns):
                    with span("setup.group_layout", attributes={"what": "eval_sets"}):
                        self.eval_layouts = tuple(
                            self._put_layout(
                                build_group_layout(dm.groups),
                                self.eval_labels[i], self.eval_weights[i],
                            )
                            for i, (_name, dm, binned) in enumerate(self.eval_sets)
                            if binned is not self.train_binned
                        )
        self._note_rank_gathers()
        # Metrics outside device_metrics.all_supported (feval, ranking
        # metrics, non-decomposable scalars) no longer force K -> 1: the
        # fused dispatch keeps K, the scan carries every eval set's margins
        # on device, and the HOST evaluates once per dispatch — metric
        # lines land every K rounds at the batch-end round index instead of
        # every round (the documented host-fallback cadence, docs/DESIGN.md
        # §Round pipeline; callbacks skip stale rounds).
        self.host_eval_batched = (
            self.rounds_per_dispatch > 1
            and bool(self.eval_sets)
            and self.device_metric_fns is None
        )
        if self.host_eval_batched:
            logger.info(
                "_rounds_per_dispatch=%d with eval metrics that cannot ride "
                "back from the device: keeping the fused dispatch; host "
                "metrics are computed once per dispatch (every %d rounds).",
                self.rounds_per_dispatch, self.rounds_per_dispatch,
            )
        # the lax.scan round path carries eval margins + metric stats on
        # device; used for K > 1 and for exact multi-process evaluation
        self.use_scan_rounds = self.rounds_per_dispatch > 1 or (
            self.device_metric_fns is not None and self.is_multiprocess
        )
        from ..telemetry import REGISTRY

        REGISTRY.gauge(
            "dispatch_fused_rounds",
            "Boosting rounds fused into one device dispatch per round "
            "program (the lax.scan length K of the fused round pipeline)",
        ).set(self.rounds_per_dispatch)

        monotone = np.zeros(self.d_pad, np.int32)
        if config.monotone_constraints:
            vals = np.asarray(config.monotone_constraints, np.int32)
            monotone[: len(vals)] = vals
        self.monotone = jnp.asarray(monotone)
        self.has_monotone = bool(config.monotone_constraints)

        # static per-round collective footprint (telemetry): the data-axis
        # histogram collectives' shapes + wire bytes, derived from the same
        # level/step structure the builders trace (docs/DESIGN.md
        # §Communication has the formula)
        self.hist_comm_plan, self.hist_comm_bytes_per_round = self._comm_plan()
        self._hist_comm_ms = None  # lazily calibrated at the first dispatch
        self._set_comm_round_fields()
        REGISTRY.gauge(
            "mesh_data_shards",
            "Row shards of the training mesh's `data` axis (1 on one device)",
        ).set(self.n_data_shards)
        REGISTRY.gauge(
            "hist_allreduce_bytes_per_round",
            "Wire bytes one round's data-axis histogram collectives move, "
            "from the round program's shapes (ring formula, docs/DESIGN.md "
            "Communication; 0 on one device)",
        ).set(self.hist_comm_bytes_per_round)
        # the round's shape as gauges (``round_class_trees``: the trees a
        # round grows; ``round_split_steps``: a loss-guided build's split
        # steps), in a method below the round program: a line moved above
        # the traced code moves the compile cache's key (PERF.md section 5)
        # and costs every job one cold compile
        _note_round_shape(self)
        tiles, tiles_unfolded, skipped_pct = self._onehot_tile_plan()
        REGISTRY.gauge(
            "hist_onehot_tiles_per_round",
            "One-hot tiles ([128 rows, 128 bin lanes]) a shard's level "
            "histogram kernel latches a round: over the build's histogram "
            "levels, row tiles x features x bin tiles after the fold (x tiles of "
            "features where they share one), once a group of class trees, less "
            "the tiles above a column's highest bin in the calls that do not "
            "fold (ops/histogram.py::_bin_fold, _tile_pack, _class_groups, "
            "_live_tiles; 0 off the kernel)",
        ).set(tiles)
        REGISTRY.gauge(
            "hist_onehot_tiles_unfolded_per_round",
            "The same count with every level's fold at 1: what the kernel "
            "would latch with the whole bin axis in the one-hot",
        ).set(tiles_unfolded)
        REGISTRY.gauge(
            "hist_tiles_skipped_pct",
            "Share (%) of the one-hot tiles of the round's calls that do not "
            "fold which the kernel does not build because no bin of their "
            "column can land on them, by the cuts the session was built with "
            "(the kernel's lists are made from num_cuts on the chip; 0 where "
            "every call folds)",
        ).set(skipped_pct)

        # every dispatch records a `host_dispatch` span (python + XLA
        # dispatch until the async call returns) and a `device_sync` span
        # (the transfer of the packed trees, which blocks on the round
        # program and is there anyway). SM_TRACE_DEVICE_SYNC = N adds a
        # block_until_ready fence on every Nth dispatch inside `device_sync`:
        # the K = 1 path's eval-apply programs are separate dispatches that
        # the tree transfer does not wait for. Resolved ONCE here, host-side,
        # like the hist knobs: the traced round path never reads env.
        from ..telemetry.tracing import DEVICE_SYNC_ENV
        from ..utils.envconfig import env_int

        self._device_sync_every = env_int(DEVICE_SYNC_ENV, 0, minimum=0)
        self._dispatch_index = 0
        self._turnaround = None  # the open `host_turnaround` span
        self._first_dispatch = None  # the open `setup.first_dispatch` span

        # model-quality plane (SM_MODEL_TELEMETRY): resolved ONCE here,
        # host-side, like the hist knobs — unset traces exactly the pre-PR
        # round program (no stats outputs at all); set adds read-only
        # reductions of g/h/margins, so committed trees are bit-identical
        # either way. The drift baseline is one bincount per feature over
        # the already-binned matrix, captured now and stamped into the
        # model manifest at save time.
        from ..telemetry import model as model_telemetry

        self.learning_stats = model_telemetry.enabled()
        self.last_learning_stats = []
        if self.learning_stats and self.bundle is None and self.cat is None:
            # (a bundled or categorical session holds no per-column bins to count)
            model_telemetry.capture_drift_baseline(self.train_binned)

        with span("setup.program_build"):
            self._round_fn = self._make_round_fn()
            self._apply_fn = self._make_apply_fn()
        self._note_setup_memory("setup.program_build")
        self._introspect_compiled_cost(self._register_round_program())

    def _put_layout(self, layout, labels, weights, specs=None):
        """A host ``GroupLayout`` as device arrays, its slot columns gathered
        there from the rows' ``labels`` and ``weights`` (device arrays, laid
        out as the rows): whole on one device, by ``specs`` (the placed
        layout's partition specs) on a mesh, a shard's columns from its rows."""
        cutoffs = self._slot_cutoffs
        if specs is None:
            placed = jax.tree_util.tree_map(lambda a: self._put(a, P()), layout)
            return with_slot_columns(placed, labels, weights, cutoffs)
        bare = specs._replace(slots=())
        placed = jax.tree_util.tree_map(self._put, layout, bare)
        fill = jax.shard_map(
            partial(with_slot_columns, cutoffs=cutoffs),
            mesh=self.mesh,
            in_specs=(bare, P("data"), P("data")),
            out_specs=specs,
            check_vma=False,
        )
        # graftlint: disable=trace-uncached-jit — session-scope construction: a mesh's train layout is filled once per training session
        return jax.jit(fill)(placed, labels, weights)

    def _rank_specs(self):
        """How a mesh shards the train rows' ``GroupLayout`` (one index a
        shard, its slot columns with it, its rows' slots with the rows);
        None on one device."""
        if self.rank_layout is None or self.mesh is None:
            return None
        by_slot = P("data", None, None)
        columns = SlotColumns(
            by_slot, by_slot, by_slot, by_slot,
            {k: P("data", None) for k in self._slot_cutoffs},
        )
        return GroupLayout((by_slot,), P("data"), P(), (columns,))

    def _note_rank_gathers(self):
        """Gauges of what a ranking round fetches through a row index: set
        once, from the layouts' shapes."""
        if self.rank_layout is None:
            return
        from ..telemetry import REGISTRY

        grouped = sum(fn.needs_groups for fn in self.device_metric_fns or ())
        shared = sum(binned is self.train_binned for _n, _dm, binned in self.eval_sets)
        train_buckets = len(self.rank_index_dev.indices)
        REGISTRY.gauge(
            "rank_row_gathers_per_round",
            "Row-to-slot gathers one round issues over all buckets: the "
            "gradient's over the train layout and each grouped device "
            "metric's over every evaluation set's (one a bucket and caller: "
            "the margins)",
        ).set(
            train_buckets * (1 + grouped * shared)
            + grouped * sum(len(layout.indices) for layout in self.eval_layouts)
        )
        REGISTRY.gauge(
            "rank_slot_constant_bytes",
            "Bytes of the slot columns the group layouts carry on the device "
            "(labels, weights, valid, gains, ideal DCG; train and evaluation "
            "sets)",
        ).set(
            sum(
                int(column.nbytes)
                for layout in (self.rank_index_dev,) + self.eval_layouts
                for column in jax.tree_util.tree_leaves(layout.slots)
            )
        )

    def _build_rank_layout(self, groups, num_row):
        """The train rows' query groups as the round program takes them: on
        one device bucketed by size (ops/ranking.build_group_layout); on a
        mesh rows are re-partitioned BY GROUP, one index a shard."""
        from ..telemetry import REGISTRY

        if self.mesh is None:
            self.rank_layout = build_group_layout(groups)
        else:
            from ..ops.ranking import build_sharded_group_layout

            # DATA shards only: with a feature axis, local_devices also
            # counts column shards, which hold the same rows
            local_shards = (
                max(1, int(self.mesh.local_mesh.shape["data"]))
                if self.is_multiprocess
                else self.n_data_shards
            )
            perm, layout, rps = build_sharded_group_layout(groups, local_shards)
            if self.is_multiprocess:
                # all hosts must agree on padded shapes
                from jax.experimental import multihost_utils

                ri = layout.indices[0]
                maxima = np.asarray(
                    multihost_utils.process_allgather(
                        np.asarray([rps, ri.shape[1], ri.shape[2]], np.int64)
                    )
                ).max(axis=0)
                perm, layout, rps = build_sharded_group_layout(
                    groups,
                    local_shards,
                    rows_per_shard=int(maxima[0]),
                    max_groups_per_shard=int(maxima[1]),
                    max_group_size=int(maxima[2]),
                )
            self.rank_perm = perm
            self.rank_layout = layout
            pos = np.full(num_row, -1, np.int64)
            m = perm >= 0
            pos[perm[m]] = np.nonzero(m)[0]
            self.rank_pos = pos
        # what a round computes against what the groups hold: set once
        REGISTRY.gauge(
            "rank_pair_slots",
            "Pair slots one round's LambdaMART pair pass computes over the "
            "padded group layout (this host's shards)",
        ).set(pair_slots(self.rank_layout))
        REGISTRY.gauge(
            "rank_pairs_real",
            "Ordered document pairs inside the query groups: the sum of the "
            "squared group sizes",
        ).set(float(np.sum(np.square(groups.astype(np.float64)))))

    def _row_shards(self, n_pad, width, spec):
        """The ``_RowLayout`` of a matrix of ``n_pad`` local rows (padding
        included) x ``width`` columns laid out by ``spec``: a shard for each
        of this process's data shards, the device that sets it up (the one
        that holds its first columns) and who holds which of its columns."""
        if self.mesh is None:
            whole = _RowShard(0, n_pad, None, ((None, slice(0, width)),))
            return _RowLayout((whole,), None, (n_pad, width))
        from jax.sharding import NamedSharding

        sharding = NamedSharding(self.mesh, spec)
        shape = (n_pad * (jax.process_count() if self.is_multiprocess else 1), width)
        by_rows = {}
        for device, (rows, cols) in sharding.addressable_devices_indices_map(shape).items():
            lo, hi, _ = rows.indices(shape[0])
            c_lo, c_hi, _ = cols.indices(shape[1])
            by_rows.setdefault((lo, hi), []).append((c_lo, device.id, device, slice(c_lo, c_hi)))
        first = min(lo for lo, _hi in by_rows)  # this process's first global row
        shards = tuple(
            _RowShard(
                lo - first, hi - first, min(places)[2],
                tuple((device, cols) for _c, _id, device, cols in sorted(places)),
            )
            for (lo, hi), places in sorted(by_rows.items())
        )
        return _RowLayout(shards, sharding, shape)

    def _shard_rows(self, arr, shard, fill, perm=None):
        """A shard's rows of a host array in original row order: the rows of
        its slice of the padded layout (``perm``: the group-partitioned
        permutation of distributed ranking), padding filled with ``fill``."""
        if perm is None:
            return _pad_rows(arr[shard.lo : shard.hi], shard.hi - shard.lo, fill)
        take = perm[shard.lo : shard.hi]
        out = np.full((len(take),) + arr.shape[1:], fill, arr.dtype)
        m = take >= 0
        out[m] = arr[take[m]]
        return out

    def _sketch(self, weights):
        """The training rows' cuts: every shard sketched on its own chip
        under ``weights`` (a block a shard; None: unit weights), the
        candidates merged over this process's shards and, in a multi-process
        job, every other process's. The exact method's candidate set is no
        sketch (every distinct midpoint of the matrix): it is taken whole."""
        max_bin = self.config.max_bin
        if max_bin is None:
            return sketch_shards([self._dtrain.features], [self._dtrain.weights], None)
        if self.cat is not None:
            # a categorical column is not sketched: its chunks' cuts follow
            # its cardinality (one device: `train()` refuses a mesh)
            numeric = self.cat.numeric_columns
            cuts = []
            if len(numeric):
                cuts = sketch_shards(
                    [block[:, numeric] for block in self._train_floats],
                    weights or [None] * len(self._train_floats),
                    max_bin,
                    self._train_rows.devices,
                )
            with span("setup.cat_encode", attributes={"what": "cuts"}):
                return self.cat.cuts(cuts)
        merge = (
            partial(_merge_cuts_across_processes, max_bin=max_bin)
            if self.is_multiprocess
            else None
        )
        return sketch_shards(
            self._train_floats,
            weights or [None] * len(self._train_floats),
            max_bin,
            self._train_rows.devices,
            merge=merge,
        )

    def _place_rows(self, blocks, layout, fill=None):
        """A matrix's row shards -> the placed (global) device array. A block
        that is on its shard's device already moves nothing; its columns pad
        to the layout's width with ``fill`` and go to whoever holds them."""
        pieces = []
        for block, shard in zip(blocks, layout.shards):
            if isinstance(block, np.ndarray):
                self._bytes_put += int(block.nbytes)
            pad = layout.shape[1] - block.shape[1]
            if pad:
                xp = np if isinstance(block, np.ndarray) else jnp
                block = xp.concatenate(
                    [block, xp.full((block.shape[0], pad), fill, block.dtype)], axis=1
                )
            for device, cols in shard.placements:
                part = block if len(shard.placements) == 1 else block[:, cols]
                pieces.append(jnp.asarray(part) if device is None else jax.device_put(part, device))
        if layout.sharding is None:
            return pieces[0]
        return jax.make_array_from_single_device_arrays(layout.shape, layout.sharding, pieces)

    def _train_bins_to_host(self, bins):
        """A placed train bin matrix's real rows and columns on the host, in
        original row order (this process's rows)."""
        self._refuse_bundled_bins()
        if self.rank_pos is not None:
            return self._to_host(bins, None)[:, : self._d_real][self.rank_pos]
        return self._to_host(bins, self.n)[:, : self._d_real]

    def _eval_bins_to_host(self, index):
        self._refuse_bundled_bins()
        dm = self.eval_sets[index][1]
        return self._to_host(self.eval_bins[index], dm.num_row)[:, : self._d_real]

    def _float_rows(self, features, layout, perm=None):
        """A matrix's float rows a shard, padding rows all NaN: missing in
        every column, so they weigh nothing in a sketch and bin to the
        missing bin, which is what padding holds. An approx job under the
        device sketch stages each block on its shard's chip ONCE —
        re-uploading [n, d] floats every dispatch would pay n*d*4 bytes of
        host->HBM per round. Trade: the staged floats stay resident
        alongside the round program for the whole job; GRAFT_SKETCH_IMPL=host
        trades them back for per-round uploads if an approx job is HBM-bound."""
        from ..data.binning import _float_block, _sketch_impl

        blocks = [self._shard_rows(features, shard, np.nan, perm) for shard in layout.shards]
        if self.approx_resketch and _sketch_impl() == "device":
            blocks = [_float_block(b, shard.device) for b, shard in zip(blocks, layout.shards)]
        return blocks

    def _shard_values(self, arr, layout):
        """A placed row vector (the hessians) a shard, for the sketch's
        weights: under the device sketch the piece each shard's chip holds,
        which never leaves it; else the host's copy, cut by shard."""
        from ..data.binning import _sketch_impl

        if _sketch_impl() != "device":
            host = np.asarray(self._to_host(arr, None), np.float32)
            return [host[shard.lo : shard.hi] for shard in layout.shards]
        if layout.sharding is None:
            return [arr]
        held = {piece.device: piece.data for piece in arr.addressable_shards}
        return [held[shard.device] for shard in layout.shards]

    def _bin_eval_rows(self, index, name, dm, layout, cuts, max_bin):
        """An evaluation set's bins a shard, each binned on its own chip
        under the training cuts."""
        floats = self._eval_floats.get(index)
        if floats is None:
            floats = self._float_rows(self._bin_column_floats(dm, name), layout)
            if self.approx_resketch:
                self._eval_floats[index] = floats
        return apply_shards(floats, cuts, max_bin, layout.devices, name=name)

    def _bin_column_floats(self, dm, name):
        """A matrix's floats as the bin-apply takes them: its own, or where
        columns are given as categories one column a bin column, a chunk's
        holding the position of the row's category (``CatLayout.expand``)."""
        if self.cat is None:
            return dm.features
        if not self.cat.same_types(dm):
            raise exc.UserError(
                "Evaluation set {!r} does not name the training data's feature_types: "
                "a column is a category in both or in neither.".format(name)
            )
        with span("setup.cat_encode", attributes={"what": name}):
            return self.cat.expand(dm.features)

    def _note_categorical_shape(self, shard_bins):
        """``_note_binned_shape`` for a session with categorical columns, in
        *original* columns: a categorical column's cell is missing where the
        row sits in the missing slot of every chunk (a row that holds a value
        sits in all but one); the sketch's counts are the numeric columns'."""
        from ..telemetry import REGISTRY

        cat, binned = self.cat, self.train_binned
        missing_bin = sum(
            int((block == binned.max_bin).sum()) for block in shard_bins
        ) - (self._n_pad - self.n) * cat.num_bin_columns
        extra_chunks = cat.num_bin_columns - cat.num_col
        numeric = cat.numeric_columns
        gauges = (
            ("train_cells_missing", "Cells of the binned training matrix in the missing bin",
             missing_bin - extra_chunks * self.n),
            ("train_cells_total", "Cells of the binned training matrix (rows x columns)",
             self.n * cat.num_col),
            ("sketch_cuts_selected", "Cut points the sketch selected, summed over columns",
             sum(len(binned.cut_points[c]) for c in numeric)),
            ("sketch_cut_slots", "Cut slots a level histogram carries: columns x (max_bin - 1)",
             len(numeric) * (binned.max_bin - 1)),
            ("train_columns_total", "Columns of the binned training matrix", cat.num_col),
            ("train_columns_categorical", "Training columns given as categories "
             "(feature_types 'c'): their bins are their codes", cat.num_col - len(numeric)),
            ("train_bin_columns", "Bin columns of a training matrix with categorical columns: "
             "a numeric column's one and a categorical column's chunks "
             "(data/categorical.py)", cat.num_bin_columns),
            ("cat_set_words", "32-bit words of a node's category set in the round program's "
             "level tables: the widest categorical column's, 32 categories a word",
             cat.set_words),
        )
        for name, text, value in gauges:
            REGISTRY.gauge(name, text).set(float(value))

    def _note_binned_shape(self, shard_bins):
        """What the binned training matrix holds, set once: how many of its
        cells sit in the missing bin (counted where the shards lie; padding
        rows, which are all missing, taken off), how many of the
        histogram's cut slots (``max_bin - 1`` a column, all of them built
        every level) the sketch filled, and how many columns hold one value
        in every row (all of a column's rows in bin 0 under its one cut, or
        none with a value at all): no split can use them, and every level
        histograms them all the same. This host's rows."""
        from ..telemetry import REGISTRY

        binned = self.train_binned
        counts = [(block == binned.max_bin).sum() for block in shard_bins]
        missing = sum(int(c) for c in counts) - (self._n_pad - self.n) * binned.num_col
        # padding rows sit in the missing bin, never in bin 0
        in_first_bin = sum(np.asarray((block == 0).sum(axis=0)) for block in shard_bins)
        constant = sum(
            len(cuts) == 0 or int(in_first_bin[f]) == binned.num_row
            for f, cuts in enumerate(binned.cut_points)
        )
        gauges = (
            ("train_cells_missing", "Cells of the binned training matrix in the missing bin",
             missing),
            ("train_cells_total", "Cells of the binned training matrix (rows x columns)",
             binned.num_row * binned.num_col),
            ("sketch_cuts_selected", "Cut points the sketch selected, summed over columns",
             sum(len(c) for c in binned.cut_points)),
            ("sketch_cut_slots", "Cut slots a level histogram carries: columns x (max_bin - 1)",
             binned.num_col * (binned.max_bin - 1)),
            ("train_columns_constant", "Training columns that hold one value in every row",
             constant),
            ("train_columns_total", "Columns of the binned training matrix", binned.num_col),
        )
        for name, text, value in gauges:
            REGISTRY.gauge(name, text).set(float(value))

    # ------------------------------------------------------------------ jit
    def _grad_hess_fn(self):
        if not self.is_ranking:
            return None
        # (margins, layout); a shard's slices under shard_map
        return partial(lambdarank_grad_hess, scheme=self.objective.scheme)

    def _make_round_fn(self):
        cfg = self.config
        num_bins = self.train_binned.num_bins
        axis_name = "data" if self.mesh is not None else None
        feature_axis = "feature" if self.has_feature_axis else None
        interaction_sets = None
        if cfg.interaction_constraints:
            d_cols = self.train_binned.num_col
            # width = padded GLOBAL columns: with a feature axis the split
            # ids crossing shards are global, and per-shard masks slice out
            # their own column segment (tree_build._local_cols)
            sets_np = np.zeros((len(cfg.interaction_constraints), self.d_pad), bool)
            for s, members in enumerate(cfg.interaction_constraints):
                for f in members:
                    if 0 <= int(f) < d_cols:
                        sets_np[s, int(f)] = True
            interaction_sets = jnp.asarray(sets_np)

        # With num_parallel_tree=K, all K trees of a round fit the *same*
        # gradients (a bagged forest step), so their summed corrections are
        # averaged via eta/K — otherwise the round overshoots by K.
        effective_eta = cfg.eta / cfg.num_parallel_tree
        common = dict(
            num_bins=num_bins,
            reg_lambda=cfg.reg_lambda,
            alpha=cfg.alpha,
            gamma=cfg.gamma,
            min_child_weight=cfg.min_child_weight,
            eta=effective_eta,
            max_delta_step=cfg.max_delta_step,
            colsample_bylevel=cfg.colsample_bylevel,
            colsample_bynode=cfg.colsample_bynode,
            axis_name=axis_name,
            interaction_sets=interaction_sets,
            feature_axis_name=feature_axis,
            n_feature_shards=self.n_feature_shards,
            d_global=self.train_binned.num_col,
            knobs=self.hist_knobs,
        )
        if cfg.grow_policy == "lossguide":
            from ..ops.lossguide import build_tree_lossguide

            builder = partial(
                build_tree_lossguide,
                max_leaves=cfg.max_leaves,
                max_depth=cfg.max_depth,
                **common,
            )
        else:
            builder = partial(build_tree, max_depth=cfg.max_depth, **common)
            if self.bundle is not None:
                builder = partial(builder, bundle=self.bundle.tables)
            if self.cat is not None:
                builder = partial(builder, cat=self.cat_tables)
        ranking_grads = self._grad_hess_fn()
        grad_hess = self.objective.grad_hess
        if self.objective.name == "survival:cox" and axis_name is not None:
            # Cox risk sets span the WHOLE dataset (cumulative sums over the
            # global time ordering), so shard-local gradients would be
            # silently wrong. Exact distributed form: all_gather the margin/
            # label/weight shards over the data axis inside the jitted round,
            # compute global gradients (replicated — padding rows carry
            # weight 0 and drop out), and slice this shard's row segment.
            # This is exact where the reference's per-worker Cox is not.
            base_grad_hess = grad_hess

            def cox_mesh_grad_hess(m, y, w):
                M = jax.lax.all_gather(m, axis_name, tiled=True)
                Y = jax.lax.all_gather(y, axis_name, tiled=True)
                Wt = jax.lax.all_gather(w, axis_name, tiled=True)
                G, H = base_grad_hess(M, Y, Wt)
                k = jax.lax.axis_index(axis_name)
                c = m.shape[0]
                return (
                    jax.lax.dynamic_slice(G, (k * c,), (c,)),
                    jax.lax.dynamic_slice(H, (k * c,), (c,)),
                )

            grad_hess = cox_mesh_grad_hess
        num_group = self.num_group
        # the class trees of a round read one bin matrix: mapped over the
        # class axis, a depth-wise build's level histogram takes their
        # gradients as one operand (ops/histogram.py::_class_groups)
        class_builder = (
            partial(builder, class_vmap=True) if self._class_operand_trees() > 1 else builder
        )
        subsample = cfg.subsample
        num_parallel = cfg.num_parallel_tree
        use_monotone = self.has_monotone
        collect_stats = self.learning_stats

        def _learning_stats(g, h, margins_new):
            # read-only reductions of the round's gradients/hessians and
            # post-update margins; telemetry/model.DEVICE_STAT_FIELDS owns
            # the layout. Sums/counts psum and extrema pmin/pmax over the
            # data axis, so the vector is globally exact and replicated
            # (matching its P() out_spec); nothing here feeds back into the
            # tree build, keeping committed trees bit-identical.
            gv = g.reshape(-1)
            hv = h.reshape(-1)
            mv = margins_new.reshape(-1)
            g_fin = jnp.isfinite(gv)
            h_fin = jnp.isfinite(hv)
            vec = jnp.stack(
                [
                    jnp.sum(jnp.where(g_fin, gv, 0.0)),
                    jnp.min(jnp.where(g_fin, gv, jnp.inf)),
                    jnp.max(jnp.where(g_fin, gv, -jnp.inf)),
                    jnp.sum(jnp.where(h_fin, hv, 0.0)),
                    jnp.min(jnp.where(h_fin, hv, jnp.inf)),
                    jnp.max(jnp.where(h_fin, hv, -jnp.inf)),
                    jnp.sum((~g_fin).astype(jnp.float32)),
                    jnp.sum((~jnp.isfinite(mv)).astype(jnp.float32)),
                ]
            ).astype(jnp.float32)
            if axis_name is not None:
                sums = jax.lax.psum(vec, axis_name)
                mins = jax.lax.pmin(vec, axis_name)
                maxs = jax.lax.pmax(vec, axis_name)
                vec = jnp.stack(
                    [
                        sums[0], mins[1], maxs[2],
                        sums[3], mins[4], maxs[5],
                        sums[6], sums[7],
                    ]
                )
            return vec

        def one_round(
            bins, margins, labels, weights, num_cuts, rng, feature_mask, monotone,
            rank_index,
        ):
            mono = monotone if use_monotone else None
            # Two rng streams: the replicated one drives feature-subset draws
            # inside build_tree (colsample_bylevel/bynode), which MUST be
            # identical on every shard so all shards pick the same splits;
            # the shard-folded one drives row subsampling, which must be
            # decorrelated per shard (each shard owns different rows).
            if axis_name is not None:
                shard_rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
            else:
                shard_rng = rng
            with stage(STAGE_GRAD):
                if ranking_grads is not None:
                    g, h = ranking_grads(margins, rank_index)
                else:
                    g, h = grad_hess(margins, labels, weights)

            def sampled(rng_k, gc, hc):
                if subsample >= 1.0:
                    return gc, hc
                with stage(STAGE_GRAD):
                    keep = (
                        jax.random.uniform(rng_k, (bins.shape[0],)) < subsample
                    ).astype(jnp.float32)
                    if gc.ndim == 1:
                        return gc * keep, hc * keep
                    return gc * keep[:, None], hc * keep[:, None]

            trees = []
            if num_group == 1:
                total_out = jnp.zeros_like(margins)
                for k in range(num_parallel):
                    rng_k = jax.random.fold_in(rng, k)
                    gk, hk = sampled(jax.random.fold_in(shard_rng, k), g, h)
                    tree, row_out = builder(
                        bins, gk, hk, num_cuts,
                        feature_mask=feature_mask, monotone=mono, rng=rng_k,
                    )
                    trees.append(tree)
                    with stage(STAGE_LEAF_MARGIN):
                        total_out = total_out + row_out
                with stage(STAGE_LEAF_MARGIN):
                    margins = margins + total_out
            else:
                # multi-class: vmap the builder over the class axis; with
                # num_parallel_tree=P the class-vmap runs P times on P row
                # subsamples (a bagged forest step per class — same layout
                # as xgboost: P trees per class per round, eta/P averaging)
                total_out = jnp.zeros_like(margins)
                for k in range(num_parallel):
                    rng_k = jax.random.fold_in(rng, k)
                    gk, hk = sampled(jax.random.fold_in(shard_rng, k), g, h)
                    with stage(STAGE_GRAD):
                        g_by_class, h_by_class = gk.T, hk.T
                    tree, row_out = jax.vmap(
                        lambda gc, hc: class_builder(
                            bins, gc, hc, num_cuts,
                            feature_mask=feature_mask, monotone=mono, rng=rng_k,
                        )
                    )(g_by_class, h_by_class)
                    trees.append(tree)
                    with stage(STAGE_LEAF_MARGIN):
                        total_out = total_out + row_out.T
                with stage(STAGE_LEAF_MARGIN):
                    margins = margins + total_out
            # pack inside the program: the host pulls ONE array per dispatch
            with stage(STAGE_PACK):
                stacked = jax.tree_util.tree_map(
                    lambda *leaves: jnp.stack(leaves), *trees
                ) if num_parallel > 1 else trees[0]
                packed = pack_round_trees(stacked)
            if not collect_stats:
                return packed, margins
            return packed, margins, _learning_stats(g, h, margins)

        K = self.rounds_per_dispatch
        colsample = cfg.colsample_bytree
        d = self.train_binned.num_col

        metric_fns = self.device_metric_fns
        shared_flags = [b is None for b in self.eval_bins]
        predict_depth = cfg.predict_depth
        eval_traversal = cfg.eval_traversal
        n_data_shards = self.n_data_shards
        # locals, not ``self``: the jitted closure must not keep the session
        # (and with it every device buffer) alive
        d_pad = self.d_pad
        n_fs = self.n_feature_shards
        backend = self.hist_knobs.backend
        bundle = self.bundle.tables if self.bundle is not None else None
        cat = self.cat_tables

        def multi_round(
            bins, margins, labels, weights, num_cuts, rng, feature_mask, monotone,
            rank_index, eval_m, eval_blw, eval_groups=(),
        ):
            # eval_blw: ((bins, labels, weights), ...) for the non-shared
            # eval sets — passed as sharded args (closures would stay global
            # under shard_map and mismatch the per-shard margins);
            # eval_groups: their GroupLayouts, where a metric is per group
            # (the shared set's is rank_index)
            # lax.scan so the round body is compiled ONCE regardless of K
            k_features = max(1, int(round(colsample * d)))

            def body(carry, j):
                margins_c, extra = carry
                rng_j = jax.random.fold_in(rng, j)
                if colsample < 1.0:
                    # same exactly-k-without-replacement draw as the host
                    # path, over GLOBAL columns; with a feature axis each
                    # shard slices its own column segment of the one mask
                    chosen = jax.random.permutation(
                        jax.random.fold_in(rng_j, 777), d
                    )[:k_features]
                    gmask = jnp.zeros(d_pad, jnp.float32).at[chosen].set(1.0)
                    if feature_axis is not None:
                        d_local = d_pad // n_fs
                        fs = jax.lax.axis_index(feature_axis)
                        mask = jax.lax.dynamic_slice(
                            gmask, (fs * d_local,), (d_local,)
                        )
                    else:
                        mask = gmask
                else:
                    mask = feature_mask
                round_out = one_round(
                    bins, margins_c, labels, weights, num_cuts, rng_j, mask,
                    monotone, rank_index,
                )
                if collect_stats:
                    packed, margins_c, lstats = round_out
                else:
                    packed, margins_c = round_out
                    lstats = None
                # every non-shared eval set's margins ride the scan carry:
                # the committed tree applies on device each round whether or
                # not metrics are device-computable, so the host-fallback
                # cadence (evaluate once per dispatch) reads fresh margins
                # without a single extra dispatch, and the carried buffers
                # stay donated round over round (donate_argnums below).
                new_extra = []
                per_set = []
                ei = 0
                for si, shared in enumerate(shared_flags):
                    if shared:
                        m_e, y_e, w_e, groups_e = margins_c, labels, weights, rank_index
                    else:
                        b_e, y_e, w_e = eval_blw[ei]
                        groups_e = eval_groups[ei] if eval_groups else None
                        m_e = _apply_packed_tree(
                            packed, b_e, extra[ei],
                            num_group, num_parallel, predict_depth, num_bins,
                            backend=backend, traversal=eval_traversal, bundle=bundle,
                            cat=cat,
                        )
                        new_extra.append(m_e)
                        ei += 1
                    if not metric_fns:
                        continue
                    # shard-local partial stats -> psum over the data
                    # axis -> finalize: metric scalars are globally
                    # exact and identical on every shard/host. The
                    # non-decomposable exception (cox-nloglik) gathers
                    # the global rows first — its replicated stats are
                    # pre-divided by the axis size so the shared psum
                    # restores the global value.
                    def _stats_for(fn, m_s, y_s, w_s, groups_s=groups_e):
                        if fn.needs_groups:
                            return fn.partial(m_s, y_s, w_s, groups_s)
                        if fn.needs_global_rows and axis_name is not None:
                            m_g = jax.lax.all_gather(m_s, axis_name, tiled=True)
                            y_g = jax.lax.all_gather(y_s, axis_name, tiled=True)
                            w_g = jax.lax.all_gather(w_s, axis_name, tiled=True)
                            return fn.partial(m_g, y_g, w_g) / n_data_shards
                        return fn.partial(m_s, y_s, w_s)

                    with stage(STAGE_EVAL_METRIC):
                        stats = jnp.concatenate(
                            [_stats_for(fn, m_e, y_e, w_e) for fn in metric_fns]
                        )
                        if axis_name is not None:
                            stats = jax.lax.psum(stats, axis_name)
                        scalars_set = []
                        off = 0
                        for fn in metric_fns:
                            scalars_set.append(
                                fn.finalize(stats[off : off + fn.size])
                            )
                            off += fn.size
                        per_set.append(jnp.stack(scalars_set))
                extra = tuple(new_extra)
                if metric_fns:
                    with stage(STAGE_EVAL_METRIC):
                        scalars = jnp.stack(per_set)      # [n_sets, n_metrics]
                else:
                    # non-empty dummy: zero-sized scan outputs are a
                    # lowering hazard on some backends
                    scalars = jnp.zeros((1, 1), jnp.float32)
                outs = (packed, scalars, lstats) if collect_stats else (packed, scalars)
                return (margins_c, extra), outs

            (margins, eval_m), outs = jax.lax.scan(
                body, (margins, eval_m), jnp.arange(K)
            )
            if collect_stats:
                packed_all, metrics_all, stats_all = outs
                return packed_all, metrics_all, margins, eval_m, stats_all
            packed_all, metrics_all = outs
            return packed_all, metrics_all, margins, eval_m

        use_scan = self.use_scan_rounds
        fn = multi_round if use_scan else one_round
        if self.mesh is None:
            if not use_scan:
                # graftlint: disable=trace-uncached-jit — session-scope construction: built once per training session, not per call (one session = one round closure = its own jit cache)
                return jax.jit(fn, donate_argnums=(1,))
            # graftlint: disable=trace-uncached-jit — session-scope construction: built once per training session, not per call (one session = one round closure = its own jit cache)
            return jax.jit(fn, donate_argnums=(1, 9))

        margin_spec = P("data") if num_group == 1 else P("data", None)
        rank_spec = self._rank_specs() or P()
        base_specs = (
            self.bins_spec,    # bins
            margin_spec,       # margins
            P("data"),         # labels
            P("data"),         # weights
            self.feat_spec,    # num_cuts
            P(),               # rng
            self.feat_spec,    # feature_mask
            self.feat_spec,    # monotone
            rank_spec,         # rank_index
        )
        stats_specs = (P(),) if collect_stats else ()
        if not use_scan:
            in_specs = base_specs
            out_specs = (P(), margin_spec) + stats_specs
            donate = (1,)
        else:
            eval_specs = tuple(
                margin_spec for m in self.eval_margins if m is not None
            )
            eval_blw_specs = tuple(
                (P("data", None), P("data"), P("data"))
                for b in self.eval_bins
                if b is not None
            )
            in_specs = base_specs + (eval_specs, eval_blw_specs, ())
            out_specs = (P(), P(), margin_spec, eval_specs) + stats_specs
            donate = (1, 9)
        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        # graftlint: disable=trace-uncached-jit — session-scope construction: built once per training session, not per call (one session = one round closure = its own jit cache)
        return jax.jit(mapped, donate_argnums=donate)

    def _make_apply_fn(self):
        cfg = self.config
        num_bins = self.train_binned.num_bins
        num_group = self.num_group
        num_parallel = cfg.num_parallel_tree

        backend = self.hist_knobs.backend
        bundle = self.bundle.tables if self.bundle is not None else None
        cat = self.cat_tables

        def apply_tree(packed, bins, margins):
            return _apply_packed_tree(
                packed, bins, margins, num_group, num_parallel,
                cfg.predict_depth, num_bins, backend=backend,
                traversal=cfg.eval_traversal, bundle=bundle, cat=cat,
            )

        if self.mesh is None:
            # graftlint: disable=trace-uncached-jit — session-scope construction: _make_apply_fn runs once per session
            return jax.jit(apply_tree, donate_argnums=(2,))
        margin_spec = P("data") if num_group == 1 else P("data", None)
        mapped = jax.shard_map(
            apply_tree,
            mesh=self.mesh,
            in_specs=(P(), P("data", None), margin_spec),
            out_specs=margin_spec,
            check_vma=False,
        )
        # graftlint: disable=trace-uncached-jit — session-scope construction: _make_apply_fn runs once per session
        return jax.jit(mapped, donate_argnums=(2,))

    # ----------------------------------------------------------- comm stats
    def _comm_plan(self):
        """(entries, wire bytes/round) of the data-axis histogram
        collectives — ops.histogram.round_comm_plan fed with this session's
        static build structure (grow policy, subtraction gating, trees per
        round)."""
        cfg = self.config
        if self.mesh is None or self.n_data_shards <= 1:
            return [], 0
        d_local, num_bins, subtract, trees_per_round = self._build_structure()
        return round_comm_plan(
            cfg.grow_policy,
            cfg.max_depth,
            cfg.max_leaves,
            d_local,
            num_bins,
            self.n_data_shards,
            subtract,
            trees_per_round=trees_per_round,
            pass_slots=self._pass_slots(subtract),
        )

    def _pass_slots(self, subtract):
        """Node slots of a loss-guided build's pass over the rows (the W of
        its kernel call and of its collective; ops/lossguide.py); 1 for a
        depth-wise job, which has none."""
        if self.config.grow_policy != "lossguide":
            return 1
        from ..ops.lossguide import pass_nodes

        return pass_nodes(self.config.max_leaves, subtract)

    def _build_structure(self):
        """(columns a shard histograms, bins, subtraction, trees a round):
        the static structure of a round's tree builds, as they trace it."""
        cfg = self.config
        # columns each data shard histograms: the whole width, unless a
        # feature axis splits them
        d_local = self.bins.shape[1] // self.n_feature_shards
        num_bins = self.train_binned.num_bins
        # the builders' own gate, so the plan matches what actually traces
        if cfg.grow_policy == "lossguide":
            from ..ops.lossguide import _subtraction_enabled

            subtract = _subtraction_enabled(cfg.max_leaves, d_local, num_bins)
        else:
            from ..ops.tree_build import _subtraction_enabled

            subtract = _subtraction_enabled(cfg.max_depth, d_local, num_bins)
        return (
            d_local, num_bins, subtract,
            cfg.num_parallel_tree * max(self.num_group, 1),
        )

    def _onehot_tile_plan(self):
        """(latched, unfolded, skipped %) one-hot tiles a round of this
        session's level histogram kernel: ops.histogram.round_onehot_tiles
        and skipped_tiles_pct over a shard's rows and columns, the dead tiles
        counted from the host's copy of the cuts (a feature shard's: the
        mean); zeros where the builder is not the kernel."""
        if choose_hist_impl(self.hist_knobs.backend) != "pallas":
            return 0, 0, 0.0
        cfg = self.config
        d_local, num_bins, subtract, trees_per_round = self._build_structure()
        reach = np.asarray(
            [len(c) for c in self.cuts] if self.bundle is None
            else self.bundle.tables.reach
        )
        if self.cat is not None:
            reach = self.cat.reach(reach)
        reach = reach.reshape(self.n_feature_shards, d_local)
        plan = (
            round_hist_levels(
                cfg.grow_policy, cfg.max_depth, cfg.max_leaves, subtract,
                self._pass_slots(subtract),
            ),
            self.bins.shape[0] // self.n_data_shards,
            d_local,
            num_bins,
            self.hist_knobs.precision,
        )
        shape = dict(
            trees_per_round=trees_per_round,
            class_trees=self._class_operand_trees(),
            dead_tiles=sum(dead_bin_tiles(shard, num_bins, self.bins.dtype) for shard in reach)
            // self.n_feature_shards,
        )
        return round_onehot_tiles(*plan, **shape) + (skipped_tiles_pct(*plan, **shape),)

    def _class_operand_trees(self):
        """Trees of a round whose gradients are ONE operand of the level
        histogram kernel: the class trees of a depth-wise round, mapped over
        the class axis (``_make_round_fn``); 1 for a one-tree round and for a
        loss-guided build, whose members each make their own call."""
        return 1 if self.config.grow_policy == "lossguide" else max(self.num_group, 1)

    def _set_comm_round_fields(self):
        """Clear the comm keys from the per-round record at session start so
        no session inherits a previous one's collectives (dart reuses this
        session for staging but dispatches its own GSPMD loop; single-device
        sessions have no collectives at all). The real values are published
        by the first ``_note_comm_dispatch`` — i.e. only by sessions that
        actually run the comm-lowered round program."""
        from ..telemetry import set_round_fields

        set_round_fields(hist_comm=None, hist_comm_bytes=None, hist_comm_ms=None)

    def _calibrate_hist_comm_ms(self):
        """Isolated latency of one round's data-axis collectives, in ms.

        Delegates to the module-level lru_cached factory keyed by
        (mesh, plan shapes): a session rebuilt on the same mesh
        with the same static plan — every sequential CV fold, an elastic
        generation that kept its topology, a dart staging rebuild — reuses
        the measured number instead of re-paying the standalone collective
        compile + timing dispatches on its first round. Returns 0.0 when
        calibration is disabled (GRAFT_HIST_COMM_CALIBRATE=0) or fails.
        """
        if not self.hist_comm_plan:
            return 0.0
        if os.environ.get("GRAFT_HIST_COMM_CALIBRATE", "1") != "1":
            return 0.0
        plan_key = tuple(
            (entry["kind"], entry["shape"], entry["count"])
            for entry in self.hist_comm_plan
        )
        try:
            return _calibrated_comm_ms(self.mesh, plan_key)
        except Exception as e:  # calibration must never break training
            # degrade THIS session to 0.0 only: a raising call is not
            # memoized by lru_cache, so the next session rebuild retries
            # instead of serving a cached failure forever
            logger.warning("hist comm calibration failed: %s", e)
            return 0.0

    def _note_comm_dispatch(self, k_rounds):
        """Fold one dispatch (k_rounds boosting rounds) into the comm
        telemetry: hist_comm_bytes_total counter + (lazily) the calibrated
        hist_comm_ms gauge and round-record field."""
        if not self.hist_comm_plan:
            return
        from ..telemetry import REGISTRY, set_round_fields

        labels = {"impl": "psum"}
        set_round_fields(
            hist_comm="psum",
            hist_comm_bytes=self.hist_comm_bytes_per_round,
        )
        if self._hist_comm_ms is None:
            self._hist_comm_ms = self._calibrate_hist_comm_ms()
            if self._hist_comm_ms:
                REGISTRY.gauge(
                    "hist_comm_ms",
                    "Calibrated isolated latency of one round's data-axis "
                    "histogram collectives (upper bound: real rounds may "
                    "overlap them with compute)",
                    labels,
                ).set(round(self._hist_comm_ms, 3))
                set_round_fields(hist_comm_ms=round(self._hist_comm_ms, 3))
        REGISTRY.counter(
            "hist_comm_bytes_total",
            "Estimated cross-shard wire bytes moved by histogram "
            "collectives (ring formula, docs/DESIGN.md Communication; a "
            "loss-guided build's passes counted one a split step, the most "
            "they can be: tree_hist_passes_total counts them)",
            labels,
        ).inc(self.hist_comm_bytes_per_round * k_rounds)

    @contextlib.contextmanager
    def _upload_span(self, what):
        """A `setup.upload` span around host staging (padding, layout) and
        the `_put` calls that follow, with the bytes they handed over. The
        transfers return before the bytes are on the device: the tail of an
        upload is asynchronous (`async_tail`) and ends under a later span."""
        with span(
            "setup.upload", attributes={"what": what, "async_tail": True}
        ) as upload:
            before = self._bytes_put
            yield
            upload.add_bytes(up=self._bytes_put - before)

    # ------------------------------------------------------------- resketch
    def _stage_train_bins(self, shard_bins, cuts, max_bin):
        """Stage the training rows' bin indices (a block a shard, where the
        bin-apply left it) + per-feature cuts as the session's padded, placed
        device arrays (cuts/num_cuts/bins). Shared by __init__ and the approx
        re-sketch so the two paths can never disagree on padding conventions.
        ``train_binned`` is the same matrix for whoever reads it on the host:
        its bins are pulled from the device when first asked for."""
        real_cuts = list(cuts)
        cuts = real_cuts + [
            np.zeros(0, np.float32) for _ in range(self.d_pad - self._d_real)
        ]
        with self._upload_span("train_bins"):
            self.cuts = cuts
            cut_counts = np.array([len(c) for c in cuts], np.int32)
            if self.cat is not None:  # no threshold split on a categorical column
                cut_counts = self.cat.numeric_cut_counts(cut_counts)
            self.num_cuts = self._put(cut_counts, self.feat_spec)
            self.bins = self._place_rows(shard_bins, self._train_rows, fill=max_bin)
        dtrain = self._dtrain
        if isinstance(dtrain, BinnedMatrix):
            self.train_binned = dtrain
            return
        self.train_binned = BinnedMatrix(
            partial(self._train_bins_to_host, self.bins),
            real_cuts,
            max_bin,
            labels=dtrain.labels,
            weights=dtrain.weights,
            groups=dtrain.groups,
            shape=(self.n, self._d_real),
        )

    def _resketch_bins(self):
        """Per-dispatch candidate re-sketch for tree_method='approx'.

        libxgboost's approx re-selects split candidates every iteration via
        a hessian-weighted quantile sketch (its GlobalApproxUpdater; the
        reference delegates to it through the tree_method HP,
        hyperparameter_validation.py:22-24). Here: the current hessians
        weigh the same shard-by-shard sketch set-up ran (merged across the
        chips and, in multi-process runs, the hosts), train + cached eval
        sets re-bin where they lie, and cuts/num_cuts refresh — all
        shapes/dtypes static, so the jitted round program is reused with
        new array CONTENTS. Committed trees are unaffected: each round's
        trees were already compacted to float thresholds under the cuts
        active when they were built. Runs before EVERY dispatch (including
        the first: libxgboost hessian-weights the iteration-0 sketch too —
        from the base margin, or real margins on checkpoint resume)."""
        if self._grad_fn is None:
            # graftlint: disable=trace-uncached-jit — memoized on self._grad_fn: constructed once per session
            self._grad_fn = jax.jit(self.objective.grad_hess)
        _g, h = self._grad_fn(self.margins, self.labels, self.weights)
        if h.ndim == 2:  # multi-class: sketch weight = summed class hessians
            h = h.sum(axis=1)
        max_bin = self.train_binned.max_bin
        cuts = self._sketch(self._shard_values(h, self._train_rows))
        self._stage_train_bins(
            apply_shards(
                self._train_floats, cuts, max_bin, self._train_rows.devices, name="train"
            ),
            cuts,
            max_bin,
        )
        # cached eval bins were built with the old cuts; the incremental
        # eval-margin apply reads bin indices, so they must re-bin too
        for i, (name, dm, _binned) in enumerate(self.eval_sets):
            if self.eval_bins[i] is None:
                continue
            layout = self._eval_rows[i]
            self.eval_bins[i] = self._place_rows(
                self._bin_eval_rows(i, name, dm, layout, cuts, max_bin), layout
            )

    # ------------------------------------------------------- device window
    def _register_round_program(self):
        """Hand the device plane a closure that lowers and compiles this
        session's round program from shapes, dtypes and shardings alone
        (``jax.ShapeDtypeStruct``): it keeps the jitted function and no
        device buffer, and nothing runs until ``round_program_stages()`` or
        the gated introspection below asks. Lowering never *executes*, so
        donated buffers are not consumed and the rng stream is untouched."""
        def aval(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

        mask_sharding = None
        if self.has_feature_axis:
            from jax.sharding import NamedSharding

            mask_sharding = NamedSharding(self.mesh, self.feat_spec)
        args = (
            self.bins,
            self.margins,
            self.labels,
            self.weights,
            self.num_cuts,
            self.rng,
            # the per-dispatch feature mask: no array of it exists yet
            jax.ShapeDtypeStruct(
                (self.bins.shape[1],), jnp.float32, sharding=mask_sharding
            ),
            self.monotone,
            self.rank_index_dev,
        )
        if self.use_scan_rounds:
            eval_m = tuple(m for m in self.eval_margins if m is not None)
            eval_blw = tuple(
                (self.eval_bins[i], self.eval_labels[i], self.eval_weights[i])
                for i in range(len(self.eval_bins))
                if self.eval_bins[i] is not None
            )
            args += (eval_m, eval_blw, self.eval_layouts)
        avals = jax.tree_util.tree_map(aval, args)
        round_fn = self._round_fn

        def compile_round_program():
            return round_fn.lower(*avals).compile()

        device_telemetry.register_round_program(compile_round_program)
        return compile_round_program

    def _introspect_compiled_cost(self, compile_round_program):
        """AOT-lower the fused round dispatch and feed its XLA
        ``cost_analysis``/``memory_analysis`` and its stage table into the
        device-window plane (``training.compiled`` record + flops/HBM
        gauges). Gated on ``SM_DEVICE_TELEMETRY`` because the AOT compile
        is real work (the jit path's own compile is served from the
        persistent compile cache, utils/compile_cache.py). Diagnostics only
        — any failure is one warning, never a failed session."""
        if not device_telemetry.enabled():
            return
        try:
            compiled = compile_round_program()
            cost = device_telemetry.cost_from_compiled(compiled)
            _table, stages = device_telemetry.note_stage_table(compiled)
            mesh_shape = dict(self.mesh.shape) if self.mesh is not None else None
            device_telemetry.note_compiled(
                cost,
                mesh_shape=mesh_shape,
                rounds_per_dispatch=self.rounds_per_dispatch,
                backend=jax.default_backend(),
                stages=stages,
            )
        except Exception as e:
            logger.warning(
                "compiled-cost introspection failed (%s); training continues "
                "without the training.compiled record",
                e,
            )

    def _abort_device_oom(self, exc):
        """A round dispatch died with the allocator exhausted: dump the HBM
        forensics (top live buffers, allocator stats, compiled memory
        analysis, last watermark), then take the shared watchdog abort path
        (checkpoint flush + flight recorder + ``training.abort``) with
        exit 86 so the platform log names the OOM instead of a raw XLA
        traceback."""
        from ..constants import EXIT_DEVICE_OOM
        from ..telemetry import device as device_telemetry
        from ..training import watchdog

        path = device_telemetry.dump_oom_forensics(exc)
        watchdog.request_abort(
            "device_oom",
            EXIT_DEVICE_OOM,
            error=str(exc)[:400],
            forensics=path or "",
        )

    # ---------------------------------------------------------------- round
    def _timed_dispatch(self, dispatch, attributes):
        """Run one round dispatch under a `host_dispatch` span: python and
        the asynchronous XLA call, up to its return. The return also ends
        the `host_turnaround` that the previous dispatch's `device_sync`
        began: from there on the device has work again."""
        pre_compile = compile_stats()["seconds"]
        with self._first_dispatch_part("load"), span("host_dispatch", attributes=attributes):
            out = dispatch()
        # an XLA compile that completed inside THIS dispatch is wall time
        # the host_dispatch span already contains; RoundTimer reports it
        # under the round's `compile` key, so remove exactly the measured
        # overlap from the phase accumulator
        overlap = compile_stats()["seconds"] - pre_compile
        if overlap > 0:
            recorder = active_recorder()
            if recorder is not None:
                recorder.add("host_dispatch", -overlap)
        self.end_turnaround()
        return out

    def end_turnaround(self):
        """Close the open `host_turnaround` span, if any. Whoever drives
        ``run_rounds()`` calls it after the last dispatch, which no
        `host_dispatch` follows (``train()`` does, in a ``finally``)."""
        if self._turnaround is not None:
            end_span(self._turnaround)
            self._turnaround = None

    def __del__(self):
        # a session dropped between dispatches takes its open span with it
        # (one whose construction failed has none)
        if getattr(self, "_turnaround", None) is not None and not sys.is_finalizing():
            self.end_turnaround()

    def run_rounds(self):
        """One device dispatch -> (list of host tree dicts, metrics or None).

        metrics: [K, n_metrics] numpy when device metrics are active (batched
        mode); None when evaluation happens host-side (K=1).

        An allocator exhaustion anywhere in the dispatch (the async XLA
        error materializes at the blocking transfer) is terminal for the
        process — no retry can succeed against a full HBM — so it routes
        through the OOM forensics dump + watchdog abort (exit 86) instead
        of unwinding as a raw traceback. Every other exception propagates
        unchanged."""
        try:
            return self._run_rounds_inner()
        except Exception as e:
            from ..telemetry import device as device_telemetry

            self.end_turnaround()  # no dispatch follows a failed one
            if device_telemetry.is_oom_error(e):
                self._abort_device_oom(e)
            raise

    def _stash_learning_stats(self, stats_dev):
        """One small host transfer per dispatch: the per-round learning
        stats vectors, decoded into dicts the train loop folds (with the
        committed-tree stats) into ``telemetry/model.note_learning`` and
        the numeric-health guard. ``[]`` when the plane is unarmed."""
        if stats_dev is None:
            self.last_learning_stats = []
            return
        from ..telemetry import model as model_telemetry

        rows = np.asarray(stats_dev)
        if rows.ndim == 1:
            rows = rows[None, :]
        self.last_learning_stats = [
            model_telemetry.decode_device_stats(rows[j])
            for j in range(rows.shape[0])
        ]

    def _run_rounds_inner(self):
        index = self._dispatch_index
        self._dispatch_index += 1
        attributes = {"k": self.rounds_per_dispatch, "dispatch": index}
        # every Nth dispatch (SM_TRACE_DEVICE_SYNC) fences all it put in flight
        fenced = (
            self._device_sync_every > 0 and index % self._device_sync_every == 0
        )
        if index == 0:
            # the first dispatch is set-up: program load and K rounds of
            # warm-up, up to the first packed trees on the host
            self._first_dispatch = begin_span(
                "setup.first_dispatch", covering=True, attributes=attributes
            )
        try:
            return self._dispatch_rounds(attributes, fenced)
        finally:
            self._end_first_dispatch()  # still open only if the dispatch raised

    def _end_first_dispatch(self):
        if self._first_dispatch is not None:
            end_span(self._first_dispatch)
            self._first_dispatch = None
            self._note_setup_memory("setup.first_dispatch")

    def _first_dispatch_part(self, part):
        """Inside `setup.first_dispatch` its `host_dispatch` is the covering
        span `setup.first_dispatch.load` too (trace, lower, compile or load
        from the cache, up to the asynchronous call's return) and its
        `device_sync` is `setup.first_dispatch.run` (K rounds running); in
        every later dispatch, nothing."""
        if self._first_dispatch is None:
            return contextlib.nullcontext()
        return span("setup.first_dispatch." + part, covering=True)

    def _note_setup_memory(self, phase):
        """The fullest chip's memory where a top-level set-up span has ended
        (`setup.bin_apply` and `setup.upload`: the last of them): gauges
        `setup_hbm_bytes{phase, what}`, five reads a session."""
        devices = jax.local_devices()[:1] if self.mesh is None else self.mesh.local_devices
        note_phase_memory(phase, devices)

    def _device_sync(self, packed, out, attributes, fenced):
        """The packed trees on the host, under a `device_sync` span: the
        transfer blocks on the round program and is there anyway. ``fenced``
        first blocks on everything the dispatch put in flight (``out``): the
        K = 1 path's eval-apply programs are separate dispatches. Where the
        span ends the device has nothing queued, so `host_turnaround`
        begins (and `setup.first_dispatch` ends)."""
        with self._first_dispatch_part("run"), span("device_sync", attributes=attributes):
            if fenced:
                jax.block_until_ready(out)
            packed_np = np.asarray(packed)
        self._end_first_dispatch()
        self._turnaround = begin_span("host_turnaround", covering=True)
        return packed_np

    def _dispatch_rounds(self, attributes, fenced):
        if self.approx_resketch:
            self._resketch_bins()
        if fault_point("train.gradient_poison", dispatch=attributes["dispatch"]):
            # numeric-poison drill: corrupt the live margins so the next
            # round's gradients genuinely go NaN through the real device
            # pipeline (the learning-telemetry guard must catch it there)
            self.margins = self.margins * jnp.float32(np.nan)
        self.rng, sub, colrng = jax.random.split(self.rng, 3)
        d_pad = self.d_pad  # the bins' width, but for a bundled session's
        if self.config.colsample_bytree < 1.0:
            # draw k of the REAL columns (padded always-missing columns are
            # never legal splits, but counting them would shrink k)
            d_real = self.train_binned.num_col
            k = max(1, int(round(self.config.colsample_bytree * d_real)))
            chosen = np.asarray(jax.random.permutation(colrng, d_real)[:k])
            mask_np = np.zeros(d_pad, np.float32)
            mask_np[chosen] = 1.0
        else:
            mask_np = np.ones(d_pad, np.float32)
        if self.has_feature_axis:
            # the global mask is column-sharded over the feature axis; place
            # it properly (required in multi-process runs)
            feature_mask = self._put(mask_np, self.feat_spec)
        else:
            feature_mask = jnp.asarray(mask_np)
        args = (
            self.bins,
            self.margins,
            self.labels,
            self.weights,
            self.num_cuts,
            sub,
            feature_mask,
            self.monotone,
            self.rank_index_dev,
        )
        if not self.use_scan_rounds:

            def _dispatch_single():
                if self.learning_stats:
                    packed, self.margins, lstats = self._round_fn(*args)
                else:
                    packed, self.margins = self._round_fn(*args)
                    lstats = None
                for i in range(len(self.eval_sets)):
                    if self.eval_margins[i] is not None:
                        self.eval_margins[i] = self._apply_fn(
                            packed, self.eval_bins[i], self.eval_margins[i]
                        )
                # return EVERY freshly dispatched output — the eval-margin
                # applies are separate jitted programs, and the attribution
                # fence must cover them too or their device time would leak
                # into build_eval / the next round's host_dispatch
                return packed, lstats, [m for m in self.eval_margins if m is not None]

            out = self._timed_dispatch(_dispatch_single, attributes)
            packed, lstats, _fenced_evals = out
            packed_np = self._device_sync(packed, out, attributes, fenced)
            self._note_comm_dispatch(1)
            self._stash_learning_stats(lstats)
            return [unpack_round_trees(packed_np, self._set_words)], None
        eval_m = tuple(m for m in self.eval_margins if m is not None)
        eval_blw = tuple(
            (self.eval_bins[i], self.eval_labels[i], self.eval_weights[i])
            for i in range(len(self.eval_bins))
            if self.eval_bins[i] is not None
        )
        out = self._timed_dispatch(
            lambda: self._round_fn(*args, eval_m, eval_blw, self.eval_layouts),
            attributes,
        )
        if self.learning_stats:
            packed, metrics, self.margins, eval_m_out, lstats = out
        else:
            packed, metrics, self.margins, eval_m_out = out
            lstats = None
        ei = 0
        for i in range(len(self.eval_margins)):
            if self.eval_margins[i] is not None:
                self.eval_margins[i] = eval_m_out[ei]
                ei += 1
        # ONE transfer for K rounds
        packed_np = self._device_sync(packed, out, attributes, fenced)
        self._note_comm_dispatch(packed_np.shape[0])
        self._stash_learning_stats(lstats)
        metrics_np = np.asarray(metrics) if self.device_metric_fns else None
        return (
            [
                unpack_round_trees(packed_np[j], self._set_words)
                for j in range(packed_np.shape[0])
            ],
            metrics_np,
        )

    # ----------------------------------------------------------------- eval
    def _to_host(self, arr, n_real):
        """Device margins -> host numpy. In multi-process mode this returns
        the *local* shard's rows; ``evaluate`` then combines per-host values
        into one global number (see its docstring)."""
        if self.is_multiprocess:
            shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start or 0)
            local = np.concatenate([np.asarray(s.data) for s in shards], axis=0)
            return local if n_real is None else local[:n_real]
        full = np.asarray(arr)
        return full if n_real is None else full[:n_real]

    def margins_for(self, index):
        dm = self.eval_sets[index][1]
        m = self.eval_margins[index]
        if m is None:
            if self.rank_pos is not None:
                # distributed-ranking layout: padding is interleaved per
                # shard; map device positions back to original row order
                full = self._to_host(self.margins, None)
                return full[self.rank_pos]
            return self._to_host(self.margins, self.n)
        return self._to_host(m, dm.num_row)

    def evaluate(self, metric_names, feval=None, forest=None):
        """Returns list of (data_name, metric_name, value) per eval set.

        In multi-process runs each host computes on its local shard and the
        values combine as a weight-sum-weighted mean across hosts, so every
        host reports identical numbers (the path for metrics that cannot
        decompose into device partials — ndcg/map/feval; decomposable ones
        ride the exact device psum path instead). This mirrors distributed
        xgboost, where python-side custom metrics are computed per worker
        and averaged rather than allreduced elementwise.

        forest: evaluate from the COMMITTED forest's margins instead of the
        session's device margins. Used by the host-fallback cadence when
        the final dispatch over-built (num_boost_round not a multiple of K,
        or an early stop mid-batch): the device margins then include
        discarded trees, so the last metric line — the one HPO reads —
        must come from the forest that was actually kept. Cost note: this
        re-predicts each eval set (train watchlist included) with the
        whole-forest predictor, once per job at the final round — exactness
        of the final line is deliberately bought with one extra predict
        pass; sizing num_boost_round to a multiple of K avoids it entirely.
        """
        if not hasattr(self, "_global_rows_cache"):
            self._global_rows_cache = {}
        if forest is not None:
            def _committed_margin(dm):
                m = _predict_margin_rows(forest, dm)
                return m.reshape(
                    (dm.num_row,)
                    if self.num_group == 1
                    else (dm.num_row, self.num_group)
                )

            entries = (
                (name, dm, _committed_margin(dm))
                for name, dm, _binned in self.eval_sets
            )
        else:
            entries = (
                (name, dm, self.margins_for(i))
                for i, (name, dm, _binned) in enumerate(self.eval_sets)
            )
        return evaluate_host_lines(
            entries,
            metric_names,
            feval,
            self.objective,
            self.num_group,
            self.config.objective_params,
            self.is_multiprocess,
            global_rows_cache=self._global_rows_cache,
        )


    # -------------------------------------------------- categorical columns
    @functools.cached_property
    def cat_tables(self):
        """The session's ``ops.categorical.CatTables``, None where no column
        is given as categories."""
        if self.cat is None:
            return None
        from ..ops.categorical import CatTables

        return CatTables(
            self.cat, self.config.max_cat_to_onehot, self.config.max_cat_threshold
        )

    @property
    def _set_words(self):
        return 0 if self.cat is None else self.cat.set_words

    @property
    def tree_cuts(self):
        """Cuts by the column a committed tree's split names: the bin
        columns' own, or by original column where some are categories."""
        return self.cuts if self.cat is None else self.cat.feature_cuts(self.cuts)

    # ------------------------------------------------------ bundled layout
    # (below the round program: a line moved above the traced code moves the
    # compile cache's key, PERF.md section 5)
    def _takes_bundles(self, dtrain, evals):
        """Whether this session's matrices take the bundled layout
        (``data/bundling.py``): the input's shape decides (sparse matrices,
        the training one at most a quarter full), within what the bundled
        scan and range test cover: a depth-wise ``hist`` build in one process
        on one device, no monotone or interaction constraints, no column
        draw below the tree's. Everything else keeps the densified path."""
        from ..data.bundling import takes_bundled_layout
        from ..ops.bundle import RANGE_BITS

        cfg = self.config
        return (
            self.mesh is None
            and not self.is_multiprocess
            and cfg.grow_policy != "lossguide"
            and cfg.tree_method != "approx"
            and cfg.max_bin is not None
            and cfg.max_bin < 1 << RANGE_BITS
            and not cfg.monotone_constraints
            and not cfg.interaction_constraints
            and cfg.colsample_bylevel >= 1.0
            and cfg.colsample_bynode >= 1.0
            and takes_bundled_layout([dtrain] + [dm for dm, _name in evals])
        )

    def _bundle_inputs(self, dtrain, evals):
        """The session's matrices bundled: the plan over the training matrix
        and every evaluation set, and the bundled bins of each. The columns
        filled in most rows go, as one dense block, through the sketch and
        the bin-apply every dense matrix takes."""
        from ..data.bundling import bundle_matrices

        matrices, names, index_of = [dtrain], ["train"], {id(dtrain): 0}
        for dm, name in evals:
            if id(dm) not in index_of:
                index_of[id(dm)] = len(matrices)
                matrices.append(dm)
                names.append(name)
        max_bin = self.config.max_bin

        def sketch_dense(block):
            cuts = sketch_shards([block], [dtrain.weights], max_bin)
            self._note_setup_memory("setup.sketch")
            return cuts

        bundled = bundle_matrices(
            [m.csr for m in matrices],
            dtrain.weights,
            max_bin,
            sketch_dense,
            lambda block, cuts, name: apply_shards([block], cuts, max_bin, name=name)[0],
            names,
        )
        bundled.index_of = index_of
        plan = bundled.plan
        logger.info(
            "bundled layout: %d sparse columns (%d of them in most rows) in %d bin "
            "columns, %d of %d bin positions used, %d conflict rows",
            plan.num_col, len(plan.dense_columns), plan.num_bundles, plan.bins_used,
            plan.num_bundles * plan.max_bin, bundled.conflict_rows,
        )
        return bundled

    def _note_bundled_shape(self, bundled):
        """``_note_binned_shape`` for a bundled session: what the input held
        and what the bundled matrix holds of it, set once."""
        from ..telemetry import REGISTRY

        plan = bundled.plan
        cells = self.n * plan.num_col
        cuts = sum(len(c) for c in plan.cut_points)
        gauges = (
            ("train_cells_present", "Cells of the training matrix that hold a value",
             bundled.cells_present),
            ("train_cells_missing", "Cells of the binned training matrix in the missing bin",
             cells - bundled.cells_present),
            ("train_cells_total", "Cells of the binned training matrix (rows x columns)", cells),
            ("sketch_cuts_selected", "Cut points the sketch selected, summed over columns", cuts),
            ("sketch_cut_slots", "Cut slots a level histogram carries: columns x (max_bin - 1)",
             plan.num_bundles * (plan.max_bin - 1)),
            ("train_columns_total", "Columns of the binned training matrix", plan.num_col),
            ("train_bundle_columns", "Bin columns of a bundled training matrix "
             "(data/bundling.py): what the level histogram reads a row", plan.num_bundles),
            ("bundle_bins_used", "Bin positions the bundles' members take", plan.bins_used),
            ("bundle_bin_slots", "Bin positions the bundles carry: bundles x max_bin",
             plan.num_bundles * plan.max_bin),
            ("bundle_conflict_rows", "Rows of the session's matrices that hold two members "
             "of one bundle, counted from the bundled matrices: 0, the plan excludes them",
             bundled.conflict_rows),
        )
        for name, text, value in gauges:
            REGISTRY.gauge(name, text).set(float(value))

    def _refuse_bundled_bins(self):
        if self.bundle is not None:
            raise exc.AlgorithmError(
                "a bundled session holds bundle positions, not per-column bins: "
                "nothing on the host may read them as a BinnedMatrix"
            )


def _note_round_shape(session):
    """The gauges that say what one boosting round of ``session`` builds,
    set once at session build (``_TrainingSession.__init__``)."""
    from ..telemetry import REGISTRY
    from ..utils.device_runtime import split_steps_per_round

    cfg = session.config
    trees = session._build_structure()[3]
    REGISTRY.gauge(
        "round_class_trees",
        "Trees one boosting round grows: classes x num_parallel_tree "
        "(1 for a binary, regression or ranking job); the class trees "
        "of a depth-wise round share one kernel call a level",
    ).set(trees)
    steps = split_steps_per_round(cfg.grow_policy, cfg.max_leaves, trees)
    REGISTRY.gauge(
        "round_split_steps",
        "Split steps one boosting round runs: (max_leaves - 1) x the trees "
        "a round grows for a loss-guided job, whose steps are one rolled "
        "loop (ops/lossguide.py); 0 for a depth-wise job",
    ).set(steps)
    REGISTRY.gauge(
        "round_eval_replay_steps",
        "Split steps one boosting round replays over evaluation rows: "
        "round_split_steps x the evaluation sets that do not share the "
        "training rows (ops/tree_build.py::predict_binned_steps); 0 for a "
        "depth-wise job, whose rows walk the heap level by level",
    ).set(steps * sum(b is not None for b in session.eval_bins))
    _tree_depth_gauge().set(0)  # the deepest leaf of THIS session's trees
    _round_hist_passes_gauge().set(0)


def _round_hist_passes_gauge():
    from ..telemetry import REGISTRY

    return REGISTRY.gauge(
        "round_hist_passes",
        "Passes over the rows in the split-step loops of the last committed "
        "round's loss-guided trees (round_split_steps would be a pass a step); "
        "0 for a depth-wise job",
    )


def _tree_depth_gauge():
    from ..telemetry import REGISTRY

    return REGISTRY.gauge(
        "tree_depth_max",
        "Depth of the deepest leaf over the trees the training session has "
        "committed (root = 0)",
    )


def note_committed_trees(trees, padded=None):
    """Counts the trees a round committed, from the compact trees the host
    holds anyway (no device work): the leaves grown, and the deepest leaf of
    the session so far (the levels a pointer walk would make of a loss-guided
    tree; its rows replay the split steps, ``round_eval_replay_steps``).
    ``padded``: the round's padded tree arrays as the dispatch's one array
    brought them; a loss-guided round's hold its ``[..., 3]`` pass counters
    (``ops/lossguide.py``): passes over the rows, node slots the passes
    filled, and filled slots a split step then used."""
    from ..ops.tree_build import PASS_COUNTS_FIELD, SET_WORDS_FIELD
    from ..telemetry import REGISTRY

    REGISTRY.counter(
        "tree_leaves_total", "Leaves of the trees committed by training rounds"
    ).inc(sum(int(np.count_nonzero(t.is_leaf)) for t in trees))
    deepest = _tree_depth_gauge()
    deepest.set(max([deepest.value] + [t.depth() for t in trees]))
    if padded is not None and SET_WORDS_FIELD in padded:
        # (counted for a categorical session alone: every other's trees are
        # told from their leaves)
        REGISTRY.counter(
            "tree_splits_total", "Splits of the trees committed by a session with "
            "categorical columns"
        ).inc(sum(int(np.count_nonzero(~t.is_leaf)) for t in trees))
        REGISTRY.counter(
            "tree_cat_splits_total", "Set-membership splits among them: a node that "
            "sends right the categories of its set (ops/categorical.py)"
        ).inc(sum(len(t.categories) for t in trees))
    if padded is None or PASS_COUNTS_FIELD not in padded:
        return
    passes, filled, used = (
        int(v) for v in np.reshape(padded[PASS_COUNTS_FIELD], (-1, 3)).sum(axis=0)
    )
    REGISTRY.counter(
        "tree_hist_passes_total",
        "Passes over the rows the split-step loops of the committed loss-guided "
        "trees ran: one level histogram kernel call each, the root's call apart",
    ).inc(passes)
    REGISTRY.counter(
        "tree_hist_pass_slots_total",
        "Node slots those passes filled with a leaf's child (a pass holds "
        "ops/lossguide.py::PASS_SLOTS)",
    ).inc(filled)
    REGISTRY.counter(
        "tree_hist_pass_slots_used_total",
        "Filled node slots whose leaf a split step then committed",
    ).inc(used)
    _round_hist_passes_gauge().set(passes)


def evaluate_host_lines(
    entries,
    metric_names,
    feval,
    objective,
    num_group,
    objective_params,
    is_multiprocess,
    global_rows_cache=None,
):
    """Host-side metric lines for ``entries`` of (name, dm, margin).

    Single-process: plain host evaluation. Multi-process, per metric:
    decomposable metrics combine EXACTLY from per-host partial stats
    (device_metrics); the cox-nloglik exception gathers the global rows
    (labels/weights cached round-invariant in ``global_rows_cache``, keyed
    by entry position); everything else (ndcg/map/feval) combines as a
    weight-sum-weighted mean — all hosts return identical lines. Shared by
    the tree booster's evaluate(), gblinear, and dart."""
    from .device_metrics import make_device_metric

    results = []       # (name, metric, local_value or None placeholder)
    pairs = []         # per entry: summable stats vector
    finalizers = []    # per entry: fn(summed stats) -> global value

    def append_weighted_mean(value, wsum):
        pairs.append(np.asarray([value * wsum, wsum], np.float64))
        finalizers.append(lambda s: float(s[0] / max(s[1], 1e-12)))

    for i, (name, dm, margin) in enumerate(entries):
        preds = None
        prob_matrix = None
        w = dm.get_weight()
        wsum = float(np.sum(w)) if w is not None else float(dm.num_row)
        for metric in metric_names:
            dmf = (
                make_device_metric(metric, objective.name, num_group, objective_params)
                if is_multiprocess
                else None
            )
            if dmf is not None and dmf.needs_groups:
                dmf = None  # per group: the weighted-mean combine below
            if dmf is not None and dmf.needs_global_rows:
                # non-decomposable (cox-nloglik): gather every host's rows
                # (padded to the max local length, weight 0) and evaluate on
                # the global arrays — exact and identical on every host, the
                # host-side mirror of the device all_gather path. Labels/
                # weights (and the agreed max length) are round-invariant:
                # gathered once per eval set and cached; only the margins
                # travel per round.
                from jax.experimental import multihost_utils

                n_loc = int(dm.num_row)

                def _padded(a, n_max):
                    out = np.zeros(n_max, np.float32)
                    out[:n_loc] = np.asarray(a, np.float32)[:n_loc]
                    return out

                cache = global_rows_cache if global_rows_cache is not None else {}
                if i not in cache:
                    w_arr = (
                        np.asarray(w, np.float32)
                        if w is not None
                        else np.ones(n_loc, np.float32)
                    )
                    n_max = int(
                        np.asarray(
                            multihost_utils.process_allgather(
                                np.asarray([n_loc], np.int64)
                            )
                        ).max()
                    )
                    yw = np.asarray(
                        multihost_utils.process_allgather(
                            np.stack(
                                [_padded(dm.labels, n_max), _padded(w_arr, n_max)]
                            )
                        ),
                        np.float64,
                    )  # [P, 2, n_max]
                    cache[i] = (n_max, yw[:, 0].ravel(), yw[:, 1].ravel())
                n_max, y_g, w_g = cache[i]
                m_g = np.asarray(
                    multihost_utils.process_allgather(_padded(margin, n_max)),
                    np.float64,
                ).ravel()
                value = eval_metrics.evaluate(
                    metric, objective.margin_to_prediction(m_g), y_g, w_g
                )
                results.append((name, metric, value))
                # identical on every host: combines to mean(value)
                append_weighted_mean(value, 1.0)
                continue
            if dmf is not None:
                # decomposable: combine exactly from per-host partial
                # stats; skip the (discarded) host-local evaluation
                w_arr = (
                    np.asarray(w, np.float32)
                    if w is not None
                    else np.ones(dm.num_row, np.float32)
                )
                stats = np.asarray(
                    dmf.partial(
                        jnp.asarray(margin),
                        jnp.asarray(dm.labels),
                        jnp.asarray(w_arr),
                    ),
                    np.float64,
                )
                results.append((name, metric, None))
                pairs.append(stats)
                finalizers.append(
                    lambda s, f=dmf: float(
                        f.finalize(jnp.asarray(s, dtype=jnp.float32))
                    )
                )
                continue
            if preds is None:
                preds = objective.margin_to_prediction(margin)
                if num_group > 1:
                    prob_matrix = objectives_mod.SoftprobMulti.margin_to_prediction(
                        objective, margin
                    )
            value = eval_metrics.evaluate(
                metric,
                preds,
                dm.labels,
                dm.weights,
                groups=dm.groups,
                prob_matrix=prob_matrix,
            )
            results.append((name, metric, value))
            if is_multiprocess:
                # non-decomposable (ndcg/map): weight-sum-weighted mean
                append_weighted_mean(value, wsum)
        if feval is not None:
            # xgboost >= 1.2 convention: feval receives the raw margin
            for metric_name, value in feval(margin, dm):
                results.append((name, metric_name, value))
                if is_multiprocess:
                    append_weighted_mean(value, wsum)
    if not is_multiprocess or not results:
        return results
    return combine_host_metric_entries(results, pairs, finalizers)


def combine_host_metric_entries(results, pairs, finalizers):
    """Cross-host combine of per-entry metric stats -> identical lines.

    ``results``: [(name, metric, local_value_or_None)] in a deterministic
    order identical on every host; ``pairs[j]``: the entry's summable stats
    vector; ``finalizers[j]``: fn(summed stats) -> float. Device partial
    stats are f32 (x64 is not enabled); the allgather rides the device too,
    so transport is f32 — the cross-host SUM happens host-side in f64 to
    avoid accumulating f32 rounding over many hosts. Shared by the tree
    booster's evaluate() and the gblinear eval loop."""
    from jax.experimental import multihost_utils

    gathered = np.asarray(
        multihost_utils.process_allgather(
            np.stack(pairs, axis=0).astype(np.float32)
        ),
        np.float64,
    )  # [P, n_entries, stat_size]
    summed = gathered.sum(axis=0)
    return [
        (name, metric, finalizers[j](summed[j]))
        for j, (name, metric, _v) in enumerate(results)
    ]


def _abort_numeric_poison(round_index):
    """The numeric-health guard tripped: a NaN/Inf count in the round's
    learning stats went nonzero. Dump the learning forensics (the last-K
    stats history, naming the first poisoned round), then take the shared
    watchdog abort path (checkpoint flush + flight recorder +
    ``training.abort``) with exit 87 — the stats counters are globally
    psum'd, so every rank sees the same poisoned round and aborts on it,
    long before the consensus digest cadence would reach exit 81."""
    from ..constants import EXIT_NUMERIC_POISON
    from ..telemetry import model as model_telemetry
    from ..training import watchdog

    path = model_telemetry.dump_learning_forensics(
        "numeric_poison", first_bad_round=round_index
    )
    watchdog.request_abort(
        "numeric_poison",
        EXIT_NUMERIC_POISON,
        round=int(round_index),
        forensics=path or "",
    )


def _refuse_categorical_combinations(config, dtrain, evals, mesh):
    """A job with columns given as categories (``feature_types`` ``c``) trains
    set-membership splits depth-wise, under ``hist``, on one device
    (``ops/categorical.py``). Every other combination is refused by name: a
    category's code is never trained as a number."""
    matrices = [dtrain] + [dm for dm, _name in evals]
    if not any(getattr(dm, "has_categorical", False) for dm in matrices):
        return
    if not getattr(dtrain, "has_categorical", False):
        raise exc.UserError(
            "An evaluation set names categorical columns (feature_types 'c') and the "
            "training data names none: a column is a category in both or in neither."
        )
    refused = [
        (dtrain.is_sparse or any(dm.is_sparse for dm in matrices),
         "sparse (CSR) input"),
        (config.booster != "gbtree", "booster={!r}".format(config.booster)),
        (config.process_type != "default", "process_type={!r}".format(config.process_type)),
        (config.grow_policy == "lossguide", "grow_policy='lossguide'"),
        (config.tree_method in ("approx", "exact"),
         "tree_method={!r}".format(config.tree_method)),
        (mesh is not None, "a device mesh (more than one chip or process)"),
        (bool(config.monotone_constraints), "monotone_constraints"),
        (bool(config.interaction_constraints), "interaction_constraints"),
        (min(config.colsample_bytree, config.colsample_bylevel, config.colsample_bynode) < 1.0,
         "colsample_bytree / colsample_bylevel / colsample_bynode below 1"),
    ]
    named = [what for hit, what in refused if hit]
    if named:
        raise exc.UserError(
            "Categorical columns (feature_types 'c') train as categories depth-wise under "
            "tree_method='hist' on one device; not yet with {}. Drop the feature_types "
            "to train the codes as numbers, or one-hot encode the columns.".format(
                ", ".join(named)
            )
        )


def train(
    params,
    dtrain,
    num_boost_round=10,
    evals=(),
    feval=None,
    callbacks=None,
    xgb_model=None,
    verbose_eval=True,
    mesh=None,
    hist_knobs=None,
):
    """Train a Forest. API mirrors ``xgb.train`` for the orchestration layer.

    xgb_model: a Forest or a model-file path to continue training from
    (checkpoint resume — reference checkpointing.py:45-55).
    mesh: optional jax Mesh with a "data" axis for multi-chip data parallelism.
    hist_knobs: optional pre-resolved histogram-knob snapshot (ops/histogram
    HistKnobs); an elastic membership reform passes the original session's
    snapshot so the rebuilt (smaller-mesh) session trains under identical
    kernel choices.
    """
    record_startup(entering_train=True)  # what ran in front of the first train(), once
    config = TrainConfig(params)
    callbacks = list(callbacks or [])
    _refuse_categorical_combinations(config, dtrain, evals, mesh)

    if isinstance(dtrain, BinnedMatrix) and (
        config.booster != "gbtree" or config.process_type != "default"
    ):
        # gblinear fits raw floats and update/refresh recomputes leaf stats
        # from them — representative values would silently change the model.
        # (The streaming-ingest gating refuses these configs up front; this
        # guards direct API callers.)
        raise exc.UserError(
            "Pre-binned training input (chunked ingest) requires "
            "booster='gbtree' with process_type='default'; got booster={!r} "
            "process_type={!r}. Use SM_INGEST_MODE=whole.".format(
                config.booster, config.process_type
            )
        )

    if config.process_type == "update" and config.booster == "gblinear":
        # checked before the gblinear branch returns: otherwise a refresh
        # request is silently reinterpreted as "boost more rounds"
        raise exc.UserError(
            "process_type 'update' can only be used with updater 'refresh' and "
            "'prune' (tree boosters); booster=gblinear does not support it."
        )

    if config.booster == "gblinear":
        from .gblinear import LinearModel, train_linear

        initial = None
        if xgb_model is not None:
            if isinstance(xgb_model, LinearModel):
                initial = xgb_model
            else:
                from .compat import load_model_any_format

                initial, _fmt = load_model_any_format(xgb_model)
                if not isinstance(initial, LinearModel):
                    raise exc.UserError(
                        "Checkpoint {} is not a gblinear model".format(xgb_model)
                    )
        return train_linear(
            config,
            dtrain,
            num_boost_round,
            evals=evals,
            feval=feval,
            callbacks=callbacks,
            initial_model=initial,
            mesh=mesh,
        )

    if xgb_model is None:
        forest = Forest(
            objective_name=config.objective,
            objective_params={
                k: v
                for k, v in config.objective_params.items()
                if k in OBJECTIVE_PARAM_KEYS
            },
            base_score=config.base_score,
            num_feature=dtrain.num_col,
            num_class=config.num_class,
            feature_names=dtrain.feature_names,
            feature_types=getattr(dtrain, "feature_types", None),
        )
    elif isinstance(xgb_model, Forest):
        forest = xgb_model
    else:
        forest = Forest.load_model(xgb_model)
    if forest.num_feature < dtrain.num_col and forest.trees:
        raise exc.UserError("feature_names mismatch between checkpoint and data")
    forest.num_feature = max(forest.num_feature, dtrain.num_col)

    if config.process_type == "update":
        from .update import train_update

        return train_update(
            config, forest, dtrain, list(evals), feval, callbacks, num_boost_round,
            mesh=mesh,
        )

    if config.booster == "dart":
        from .dart import train_dart

        return train_dart(
            config, forest, dtrain, list(evals), feval, callbacks, num_boost_round,
            mesh=mesh,
        )

    metric_names = _eval_metric_names(config, forest.objective())
    session = _TrainingSession(
        config,
        dtrain,
        list(evals),
        forest,
        mesh=mesh,
        metric_names=metric_names,
        has_feval=feval is not None,
        hist_knobs=hist_knobs,
        bundles=True,
    )

    for cb in callbacks:
        if hasattr(cb, "before_training"):
            forest = cb.before_training(forest) or forest

    def _trees_for_round(arrs):
        if session.bundle is not None:
            # a bundled build's splits name a bundle and a position
            arrs = session.bundle.original_splits(arrs)
        if session.num_group > 1 and config.num_parallel_tree > 1:
            # stacked [P, C, ...]: commit class-major (class 0's P trees,
            # then class 1's, ...) matching xgboost's per-group layout
            return (
                [
                    compact_padded_tree(
                        {k: v[t, c] for k, v in arrs.items()}, session.tree_cuts
                    )
                    for c in range(session.num_group)
                    for t in range(config.num_parallel_tree)
                ],
                [
                    c
                    for c in range(session.num_group)
                    for _ in range(config.num_parallel_tree)
                ],
            )
        if session.num_group > 1:
            return (
                [
                    compact_padded_tree({k: v[c] for k, v in arrs.items()}, session.tree_cuts)
                    for c in range(session.num_group)
                ],
                list(range(session.num_group)),
            )
        if config.num_parallel_tree > 1:
            return (
                [
                    compact_padded_tree({k: v[t] for k, v in arrs.items()}, session.tree_cuts)
                    for t in range(config.num_parallel_tree)
                ],
                [0] * config.num_parallel_tree,
            )
        return [compact_padded_tree(arrs, session.tree_cuts)], [0]

    evals_log = {}
    start_round = forest.num_boosted_rounds
    end_round = start_round + num_boost_round
    rnd = start_round
    stop = False
    try:
        while rnd < end_round and not stop:
            trees_batch, batch_metrics = session.run_rounds()
            # what follows, up to the return of the next dispatch, is the
            # session's `host_turnaround`; its parts are spans of their own
            for j, tree_np in enumerate(trees_batch):
                if rnd >= end_round:
                    break  # trees past the requested count are discarded
                with span("commit", attributes={"round": rnd}):
                    trees, info = _trees_for_round(tree_np)
                    forest.append_round(trees, info)
                    note_committed_trees(trees, tree_np)

                if j < len(session.last_learning_stats):
                    # model-quality plane: device reductions + committed-tree
                    # stats -> one training.learning record, then the numeric-
                    # health guard (NaN/Inf counters nonzero -> forensics dump
                    # + exit 87 on every rank, naming this round)
                    from ..telemetry import model as model_telemetry

                    stats = dict(session.last_learning_stats[j])
                    stats.update(model_telemetry.tree_stats(trees))
                    model_telemetry.note_learning(rnd, stats)
                    if model_telemetry.first_poisoned_round([stats], rnd) is not None:
                        _abort_numeric_poison(rnd)

                with span("eval_log", attributes={"round": rnd}):
                    if batch_metrics is not None:
                        # device-computed per-round metrics: [K, n_sets, n_metrics]
                        results = [
                            (name, metric_name, float(batch_metrics[j, si, i]))
                            for si, (name, _dm, _b) in enumerate(session.eval_sets)
                            for i, metric_name in enumerate(session.device_metric_names)
                        ]
                    elif not session.eval_sets:
                        results = []
                    elif not session.host_eval_batched:
                        results = session.evaluate(metric_names, feval=feval)
                    elif j == len(trees_batch) - 1:
                        # host-fallback cadence: the fused K-round dispatch finished
                        # and the device margins cover exactly the committed trees —
                        # one host evaluation per dispatch, attributed to the
                        # batch-end round.
                        results = session.evaluate(metric_names, feval=feval)
                    elif rnd == end_round - 1:
                        # final round lands mid-batch (num_boost_round % K != 0):
                        # the device margins include the over-built, discarded trees
                        # — evaluate the committed forest so the last metric line
                        # (the one HPO reads) is exact.
                        results = session.evaluate(metric_names, feval=feval, forest=forest)
                    else:
                        results = []  # stale round inside the fused batch
                    for data_name, metric_name, value in results:
                        by_metric = evals_log.setdefault(data_name, {})
                        by_metric.setdefault(metric_name, []).append(value)

                # covering: checkpoint, eval-monitor and RoundTimer spans lie inside
                with span("callbacks", covering=True, attributes={"round": rnd}):
                    for cb in callbacks:
                        if hasattr(cb, "after_iteration") and cb.after_iteration(
                            forest, rnd, evals_log
                        ):
                            stop = True
                rnd += 1
                if stop:
                    break
    finally:
        # also on a callback's or a dispatch's exception: no span of this
        # job stays open on the caller's thread
        session.end_turnaround()

    for cb in callbacks:
        if hasattr(cb, "after_training"):
            forest = cb.after_training(forest) or forest
    return forest
