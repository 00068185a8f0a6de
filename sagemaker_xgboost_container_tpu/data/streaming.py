"""Resilient out-of-core channel ingest: chunked sharded readers with a
rank-consistent skip/quarantine policy and a distributed quantile-sketch
merge.

The whole-file readers (``data/readers.py``) materialize every channel file
as float32 on the host — O(dataset) peak memory — and a single corrupt,
truncated or oversized file kills the entire multi-host job. This module is
the north-star-scale replacement path:

* **Chunk planning** — every channel file splits into deterministic chunks:
  newline-aligned byte ranges (csv/libsvm), record-aligned byte ranges
  (recordio-protobuf, by walking the 8-byte record headers), or row-group
  ranges (parquet, from the file metadata). The plan is a pure function of
  the (sorted, realpath-keyed) file listing and ``SM_INGEST_CHUNK_BYTES``,
  so every host of a cluster derives the same plan from the same bytes —
  with ``SM_INGEST_SHARD=1`` ranks take chunks round-robin from a shared
  (replicated) channel instead of each re-reading all of it.
* **Two passes, bounded memory** — pass 1 parses chunk-by-chunk into a
  per-feature *summary sketch* (distinct values + aggregated weights,
  capped at ``SM_INGEST_SKETCH_SIZE`` entries/feature) and drops the
  floats; pass 2 re-parses each chunk, bins it against the agreed cuts
  (reusing the lru-cached device apply kernel from ``data/binning.py``) and
  writes it into a preallocated uint8/uint16 matrix. Channels whose cuts
  are already agreed (validation bins with the training channel's edges)
  skip the second read entirely: pass 1 bins each chunk as it parses and
  assembly is a copy. Peak incremental memory is
  O(chunk + sketch + binned shard), never O(float32 dataset).
* **Identical bin edges on every rank** — in multi-host jobs the per-rank
  sketch summaries allgather over the ``Cluster.synchronize`` framing
  (dedicated ``SM_INGEST_PORT``); every rank merges the same rank-ordered
  summaries deterministically, so cut points are identical everywhere.
  Single-host, the local sketch is exact: for unit/integer row weights the
  cuts (and therefore the binned matrix and the committed trees) are
  **bitwise identical** to the whole-file readers (see
  ``binning.cuts_from_summaries`` for the float-weight ulp caveat).
* **Retry -> skip -> quarantine** — each chunk read/parse runs under the
  transient-retry policy (``SM_IO_RETRY_*``, site ``ingest.chunk``) behind
  the ``data.chunk`` fault point. A chunk that still fails is handled per
  ``SM_INGEST_BAD_CHUNK_ACTION``: ``fail`` (default) or ``skip`` under an
  ``SM_INGEST_MAX_BAD_CHUNKS`` budget. The skip set is **agreed cross-rank**
  (the same allgather that merges the sketches) before any binning
  proceeds, so no two ranks ever train on differently-sharded data; every
  skipped chunk lands in the quarantine record that ``train_job`` stamps
  into the final model manifest (and ``ingest-quarantine.json``).
* **Fail loudly, consistently** — a ``fail``-policy bad chunk, an exhausted
  skip budget, a plan divergence between ranks, or a chunk that changed
  between the two passes raises :class:`IngestError`; the training wiring
  converts it into ``EXIT_INGEST_FAILED`` (85) with a flight-recorder dump
  on **every** rank (each rank reached the same verdict from the same
  allgathered state — the PR-5 consensus pattern).

The whole-file readers remain the small-channel fast path and the
behavioral spec this path matches bit-identically on fault-free input.
"""

import base64
import hashlib
import io
import json
import logging
import os
import shutil
import struct
import threading

import numpy as np

from ..constants import EXIT_INGEST_FAILED
from ..telemetry.registry import REGISTRY
from ..telemetry.emit import emit_metric
from ..telemetry.tracing import trace_span
from ..toolkit import exceptions as exc
from ..utils.envconfig import env_bool, env_float, env_int, env_port
from ..utils.faults import fault_point
from ..utils.retry import retry_transient
from ..utils.warn_once import warn_once
from . import content_types as ct
from . import readers
from .binning import BinnedMatrix, apply_cut_points, cuts_from_summaries
from .matrix import _densify_with_nan
from .recordio import RECORDIO_MAGIC, read_recordio_protobuf

logger = logging.getLogger(__name__)

INGEST_MODE_ENV = "SM_INGEST_MODE"
INGEST_CHUNK_BYTES_ENV = "SM_INGEST_CHUNK_BYTES"
INGEST_ACTION_ENV = "SM_INGEST_BAD_CHUNK_ACTION"
INGEST_MAX_BAD_ENV = "SM_INGEST_MAX_BAD_CHUNKS"
INGEST_SHARD_ENV = "SM_INGEST_SHARD"
INGEST_SKETCH_SIZE_ENV = "SM_INGEST_SKETCH_SIZE"
INGEST_WIRE_SKETCH_ENV = "SM_INGEST_WIRE_SKETCH"
INGEST_PORT_ENV = "SM_INGEST_PORT"
INGEST_TIMEOUT_ENV = "SM_INGEST_TIMEOUT_S"

# NOT the rendezvous (9099), heartbeat (9199), abort (9299), consensus
# (9399) or reform (9499) ports: the sketch/skip allgather happens before
# any of those planes exist, but a later elastic reform may replay ingest
DEFAULT_INGEST_PORT = 9599

# uniform frame bound for the ingest allgather (every rank must pass the
# same value to Cluster.synchronize; per-rank payload sizes differ, so a
# payload-derived bound would let a small-payload rank refuse a legitimate
# large frame). 1 GiB is far beyond any real sketch reply while still
# sanity-capping a garbage length prefix; the recv stays time-deadlined.
_INGEST_FRAME_CAP = 1 << 30


class IngestError(RuntimeError):
    """A chunked-ingest failure every rank reaches identically.

    ``reason`` is machine-readable (``bad_chunk``, ``budget_exceeded``,
    ``plan_failed``, ``plan_divergence``, ``exchange_failed``,
    ``chunk_drift``); the training
    wiring converts any IngestError into ``EXIT_INGEST_FAILED`` (85) with a
    flight-recorder dump.
    """

    def __init__(self, reason, message, **details):
        super().__init__(message)
        self.reason = reason
        self.details = details


class ChannelSemanticError(exc.UserError):
    """A channel-level semantic problem our own chunk parsers detect (wrong
    column count for ``csv_weights``, no feature columns): every chunk of the
    channel fails identically, so quarantining it as "corrupt bytes" would
    burn the skip budget (or exit 85) on what is a customer data-format
    error. The bad-chunk ladder re-raises this class so it surfaces as the
    whole-file readers' ``UserError`` — parser errors from genuinely
    malformed bytes (e.g. a corrupt libsvm line) stay quarantinable."""


def channel_has_sidecars(content_type, *paths):
    """True when libsvm ``.weight``/``.group`` companion files exist under
    any of the channel ``paths``. Only the whole-file readers honor them —
    per-file row spans don't map onto byte-range chunks — so their presence
    pins the whole-file path (``auto`` falls back; forced ``chunked``
    refuses loudly rather than silently dropping weights/groups)."""
    if ct.get_content_type(content_type) != ct.LIBSVM:
        return False
    for path in paths:
        if not path:
            continue
        if os.path.isfile(path):
            if any(
                os.path.isfile(path + s) for s in readers._SIDECAR_SUFFIXES
            ):
                return True
            continue
        for _root, _dirs, names in os.walk(path):
            if any(n.endswith(readers._SIDECAR_SUFFIXES) for n in names):
                return True
    return False


class IngestConfig(object):
    """One resolved snapshot of every SM_INGEST_* knob (resolved per
    channel ingest; malformed values warn once and fall back)."""

    def __init__(self):
        mode = os.environ.get(INGEST_MODE_ENV, "auto")
        if mode not in ("auto", "whole", "chunked"):
            warn_once(
                logger, "ingest.mode",
                "%s=%r is not auto|whole|chunked; using auto",
                INGEST_MODE_ENV, mode,
            )
            mode = "auto"
        action = os.environ.get(INGEST_ACTION_ENV, "fail")
        if action not in ("fail", "skip"):
            warn_once(
                logger, "ingest.action",
                "%s=%r is not fail|skip; using fail",
                INGEST_ACTION_ENV, action,
            )
            action = "fail"
        self.mode = mode
        self.action = action
        self.chunk_bytes = env_int(
            INGEST_CHUNK_BYTES_ENV, 64 * 1024 * 1024, minimum=4096
        )
        self.max_bad = env_int(INGEST_MAX_BAD_ENV, 8, minimum=0)
        self.shard = env_bool(INGEST_SHARD_ENV, False)
        self.sketch_size = env_int(INGEST_SKETCH_SIZE_ENV, 1 << 17, minimum=256)
        self.wire_sketch = env_int(INGEST_WIRE_SKETCH_ENV, 512, minimum=64)
        self.port = env_port(INGEST_PORT_ENV, DEFAULT_INGEST_PORT)
        self.timeout_s = env_float(INGEST_TIMEOUT_ENV, 300.0, minimum=1.0)


def resolve_ingest_config():
    return IngestConfig()


def supports_streaming(train_cfg):
    """-> (ok, reason, max_bin) for this training config.

    Mirrors ``models/booster.TrainConfig``'s max_bin resolution (the session
    validates the pre-binned matrix against its own parse, so drift fails
    loudly, not silently). Chunked ingest needs the binned training path:
    gblinear fits the raw floats, ``process_type=update`` revisits committed
    trees, ``tree_method=exact`` is unbounded-bin by design, the approx
    per-round re-sketch needs the float channel resident, and columns given
    as categories are laid out from their float codes.
    """
    p = train_cfg or {}
    booster = p.get("booster", "gbtree")
    if booster not in ("gbtree",):
        return False, "booster={} trains on float features".format(booster), None
    if p.get("process_type", "default") != "default":
        return False, "process_type=update revisits committed trees", None
    tree_method = p.get("tree_method", "auto")
    if tree_method == "exact":
        return False, "tree_method=exact is unbounded-bin", None
    if tree_method == "approx":
        return False, "tree_method=approx re-sketches from float features", None
    if p.get("enable_categorical", "false") == "true" and "c" in (p.get("feature_types") or ()):
        # the channels carry no types, the hyperparameters name them; chunked
        # ingest sketches every column as a number, so a category's code would
        # train as an ordered value (data/categorical.py lays it out from the
        # floats)
        return False, "feature_types 'c' lays out categories from float features", None
    if p.get("max_bin") is not None:
        max_bin = int(p["max_bin"])
    elif p.get("sketch_eps"):
        max_bin = int(min(max(1.0 / float(p["sketch_eps"]), 2), 1024))
    else:
        max_bin = 256
    return True, None, max_bin


# ---------------------------------------------------------------------------
# Chunk planning
# ---------------------------------------------------------------------------


class Chunk(object):
    """One deterministic unit of channel ingest.

    ``unit`` is ``bytes`` (newline/record-aligned ``[start, end)`` byte
    range), ``rowgroups`` (parquet row-group range) or ``file`` (whole-file
    fallback when a binary file's metadata cannot be walked — the parse
    error then lands somewhere quarantinable instead of killing planning).
    """

    __slots__ = ("file", "start", "end", "index", "unit", "size")

    def __init__(self, file, start, end, index, unit, size):
        self.file = file
        self.start = start
        self.end = end
        self.index = index
        self.unit = unit
        self.size = int(size)

    def describe(self):
        return {
            "file": self.file,
            "start": int(self.start),
            "end": int(self.end),
            "unit": self.unit,
            "index": int(self.index),
            # byte size for every unit (row-group/whole-file chunks carry
            # the metadata estimate) so quarantine byte accounting doesn't
            # read 0 for non-byte-range chunks
            "size": int(self.size),
        }


class ChunkPlan(object):
    def __init__(self, fmt, chunks, delimiter=None):
        self.fmt = fmt
        self.chunks = chunks
        self.delimiter = delimiter

    def fingerprint(self):
        doc = json.dumps(
            [[c.file, int(c.start), int(c.end), c.unit] for c in self.chunks],
            sort_keys=True,
        )
        return hashlib.sha256(doc.encode()).hexdigest()


def _newline_ranges(path, size, chunk_bytes):
    """Newline-aligned byte ranges covering ``[0, size)``."""
    if size <= chunk_bytes:
        return [(0, size)]
    bounds = [0]
    with open(path, "rb") as f:
        target = chunk_bytes
        while target < size:
            f.seek(target)
            f.readline()  # finish the line the target landed inside
            pos = f.tell()
            if pos >= size:
                break
            bounds.append(pos)
            target = pos + chunk_bytes
    bounds.append(size)
    return list(zip(bounds[:-1], bounds[1:]))


def _recordio_ranges(path, size, chunk_bytes):
    """Record-aligned byte ranges by walking the 8-byte record headers.

    Planning reads headers only (seek-past payloads). A corrupt header stops
    the walk and the remainder becomes one final chunk, so the corruption is
    met at *parse* time inside a chunk the skip policy can quarantine.
    """
    if size <= chunk_bytes:
        return [(0, size)]
    bounds = [0]
    try:
        with open(path, "rb") as f:
            while True:
                header = f.read(8)
                if len(header) < 8:
                    break
                magic, length = struct.unpack("<II", header)
                if magic != RECORDIO_MAGIC:
                    break  # corrupt record: leave the tail as one chunk
                padded = (length + 3) & ~3
                f.seek(padded, 1)
                pos = f.tell()
                if pos >= size:
                    break
                if pos - bounds[-1] >= chunk_bytes:
                    bounds.append(pos)
    except OSError:
        return [(0, size)]
    bounds.append(size)
    return [(a, b) for a, b in zip(bounds[:-1], bounds[1:]) if b > a]


def _parquet_rowgroup_ranges(path, chunk_bytes):
    """-> list of (rg_start, rg_end) row-group ranges, or None for the
    whole-file fallback (unreadable metadata)."""
    import pyarrow.parquet as pq

    try:
        meta = pq.ParquetFile(path).metadata
    except Exception:
        return None
    if meta.num_row_groups == 0:
        # a legitimate empty part (ParquetWriter opened/closed with no
        # tables — a common Spark artifact): contributes no chunks, exactly
        # like the whole-file reader's 0-row read of it
        return []
    if meta.num_row_groups == 1:
        return [(0, 1)]
    ranges = []
    lo, acc = 0, 0
    for rg in range(meta.num_row_groups):
        acc += max(0, meta.row_group(rg).total_byte_size)
        if acc >= chunk_bytes and rg + 1 < meta.num_row_groups:
            ranges.append((lo, rg + 1))
            lo, acc = rg + 1, 0
    ranges.append((lo, meta.num_row_groups))
    return ranges


def plan_channel(files, fmt, chunk_bytes):
    """files (staged listing) -> ChunkPlan with globally-indexed chunks.

    Chunk identity is the *realpath* (the staged symlink names carry a
    salted per-process hash suffix; the target path is what every host and
    every rerun agrees on).
    """
    delimiter = None
    if fmt == ct.CSV and files:
        try:
            delimiter = readers._channel_delimiter(files, site="ingest.plan")
        except OSError as e:
            # same contract as the per-file planning below: a persistent IO
            # failure must land in the exit-85 plane (and ride the
            # pre-exchange error broadcast), never escape as a raw OSError
            raise IngestError(
                "plan_failed",
                "chunk planning failed sniffing the channel delimiter "
                "({}); no plan can be agreed".format(e),
            )
    chunks = []
    for f in files:
        real = os.path.realpath(f)

        def _file_ranges():
            size = os.path.getsize(real)
            if fmt in (ct.CSV, ct.LIBSVM):
                return [
                    (s, e, "bytes", e - s)
                    for s, e in _newline_ranges(real, size, chunk_bytes)
                ]
            if fmt == ct.PARQUET:
                rgs = _parquet_rowgroup_ranges(real, chunk_bytes)
                if rgs is None:
                    return [(0, size, "file", size)]
                share = size // max(1, len(rgs))
                return [(a, b, "rowgroups", share) for a, b in rgs]
            # recordio-protobuf
            return [
                (s, e, "bytes", e - s)
                for s, e in _recordio_ranges(real, size, chunk_bytes)
            ]

        try:
            # same transient-retry policy as the chunk reads: a planning-time
            # IO blip must not escape as a raw OSError (no dump, no exit 85,
            # peers stuck in the allgather blaming "exchange_failed")
            ranges = retry_transient(_file_ranges, site="ingest.plan")
        except OSError as e:
            raise IngestError(
                "plan_failed",
                "chunk planning failed reading {} ({}); no plan can be "
                "agreed".format(real, e),
            )
        for start, end, unit, nbytes in ranges:
            chunks.append(Chunk(real, start, end, len(chunks), unit, nbytes))
    return ChunkPlan(fmt, chunks, delimiter=delimiter)


# ---------------------------------------------------------------------------
# Chunk parsing (shared by both passes)
# ---------------------------------------------------------------------------


class _ChunkData(object):
    __slots__ = ("features", "labels", "weights", "qids")

    def __init__(self, features, labels, weights=None, qids=None):
        self.features = features  # float32 [rows, local_width], NaN = missing
        self.labels = labels      # float32 [rows] or None (recordio w/o label)
        self.weights = weights    # float32 [rows] or None
        self.qids = qids          # int64 [rows] or None


def _read_range(path, start, end):
    with open(path, "rb") as f:
        f.seek(start)
        return f.read(end - start)


def _parse_csv_chunk(raw, delimiter, csv_weights):
    import pandas as pd

    try:
        frame = pd.read_csv(
            io.BytesIO(raw), header=None, delimiter=delimiter, dtype=np.float32
        )
    except pd.errors.EmptyDataError:
        return _ChunkData(np.empty((0, 0), np.float32), np.empty(0, np.float32))
    data = frame.to_numpy(dtype=np.float32)
    if data.shape[1] < (3 if csv_weights == 1 else 2):
        raise ChannelSemanticError(
            "csv_weights=1 requires a weight column after the label"
            if csv_weights == 1
            else "CSV data needs at least a label column and one feature column"
        )
    labels = data[:, 0]
    if csv_weights == 1:
        return _ChunkData(data[:, 2:], labels, weights=data[:, 1])
    return _ChunkData(data[:, 1:], labels)


def _parse_libsvm_chunk(raw):
    parsed = readers.parse_libsvm_text(raw.decode(errors="ignore"))
    if parsed is None:
        return _ChunkData(np.empty((0, 0), np.float32), np.empty(0, np.float32))
    csr, labels, weights, qids = parsed
    return _ChunkData(_densify_with_nan(csr), labels, weights=weights, qids=qids)


def _parse_parquet_chunk(chunk):
    import pyarrow.parquet as pq

    if chunk.unit == "file":
        table = pq.read_table(chunk.file)
    else:
        table = pq.ParquetFile(chunk.file).read_row_groups(
            list(range(chunk.start, chunk.end))
        )
    data = table.to_pandas().to_numpy(dtype=np.float32)
    if data.size and data.shape[1] < 2:
        raise ChannelSemanticError(
            "Parquet data needs at least a label column and one feature column"
        )
    if data.shape[0] == 0:
        return _ChunkData(np.empty((0, 0), np.float32), np.empty(0, np.float32))
    return _ChunkData(data[:, 1:], data[:, 0])


def _parse_recordio_chunk(raw):
    features, labels = read_recordio_protobuf(raw)
    import scipy.sparse as sp

    if sp.issparse(features):
        features = _densify_with_nan(features.tocsr())
    features = np.asarray(features, np.float32)
    if features.ndim != 2:
        features = features.reshape(len(features), -1)
    return _ChunkData(
        features, None if labels is None else np.asarray(labels, np.float32)
    )


def _parse_chunk(plan, chunk, csv_weights):
    if plan.fmt == ct.CSV:
        return _parse_csv_chunk(
            _read_range(chunk.file, chunk.start, chunk.end), plan.delimiter, csv_weights
        )
    if plan.fmt == ct.LIBSVM:
        return _parse_libsvm_chunk(_read_range(chunk.file, chunk.start, chunk.end))
    if plan.fmt == ct.PARQUET:
        return _parse_parquet_chunk(chunk)
    return _parse_recordio_chunk(_read_range(chunk.file, chunk.start, chunk.end))


def _load_chunk(plan, chunk, csv_weights):
    """One chunk read+parse under the transient-retry policy, behind the
    ``data.chunk`` fault point (chaos drills arm it per hit)."""

    def _attempt():
        fault_point(
            "data.chunk",
            path=chunk.file,
            start=chunk.start,
            end=chunk.end,
            index=chunk.index,
        )
        return _parse_chunk(plan, chunk, csv_weights)

    return retry_transient(_attempt, site="ingest.chunk")


# ---------------------------------------------------------------------------
# Summary sketch (distinct values + aggregated weights per feature)
# ---------------------------------------------------------------------------


def _dedup_sorted(v, w):
    """SORTED (values, weights) -> unique values + segment weight sums.

    Bitwise-identical to ``np.unique(v, return_index=True)`` + ``reduceat``
    on sorted input, but linear: np.unique re-sorts the array, and at
    sketch capacity (131k entries x features) that hidden O(S log S) was
    the dominant per-chunk merge cost at north-star channel sizes.
    """
    if len(v) == 0:
        return v.astype(np.float32), w
    keep = np.empty(len(v), bool)
    keep[0] = True
    np.not_equal(v[1:], v[:-1], out=keep[1:])
    start = np.flatnonzero(keep)
    return v[start].astype(np.float32), np.add.reduceat(w, start)


def _merge_summary(a, b):
    """Merge two (values, weights) summaries: union values, sum weights.

    The stable argsort over the concatenation of two sorted runs is
    adaptive (timsort) — effectively linear — and the weight-sum order it
    produces is exactly the sequential order the whole-path parity tests
    pin, so this merge stays bitwise-faithful.
    """
    v = np.concatenate([a[0], b[0]])
    w = np.concatenate([a[1], b[1]])
    order = np.argsort(v, kind="stable")
    return _dedup_sorted(v[order], w[order])


def _compress_summary(values, weights, cap):
    """Deterministically cap a summary at ``cap`` entries — a hard bound
    (the SM_INGEST_SKETCH_SIZE / SM_INGEST_WIRE_SKETCH knob contract), so
    the extremes are always kept and only cap-2 interior quantile picks
    join them.

    Keeps evenly spaced cumulative-weight quantile picks and folds each
    dropped entry's weight into the next kept one, preserving the total
    weight and the cumulative-weight curve the cut selection reads. Below
    the cap this is the identity — which is where the bitwise whole-path
    equivalence contract holds.
    """
    n = len(values)
    if n <= cap:
        return values, weights
    cum = np.concatenate([[0.0], np.cumsum(weights, dtype=np.float64)])
    if cap <= 2:
        picks = np.unique(np.array([0, n - 1]))
    else:
        targets = cum[-1] * (
            np.arange(1, cap - 1, dtype=np.float64) / (cap - 1)
        )
        interior = np.clip(
            np.searchsorted(cum[1:], targets, side="left"), 0, n - 1
        )
        picks = np.unique(np.concatenate([[0, n - 1], interior]))
    new_w = np.diff(cum[picks + 1], prepend=0.0)
    return values[picks], new_w


class SummarySketch(object):
    """Per-feature streaming summary: (distinct f32 values, f64 weight sums).

    Exact (and therefore whole-path bitwise-faithful through
    ``cuts_from_summaries``) while a feature's distinct-value count stays
    under ``cap``; beyond it the summary compresses deterministically with
    one warning (quality degrades gracefully, memory stays bounded).
    """

    def __init__(self, cap):
        self.cap = cap
        self.cols = {}

    def update(self, features, row_weights):
        n, d = features.shape
        if n == 0:
            return
        w_rows = (
            np.ones(n, np.float64)
            if row_weights is None
            else np.asarray(row_weights, np.float64)
        )
        for f in range(d):
            col = features[:, f]
            mask = ~np.isnan(col)
            if not mask.any():
                continue
            v = col[mask]
            w = w_rows[mask]
            order = np.argsort(v, kind="stable")
            summary = _dedup_sorted(v[order], w[order])
            cur = self.cols.get(f)
            if cur is not None:
                summary = _merge_summary(cur, summary)
            if len(summary[0]) > self.cap:
                warn_once(
                    logger, "ingest.sketch_cap",
                    "ingest sketch exceeded %s=%d distinct values for a "
                    "feature; compressing (cuts stay rank-consistent but are "
                    "no longer bitwise whole-path identical)",
                    INGEST_SKETCH_SIZE_ENV,
                    self.cap,
                )
                summary = _compress_summary(summary[0], summary[1], self.cap)
            self.cols[f] = summary

    def summaries(self, width):
        empty = (np.empty(0, np.float32), np.empty(0, np.float64))
        return [self.cols.get(f, empty) for f in range(width)]

    # ------------------------------------------------------------- wire form
    def encode(self, width, wire_cap):
        values, weights = [], []
        for v, w in self.summaries(width):
            v, w = _compress_summary(v, w, wire_cap)
            values.append(base64.b64encode(np.asarray(v, np.float32).tobytes()).decode("ascii"))
            weights.append(base64.b64encode(np.asarray(w, np.float64).tobytes()).decode("ascii"))
        return {"width": width, "values": values, "weights": weights}

    @staticmethod
    def decode_summaries(doc):
        out = []
        for vb, wb in zip(doc["values"], doc["weights"]):
            out.append(
                (
                    np.frombuffer(base64.b64decode(vb), np.float32),
                    np.frombuffer(base64.b64decode(wb), np.float64),
                )
            )
        return out


# ---------------------------------------------------------------------------
# Quarantine bookkeeping (job-global, stamped into the model manifest)
# ---------------------------------------------------------------------------

_state_lock = threading.Lock()
_staging_seq = 0         # unique staging dirs for same-process multi-ingest
_skipped_chunks = []     # agreed union across ranks and channels
_rows_skipped = 0
_bytes_skipped = 0
_bad_total = 0           # counts toward the cross-channel budget


def reset_ingest_state():
    """Clear the job-global quarantine record and skip budget.

    Called at the start of every streaming job ingest (the
    ``get_validated_data_matrices`` wiring) and by tests: a second ingest in
    the same process (local mode, an elastic-reform replay) must not start
    with the previous run's budget consumed or duplicate its quarantine
    entries into the new model's manifest."""
    global _rows_skipped, _bytes_skipped, _bad_total
    with _state_lock:
        del _skipped_chunks[:]
        _rows_skipped = 0
        _bytes_skipped = 0
        _bad_total = 0


def quarantine_record():
    """-> the job's quarantine manifest dict, or None when nothing was
    skipped. Schema: ``action``, ``max_bad_chunks``, ``chunks_skipped``,
    ``rows_skipped`` (best-effort: rows are only known when the bad chunk's
    bytes were still countable), ``bytes_skipped`` and ``skipped_chunks``
    (one entry per chunk: file/start/end/unit/index/channel/rank/error)."""
    with _state_lock:
        if not _skipped_chunks:
            return None
        cfg = resolve_ingest_config()
        return {
            "action": cfg.action,
            "max_bad_chunks": cfg.max_bad,
            "chunks_skipped": len(_skipped_chunks),
            "rows_skipped": int(_rows_skipped),
            "bytes_skipped": int(_bytes_skipped),
            "skipped_chunks": [dict(c) for c in _skipped_chunks],
        }


def write_quarantine_manifest(directory):
    """Write ``ingest-quarantine.json`` under ``directory`` (master-side,
    next to the model artifact so it travels in model.tar.gz). -> path or
    None when the job skipped nothing."""
    record = quarantine_record()
    if record is None:
        return None
    path = os.path.join(directory, "ingest-quarantine.json")
    tmp = os.path.join(directory, ".ingest-quarantine.json.tmp")
    with open(tmp, "w") as f:
        json.dump(record, f, sort_keys=True, indent=2)
    os.replace(tmp, path)
    return path


def abort_on_ingest_failure(err):
    """Convert an IngestError into the coordinated exit-85 abort: one
    ``training.abort`` record + flight-recorder dump, then
    ``EXIT_INGEST_FAILED``. Every rank that reached the (allgathered)
    verdict calls this with the same state."""
    from ..training import watchdog

    record = quarantine_record() or {}
    watchdog.request_abort(
        "ingest_failed",
        EXIT_INGEST_FAILED,
        ingest_reason=getattr(err, "reason", "unknown"),
        detail=str(err),
        chunks_skipped=record.get("chunks_skipped", 0),
    )


# ---------------------------------------------------------------------------
# The ingest pipeline
# ---------------------------------------------------------------------------


def _chunks_counter(status):
    return REGISTRY.counter(
        "ingest_chunks_total",
        "Channel chunks ingested by the streaming reader",
        {"status": status},
    )


def _bytes_counter(status):
    return REGISTRY.counter(
        "ingest_bytes_total",
        "Channel bytes ingested (ok) or quarantined (skipped)",
        {"status": status},
    )


def _rows_skipped_counter():
    return REGISTRY.counter(
        "ingest_rows_skipped_total",
        "Rows lost to quarantined chunks (best-effort row counts)",
    )


class _Pass1State(object):
    def __init__(self):
        self.rows = {}        # chunk index -> parsed row count
        self.ncol = 0         # feature width (labels/weights split off)
        self.bad = []         # [{chunk fields..., error, rows}]
        self.failed = None    # fail-policy error string
        self.missing_labels = False
        self.has_qids = False # any non-empty chunk carried libsvm qid:
        self.blocks = {}      # chunk index -> pre-binned block (cut-supplied
                              # channels bin during pass 1: one read, no
                              # drift window — see _assemble_blocks)


def _estimate_rows(chunk, fmt):
    """Best-effort row count of a bad text chunk (newline count)."""
    if fmt not in (ct.CSV, ct.LIBSVM) or chunk.unit != "bytes":
        return 0
    try:
        return _read_range(chunk.file, chunk.start, chunk.end).count(b"\n")
    except Exception:
        return 0


def _pad_to_width(feats, width):
    """Narrow chunk (libsvm local width / csv positional-column alignment):
    pad with all-missing columns, exactly like the whole-file concat/vstack
    union."""
    if feats.shape[1] >= width:
        return feats
    pad = np.full((feats.shape[0], width - feats.shape[1]), np.nan, np.float32)
    return np.concatenate([feats, pad], axis=1)


def _pass1(plan, assigned, cfg, sketch, csv_weights, rank, channel, bin_ctx=None):
    state = _Pass1State()
    for chunk in assigned:
        with trace_span(
            "data.chunk",
            attributes={
                "pass": 1,
                "file": os.path.basename(chunk.file),
                "start": chunk.start,
                "end": chunk.end,
                "index": chunk.index,
                "channel": channel,
            },
        ):
            try:
                data = _load_chunk(plan, chunk, csv_weights)
            except (KeyboardInterrupt, SystemExit, ChannelSemanticError):
                raise
            except Exception as e:
                entry = dict(
                    chunk.describe(),
                    channel=channel,
                    rank=rank,
                    error="{}: {}".format(type(e).__name__, e),
                    rows=_estimate_rows(chunk, plan.fmt),
                )
                state.bad.append(entry)
                if cfg.action == "fail":
                    # name the chunk, not just the exception: this string is
                    # what the exit-85 training.abort record's detail carries
                    state.failed = "{}[{}:{}) {}".format(
                        os.path.basename(chunk.file), chunk.start, chunk.end,
                        entry["error"],
                    )
                    logger.error(
                        "bad chunk %s[%s:%s) under %s=fail: %s",
                        os.path.basename(chunk.file), chunk.start, chunk.end,
                        INGEST_ACTION_ENV, e,
                    )
                    break
                logger.warning(
                    "bad chunk %s[%s:%s): %s — marked for the cross-rank "
                    "skip agreement (%d bad so far on this rank)",
                    os.path.basename(chunk.file), chunk.start, chunk.end, e,
                    len(state.bad),
                )
                if len(state.bad) > cfg.max_bad:
                    # the global verdict can only be worse; stop burning IO
                    break
                continue
        state.rows[chunk.index] = data.features.shape[0]
        state.ncol = max(state.ncol, data.features.shape[1])
        if data.qids is not None and data.features.shape[0] > 0:
            state.has_qids = True
        if data.labels is None and data.features.shape[0] > 0:
            state.missing_labels = True
        if sketch is not None:
            sketch.update(data.features, data.weights)
        elif bin_ctx is not None and data.features.shape[1] <= bin_ctx[2]:
            # cuts are already agreed (validation channels): bin now and
            # drop the floats — the channel is read ONCE, the whole-channel
            # second parse _pass2 would pay buys nothing here. A chunk wider
            # than the cuts gets no block; that job raises the
            # val-wider-than-train UserError before assembly.
            cuts_b, max_bin_b, width_b = bin_ctx
            state.blocks[chunk.index] = (
                apply_cut_points(
                    _pad_to_width(data.features, width_b), cuts_b, max_bin_b
                ),
                data.labels,
                data.weights,
                data.qids,
            )
    return state


def _exchange_state(world, current_host, payload, cfg, master_addr=None):
    """One allgather of per-rank ingest state -> rank-ordered payload list.

    Any transport failure is an IngestError: unlike the consensus guard
    (which can skip a check), ingest cannot proceed without agreed cuts and
    an agreed skip set.
    """
    if not world:
        return [payload]
    from ..parallel.distributed import Cluster

    cluster = Cluster(world, current_host, port=cfg.port)
    if master_addr is not None:
        cluster.master_host = master_addr
    try:
        # the master's reply is the rank-ordered payload LIST (~world x one
        # payload), and a sketch payload alone (features x wire cap x ~12
        # base64 bytes per entry) can exceed the 1 MiB control default on
        # the flagship wide-channel multi-host shape. The bound must be
        # IDENTICAL on every rank (synchronize's contract) and payload
        # sizes are not — a cuts-holding rank sends no sketch while a
        # sketching rank may ship megabytes — so use a uniform generous
        # cap: the exchange stays time-deadlined either way
        return cluster.synchronize(
            payload, timeout=cfg.timeout_s,
            max_frame_bytes=_INGEST_FRAME_CAP,
        )
    except Exception as e:
        raise IngestError(
            "exchange_failed",
            "ingest state allgather failed ({}); cuts and the skip set "
            "cannot be agreed — aborting rather than training on "
            "potentially misaligned shards".format(e),
        )


def _verdict(replies, cfg, channel, rank=0):
    """The rank-identical part: skip-set union, budget, consistency.

    Every rank evaluates this over the same rank-ordered replies, so every
    rank raises (or proceeds) identically — the PR-5 consensus pattern
    applied to ingest. ``rank`` scopes the *metric counters* to this rank's
    own chunks (a fleet-wide Prometheus sum must not multiply the skip
    count by the world size); the quarantine record keeps the agreed union.
    """
    global _rows_skipped, _bytes_skipped, _bad_total
    all_bad = [dict(b) for r in replies for b in r.get("bad", ())]
    failures = [r["failed"] for r in replies if r.get("failed")]
    plans = {r.get("plan") for r in replies if r.get("plan") is not None}
    if cfg.shard and len(plans) > 1:
        raise IngestError(
            "plan_divergence",
            "ranks derived different chunk plans for a sharded channel "
            "({} distinct fingerprints) — the channel is not identical "
            "across hosts".format(len(plans)),
            fingerprints=sorted(plans),
        )
    if failures:
        raise IngestError(
            "bad_chunk",
            "unreadable chunk under {}=fail: {}".format(
                INGEST_ACTION_ENV, failures[0]
            ),
            bad_chunks=all_bad,
        )
    with _state_lock:
        new_total = _bad_total + len(all_bad)
    if new_total > cfg.max_bad:
        first = all_bad[0] if all_bad else None
        raise IngestError(
            "budget_exceeded",
            "{} bad chunk(s) across ranks exceed {}={} — refusing to train "
            "on what remains{}".format(
                new_total, INGEST_MAX_BAD_ENV, cfg.max_bad,
                "" if first is None else " (first: {}[{}:{}) {})".format(
                    os.path.basename(first["file"]), first["start"],
                    first["end"], first["error"],
                ),
            ),
            bad_chunks=all_bad,
        )
    if all_bad:
        def _chunk_bytes(b):
            return max(0, int(b.get("size", b["end"] - b["start"])))

        skipped_bytes = sum(_chunk_bytes(b) for b in all_bad)
        skipped_rows = sum(int(b.get("rows", 0)) for b in all_bad)
        with _state_lock:
            _bad_total = new_total
            _skipped_chunks.extend(all_bad)
            _rows_skipped += skipped_rows
            _bytes_skipped += skipped_bytes
        own = [b for b in all_bad if b.get("rank") == rank]
        _chunks_counter("skipped").inc(len(own))
        _bytes_counter("skipped").inc(sum(_chunk_bytes(b) for b in own))
        _rows_skipped_counter().inc(sum(int(b.get("rows", 0)) for b in own))
        emit_metric(
            "ingest.quarantine",
            channel=channel,
            chunks_skipped=len(all_bad),
            rows_skipped=skipped_rows,
            bytes_skipped=skipped_bytes,
            budget=cfg.max_bad,
        )
        logger.warning(
            "quarantined %d chunk(s) (~%d rows, %d bytes) in channel %r by "
            "cross-rank agreement; training proceeds without them",
            len(all_bad), skipped_rows, skipped_bytes, channel,
        )
    return all_bad


class _MatrixAssembler(object):
    """Shared per-chunk accumulator for both binning paths (the pass-2
    re-parse and the pass-1 block cache): the preallocated matrix writes,
    lazy weights init, the zero-row qid rule and the ok-chunk counters
    live in ONE place so a fix to either path cannot miss the other."""

    def __init__(self, n_total, width, max_bin):
        dtype = np.uint8 if max_bin + 1 <= 256 else np.uint16
        self.bins = np.empty((n_total, width), dtype)
        self.labels = np.empty(n_total, np.float32)
        self.weights = None
        self._n_total = n_total
        self._qids = []
        self._qids_ok = True
        self._offset = 0

    def add(self, chunk, block, labels, weights, qids):
        rows = block.shape[0]
        self.bins[self._offset : self._offset + rows] = block
        self.labels[self._offset : self._offset + rows] = (
            np.nan if labels is None else labels
        )
        if weights is not None:
            if self.weights is None:
                self.weights = np.ones(self._n_total, np.float32)
            self.weights[self._offset : self._offset + rows] = weights
        if qids is not None:
            self._qids.append(np.asarray(qids, np.int64))
        elif rows > 0:
            # only a chunk with actual rows can invalidate the channel's
            # qid coverage — an empty chunk (blank/comment lines) has no
            # rows to group and must not drop every query group
            self._qids_ok = False
        self._offset += rows
        _chunks_counter("ok").inc()
        _bytes_counter("ok").inc(max(0, chunk.size))

    def finish(self):
        groups = None
        if self._qids_ok and self._qids:
            groups = readers._qids_to_groups(np.concatenate(self._qids))
        return self.bins, self.labels, self.weights, groups


def _pass2(plan, kept, state_rows, cuts, max_bin, width, csv_weights, channel):
    asm = _MatrixAssembler(
        sum(state_rows[c.index] for c in kept), width, max_bin
    )
    for chunk in kept:
        with trace_span(
            "data.chunk",
            attributes={
                "pass": 2,
                "file": os.path.basename(chunk.file),
                "start": chunk.start,
                "end": chunk.end,
                "index": chunk.index,
                "channel": channel,
            },
        ):
            try:
                data = _load_chunk(plan, chunk, csv_weights)
            except (KeyboardInterrupt, SystemExit, ChannelSemanticError):
                raise
            except Exception as e:
                raise IngestError(
                    "chunk_drift",
                    "chunk {}[{}:{}) failed on the binning pass after the "
                    "skip set was agreed ({}); re-agreeing is impossible "
                    "without desharding the cluster".format(
                        os.path.basename(chunk.file), chunk.start, chunk.end, e
                    ),
                )
            rows = data.features.shape[0]
            if rows != state_rows[chunk.index]:
                raise IngestError(
                    "chunk_drift",
                    "chunk {}[{}:{}) changed between passes ({} rows, "
                    "expected {})".format(
                        os.path.basename(chunk.file), chunk.start, chunk.end,
                        rows, state_rows[chunk.index],
                    ),
                )
            feats = _pad_to_width(data.features, width)
            asm.add(
                chunk, apply_cut_points(feats, cuts, max_bin),
                data.labels, data.weights, data.qids,
            )
    return asm.finish()


def _assemble_blocks(kept, state, max_bin, width):
    """Assemble the matrix from the blocks pass 1 already binned (cut-
    supplied channels): a copy, not a re-read — half the IO/parse of the
    two-pass path, and the between-pass drift window does not exist.
    Blocks pop as they copy, so the transient doubling of the binned
    footprint shrinks chunk by chunk (still O(binned shard))."""
    asm = _MatrixAssembler(
        sum(state.rows[c.index] for c in kept), width, max_bin
    )
    for chunk in kept:
        asm.add(chunk, *state.blocks.pop(chunk.index))
    return asm.finish()


def ingest_channel(
    data_path,
    content_type,
    max_bin,
    channel="train",
    csv_weights=0,
    cut_points=None,
    hosts=None,
    current_host=None,
    master_addr=None,
    config=None,
):
    """Chunked sharded ingest of one channel -> :class:`BinnedMatrix`.

    ``cut_points`` supplies pre-agreed cuts (validation channels bin with
    the training channel's edges and skip the sketch); otherwise pass 1
    builds the distributed sketch and every rank derives identical cuts
    from the merged summaries. ``hosts``/``current_host`` arm the cross-rank
    exchange (single-host jobs short-circuit it); a host whose channel path
    holds no data still participates (empty payload) and returns None, so
    peers never hang waiting for its sketch.

    Raises :class:`IngestError` for every failure the cluster must answer
    with exit 85, and the whole-file readers' ``UserError``s for semantic
    problems (no labels, non-finite labels, too-few columns).
    """
    cfg = config or resolve_ingest_config()
    fmt = ct.get_content_type(content_type)
    world = sorted(hosts) if hosts and len(hosts) > 1 else None
    rank = world.index(current_host) if world else 0

    # per-invocation staging dir: the whole-file readers' fixed staging path
    # is fine one-container-per-host, but loopback drills/tests run several
    # ranks per machine (even per process) and concurrent rmtree+restage
    # would clobber each other. Chunk identity uses realpaths, so the staged
    # location never matters.
    with _state_lock:
        global _staging_seq
        _staging_seq += 1
        seq = _staging_seq
    staging_dir = "{}-chunked-{}-{}".format(readers.STAGING_DIR, os.getpid(), seq)
    sketch = SummarySketch(cfg.sketch_size) if cut_points is None else None
    plan = ChunkPlan(fmt, [])
    assigned = []
    state = _Pass1State()
    local_error = None
    try:
        try:
            try:
                staged = readers.stage_input_files(
                    data_path, staging_dir=staging_dir
                )
                files = (
                    readers._list_data_files(staged)
                    if staged is not None
                    else []
                )
            except OSError as e:
                # staging/listing IO lives OUTSIDE the ingest.plan retry
                # site but must land in the same exit-85 plane (and ride
                # the pre-exchange error broadcast below): a raw OSError
                # here would strand every peer in the allgather for
                # SM_INGEST_TIMEOUT_S blaming "exchange_failed"
                raise IngestError(
                    "plan_failed",
                    "chunk planning failed staging/listing the channel "
                    "({}); no plan can be agreed".format(e),
                )
            plan = plan_channel(files, fmt, cfg.chunk_bytes)
            n_files = len(files)
        finally:
            # chunks carry realpaths — the staged symlink tree is only
            # needed for listing/planning, and per-invocation dirs would
            # otherwise accumulate in /tmp (2 per job, more across drills
            # and replays). Remove by the name we chose: stage_input_files
            # creates the dir even when it finds nothing to stage (and
            # then returns None).
            shutil.rmtree(staging_dir, ignore_errors=True)
        if world and cfg.shard:
            assigned = [c for c in plan.chunks if c.index % len(world) == rank]
        else:
            assigned = list(plan.chunks)
        logger.info(
            "chunked ingest of channel %r: %d file(s), %d chunk(s) planned, "
            "%d assigned to this rank (chunk_bytes=%d, action=%s)",
            channel, n_files, len(plan.chunks), len(assigned),
            cfg.chunk_bytes, cfg.action,
        )
        bin_ctx = (
            None
            if cut_points is None
            else (cut_points, max_bin, len(cut_points))
        )
        state = _pass1(
            plan, assigned, cfg, sketch, csv_weights, rank, channel,
            bin_ctx=bin_ctx,
        )
    except (exc.UserError, IngestError) as e:
        # a rank that fails BEFORE the allgather (delimiter mismatch,
        # semantic parse error, plan IO failure) must still join it —
        # bailing here would strand every peer in the exchange for
        # SM_INGEST_TIMEOUT_S and misattribute the failure to
        # "exchange_failed". The error rides the payload (like
        # missing_labels) and every rank raises it identically below.
        if world is None:
            raise
        local_error = {
            "kind": "ingest" if isinstance(e, IngestError) else "user",
            "reason": getattr(e, "reason", None),
            "message": str(e),
        }
        logger.error(
            "local ingest failure on channel %r (broadcast to peers): %s",
            channel, e,
        )

    payload = {
        "rank": rank,
        "channel": channel,
        "chunks": len(assigned),
        "rows": int(sum(state.rows.values())),
        "ncol": int(state.ncol),
        "bad": state.bad,
        "failed": state.failed,
        "plan": (
            plan.fingerprint()
            if (world and cfg.shard and local_error is None)
            else None
        ),
        "missing_labels": bool(state.missing_labels),
        "qids": bool(state.has_qids),
        "error": local_error,
    }
    if world and sketch is not None:
        payload["sketch"] = sketch.encode(state.ncol, cfg.wire_sketch)
    replies = _exchange_state(world, current_host, payload, cfg, master_addr)
    for r in replies:
        # rank-identical: the first (rank-ordered) local failure fails
        # every rank the same way, before any verdict/cut derivation
        err = r.get("error")
        if err:
            if err.get("kind") == "user":
                raise exc.UserError(err.get("message", "ingest failed"))
            raise IngestError(
                err.get("reason") or "plan_failed",
                err.get("message", "ingest failed"),
            )
    all_bad = _verdict(replies, cfg, channel, rank=rank)
    if world and cfg.shard and any(r.get("qids") for r in replies):
        # rank-identical refusal (derived from the agreed replies): chunk
        # round-robin would fragment qid query groups across ranks and
        # silently corrupt ranking gradients
        raise exc.UserError(
            "SM_INGEST_SHARD=1 cannot preserve libsvm query groups (qid:): "
            "chunk round-robin fragments groups across ranks; disable "
            "sharding for ranking data."
        )

    width = max(int(r.get("ncol", 0)) for r in replies)
    total_rows = sum(int(r.get("rows", 0)) for r in replies)
    if total_rows == 0:
        return None  # empty channel everywhere: the caller's "no data" path
    if width == 0:
        raise exc.UserError(
            "Channel {!r} parsed to zero feature columns; check the data "
            "format ({}).".format(channel, fmt)
        )
    if any(r.get("missing_labels") for r in replies):
        raise exc.UserError(readers.NO_LABEL_ERROR)

    if cut_points is None:
        if world:
            merged = SummarySketch(cfg.sketch_size)
            for r in replies:
                doc = r.get("sketch")
                if not doc:
                    continue
                for f, summary in enumerate(SummarySketch.decode_summaries(doc)):
                    if len(summary[0]) == 0:
                        continue
                    cur = merged.cols.get(f)
                    out = summary if cur is None else _merge_summary(cur, summary)
                    if len(out[0]) > cfg.sketch_size:
                        out = _compress_summary(out[0], out[1], cfg.sketch_size)
                    merged.cols[f] = out
            summaries = merged.summaries(width)
        else:
            summaries = sketch.summaries(width)
        cuts = cuts_from_summaries(summaries, max_bin)
    else:
        cuts = cut_points
        if len(cuts) < width:
            raise exc.UserError(
                "Channel {!r} has {} feature columns but the training "
                "channel binned only {} — validation data must not be wider "
                "than training data".format(channel, width, len(cuts))
            )
        width = len(cuts)

    # which agreed-bad chunks are MINE to drop: under sharding every rank
    # reads the same plan, so (file, start, end) is a global identity; in
    # per-host-channel mode (ShardedByS3Key) two hosts may hold same-named
    # paths with different bytes, so only this rank's own entries apply
    skipped_idx = {
        (b["file"], b["start"], b["end"])
        for b in all_bad
        if b.get("channel") == channel and (cfg.shard or b.get("rank") == rank)
    }
    kept = [
        c
        for c in assigned
        if c.index in state.rows
        and (c.file, int(c.start), int(c.end)) not in skipped_idx
    ]
    if cut_points is not None and all(c.index in state.blocks for c in kept):
        bins, labels, weights, groups = _assemble_blocks(
            kept, state, max_bin, width
        )
    else:
        bins, labels, weights, groups = _pass2(
            plan, kept, state.rows, cuts, max_bin, width, csv_weights, channel
        )
    if labels.size == 0:
        return None
    if not np.isfinite(labels).all():
        raise exc.UserError(
            "Input data contains non-finite labels (NaN/inf). Please check "
            "that the label column is present and numeric in every row."
        )
    return BinnedMatrix(
        bins, cuts, max_bin, labels=labels, weights=weights, groups=groups
    )
