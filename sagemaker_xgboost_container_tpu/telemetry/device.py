"""Device-window attribution plane (``SM_DEVICE_TELEMETRY``): what the fused
round program *costs*, what HBM is actually resident, and why a dispatch
OOMs.

PR 7/10/13 split every round into compile/host/device/collective/wire, but
the ``device`` bucket itself stayed a black box. This module opens it with
four connected pieces, all env-gated like the fleet plane (zero threads,
zero records, zero registry series when ``SM_DEVICE_TELEMETRY`` is unset):

* **Compiled-cost introspection** — at session build the booster AOT-lowers
  the fused round dispatch and feeds ``cost_analysis()`` /
  ``memory_analysis()`` through :func:`cost_from_compiled` into
  :func:`note_compiled`: one ``training.compiled`` record (flops, bytes
  accessed, peak arg/output/temp HBM bytes, per mesh shape and
  ``rounds_per_dispatch``) plus the ``device_flops_per_round`` /
  ``device_hbm_peak_bytes`` gauges.
* **Per-round HBM watermark** — RoundTimer samples
  :func:`sample_device_memory` every ``SM_HBM_SAMPLE_EVERY`` rounds
  (:func:`sample_watermark`). The sampler is the ONE cached
  O(live-buffers) walk shared with the heartbeat plane
  (``telemetry/cluster.py`` delegates here), so heartbeats and round
  sampling never pay it twice per interval. Watermarks ride the fleet
  span shipper to rank 0, where ``/status`` renders a memory section and
  names a *memory*-skewed rank.
* **Stage table** — the round program names its own work: every stage of
  a boosting round is traced under a ``jax.named_scope`` (:data:`STAGES`),
  which XLA keeps as ``op_name`` metadata on every instruction, fusions
  included. :func:`round_program_stages` reads the optimised HLO of the
  session's round program into ``{instruction name: stage}``; a profiler
  trace names each device event by its instruction, so the join gives
  device time by stage. Lazy: the session registers a closure over shapes
  only (:func:`register_round_program`); nothing is lowered until somebody
  asks, and the ``training.compiled`` record carries the table's summary.
* **OOM forensics** — :func:`dump_oom_forensics` writes
  ``hbm-forensics-rank<r>.json`` (top live buffers by shape/size,
  allocator stats, the compiled memory analysis, the last watermark) on
  the booster's ``RESOURCE_EXHAUSTED`` path before the watchdog abort
  (exit 86, ``EXIT_DEVICE_OOM``). The forensics path is robustness, not
  telemetry: like exits 79-85 it fires regardless of the gate.
"""

import json
import logging
import os
import re
import threading
import time

from ..utils.envconfig import env_bool, env_int
from .emit import emit_metric
from .registry import REGISTRY
from .spans import span

logger = logging.getLogger(__name__)

#: master gate: unset ⇒ no records, no gauges, no sampling cadence
DEVICE_TELEMETRY_ENV = "SM_DEVICE_TELEMETRY"
#: watermark cadence in rounds (>= 1); read once per training session
HBM_SAMPLE_EVERY_ENV = "SM_HBM_SAMPLE_EVERY"
DEFAULT_HBM_SAMPLE_EVERY = 8

#: stage names of the round program, in the order a round runs them. Each is
#: a ``jax.named_scope`` where the work is traced (models/booster.py,
#: ops/tree_build.py, ops/histogram.py) and so the ``op_name`` metadata of
#: every HLO instruction under it; ``hist_allreduce`` exists on a mesh only.
STAGE_GRAD = "grad"
#: inside ``grad`` for the ranking objectives (ops/ranking.py): the margins
#: from rows to group slots, the pair pass with its ranks and sums, slots back
#: to rows
STAGE_RANK_GATHER = "rank_gather"
STAGE_RANK_PAIRS = "rank_pairs"
STAGE_RANK_SCATTER = "rank_scatter"
STAGE_HIST = "hist"
STAGE_HIST_ALLREDUCE = "hist_allreduce"
STAGE_NODE_TOTALS = "node_totals"
STAGE_SPLIT_SCAN = "split_scan"
STAGE_CAT_SCAN = "cat_scan"
#: a loss-guided build's split-step loop (ops/lossguide.py): the argmax over
#: the candidate store, the tree and store updates, the histogram cache's slot
#: writes and the loop itself; the step's kernel, scan and routing have their
#: own stages inside it
STAGE_STEP_PICK = "step_pick"
STAGE_ROUTE_ROWS = "route_rows"
STAGE_LEAF_MARGIN = "leaf_margin"
STAGE_EVAL_APPLY = "eval_apply"
STAGE_EVAL_METRIC = "eval_metric"
STAGE_PACK = "pack"
STAGES = (
    STAGE_GRAD,
    STAGE_RANK_GATHER,
    STAGE_RANK_PAIRS,
    STAGE_RANK_SCATTER,
    STAGE_HIST,
    STAGE_HIST_ALLREDUCE,
    STAGE_NODE_TOTALS,
    STAGE_SPLIT_SCAN,
    STAGE_CAT_SCAN,
    STAGE_STEP_PICK,
    STAGE_ROUTE_ROWS,
    STAGE_LEAF_MARGIN,
    STAGE_EVAL_APPLY,
    STAGE_EVAL_METRIC,
    STAGE_PACK,
)

#: one cached device-memory walk serves every consumer inside this window
SAMPLE_MAX_AGE_S = 1.0

_state_lock = threading.Lock()
_last_compiled = None  # the note_compiled record (train round program)
_last_watermark = None  # the last sample_watermark result
_round_program = None  # () -> jax Compiled, registered by the live session
_stage_table = None  # round_program_stages() of _round_program, once asked
_watermark_high = 0  # high-water bytes_in_use across watermark samples

_sample_lock = threading.Lock()
_sample_cache = None  # (monotonic stamp, snapshot dict)


def enabled():
    return env_bool(DEVICE_TELEMETRY_ENV, False)


def hbm_sample_every():
    return env_int(HBM_SAMPLE_EVERY_ENV, DEFAULT_HBM_SAMPLE_EVERY, minimum=1)


def sample_cadence():
    """Watermark cadence for RoundTimer: 0 (never sample) when the plane is
    unarmed, else ``SM_HBM_SAMPLE_EVERY``. Resolved once per session by the
    caller — the per-round path never reads env."""
    return hbm_sample_every() if enabled() else 0


# ------------------------------------------------------- cached memory walk
def _sample_uncached():
    """One O(devices) + O(live-buffers) walk: per-device allocator stats
    where the backend reports them (TPU), else the summed footprint of live
    jax arrays — the same ladder the heartbeat plane used before it was
    hoisted here. Never raises."""
    snap = {
        "total_bytes_in_use": 0,
        "peak_bytes_in_use": 0,
        "bytes_limit": 0,
        "source": "none",
        "devices": [],
    }
    try:
        import jax

        seen_stats = False
        for dev in jax.devices():
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if not stats or "bytes_in_use" not in stats:
                continue
            seen_stats = True
            entry = {
                "id": getattr(dev, "id", len(snap["devices"])),
                "kind": getattr(dev, "device_kind", "unknown"),
                "bytes_in_use": int(stats["bytes_in_use"]),
                "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
            }
            snap["devices"].append(entry)
            snap["total_bytes_in_use"] += entry["bytes_in_use"]
            snap["peak_bytes_in_use"] += entry["peak_bytes_in_use"]
            snap["bytes_limit"] += entry["bytes_limit"]
        if seen_stats:
            snap["source"] = "memory_stats"
            return snap
        snap["total_bytes_in_use"] = int(
            sum(getattr(a, "nbytes", 0) for a in jax.live_arrays())
        )
        snap["source"] = "live_arrays"
    except Exception:
        pass
    return snap


def sample_device_memory(max_age_s=SAMPLE_MAX_AGE_S):
    """The shared device-memory snapshot, cached for ``max_age_s`` seconds
    so the heartbeat sender, the round watermark, and ``/status`` together
    pay at most one live-buffer walk per interval. ``max_age_s=0`` forces a
    fresh walk (OOM forensics). Passive and ungated: creates no threads and
    emits nothing, so unarmed callers (the heartbeat plane) stay inert."""
    global _sample_cache
    now = time.monotonic()
    with _sample_lock:
        cached = _sample_cache
        if cached is not None and now - cached[0] <= max_age_s:
            return cached[1]
    snap = _sample_uncached()
    with _sample_lock:
        _sample_cache = (time.monotonic(), snap)
    return snap


# --------------------------------------------------------- compiled program
def cost_from_compiled(compiled):
    """Extract the cost/memory analyses of a jax AOT ``Compiled`` into one
    flat dict of floats/ints (absent analyses yield zeros — some backends
    return nothing for trivial programs). ``cost_analysis()`` is a dict on
    recent jax and a one-element list of dicts on older releases; both
    shapes are handled."""
    cost = {"flops": 0.0, "bytes_accessed": 0.0, "transcendentals": 0.0}
    try:
        analysis = compiled.cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else {}
        if isinstance(analysis, dict):
            cost["flops"] = float(analysis.get("flops", 0.0) or 0.0)
            cost["bytes_accessed"] = float(
                analysis.get("bytes accessed", 0.0) or 0.0
            )
            cost["transcendentals"] = float(
                analysis.get("transcendentals", 0.0) or 0.0
            )
    except Exception as e:
        logger.debug("cost_analysis unavailable: %s", e)
    mem = {"arg_bytes": 0, "out_bytes": 0, "temp_bytes": 0, "alias_bytes": 0}
    try:
        analysis = compiled.memory_analysis()
        mem["arg_bytes"] = int(
            getattr(analysis, "argument_size_in_bytes", 0) or 0
        )
        mem["out_bytes"] = int(getattr(analysis, "output_size_in_bytes", 0) or 0)
        mem["temp_bytes"] = int(getattr(analysis, "temp_size_in_bytes", 0) or 0)
        mem["alias_bytes"] = int(getattr(analysis, "alias_size_in_bytes", 0) or 0)
    except Exception as e:
        logger.debug("memory_analysis unavailable: %s", e)
    cost.update(mem)
    return cost


def note_compiled(
    cost,
    mesh_shape=None,
    rounds_per_dispatch=1,
    backend=None,
    kind="train_round",
    registry=None,
    stages=None,
):
    """Fold one program's cost dict (:func:`cost_from_compiled`) into the
    plane: emit the ``training.compiled`` record, set the gauges, and keep
    the record for ``/status`` and OOM forensics. The
    caller gates on :func:`enabled` — this function assumes the plane is
    armed. Returns the record."""
    k = max(int(rounds_per_dispatch or 1), 1)
    record = dict(cost)
    record["kind"] = kind
    record["rounds_per_dispatch"] = k
    record["flops_per_round"] = round(record.get("flops", 0.0) / k, 1)
    record["bytes_per_round"] = round(record.get("bytes_accessed", 0.0) / k, 1)
    # peak resident HBM of one dispatch: everything the executable holds at
    # once — donated/aliased args overlap outputs, so subtract the alias
    peak = (
        record.get("arg_bytes", 0)
        + record.get("out_bytes", 0)
        + record.get("temp_bytes", 0)
        - record.get("alias_bytes", 0)
    )
    record["hbm_peak_bytes"] = int(max(peak, 0))
    if mesh_shape:
        record["mesh_shape"] = {str(a): int(n) for a, n in dict(mesh_shape).items()}
    if backend:
        record["backend"] = backend
    if stages:
        # the stage table's summary (stages_from_hlo_text): instructions and
        # result bytes per stage, "" for what was traced under none
        record["stages"] = stages
    global _last_compiled
    with _state_lock:
        if kind == "train_round" or _last_compiled is None:
            _last_compiled = record
    reg = registry or REGISTRY
    reg.gauge(
        "device_flops_per_round",
        "Compiled FLOPs of one boosting round (XLA cost_analysis / K)",
    ).set(record["flops_per_round"])
    reg.gauge(
        "device_hbm_peak_bytes",
        "Peak resident HBM bytes of one round dispatch (arg+out+temp-alias)",
    ).set(record["hbm_peak_bytes"])
    emit_metric("training.compiled", **record)
    from . import fleet

    fleet.note_status(compiled=record)
    return record


def last_compiled():
    with _state_lock:
        return dict(_last_compiled) if _last_compiled is not None else None


# ---------------------------------------------------------------- watermark
def sample_watermark(round_index, registry=None):
    """One per-round HBM watermark sample (RoundTimer, on the
    ``SM_HBM_SAMPLE_EVERY`` cadence — the caller owns the cadence check).
    Updates the ``hbm_watermark_bytes`` gauge and the wire-side state the
    fleet shipper sends to rank 0. Returns the watermark dict."""
    snap = sample_device_memory()
    watermark = {
        "round": int(round_index),
        "bytes_in_use": int(snap["total_bytes_in_use"]),
        "peak_bytes": int(snap["peak_bytes_in_use"]),
        "source": snap["source"],
    }
    global _last_watermark, _watermark_high
    with _state_lock:
        _last_watermark = watermark
        _watermark_high = max(_watermark_high, watermark["bytes_in_use"])
    (registry or REGISTRY).gauge(
        "hbm_watermark_bytes",
        "Live HBM bytes at the last per-round watermark sample",
    ).set(watermark["bytes_in_use"])
    return watermark


def watermark_wire():
    """The latest watermark for the fleet span shipper (None when the plane
    is unarmed or no round has been sampled yet — an absent key costs the
    frame nothing)."""
    if not enabled():
        return None
    with _state_lock:
        if _last_watermark is None:
            return None
        wire = dict(_last_watermark)
        wire["high_bytes"] = _watermark_high
        return wire


def memory_status():
    """The local memory section for ``/status`` and the SIGQUIT dump: a
    fresh (cached) sample plus the watermark history and the compiled
    program's predicted peak. None when the plane is unarmed."""
    if not enabled():
        return None
    doc = {"current": sample_device_memory()}
    with _state_lock:
        if _last_watermark is not None:
            doc["watermark"] = dict(_last_watermark)
            doc["high_bytes"] = _watermark_high
        if _last_compiled is not None:
            doc["compiled_hbm_peak_bytes"] = _last_compiled.get(
                "hbm_peak_bytes", 0
            )
    return doc


# -------------------------------------------------------------- stage table
def stage(name):
    """The ``jax.named_scope`` of one round-program stage (trace time only:
    a scope is HLO metadata and costs nothing at run time)."""
    import jax

    return jax.named_scope(name)


# a scope traced under ``jax.vmap`` is spelled ``vmap(route_rows)``
_VMAPPED_SCOPE = re.compile(r"^(?:vmap\()+([^()]*)\)+$")


def stage_of_op_name(op_name):
    """The innermost stage among the ``/``-separated scopes of an HLO
    ``op_name`` (``jit(multi_round)/while/body/route_rows/gather``; under the
    class ``vmap`` of a multi-class round
    ``.../closed_call/vmap(route_rows)/gather``), or None where the
    instruction was traced under no stage."""
    for part in reversed(op_name.split("/")):
        vmapped = _VMAPPED_SCOPE.match(part)
        if vmapped:
            part = vmapped.group(1)
        if part in STAGES:
            return part
    return None


# "  ROOT %fusion.12 = (u32[1]{0}, u32[1]{0}) fusion(...), ..., metadata={op_name="..." ...}"
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_CALLED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_HLO_ARRAY = re.compile(r"\b(pred|[suf]\d+|bf16|c\d+)\[([\d,]*)\]")
_DTYPE_BYTES = {"pred": 1, "bf16": 2}


def _result_bytes(rest):
    """Bytes of an instruction's result, from the shape in front of its
    opcode (``f32[32,64,256]{2,1,0} fusion(...)``; a tuple sums its parts)."""
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape = rest[: end + 1]
    else:
        shape = rest.split(" ", 1)[0]
    total = 0
    for dtype, dims in _HLO_ARRAY.findall(shape):
        width = _DTYPE_BYTES.get(dtype) or max(int(dtype.lstrip("sufc")) // 8, 1)
        count = 1
        for dim in dims.split(","):
            count *= int(dim) if dim else 1
        total += width * count
    return total


def stages_from_hlo_text(text):
    """Optimised HLO text -> (``{instruction name: stage}``, summary).

    Every instruction whose ``op_name`` lies under a stage scope is in the
    table, whichever computation holds it: a profiler trace names a device
    event by the instruction that ran (``%fusion.12 = ...``), and a fusion
    carries the ``op_name`` of its root. The summary counts, per stage (and
    under ``""`` for what has none), the instructions that run as
    operations of their own (not the bodies of fusions and reducers) and
    the bytes of their results."""
    table = {}
    rows = []  # (computation, instruction, stage or "", result bytes)
    inlined = set()  # computations that are the body of a fusion or reducer
    computation = ""
    for line in text.splitlines():
        header = _HLO_COMPUTATION.match(line)
        if header:
            computation = header.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        inlined.update(_HLO_CALLED.findall(rest))
        op = _HLO_OP_NAME.search(rest)
        found = stage_of_op_name(op.group(1)) if op else None
        if found is not None:
            table[name] = found
        rows.append((computation, name, found or "", _result_bytes(rest)))
    summary = {}
    for comp, _name, found, nbytes in rows:
        if comp in inlined:
            continue
        entry = summary.setdefault(found, {"instructions": 0, "result_bytes": 0})
        entry["instructions"] += 1
        entry["result_bytes"] += nbytes
    return table, summary


def register_round_program(compile_fn):
    """The live session's round program, as a closure that lowers and
    compiles it from shapes and dtypes alone (``jax.ShapeDtypeStruct``: it
    keeps no device buffer alive). Registering costs nothing; the closure
    runs when :func:`round_program_stages` is first asked."""
    global _round_program, _stage_table
    with _state_lock:
        _round_program = compile_fn
        _stage_table = None


def note_stage_table(compiled):
    """Read and keep the stage table of a compiled round program; returns
    (table, summary). The session's gated introspection calls it with the
    executable it compiled for ``cost_analysis`` anyway."""
    global _stage_table
    parsed = stages_from_hlo_text(compiled.as_text())
    with _state_lock:
        _stage_table = parsed
    return parsed


def round_program_stages():
    """``{instruction name: stage}`` for the session's round program, from
    the optimised HLO of its executable. Lowers and compiles on the first
    call (the jit path's own compile is served from the persistent cache,
    so this is a second one); ``{}`` when no session has registered."""
    with _state_lock:
        parsed, compile_fn = _stage_table, _round_program
    if parsed is None:
        if compile_fn is None:
            return {}
        # a span of its own: the table's cost, and the program load it
        # causes, are named in the registry and kept apart from set-up's
        with span("stage_table"):
            parsed = note_stage_table(compile_fn())
    return dict(parsed[0])


# ------------------------------------------------------------ OOM forensics
def is_oom_error(exc):
    """Does this exception look like a device allocator exhaustion? XLA
    surfaces OOM as ``XlaRuntimeError: RESOURCE_EXHAUSTED: ...`` (the class
    is backend-private, so match text, not type)."""
    text = "{}: {}".format(type(exc).__name__, exc)
    return (
        "RESOURCE_EXHAUSTED" in text
        or "Resource exhausted" in text
        or "out of memory" in text.lower()
    )


def _top_live_buffers(top_n=32):
    """Live device buffers grouped by (shape, dtype), largest total first —
    the 'what is actually resident' table of the forensics dump."""
    import jax

    groups = {}
    for arr in jax.live_arrays():
        try:
            key = (tuple(getattr(arr, "shape", ())), str(getattr(arr, "dtype", "?")))
            entry = groups.setdefault(
                key, {"shape": list(key[0]), "dtype": key[1], "count": 0, "total_bytes": 0}
            )
            entry["count"] += 1
            entry["total_bytes"] += int(getattr(arr, "nbytes", 0))
        except Exception:
            continue
    ranked = sorted(groups.values(), key=lambda e: -e["total_bytes"])
    return ranked[:top_n]


def _forensics_dir(default_dir=None):
    """Durable-location ladder, mirroring the flight-recorder dump: the
    explicit export dir, then the caller's hint (live checkpoint dir /
    model dir), then the working directory."""
    from . import tracing

    explicit = os.environ.get(tracing.TRACE_EXPORT_DIR_ENV)
    if explicit:
        return explicit
    if default_dir:
        return default_dir
    try:
        from ..training import checkpointing

        dirs = checkpointing.active_checkpoint_dirs()
        if dirs:
            return dirs[0]
    except Exception:
        pass
    from ..constants import SM_MODEL_DIR

    return os.environ.get(SM_MODEL_DIR) or "."


def dump_oom_forensics(exc, default_dir=None, top_n=32):
    """Write ``hbm-forensics-rank<r>.json`` for a device OOM: the error,
    a fresh allocator walk, the top live buffers by footprint, the compiled
    program's memory analysis, and the last watermark. Robustness path —
    runs regardless of ``SM_DEVICE_TELEMETRY`` (an OOM'd job's last act
    should always name the buffers that killed it). Never raises; returns
    the path or None."""
    try:
        from . import tracing

        rank = tracing.get_rank()
        doc = {
            "reason": "device_oom",
            "rank": rank,
            "error": str(exc)[:2000],
        }
        try:
            doc["memory"] = sample_device_memory(max_age_s=0.0)
        except Exception:
            pass
        try:
            doc["top_live_buffers"] = _top_live_buffers(top_n)
        except Exception:
            pass
        with _state_lock:
            if _last_compiled is not None:
                doc["compiled"] = dict(_last_compiled)
            if _last_watermark is not None:
                doc["last_watermark"] = dict(_last_watermark)
                doc["watermark_high_bytes"] = _watermark_high
        directory = _forensics_dir(default_dir)
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, "hbm-forensics-rank{}.json".format(rank))
        with open(path, "w") as f:
            json.dump(doc, f, default=str)
            f.write("\n")
        logger.error(
            "device OOM: HBM forensics (top live buffers, allocator stats, "
            "compiled memory analysis) dumped to %s", path
        )
        return path
    except Exception:
        logger.exception("HBM forensics dump failed; aborting anyway")
        return None


def _reset_for_tests():
    global _last_compiled, _last_watermark, _watermark_high, _sample_cache
    global _round_program, _stage_table
    with _state_lock:
        _round_program = None
        _stage_table = None
        _last_compiled = None
        _last_watermark = None
        _watermark_high = 0
    with _sample_lock:
        _sample_cache = None
