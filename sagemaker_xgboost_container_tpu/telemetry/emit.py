"""Structured single-line JSON metric records on stdout.

This is the CloudWatch metric-definition surface: SageMaker training jobs
declare ``{"Name": ..., "Regex": ...}`` pairs and CloudWatch scrapes the
container's stdout with them (the reference's only metric contract —
SURVEY §5). One record per line, compact JSON, ``"metric"`` first, remaining
keys sorted — so a regex like ``"round_ms": ([0-9.]+)`` is stable across
releases. Records never contain tabs, keeping them disjoint from the HPO
eval-line contract (``[<iter>]\\t<data>-<metric>:<value>``).

``SM_STRUCTURED_METRICS=false`` silences every record (default on).
"""

import json
import sys
import threading

from ..utils.envconfig import env_bool

STRUCTURED_METRICS_ENV = "SM_STRUCTURED_METRICS"

_write_lock = threading.Lock()

# Extra fields merged into every ``training.round`` record (see
# profiling.RoundTimer). Set by the training session for facts only it
# knows (e.g. the histogram collective and its per-round wire bytes);
# process-wide like ROUND_STATE, last writer wins, which matches
# sequential training sessions.
_round_fields = {}
_round_fields_lock = threading.Lock()


def set_round_fields(**fields):
    """Merge fields into the per-round record; a value of None removes
    the key (so a later single-device session clears a mesh session's
    comm fields instead of reporting them stale)."""
    with _round_fields_lock:
        for key, value in fields.items():
            if value is None:
                _round_fields.pop(key, None)
            else:
                _round_fields[key] = value


def get_round_fields():
    """Snapshot of the extra per-round fields (copy — safe to mutate)."""
    with _round_fields_lock:
        return dict(_round_fields)


def structured_enabled():
    return env_bool(STRUCTURED_METRICS_ENV, True)


def _jsonable(value):
    try:
        json.dumps(value)
        return value
    except (TypeError, ValueError):
        return str(value)


def emit_metric(metric, **fields):
    """Write one structured record; no-op when disabled. Returns the line
    (or None) so callers/tests can assert on it without re-capturing stdout."""
    if not structured_enabled():
        return None
    record = {"metric": metric}
    for key in sorted(fields):
        record[key] = _jsonable(fields[key])
    line = json.dumps(record, separators=(", ", ": "))
    with _write_lock:
        sys.stdout.write(line + "\n")
        sys.stdout.flush()
    return line


def snapshot_fields(registry):
    """Flatten a registry into scalar fields for one snapshot record.

    Counters/gauges become ``name{k=v,...}`` keys; histograms contribute
    ``_count``/``_sum`` plus p50/p95 estimates. Used by the serving-side
    periodic reporter (SM_METRICS_EMIT_INTERVAL_S) so CloudWatch can scrape
    serving metrics without a Prometheus stack.
    """
    fields = {}
    for name, kind, _help, series in registry.collect():
        for metric in series:
            suffix = (
                "{" + ",".join(
                    "{}={}".format(k, v) for k, v in sorted(metric.labels.items())
                ) + "}"
                if metric.labels
                else ""
            )
            key = name + suffix
            if kind == "histogram":
                fields[key + "_count"] = metric.count
                fields[key + "_sum"] = round(metric.sum, 6)
                if metric.count:
                    fields[key + "_p50"] = round(metric.quantile(0.5), 6)
                    fields[key + "_p95"] = round(metric.quantile(0.95), 6)
            else:
                fields[key] = metric.value
    return fields
