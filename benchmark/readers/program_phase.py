"""A span or counter of the program's own, from its registry
(``telemetry.REGISTRY``): the ``sum`` or ``mean`` over the series of
``metric`` whose labels match ``where`` (label: value) and none of ``unless``
(label: values). For a histogram (``training_phase_seconds{phase}``, which
every ``telemetry.span()`` feeds) the seconds and the count of its spans; for
a counter (``xla_program_seconds_total{stage,phase}``) its value. ``less``
names phases of the same histogram whose seconds are taken off first (a
covering span less a part of it). Times ``scale``. None where the program
has no such series, as a parent without these spans has not, and where the
run's trace shows no work on a device: a CPU rehearsal takes other paths
through set-up (the host sketch), and its seconds do not stand under these
names."""


def ran_on_device(run):
    trace = run.get("trace")
    return trace is not None and bool(trace.busy_s)


def series(metric, where=None, unless=None):
    try:
        from sagemaker_xgboost_container_tpu.telemetry import REGISTRY
    except ImportError:
        return []
    out = []
    for name, _kind, _help, family in REGISTRY.collect():
        if name != metric:
            continue
        for s in family:
            labels = s.labels or {}
            if any(labels.get(k) != v for k, v in (where or {}).items()):
                continue
            if any(labels.get(k) in vs for k, vs in (unless or {}).items()):
                continue
            out.append(s)
    return out


def totals(found):
    """(seconds or value, count) over histogram or counter series."""
    total, count = 0.0, 0
    for s in found:
        if s.kind == "histogram":
            total += s.sum
            count += s.count
        else:
            total += s.value
            count += 1
    return total, count


def read(run, args):
    found = series(args["metric"], args.get("where"), args.get("unless"))
    if not found or not ran_on_device(run):
        return None
    total, count = totals(found)
    for phase in args.get("less", ()):
        total -= totals(series(args["metric"], {"phase": phase}))[0]
    if args["reduce"] == "mean":
        total /= count
    return total * args.get("scale", 1.0)
