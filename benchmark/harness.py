"""Resolve a cell to its files, drive its kind, print the result line.

Nothing here knows a configuration, a traffic mix or a metric by name: a
later PR adds files and an entry in ``BENCHMARK.json`` and edits nothing here.
"""

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
EXIT_NO_CHIP = 2


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_benchmark():
    return load_json(ROOT, "BENCHMARK.json")


def resolve_cell(bench, workload):
    """The cell's entry, configuration and traffic, each from its own file."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            "unknown workload {!r}; BENCHMARK.json has {}".format(workload, sorted(cells))
        )
    cell = cells[workload]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, config_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def cell_metrics(bench, section, workload, reported=None):
    """Metric entries of ``section`` that this cell reports. A per-layer
    metric without ``workloads`` belongs to every cell that reports the
    end-to-end metric it moves."""
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if workload in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def load_kind(traffic):
    return importlib.import_module("benchmark.kinds." + traffic["kind"])


def load_reader(metric_name):
    """(read function, args) of one per-layer metric, from its own file."""
    spec = load_json(HERE, "layer_metrics", metric_name + ".json")
    module = importlib.import_module("benchmark.readers." + spec["reader"])
    return module.read, spec.get("args", {})


def require_chips(chips):
    """Exit 2, with no result line, unless jax holds ``chips`` accelerators.
    ``JAX_PLATFORMS=cpu`` asks for the CPU by name (tests, rehearsals): the
    result line then says ``"platform": "cpu"`` and is no device number."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.stderr.write("benchmark: jax found no device: {}\n".format(e))
        sys.exit(EXIT_NO_CHIP)
    on_cpu = devices[0].platform == "cpu"
    if on_cpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.stderr.write(
            "benchmark: jax found no accelerator; a CPU run has to be asked "
            "for by name (JAX_PLATFORMS=cpu) and is never a device number\n"
        )
        sys.exit(EXIT_NO_CHIP)
    if not on_cpu and len(devices) < chips:
        sys.stderr.write(
            "benchmark: the cell needs {} chips, jax found {}\n".format(chips, len(devices))
        )
        sys.exit(EXIT_NO_CHIP)
    return devices


def memory_taken(devices=None):
    """Bytes taken on the fullest chip right now: buffers in use and what the
    runtime holds reserved for programs' scratch. The v5e runtime keeps the
    second out of ``peak_bytes_in_use`` (PERF.md section 4)."""
    if devices is None:
        import jax

        devices = jax.local_devices()
    taken = 0
    for d in devices:
        stats = d.memory_stats() or {}
        taken = max(taken, int(stats.get("bytes_in_use", 0)) + int(stats.get("bytes_reserved", 0)))
    return taken


def device_fields(devices, memory_samples=()):
    """``memory_peak_bytes`` is the most the fullest chip was seen to hold:
    the allocator's ``peak_bytes_in_use``, or what was taken (``memory_taken``)
    when the run sampled it or now that it has ended."""
    peak = max([memory_taken(devices), *memory_samples])
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        print("memory {}: {}".format(d, json.dumps(stats, sort_keys=True)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def print_checks(checks):
    """Every number compared, beside its limit, in every run."""
    for c in checks:
        print(
            "check {name}: value={value!r} limit={limit!r} {verdict}".format(
                verdict="ok" if c["ok"] else "FAILED", **c
            )
        )


def run_cell(workload, seed, seconds, trace, t_process_start, shrink=None):
    """One run of one cell. ``shrink`` overrides keys of the configuration:
    it is for the tests and CPU rehearsals, which drive the cell's own files
    at another scale."""
    bench = load_benchmark()
    cell, config, traffic = resolve_cell(bench, workload)
    config.update(shrink or {})
    try:
        import sagemaker_xgboost_container_tpu  # noqa: F401  the system under test
    except ImportError as e:
        sys.stderr.write("benchmark: the program is not in this directory: {}\n".format(e))
        return EXIT_NO_CHIP
    devices = require_chips(cell["chips"])
    kind = load_kind(traffic)
    run = kind.run(
        {
            "cell": cell,
            "config": config,
            "traffic": traffic,
            "seed": int(seed),
            "seconds": float(seconds),
            "trace": trace,
            "t_process_start": t_process_start,
        }
    )
    run["device_kind"] = devices[0].device_kind
    print_checks(run["checks"])
    correct = all(c["ok"] for c in run["checks"])
    end_to_end = cell_metrics(bench, "end_to_end", workload)
    metrics = {}
    if not trace:
        for m in end_to_end:
            metrics[m["name"]] = {"value": run["end_to_end"][m["name"]], "unit": m["unit"]}
    else:
        reported = {m["name"] for m in end_to_end}
        for m in cell_metrics(bench, "per_layer", workload, reported):
            read, args = load_reader(m["name"])
            value = read(run, args)
            if value is not None:  # a reader that finds nothing reports nothing
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = device_fields(devices[: cell["chips"]], run.get("memory_samples", ()))
    line = {
        "correct": bool(correct),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace and run.get("trace") is not None:
        device["busy_s"] = run["trace"].busy_s
        device["window_s"] = run["trace"].window_s
        line["breakdown"] = run["trace"].breakdown()
    sys.stdout.flush()
    print(json.dumps(line))
    sys.stdout.flush()
    return 0
