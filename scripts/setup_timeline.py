#!/usr/bin/env python3
"""One run of a benchmark cell with its set-up timeline printed after the
result line: every second from process start to the window under the span,
counter or gauge of the program that holds it (PERF.md section 5, "In front
of the window"). Same arguments as ``benchmark/run.py``:

    python3 scripts/setup_timeline.py --workload higgs-d8.train-fused \\
        --seed <n> --seconds 20 --trace 0

The line ``timeline {...}`` carries what the per-layer metrics of the set-up
read, for every cell and without a trace (``higgs-d8.train-fused`` is on none
of their lists yet): the start-up and set-up spans of
``training_phase_seconds`` (count and seconds), the program-load counters by
stage and as wall, the ``setup_hbm_bytes`` gauges, the round's shape as the
session stated it (``ROUND_SHAPE_GAUGES``), each ``setup.*`` span as it
ended (seconds after ``train()`` was entered, its length, its thread), and
what the caller itself did in front of ``train()`` that the program cannot
see: the import of jax and the back end coming up, timed here because the
benchmark enumerates the devices before the program does. With
``chiprun_out/`` beside it the line is also written to
``chiprun_out/timeline/<workload>.<seed>.json``. Works on a parent without the
new spans: it prints what that program has.
"""

import time

T_PROCESS_START = time.time()  # as benchmark/run.py: setup_s counts from here

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


# what the session says of its round and its columns at build: the one-hot
# tiles the level histogram latches, the share the live-tile rule skips
ROUND_SHAPE_GAUGES = (
    "hist_onehot_tiles_per_round", "hist_onehot_tiles_unfolded_per_round",
    "hist_tiles_skipped_pct", "train_columns_constant", "train_columns_total",
    "sketch_cuts_selected",
)


def seconds_of(what):
    started = time.time()
    what()
    return time.time() - started


def record_span_ends(ended):
    """Every ``setup.*`` span as it ends: (name, start, seconds, thread)."""
    from sagemaker_xgboost_container_tpu.models import booster
    from sagemaker_xgboost_container_tpu.telemetry import spans

    real = spans.end_span

    def recording(open_span, emit=False):
        elapsed = real(open_span, emit=emit)
        if open_span.name.startswith("setup."):
            ended.append((open_span.name, open_span.start, elapsed, threading.get_ident()))
        return elapsed

    spans.end_span = booster.end_span = recording


def timeline(caller, ended):
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY

    out = {"caller": caller, "phases": {}, "program_seconds": {}, "program_wall": {}, "hbm": {},
           "round_shape": {}}
    for name, _kind, _help, family in REGISTRY.collect():
        for s in family:
            labels = s.labels or {}
            if name == "training_phase_seconds" and labels["phase"].startswith(("startup.", "setup.")):
                out["phases"][labels["phase"]] = [s.count, s.sum]
            elif name == "xla_program_seconds_total":
                key = labels["stage"] + " " + labels["phase"]
                out["program_seconds"][key] = s.value
            elif name == "xla_program_wall_seconds_total":
                out["program_wall"][labels["phase"]] = s.value
            elif name == "setup_hbm_bytes":
                out["hbm"][labels["phase"] + " " + labels["what"]] = s.value
            elif name == "process_start_time_seconds":
                out["process_start_time_seconds"] = s.value
            elif name in ROUND_SHAPE_GAUGES:
                out["round_shape"][name] = s.value
    threads = {}
    first = min((start for _n, start, _s, _t in ended), default=0.0)
    out["spans"] = [
        [name, round(start - first, 4), round(seconds, 4), threads.setdefault(thread, len(threads))]
        for name, start, seconds, thread in sorted(ended, key=lambda e: e[1])
    ]
    return out


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    caller = {
        "interpreter_s": None,
        "t_process_start": T_PROCESS_START,
        "jax_import_s": seconds_of(lambda: __import__("jax")),
        "backend_init_s": seconds_of(lambda: __import__("jax").devices()),
    }
    from benchmark import harness

    ended = []
    record_span_ends(ended)
    rc = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), T_PROCESS_START)
    out = timeline(caller, ended)
    if "process_start_time_seconds" in out:
        caller["interpreter_s"] = T_PROCESS_START - out["process_start_time_seconds"]
    line = json.dumps(dict(out, workload=args.workload, seed=args.seed, trace=args.trace))
    sys.stdout.write("timeline " + line + "\n")
    sys.stdout.flush()
    directory = os.path.join(ROOT, "chiprun_out")
    if os.path.isdir(directory):
        os.makedirs(os.path.join(directory, "timeline"), exist_ok=True)
        name = "{}.{}.json".format(args.workload, args.seed)
        with open(os.path.join(directory, "timeline", name), "w") as f:
            f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
