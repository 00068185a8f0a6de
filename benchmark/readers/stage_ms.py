"""Device time by stage of the round program: the self-times of the first
chip's events (``trace_reduce.self_times``), each joined by its instruction's
name to the stage the program gives it
(``telemetry.device.round_program_stages()``: the optimised HLO's ``op_name``
under the ``jax.named_scope`` of each stage). ``stage`` names one; ``""`` is
what finds none. In ms per traced unit (``per``), or with ``share`` as a
percentage of all the self-time. None where the trace holds no device events,
the program publishes no table (a parent before it did), or the stage is
absent from the program."""

import re

from benchmark import trace_reduce

# an event's name starts with its instruction's: "%fusion.764 = u16[5000]{0:T(..."
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")


def program_stage_table():
    try:
        from sagemaker_xgboost_container_tpu.telemetry import device
    except ImportError:
        return None
    stages = getattr(device, "round_program_stages", None)
    return stages() if stages is not None else None


def stage_seconds(run):
    """{stage, "" for none: seconds of self-time}, computed once a run."""
    if "stage_seconds" not in run:
        trace = run.get("trace")
        table = None
        if trace is not None and trace.first_chip:
            table = run.get("stage_table") or program_stage_table()
        totals = None
        if table:
            totals = {}
            for name, self_ns in trace_reduce.self_times(trace.first_chip):
                m = INSTRUCTION.match(name)
                stage = table.get(m.group(1), "") if m else ""
                totals[stage] = totals.get(stage, 0.0) + self_ns / 1e9
        run["stage_seconds"] = totals
    return run["stage_seconds"]


def read(run, args):
    totals = stage_seconds(run)
    if not totals or args["stage"] not in totals:
        return None
    seconds = totals[args["stage"]]
    if args.get("share"):
        return 100.0 * seconds / sum(totals.values())
    return 1e3 * seconds / run["traced_units"][args["per"]]
