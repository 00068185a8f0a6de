"""A kernel's share of its roofline: the least time the chip could take for
the work the algorithm needs in the traced rounds (``benchmark/needed_work.py``,
by the published peaks in ``benchmark/peaks.py``) over the kernel's time in
the trace. The work is counted by round (``max_depth`` levels, all the trees
of a round), not by kernel call, so it does not matter how the program splits
a level into calls. Never clipped: a share over 100 % is a fault in the count."""

from benchmark import needed_work, peaks


def read(run, args):
    trace = run.get("trace")
    if trace is None:
        return None
    events = trace.kernel_events(args["pattern"])
    if not events:
        return None
    config = run["config"]
    params = config["params"]
    level = getattr(needed_work, args["work"])(
        int(config["train_rows"]),
        int(config["num_feature"]),
        int(params["max_bin"]) + 1,
        trees=int(params.get("num_class", 1)),
    )
    least, _bound = needed_work.least_seconds(level, peaks.peaks_for(run["device_kind"]))
    levels = int(params["max_depth"]) * run["traced_units"]["round"]
    return 100.0 * least * levels / sum(events)
