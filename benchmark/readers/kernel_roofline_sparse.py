"""A kernel's share of its roofline over a sparse matrix: the least time the
chip could take for the bin reads the traced rounds' level histograms *need*
(``benchmark/needed_work_sparse.py``: every present cell once a level, the
cells counted by the kind from the generated CSR; peaks by
``benchmark/peaks.py``) over the kernel's time in the trace. Counted by round
(``max_depth`` levels), not by kernel call. Never clipped: a share over 100 %
is a fault in the count. None where the kind hands over no count of present
cells."""

from benchmark import needed_work, needed_work_sparse, peaks


def read(run, args):
    trace = run.get("trace")
    present = run.get("train_cells_present")
    if trace is None or present is None:
        return None
    events = trace.kernel_events(args["pattern"])
    if not events:
        return None
    config = run["config"]
    params = config["params"]
    level = needed_work_sparse.level_histogram(
        int(config["train_rows"]),
        int(present),
        int(params["max_bin"]) + 1,
        trees=int(params.get("num_class", 1)),
    )
    least, _bound = needed_work.least_seconds(level, peaks.peaks_for(run["device_kind"]))
    levels = int(params["max_depth"]) * run["traced_units"]["round"]
    return 100.0 * least * levels / sum(events)
