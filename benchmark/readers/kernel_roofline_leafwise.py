"""A kernel's share of its roofline in a loss-guided round: the least time
the chip could take for the row reads the traced rounds' histograms *need*
(``benchmark/needed_work_leafwise.py``: every row once at every depth at which
its node splits, counted by the benchmark's own routing of the traced rounds'
trees; peaks by ``benchmark/peaks.py``) over the kernel's time in the trace.
For complete depth-d trees the count is ``readers/kernel_roofline.py``'s
``max_depth`` levels of all the rows. Never clipped: a share over 100 % is a
fault in the count. None where the kind hands over no traced trees."""

from benchmark import needed_work, needed_work_leafwise, peaks


def read(run, args):
    trace = run.get("trace")
    trees = run.get("traced_trees")
    if trace is None or not trees:
        return None
    events = trace.kernel_events(args["pattern"])
    if not events:
        return None
    config = run["config"]
    work = needed_work_leafwise.tree_histograms(
        trees, run["train_x"], int(config["num_feature"]),
        int(config["params"]["max_bin"]) + 1,
    )
    least, _bound = needed_work.least_seconds(work, peaks.peaks_for(run["device_kind"]))
    return 100.0 * least / sum(events)
