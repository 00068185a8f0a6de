"""Sum of the device durations of the kernel whose event name matches
``pattern``, in ms per traced unit (``per``)."""


def read(run, args):
    trace = run.get("trace")
    if trace is None:
        return None
    events = trace.kernel_events(args["pattern"])
    if not events:
        return None
    return 1e3 * sum(events) / run["traced_units"][args["per"]]
