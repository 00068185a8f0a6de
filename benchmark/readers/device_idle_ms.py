"""Device idle time in the traced window, in ms per traced unit (``per``)."""


def read(run, args):
    trace = run.get("trace")
    if trace is None or not trace.busy_s:
        return None
    idle_s = trace.window_s - trace.busy_s
    return 1e3 * idle_s / run["traced_units"][args["per"]]
