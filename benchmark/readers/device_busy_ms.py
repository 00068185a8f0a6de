"""Union of the device-busy intervals of the traced window, in ms per traced
unit (``per``)."""


def read(run, args):
    trace = run.get("trace")
    if trace is None or not trace.busy_s:
        return None
    return 1e3 * trace.busy_s / run["traced_units"][args["per"]]
