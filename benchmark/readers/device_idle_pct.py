"""1 - busy over the traced window, in percent."""


def read(run, args):
    trace = run.get("trace")
    if trace is None or not trace.busy_s:
        return None
    return 100.0 * trace.idle_share
