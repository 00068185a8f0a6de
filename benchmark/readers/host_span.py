"""A span the kind timed on the host's clock outside the window, by name."""


def read(run, args):
    return run.get("host_spans", {}).get(args["span"])
