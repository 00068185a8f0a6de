"""How unevenly the chips of a traced run were busy: the busiest chip's busy
time (the union of its operations' intervals, ``trace_reduce.busy_intervals``)
over the least busy chip's, less one, in percent. A collective ends when its
last member arrives, so what the other chips wait for a lagging share shows
here as the gap between them. None where the trace holds fewer than two
chips' planes, or a chip that never ran."""

from benchmark import trace_reduce


def read(run, args):
    trace = run.get("trace")
    if trace is None or len(trace.planes) < 2:
        return None
    busy = [
        sum(end - start for start, end in trace_reduce.busy_intervals(ops))
        for ops in trace.planes.values()
    ]
    if not min(busy):
        return None
    return 100.0 * (max(busy) / min(busy) - 1.0)
