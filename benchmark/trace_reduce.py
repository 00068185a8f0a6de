"""From a ``jax.profiler`` trace (``.xplane.pb``) to busy time, idle gaps and
kernel time. Read with ``jax.profiler.ProfileData`` and nothing else.

A device plane is one chip (``/device:TPU:<n>``); its ``XLA Ops`` line holds
one event per executed operation. Container operations (a ``while`` around
the K-round scan, a ``conditional``) span the events of their bodies, so
busy time is the *union* of the intervals and an operation's own time is
its duration less its children's.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
# an event's name is the instruction's HLO text: "%fusion.764 = u16[5000]{0:T(..."
HLO_NAME = re.compile(r"^(%?[\w\-]+?)(?:\.\d+)? = \(?(\w+\[[\d,]*\])")


def short_name(name):
    """Group key for the breakdown: the instruction's name without its number,
    and the (first) shape it produces, so that the eight level calls of one
    kernel, or the fusions of one shape, read as one line."""
    m = HLO_NAME.match(name)
    return "{} {}".format(m.group(1), m.group(2)) if m else name[:80]


def find_xplane(trace_dir):
    hits = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return hits[-1] if hits else None


def load_device_ops(path):
    """{plane name: [(name, start_ns, duration_ns), ...] sorted by start}."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = list(plane.lines)
        chosen = [ln for ln in lines if ln.name == OPS_LINE] or lines
        ops = []
        for ln in chosen:
            for ev in ln.events:
                ops.append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
        ops.sort(key=lambda e: (e[1], -e[2]))
        planes[plane.name] = ops
    return planes


def busy_intervals(ops):
    """Merged [start, end) intervals in which some operation ran."""
    merged = []
    for _name, start, dur in ops:
        end = start + dur
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def self_times(ops):
    """[(name, self_ns)]: each event's duration less the events nested in it."""
    out = []
    stack = []  # (end, index into out)
    for name, start, dur in ops:
        end = start + dur
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            out[parent][1] -= dur
        out.append([name, dur])
        stack.append((end, len(out) - 1))
    return [(n, max(s, 0.0)) for n, s in out]


class TraceSummary:
    """What the per-layer readers and the result line take from one trace."""

    def __init__(self, planes, window_s=None):
        self.planes = planes
        starts = [ops[0][1] for ops in planes.values() if ops]
        ends = [max(s + d for _n, s, d in ops) for ops in planes.values() if ops]
        span_s = (max(ends) - min(starts)) / 1e9 if starts else 0.0
        self.span_s = span_s
        # the host's clock around start_trace/stop_trace where the kind gives
        # it, else the span of the device events themselves
        self.window_s = float(window_s) if window_s else span_s
        busy = [
            sum(e - s for s, e in busy_intervals(ops)) / 1e9 for ops in planes.values()
        ]
        self.busy_s = sum(busy) / len(busy) if busy else 0.0

    @classmethod
    def from_dir(cls, trace_dir, window_s=None):
        path = find_xplane(trace_dir)
        if path is None:
            return cls({}, window_s)
        return cls(load_device_ops(path), window_s)

    @property
    def idle_share(self):
        if not self.window_s:
            return None
        return 1.0 - self.busy_s / self.window_s

    @property
    def first_chip(self):
        """The first chip's operations: kernels and the breakdown read one chip."""
        return next(iter(self.planes.values()), [])

    def kernel_events(self, pattern):
        """Durations (s) of the events whose name matches, first chip."""
        rx = re.compile(pattern)
        return [d / 1e9 for n, _s, d in self.first_chip if rx.search(n)]

    def top_ops(self, limit=10):
        total = {}
        for name, self_ns in self_times(self.first_chip):
            key = short_name(name)
            total[key] = total.get(key, 0.0) + self_ns / 1e9
        ranked = sorted(total.items(), key=lambda kv: -kv[1])
        return [[n, s] for n, s in ranked[:limit]]

    def idle_gaps(self, limit=10):
        """Longest gaps on the first chip, named by the operations around
        them (the program has no host spans yet to name them by)."""
        ops = self.first_chip
        merged = busy_intervals(ops)
        names_by_start, names_by_end = {}, {}
        for n, s, d in ops:
            names_by_start.setdefault(s, n)
            names_by_end[s + d] = n
        gaps = [
            [
                "after:{}|before:{}".format(
                    short_name(names_by_end.get(e0, "?")), short_name(names_by_start.get(s1, "?"))
                ),
                (s1 - e0) / 1e9,
            ]
            for (_s0, e0), (s1, _e1) in zip(merged, merged[1:])
        ]
        if self.planes and self.window_s > self.span_s:
            # the window is timed on the host's clock around the trace: what
            # lies outside the device's first-to-last operation is the host
            gaps.append(["host:before_first_and_after_last_op", self.window_s - self.span_s])
        gaps.sort(key=lambda g: -g[1])
        return gaps[:limit]

    def breakdown(self):
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}
