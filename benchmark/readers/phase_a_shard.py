"""Seconds a shard of the child spans of a set-up phase
(``training_phase_seconds``, as ``program_phase`` reads it): a mesh's shards
are set up side by side, a thread a shard, so the seconds of ``phase`` summed
over every shard, block and matrix are divided by the shards. Their number
is read where the spans are: ``shard_phase`` ends once a shard and matrix
(``setup.sketch.shard``), ``whole_phase`` once a matrix (``setup.sketch``).
On one chip the sum itself. None where the program has no such spans, as a
parent before them has not, and where the run was not on a device
(``program_phase.ran_on_device``)."""

from benchmark.readers import program_phase


def spans_of(phase):
    """(seconds, count) of the spans named ``phase``."""
    return program_phase.totals(
        program_phase.series("training_phase_seconds", {"phase": phase})
    )


def read(run, args):
    seconds, parts = spans_of(args["phase"])
    shards, wholes = spans_of(args["shard_phase"])[1], spans_of(args["whole_phase"])[1]
    if not parts or not shards or not wholes or not program_phase.ran_on_device(run):
        return None
    return seconds * wholes / shards
