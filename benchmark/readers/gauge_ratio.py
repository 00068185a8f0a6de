"""One gauge of the program's registry over another, times ``scale``: a
share the program can state from shapes alone, set once at set-up
(``rank_pairs_real`` over ``rank_pair_slots``: the real pairs of the query
groups over the pair slots a round computes). None where the program has
no such gauges, as a parent before them has not, or the second is zero."""

from benchmark.readers import program_phase


def read(run, args):
    over = program_phase.series(args["over"])
    under = program_phase.series(args["under"])
    if not over or not under or not under[0].value:
        return None
    return args.get("scale", 1.0) * over[0].value / under[0].value
