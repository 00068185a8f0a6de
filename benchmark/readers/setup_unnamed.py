"""What of the set-up no span of the program names: the host span ``span``
the kind timed around it (``train_first_round_s``) less the sum of the
program's own top-level set-up spans (``phases`` of
``training_phase_seconds``). None where the program has none of them, or
the run was not on a device (``program_phase.ran_on_device``)."""

from benchmark.readers import program_phase


def read(run, args):
    whole = run.get("host_spans", {}).get(args["span"])
    named = [
        s
        for phase in args["phases"]
        for s in program_phase.series("training_phase_seconds", {"phase": phase})
    ]
    if whole is None or not named or not program_phase.ran_on_device(run):
        return None
    return whole - program_phase.totals(named)[0]
