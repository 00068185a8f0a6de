#!/usr/bin/env python3
"""Dispatch a loss-guided round program many times on the chip and say
whether every dispatch came back.

    python scripts/leafwise_dispatch_soak.py --seeds 1,2,3,4,5 --dispatches 6

A loss-guided round (``ops/lossguide.py``: ``max_leaves - 1`` split steps
under one rolled loop) takes its evaluation rows through the new tree by
the step replay since PR 44 (``ops/tree_build.py::predict_binned_steps``: a
second rolled loop, one column slice a split step and no gather; up to PR 43
the pointer walk, ``predict_binned``, whose every step gathered two ``bool``
tables over the evaluation rows), and PR 41 found a v5e stopping for good in
a gather of a ``pred`` operand that XLA had placed in VMEM: a new round
program runs here first. This drives the path a job takes (``models.train()``, the device
sketch, K rounds a dispatch, ``logloss`` of both sets from the device) on
seeded rows at ``higgs-leafwise-l255``'s shape by default, one session a
seed, times every dispatch, and leaves at once, exit 3, when one is not back
after ``--hang-after`` seconds: a stopped program never returns, and a run
that waits for it is charged to its time limit. Exit 0 with one JSON line
last. Needs the chip (exit 2 without one); ``--cpu-rehearsal`` runs the same
code on 3,000 rows, which proves the script and nothing about the chip.
"""

import argparse
import json
import os
import sys
import threading
import time

T0 = time.time()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seeds", default="1,2,3,4,5")
    parser.add_argument("--rows", type=int, default=10_500_000)
    parser.add_argument("--validation-rows", type=int, default=500_000)
    parser.add_argument("--cols", type=int, default=28)
    parser.add_argument("--leaves", type=int, default=255)
    parser.add_argument("--rounds-per-dispatch", type=int, default=2)
    parser.add_argument("--dispatches", type=int, default=6)
    parser.add_argument("--hang-after", type=float, default=120.0)
    parser.add_argument("--cpu-rehearsal", action="store_true")
    args = parser.parse_args(argv)

    import jax

    from benchmark.datagen import higgs_like
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import booster, train
    from sagemaker_xgboost_container_tpu.utils.compile_cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if args.cpu_rehearsal:
        args.rows, args.validation_rows, args.leaves = 3_000, 500, 15
    elif platform != "tpu":
        print("leafwise_dispatch_soak: no TPU device (platform {})".format(platform))
        return 2
    enable_compile_cache()
    k = args.rounds_per_dispatch
    params = {
        "objective": "binary:logistic", "tree_method": "hist", "grow_policy": "lossguide",
        "max_depth": 0, "max_leaves": args.leaves, "eta": 0.1, "min_child_weight": 100,
        "lambda": 1.0, "max_bin": 256, "eval_metric": "logloss", "_rounds_per_dispatch": k,
    }
    if args.cpu_rehearsal:
        params["min_child_weight"] = 1

    seconds = []
    real_sync = booster._TrainingSession._device_sync

    def hung():
        print(
            "HUNG: dispatch {} not back after {:.0f} s (t = {:.1f} s)".format(
                len(seconds) + 1, args.hang_after, time.time() - T0
            ),
            flush=True,
        )
        os._exit(3)

    def sync(self, packed, out, attributes, fenced):
        start = time.time()
        watchdog = threading.Timer(args.hang_after, hung)
        watchdog.daemon = True
        watchdog.start()
        try:
            return real_sync(self, packed, out, attributes, fenced)
        finally:
            watchdog.cancel()
            seconds.append(time.time() - start)
            print(
                "DISPATCH {} back: sync {:.2f} s (t = {:.1f} s)".format(
                    len(seconds), seconds[-1], time.time() - T0
                ),
                flush=True,
            )

    booster._TrainingSession._device_sync = sync
    # [passes, slots filled, slots used] of every committed tree (PR 43: a
    # pass histograms the best open leaves at once, ops/lossguide.py)
    pass_counts = []
    real_note = booster.note_committed_trees

    def note(trees, padded=None):
        if padded is not None and "hist_passes" in padded:
            pass_counts.append([int(v) for v in padded["hist_passes"].reshape(-1, 3).sum(axis=0)])
        return real_note(trees, padded)

    booster.note_committed_trees = note
    sessions = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        data = higgs_like.make(
            {"train_rows": args.rows, "validation_rows": args.validation_rows,
             "num_feature": args.cols},
            seed,
        )
        before, counted = len(seconds), len(pass_counts)
        sets = {name: DataMatrix(x, labels=y) for name, (x, y) in data.items()}
        forest = train(
            dict(params, seed=seed % (1 << 31)),
            sets["train"],
            num_boost_round=k * args.dispatches,
            evals=[(sets["train"], "train"), (sets["validation"], "validation")],
            verbose_eval=False,
        )
        sessions.append({
            "seed": seed,
            "dispatches": len(seconds) - before,
            "sync_s": [round(s, 3) for s in seconds[before:]],
            "leaves": [int((t.left < 0).sum()) for t in forest.trees],
            "depth": [t.depth() for t in forest.trees],
            "hist_passes": pass_counts[counted:],
        })
        print("SESSION {}".format(json.dumps(sessions[-1])), flush=True)
        del data, sets, forest
    ok = all(s["dispatches"] >= args.dispatches for s in sessions)
    print(json.dumps({
        "ok": ok, "rows": args.rows, "validation_rows": args.validation_rows,
        "cols": args.cols, "leaves": args.leaves, "rounds_per_dispatch": k,
        "dispatches": len(seconds), "sessions": sessions,
        "eval_traversal": booster.TrainConfig(params).eval_traversal,
        "device": {"platform": platform, "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
