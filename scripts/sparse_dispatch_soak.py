#!/usr/bin/env python3
"""Dispatch a bundled round program many times on the chip and say whether
every dispatch came back.

    python scripts/sparse_dispatch_soak.py --seeds 1,2 --dispatches 10

A session over sparse input (``data/bundling.py``) traces a round program no
dense session does: the bundled split scan (masked sums as dots, a winner's
range word) and the range test in the build's routing and in the evaluation
walk (``ops/bundle.py``). PR 41's program stopped for good at its third
dispatch after two green runs, so a new round program runs here first. This
drives the path a job takes (``models.train()`` over CSR matrices, the plan,
the device sketch of the dense columns, K rounds a dispatch, ``logloss`` of
both sets from the device) on seeded rows at ``allstate-onehot-d8``'s shape
by default, one session a seed, times every dispatch, and leaves at once,
exit 3, when one is not back after ``--hang-after`` seconds: a stopped
program never returns, and a run that waits for it is charged to its time
limit. Exit 0 with one JSON line last. Needs the chip (exit 2 without one);
``--cpu-rehearsal`` runs the same code on 20,000 rows, which proves the
script and nothing about the chip.
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
    parser.add_argument("--seeds", default="1,2")
    parser.add_argument("--rows", type=int, default=12_184_290)
    parser.add_argument("--validation-rows", type=int, default=1_000_000)
    parser.add_argument("--rounds-per-dispatch", type=int, default=8)
    parser.add_argument("--dispatches", type=int, default=10)
    parser.add_argument("--hang-after", type=float, default=120.0)
    parser.add_argument("--cpu-rehearsal", action="store_true")
    args = parser.parse_args(argv)

    import jax

    from benchmark.datagen import allstate_like
    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import booster, train
    from sagemaker_xgboost_container_tpu.telemetry import REGISTRY
    from sagemaker_xgboost_container_tpu.utils.compile_cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if args.cpu_rehearsal:
        args.rows, args.validation_rows, args.rounds_per_dispatch = 20_000, 3_000, 2
    elif platform != "tpu":
        print("sparse_dispatch_soak: no TPU device (platform {})".format(platform))
        return 2
    enable_compile_cache()
    k = args.rounds_per_dispatch
    params = {
        "objective": "binary:logistic", "tree_method": "hist", "max_depth": 8, "eta": 0.1,
        "min_child_weight": 100, "lambda": 1.0, "max_bin": 256, "eval_metric": "logloss",
        "_rounds_per_dispatch": k,
    }
    if args.cpu_rehearsal:
        params.update(max_depth=4, min_child_weight=5)

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
    sessions = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        data = allstate_like.make(
            {"train_rows": args.rows, "validation_rows": args.validation_rows,
             "num_feature": allstate_like.NUM_FEATURE},
            seed,
        )
        before = len(seconds)
        sets = {name: DataMatrix(x, labels=y) for name, (x, y) in data.items()}
        forest = train(
            dict(params, seed=seed % (1 << 31)),
            sets["train"],
            num_boost_round=k * args.dispatches,
            evals=[(sets["train"], "train"), (sets["validation"], "validation")],
            verbose_eval=False,
        )
        gauges = {
            name: family[0].value
            for name, _kind, _help, family in REGISTRY.collect()
            if name in ("train_bundle_columns", "bundle_conflict_rows", "bundle_bins_used")
            and family
        }
        sessions.append({
            "seed": seed,
            "dispatches": len(seconds) - before,
            "sync_s": [round(s, 3) for s in seconds[before:]],
            "leaves": [int((t.left < 0).sum()) for t in forest.trees],
            "gauges": gauges,
        })
        print("SESSION {}".format(json.dumps(sessions[-1])), flush=True)
        del data, sets, forest
    ok = all(
        s["dispatches"] >= args.dispatches and s["gauges"].get("train_bundle_columns")
        for s in sessions
    )
    print(json.dumps({
        "ok": bool(ok), "rows": args.rows, "validation_rows": args.validation_rows,
        "rounds_per_dispatch": k, "dispatches": len(seconds), "sessions": sessions,
        "device": {"platform": platform, "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
