#!/usr/bin/env python3
"""Dispatch a multi-class round program many times on the chip and say
whether every dispatch came back.

    python scripts/class_dispatch_soak.py --classes 10 --dispatches 6
    python scripts/class_dispatch_soak.py --classes 3 --chips 4
    python scripts/class_dispatch_soak.py --classes 1 --rows 8800000 --cols 28 --depth 8 --dispatches 10

The class trees of a depth-wise round share one call of the level histogram
kernel a level (``ops/histogram.py::_class_groups``). PR 41's first build of
that call stopped for good at its second or third dispatch in six processes
of eleven, after a cold and a traced run had passed: XLA had handed the kernel
its gradient and node operands in VMEM. This drives the path a job takes
(``models.train()``, K = 8 rounds a dispatch, the device sketch, `mlogloss` of
both sets from the device) on seeded rows at `mnist8m-mc10`'s shape a chip by
default, times every dispatch, and leaves at once, exit 3, when one is not
back after ``--hang-after`` seconds: a stopped program never returns, and a
run that waits for it is charged to its time limit. Exit 0 with one JSON line
last. Needs the chip (exit 2 without one); ``--cpu-rehearsal`` runs the same
code on 2,000 rows through the interpreter, which proves the script and
nothing about the chip. ``--classes 1`` dispatches a one-tree round program
(``binary:logistic``, `logloss`) instead: the soak of PR 47's packed kernel
body (``ops/histogram.py::_tile_pack``) at `higgs-d8`'s and `criteo-tb-d8`'s
shapes.
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
    parser.add_argument("--classes", type=int, default=10)
    parser.add_argument("--rows", type=int, default=506_250, help="rows a chip")
    parser.add_argument("--cols", type=int, default=784)
    parser.add_argument("--depth", type=int, default=5)
    parser.add_argument("--dispatches", type=int, default=6)
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--hang-after", type=float, default=60.0)
    parser.add_argument("--cpu-rehearsal", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import numpy as np

    from sagemaker_xgboost_container_tpu.data.matrix import DataMatrix
    from sagemaker_xgboost_container_tpu.models import booster, train
    from sagemaker_xgboost_container_tpu.ops.histogram import resolve_hist_knobs

    platform = jax.devices()[0].platform
    if args.cpu_rehearsal:
        args.rows, args.cols = 2_000, 12
    elif platform != "tpu":
        print("class_dispatch_soak: no TPU device (platform {})".format(platform))
        return 2
    mesh = None
    if args.chips > 1:
        from jax.sharding import Mesh

        if len(jax.devices()) < args.chips:
            print("class_dispatch_soak: {} devices, {} asked for".format(len(jax.devices()), args.chips))
            return 2
        mesh = Mesh(np.array(jax.devices()[: args.chips]), axis_names=("data",))

    rng = np.random.default_rng(args.seed)
    n = args.rows * args.chips
    # a few levels of integers a column, as pixel columns have, and a label
    # the first columns decide: every class grows trees that really split
    X = rng.integers(0, 200, size=(n, args.cols), dtype=np.uint8).astype(np.float32)
    y = (X[:, : min(8, args.cols)].sum(axis=1) % args.classes).astype(np.float32)
    held = slice(0, max(args.classes * 8, n // 800))
    params = {
        "objective": "multi:softmax", "num_class": args.classes, "max_depth": args.depth,
        "eta": 0.2, "max_bin": 256, "eval_metric": "mlogloss", "_rounds_per_dispatch": 8,
    }
    if args.classes == 1:                           # a one-tree round program
        y = (X[:, : min(8, args.cols)].sum(axis=1) % 2).astype(np.float32)
        del params["num_class"]
        params.update(objective="binary:logistic", eval_metric="logloss")

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
    knobs = resolve_hist_knobs()
    if args.cpu_rehearsal:
        knobs = knobs._replace(backend="tpu")       # the kernel, interpreted
    forest = train(
        params, DataMatrix(X, labels=y), num_boost_round=8 * args.dispatches,
        evals=[(DataMatrix(X[held], labels=y[held]), "validation")],
        verbose_eval=False, mesh=mesh, hist_knobs=knobs,
    )
    print(json.dumps({
        "ok": len(seconds) >= args.dispatches,
        "classes": args.classes, "rows_a_chip": args.rows, "cols": args.cols,
        "chips": args.chips, "dispatches": len(seconds), "trees": len(forest.trees),
        "sync_s": [round(s, 3) for s in seconds],
        "device": {"platform": platform, "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
    }))
    return 0 if len(seconds) >= args.dispatches else 1


if __name__ == "__main__":
    sys.exit(main())
