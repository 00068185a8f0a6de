#!/usr/bin/env python
"""Per-stage timing dissection of one boosting round on the current backend.

Times each stage of the bench configuration (bench.py: 1M x 28, depth 8,
max_bin 256, binary:logistic) in isolation under jit, so a round's time on
the device can be attributed: grad/hess, per-level histograms (with the sibling
subtraction that the real build does), node totals, split scan, row routing
(gather vs onehot), eval prediction, and the full fused tree build.

Prints one "stage: ms" line per stage plus a JSON summary line at the end.
Honors GRAFT_HIST_IMPL / GRAFT_HIST_MM_PREC / GRAFT_ROUTE_IMPL. Run under an
external timeout, like anything that holds a device.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

N_ROWS = int(os.getenv("DISSECT_ROWS", "1000000"))
N_FEATURES = int(os.getenv("DISSECT_FEATURES", "28"))
MAX_DEPTH = int(os.getenv("DISSECT_MAX_DEPTH", "8"))
MAX_BIN = int(os.getenv("DISSECT_MAX_BIN", "256"))
REPS = int(os.getenv("DISSECT_REPS", "5"))


def _time(fn, *args):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def main():
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import histogram as H
    from sagemaker_xgboost_container_tpu.ops import tree_build as TB
    from sagemaker_xgboost_container_tpu.ops.split import find_best_splits

    print("backend:", jax.default_backend(), flush=True)
    print(
        "impl={} prec={} route={}".format(
            os.environ.get("GRAFT_HIST_IMPL", "flat"),
            os.environ.get("GRAFT_HIST_MM_PREC", "bf16x2"),
            os.environ.get("GRAFT_ROUTE_IMPL", "gather"),
        ),
        flush=True,
    )

    rng = np.random.RandomState(0)
    n, d, B = N_ROWS, N_FEATURES, MAX_BIN + 1
    bin_dtype = np.uint8 if B <= 256 else np.uint16  # match binning storage
    bins = jnp.asarray(rng.randint(0, MAX_BIN, size=(n, d)).astype(bin_dtype))
    margins = jnp.asarray(rng.randn(n).astype(np.float32) * 0.3)
    labels = jnp.asarray((rng.rand(n) > 0.5).astype(np.float32))
    jax.block_until_ready((bins, margins, labels))

    timings = {}

    # --- grad/hess (binary:logistic) ------------------------------------
    @jax.jit
    def gradhess(m, y):
        p = jax.nn.sigmoid(m)
        return p - y, p * (1.0 - p)

    timings["grad_hess"] = _time(gradhess, margins, labels)

    # --- per-level histogram cost, as the real build pays it ------------
    # level 0: full width-1 histogram. levels 1..max_depth-1 with
    # subtraction: only the left-child half is histogrammed (width/2
    # output), over ~all rows. last level: node_totals only.
    node_fns = {}

    def hist_at(width_out):
        key = ("hist", width_out)
        if key not in node_fns:
            node_fns[key] = jax.jit(
                lambda b, g, h, nl: H.level_histogram(b, g, h, nl, width_out, B)
            )
        return node_fns[key]

    grad, hess = gradhess(margins, labels)
    jax.block_until_ready((grad, hess))

    hist_total = 0.0
    for level in range(MAX_DEPTH):
        if level == 0:
            width_out = 1
        else:
            width_out = 2 ** (level - 1)  # subtraction: left children only
        nl = jnp.asarray(rng.randint(0, width_out, size=n).astype(np.int32))
        ms = _time(hist_at(width_out), bins, grad, hess, nl)
        timings["hist_L{}[{}]".format(level, width_out)] = ms
        hist_total += ms
    timings["hist_all_levels"] = hist_total

    # --- last-level node totals -----------------------------------------
    W_last = 2**MAX_DEPTH
    nl = jnp.asarray(rng.randint(0, W_last, size=n).astype(np.int32))
    fn_tot = jax.jit(lambda g, h, x: H.node_totals(g, h, x, W_last))
    timings["node_totals[{}]".format(W_last)] = _time(fn_tot, grad, hess, nl)

    # --- split scan across all levels -----------------------------------
    num_cuts = jnp.full((d,), MAX_BIN - 1, jnp.int32)
    split_total = 0.0
    for level in range(MAX_DEPTH):
        W = 2**level
        Gl = jnp.asarray(rng.rand(W, d, B).astype(np.float32))
        Hl = jnp.asarray(np.abs(rng.rand(W, d, B)).astype(np.float32))
        fn = jax.jit(lambda G, Hh: find_best_splits(G, Hh, num_cuts))
        ms = _time(fn, Gl, Hl)
        split_total += ms
    timings["split_scan_all_levels"] = split_total

    # --- routing (one level at full width) ------------------------------
    split_feat = jnp.asarray(rng.randint(0, d, size=n).astype(np.int32))

    @jax.jit
    def route(b, sf):
        row_bin = TB.row_bin_lookup(b, sf)
        return row_bin > 128

    timings["route_lookup[n]"] = _time(route, bins, split_feat) * MAX_DEPTH
    timings["route_one_level"] = timings["route_lookup[n]"] / MAX_DEPTH

    # --- full tree build (the real fused program) -----------------------
    @jax.jit
    def full_tree(b, g, h):
        tree, row_out = TB.build_tree(
            b, g, h, num_cuts, MAX_DEPTH, B, eta=0.2
        )
        return TB.pack_tree(tree), row_out

    timings["full_tree_build"] = _time(full_tree, bins, grad, hess)

    # --- full round incl. grad/hess + margin update ---------------------
    @jax.jit
    def full_round(b, m, y):
        g, h = gradhess(m, y)
        tree, row_out = TB.build_tree(b, g, h, num_cuts, MAX_DEPTH, B, eta=0.2)
        return TB.pack_tree(tree), m + row_out

    timings["full_round"] = _time(full_round, bins, margins, labels)

    for k, v in timings.items():
        print("{:28s} {:9.2f} ms".format(k, v), flush=True)
    print(json.dumps({"backend": jax.default_backend(), "timings_ms": timings}))


if __name__ == "__main__":
    main()
