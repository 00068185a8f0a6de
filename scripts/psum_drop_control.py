#!/usr/bin/env python3
"""The control of a mesh cell's own mechanism: one run of a benchmark cell in
which the LAST data shard's level histograms are left out of the histogram
collective (``ops/histogram.py::apply_hist_collective``, which every builder
calls): every level's sums then lack that shard's rows, a quarter of them on
a mesh of 4, as they would if a share's contribution were lost on the way.
Routing, node totals, the metric's ``psum`` and the stored trees' layout stay
as they are. It has to read ``correct: false`` (PERF.md section 2). Same
arguments as ``benchmark/run.py``:

    python3 scripts/psum_drop_control.py --workload \\
        criteo-tb-d8-host4.train-fused-mesh --seed <n> --seconds 20 --trace 0

The program has no option for this: the script swaps the collective for the
length of the run. On one device (no `data` axis) it changes nothing.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402  (sets T_PROCESS_START)


def install():
    import jax
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import histogram, lossguide, tree_build

    collective = histogram.apply_hist_collective

    def without_last_shard(G, H, axis_name):
        if axis_name is not None:
            keep = jax.lax.axis_index(axis_name) != jax.lax.axis_size(axis_name) - 1
            G, H = jnp.where(keep, G, 0.0), jnp.where(keep, H, 0.0)
        return collective(G, H, axis_name)

    for module in (histogram, tree_build, lossguide):
        module.apply_hist_collective = without_last_shard


if __name__ == "__main__":
    install()
    sys.exit(run.main())
