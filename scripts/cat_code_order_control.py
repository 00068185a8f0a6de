#!/usr/bin/env python3
"""A control of ``allstate-cat-d8``'s own mechanism: one run of a benchmark
cell in which the partition scan takes a column's categories **in the order
of their codes**, not in the order of their gradient statistics
(``ops/categorical.py::_rank``). Everything else stays: the candidates are
still prefix sets of an order, scanned from both ends under the same caps,
the winner's set is read back off the same places, rows are routed by the
set the tree records. So every stored sum is right and every teacher-forced
number passes; what is lost is the *search*: a prefix of the code order is
an arbitrary set, and the reference's own partition scan finds a better one
at nearly every node. It has to read ``correct: false`` by
``cat_partition_regret`` (PERF.md section 2). Same arguments as
``benchmark/run.py``:

    python3 scripts/cat_code_order_control.py --workload allstate-cat-d8.train-fused \\
        --seed <n> --seconds 20 --trace 0

The program has no option for this: the script swaps the ordering for the
length of the run. In a session without categorical columns it changes
nothing.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402  (sets T_PROCESS_START)


def install():
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import categorical

    def code_order(key):
        # a node's held categories by code, its absent ones (key +inf) behind them
        present = jnp.isfinite(key)
        held = present.sum(axis=1, keepdims=True, dtype=jnp.int32)
        return jnp.where(
            present,
            jnp.cumsum(present, axis=1, dtype=jnp.int32) - 1,
            held + jnp.cumsum(~present, axis=1, dtype=jnp.int32) - 1,
        )

    categorical._rank = code_order


if __name__ == "__main__":
    install()
    sys.exit(run.main())
