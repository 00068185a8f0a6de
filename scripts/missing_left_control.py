#!/usr/bin/env python3
"""The control of ``criteo-tb-d8``'s own mechanism: one run of a benchmark
cell in which every row whose split feature is missing goes LEFT, whatever
default direction the split scan chose and the tree records: in the build's
routing, in the leaf pass and in the evaluation walk (all read a row's bin
through ``ops/tree_build.py::row_bin_lookup``). Histograms, the scan and the
stored trees stay as they are, so wherever the scan sends missing rows right
the children are built from rows the tree does not give them. It has to read
``correct: false`` (PERF.md section 2). Same arguments as ``benchmark/run.py``:

    python3 scripts/missing_left_control.py --workload criteo-tb-d8.train-fused \\
        --seed <n> --seconds 20 --trace 0

The program has no option for this: the script swaps the lookup for the
length of the run. In a dense cell (no missing value) it changes nothing.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness, run  # noqa: E402  (sets T_PROCESS_START)


def install(missing_bin):
    import jax.numpy as jnp

    from sagemaker_xgboost_container_tpu.ops import tree_build

    lookup = tree_build.row_bin_lookup

    def never_missing(bins, feat_idx, impl=None):
        row_bin = lookup(bins, feat_idx, impl=impl)
        # below every split bin: `row_bin > split_bin` is false, the row goes left
        return jnp.where(row_bin == missing_bin, -1, row_bin)

    tree_build.row_bin_lookup = never_missing


if __name__ == "__main__":
    workload = sys.argv[sys.argv.index("--workload") + 1]
    _cell, config, _traffic = harness.resolve_cell(harness.load_benchmark(), workload)
    install(int(config["params"]["max_bin"]))
    sys.exit(run.main())
