#!/usr/bin/env python3
"""A control of ``allstate-cat-d8``'s own mechanism: one run of a benchmark
cell in which a row whose value of its node's split column is missing goes
LEFT, whatever default direction the scan chose and the tree records: in the
build's routing and in the evaluation walk (both decide through
``ops/categorical.py::CatTables.go_right``). Histograms, the scan and the
stored trees stay as they are, so wherever the scan sends missing rows right
the children are built from rows the tree does not give them. It has to read
``correct: false`` (PERF.md section 2). Same arguments as
``benchmark/run.py``:

    python3 scripts/cat_unknown_left_control.py --workload allstate-cat-d8.train-fused \\
        --seed <n> --seconds 20 --trace 0

The program has no option for this: the script swaps the decision for the
length of the run. In a session without categorical columns it changes
nothing.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402  (sets T_PROCESS_START)


def install():
    from sagemaker_xgboost_container_tpu.ops.categorical import CatTables

    sound = CatTables.go_right

    def unknown_left(self, value, split_bin, default_left, word):
        return sound(self, value, split_bin, default_left | True, word)

    CatTables.go_right = unknown_left


if __name__ == "__main__":
    install()
    sys.exit(run.main())
