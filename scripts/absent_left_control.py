#!/usr/bin/env python3
"""The control of ``allstate-onehot-d8``'s own mechanism: one run of a
benchmark cell in which every row that holds no value of its node's split
column goes LEFT, whatever default direction the split scan chose and the
tree records: in the build's routing and in the evaluation walk (both decide
through ``ops/bundle.py::BundleTables.go_right``, the range test of a bundled
session). Histograms, the scan and the stored trees stay as they are, so
wherever the scan sends absent rows right the children are built from rows
the tree does not give them; 99 % of this matrix is absent cells, so that is
most of the routing. It has to read ``correct: false`` (PERF.md section 2).
Same arguments as ``benchmark/run.py``:

    python3 scripts/absent_left_control.py --workload allstate-onehot-d8.train-fused \\
        --seed <n> --seconds 20 --trace 0

The program has no option for this: the script swaps the decision for the
length of the run. In a session that is not bundled it changes nothing.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402  (sets T_PROCESS_START)


def install():
    from sagemaker_xgboost_container_tpu.ops.bundle import BundleTables

    def absent_left(cls, row_bin, range_word, split_bin, default_left):
        return ~cls.absent(row_bin, range_word) & (row_bin > split_bin)

    BundleTables.go_right = classmethod(absent_left)


if __name__ == "__main__":
    install()
    sys.exit(run.main())
