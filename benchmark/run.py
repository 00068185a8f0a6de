#!/usr/bin/env python3
"""The benchmark's one command: run one cell once, print one result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from ``BENCHMARK.json``: its
configuration (``benchmark/configs/<config>.json``), its traffic mix
(``benchmark/traffic/<traffic>.json``, whose ``kind`` names a module under
``benchmark/kinds/``) and, in a traced run, its per-layer metrics
(``benchmark/layer_metrics/<metric>.json``, whose ``reader`` names a module
under ``benchmark/readers/``). See ``benchmark/README.md``.
"""

import time

T_PROCESS_START = time.time()  # before the heavy imports: setup_s counts them

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    return harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), T_PROCESS_START
    )


if __name__ == "__main__":
    sys.exit(main())
