#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for; without them it exits non-zero before any work.  See
bench/harness.py for what a run does.
"""

import time

T_START = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout root, not this directory, leads the path: bench's own
# module names then cannot shadow another package's
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
