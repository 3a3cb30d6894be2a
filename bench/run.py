#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <config>.<traffic> --seed N \\
        --seconds S --trace 0|1

See ``bench/harness.py`` for what a run does, and ``PERF.md`` for the
cells.  Exits nonzero, printing no result, without a TPU or with fewer
chips than the cell needs.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from harness import main

    raise SystemExit(main(sys.argv[1:], t_start=T_START))
