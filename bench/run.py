#!/usr/bin/env python3
"""The on-chip benchmark of the LSM-VEC serving path.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of `BENCHMARK.json` on the chip it is started on and
prints the result object as the last line of stdout.  Exits non-zero,
printing no result, without a TPU or without the program (`src/`).
"""

import time

START = time.monotonic()          # set-up is timed from process start

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

if __name__ == "__main__":
    sys.stdout.reconfigure(line_buffering=True)
    from harness.main import main
    sys.exit(main(start=START))
