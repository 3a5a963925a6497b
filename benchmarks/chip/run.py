#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell's configuration, traffic and
metrics are read from BENCHMARK.json and the files beside this script; see
harness.py. Exits non-zero, printing no result, without a TPU.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

# import the benchmark as a package from the checkout's root, not this
# script's directory (whose trace.py would shadow the standard library's)
sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
