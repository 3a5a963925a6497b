#!/usr/bin/env python3
"""Readings for the correctness limits: the program's and the control's.

    python3 benchmarks/chip/control.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in one process, one run of the cell (a window of
``--seconds``), then its check twice: on the program's outputs, and on the
control's (the plain reference at three bf16 passes per product, one
precision step below the configuration's float32 at ``HIGHEST``). Prints
one JSON line per seed. A limit sits above the largest program reading and
below the smallest control reading. The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks.chip import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    t = T_START
    for seed in args.seeds:
        try:
            out = harness.run_cell(args.workload, seed=seed,
                                   seconds=args.seconds, trace=False,
                                   t_start=t,
                                   controls=({"passes": 3},))
        except harness.BenchError as e:
            print(f"control: {e}", file=sys.stderr, flush=True)
            return 2
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "checks": out["checks"],
                          "control": out["control_checks"][0],
                          "metrics": out["metrics"]}), flush=True)
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
