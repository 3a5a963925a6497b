"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Full (non-smoke) runs:
``python -m benchmarks.<name>`` individually.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale grids (slow on CPU)")
    args, _ = ap.parse_known_args()
    smoke = not args.full

    print("name,us_per_call,derived")

    from . import plan_table
    plan_table.run(smoke=smoke)

    from . import fft_throughput
    fft_throughput.run(smoke=smoke)

    from . import stepwise_opt
    stepwise_opt.run(smoke=smoke)

    from . import fft_roofline
    fft_roofline.run(smoke=smoke)

    from . import abft_overhead
    abft_overhead.run(smoke=smoke)

    from . import ft_gemm_overhead
    ft_gemm_overhead.run(smoke=smoke)

    from . import error_injection
    error_injection.run(smoke=smoke)

    from . import fft_distributed
    fft_distributed.run(smoke=smoke)
    fft_distributed.run_mesh2d(smoke=smoke)


if __name__ == "__main__":
    main()
