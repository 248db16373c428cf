"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes JSON payloads to
results/bench/.  Roofline analysis over the dry-run artifacts is
``python -m benchmarks.roofline [results/dryrun]``.
"""
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (
        bench_adder,
        bench_anneal,
        bench_kernel,
        bench_learning,
        bench_maxcut,
        bench_table1,
        bench_tempering,
        bench_variability,
    )

    print("name,us_per_call,derived")
    bench_table1.run()        # Table 1: throughput/comparison
    bench_kernel.run()        # kernel traffic model
    bench_variability.run()   # Fig 8a
    bench_anneal.run()        # Fig 9a
    bench_maxcut.run()        # Fig 9b
    bench_tempering.run()     # beyond-paper: PT vs SA
    bench_learning.run()      # Fig 7b/c (slowest: CD training)
    bench_adder.run()         # Fig 8b
    print("done", file=sys.stderr)


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()
