"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``bench/`` and
the program under ``src/``, on a machine with the TPU chips the cell asks
for.  It refuses (exit code 2, no result) anywhere else.  The last line of
standard output is the result as one JSON object; the numbers compared for
``correct`` are printed, each beside its limit, as the last lines of
standard error.  JAX's persistent compilation cache lives in
``<checkout>/.jax_cache`` and Python's bytecode in ``<checkout>/.pycache``,
so only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# Python's bytecode, like JAX's programs, is cached in the checkout: the
# first run compiles the sources it imports, later runs load them
sys.pycache_prefix = str(ROOT / ".pycache")
sys.dont_write_bytecode = False

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import harness
    except ImportError as e:
        print(f"bench/run.py: refused: {e}", file=sys.stderr)
        return 2
    try:
        from repro.runtime.compile_cache import use_compile_cache
        use_compile_cache()
        line = harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    except (ImportError, harness.Refused) as e:
        print(f"bench/run.py: refused: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line))
    sys.stdout.flush()
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
