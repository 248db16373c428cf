"""Sweep the offered rate of a serving cell to find the rate it sustains.

    python bench/knee.py --config chip440 --traffic serve \
        --rates 10,20,40 --seconds 20 --seed 1

One process, one window per rate (the same mix, scaled).  For each rate it
prints the answered and failed requests, the median and 95th percentile
latency from when each request was due, the batch occupancy, and the
backlog left when the window closed (requests due but not yet answered)
with the seconds it took to drain it.  A backlog that grows with the window
marks a rate above what the service sustains.  Used once, to fix the
cell's rate at about four fifths of the knee; the benchmark's runs offer
that fixed rate and never search.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", default="chip440")
    ap.add_argument("--traffic", default="serve")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    import load
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    harness.devices(1)
    cell = harness.Cell(
        name=f"{args.config}.{args.traffic}", chips=1,
        config=json.loads(
            (HERE / "configs" / f"{args.config}.json").read_text()),
        traffic=json.loads(
            (HERE / "traffic" / f"{args.traffic}.json").read_text()),
        end_to_end=[], per_layer=[], root=ROOT)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        drv = load.generator(cell, args.seed)
        t0 = time.perf_counter()
        drv.run(args.seconds)
        lat = sorted(drv.latencies())
        n, bad = drv.attempted()
        layer = drv.layer_counters()
        done_at = [r[2] + d[0] for r, d in zip(drv.results, drv.due)
                   if r is not None and r[0] == "ok"]
        backlog = sum(1 for x in done_at if x > args.seconds)
        print(json.dumps({
            "rate_per_s": rate, "requests": n, "failed": bad,
            "p50_ms": 1e3 * lat[len(lat) // 2] if lat else None,
            "p95_ms": 1e3 * lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
            if lat else None,
            "occupancy": layer["occupancy"], "queue_ms": layer["queue_ms"],
            "late_ms_max": layer["late_ms_max"],
            "answered_after_close": backlog,
            "drain_s": drv.elapsed_to_drain - args.seconds,
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
