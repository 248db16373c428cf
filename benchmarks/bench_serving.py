"""Serving-layer latency/throughput benchmark (docs/serving.md).

The full-stack p-bits survey (arXiv:2302.06457) and the SpikeHard
methodology both argue the deployment figure of merit is not raw sweep
rate but the *split*: model-load overhead vs per-invocation overhead vs
steady-state throughput.  This bench publishes exactly that split for
the `repro.serve` stack, tracked across PRs in the ``serving`` section
of BENCH_kernel.json:

* ``model_load`` — cold cost of bringing a shape bucket up: Session
  construction, chip programming, and the first-call XLA compile
  (amortized by the LRU compile cache across every request that fits
  the bucket).
* ``invocation`` — warm per-launch overhead at S=1: what a request pays
  to ride a launch, excluding sweep work.
* ``steady_state`` — warm resident-launch throughput at the serving S:
  microseconds per sweep and sweeps/second at the paper-chip bucket.
* ``compile_cache`` — end-to-end request latency through
  `SamplerService` split three ways: ``recompile`` (first request into
  an empty cache — Session build + XLA compile; also published under
  the legacy ``miss`` key), ``hit`` (same program again), and
  ``program_swap`` (warm bucket, fresh couplings every request — the
  runtime-weight-streaming path, which must cost ~a hit, not a
  recompile).
* ``steady_state_degraded`` — (forced 2-device subprocess) per-sweep
  time on the healthy 2-shard mesh vs after a scripted mid-stream shard
  kill degraded it to single-device, plus the one-off replay/recompile
  cost of the degradation itself.

Usage: PYTHONPATH=src:. python benchmarks/bench_serving.py [--quick]
(--quick uses small shapes for CI smoke and does not touch the tracked
root file.)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_json, timed, timer

ROOT = Path(__file__).resolve().parent.parent


def _codes(g, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(-40, 41, size=g.edges.shape[0], dtype=np.int32),
            rng.integers(-10, 11, size=g.n_nodes, dtype=np.int32))


def bench_bucket_split(bshape, B, S, iters=3) -> list[dict]:
    """model_load / invocation / steady_state rows for one bucket."""
    from repro import api
    from repro.core import pbit
    from repro.serve import SamplerService, make_bucket_graph

    svc = SamplerService(capacity_chains=B, buckets=(bshape,))
    g = make_bucket_graph(*bshape)
    spec = svc.bucket_spec(g)
    t_session, sess = timed(api.Session, spec)
    J, h = _codes(g)
    t_program, chip = timed(sess.program_edges, jnp.asarray(J),
                            jnp.asarray(h))
    km, kn = jax.random.split(jax.random.PRNGKey(0))
    m0 = pbit.random_spins(km, B, g.n_nodes)
    ns = sess.noise_state(kn)
    betas = jnp.ones((S,), jnp.float32)
    betas1 = jnp.ones((1,), jnp.float32)

    t_first, _ = timed(sess.sample, chip, m0, ns, betas)  # compile + run
    t_steady = timer(sess.sample, chip, m0, ns, betas, warmup=0,
                     iters=iters)
    t_invoke = timer(sess.sample, chip, m0, ns, betas1, iters=iters)

    bucket = f"{bshape[0]}x{bshape[1]}"
    return [
        {"phase": "model_load", "bucket": bucket, "N": int(g.n_nodes),
         "B": B, "session_build_ms": t_session * 1e3,
         "program_ms": t_program * 1e3,
         "first_call_compile_ms": max(t_first - t_steady, 0.0) * 1e3},
        {"phase": "invocation", "bucket": bucket, "N": int(g.n_nodes),
         "B": B, "S": 1, "us_per_call": t_invoke * 1e6},
        {"phase": "steady_state", "bucket": bucket, "N": int(g.n_nodes),
         "B": B, "S": S, "us_per_sweep": t_steady / S * 1e6,
         "sweeps_per_sec": S / t_steady,
         "chain_sweeps_per_sec": S * B / t_steady},
    ]


def bench_compile_cache(bshape, B, S) -> dict:
    """End-to-end request latency: recompile vs hit vs program swap.

    ``miss_ms``/``recompile_ms`` are the same event under two names (the
    old dashboard key survives the split): the first request into an
    empty cache pays Session build + XLA compile.  ``program_swap_ms``
    re-codes the warm bucket with fresh couplings every request — the
    program is a runtime operand (`Session.sample_program`), so a swap
    rides the compiled executable and must sit near ``hit_ms``, orders
    of magnitude under ``recompile_ms``."""
    from repro.core.chimera import make_chimera
    from repro.serve import SampleRequest, SamplerService

    svc = SamplerService(capacity_chains=B, buckets=(bshape,))
    g = make_chimera(*bshape)
    J, h = _codes(g)

    def request_latency(J, h):
        t0 = time.perf_counter()
        t = svc.submit(SampleRequest(tenant="bench", graph=g, J_codes=J,
                                     h_codes=h, chains=1, n_sweeps=S))
        svc.drain()
        assert t.result().status == "ok"
        return (time.perf_counter() - t0) * 1e3

    miss_ms = request_latency(J, h)
    hit_ms = min(request_latency(J, h) for _ in range(3))
    swap_ms = min(request_latency(*_codes(g, seed)) for seed in (1, 2, 3))
    # new couplings every swap request, still exactly one compile ever
    assert svc.cache.stats()["misses"] == 1
    return {"phase": "compile_cache",
            "bucket": f"{bshape[0]}x{bshape[1]}", "B": B, "S": S,
            "miss_ms": miss_ms, "hit_ms": hit_ms,
            "speedup": miss_ms / max(hit_ms, 1e-9),
            "recompile_ms": miss_ms, "program_swap_ms": swap_ms,
            "swap_speedup": miss_ms / max(swap_ms, 1e-9)}


_DEGRADED_WORKER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax
import numpy as np
from jax.sharding import Mesh
from repro.core.chimera import make_chimera
from repro.serve import (FaultEvent, FaultInjector, FaultPlan,
                         SampleRequest, SamplerService,
                         ShardHealthMonitor)

ROWS, COLS, B, S, R = {rows}, {cols}, {B}, {S}, {R}
KILL = R // 2
mesh = Mesh(np.asarray(jax.devices()), ("data",))
plan = FaultPlan.make([FaultEvent(step=KILL, kind="kill_shard", shard=1)])
svc = SamplerService(seed=0, capacity_chains=B, mesh=mesh,
                     monitor=ShardHealthMonitor(),
                     injector=FaultInjector(plan),
                     buckets=((ROWS, COLS),))
g = make_chimera(ROWS, COLS)
rng = np.random.default_rng(0)
J = rng.integers(-40, 41, size=g.edges.shape[0], dtype=np.int32)
h = rng.integers(-10, 11, size=g.n_nodes, dtype=np.int32)
# chains=B: each request fills a launch, so launch seq == request index
tickets = [svc.submit(SampleRequest(
    tenant="bench", graph=g, J_codes=J, h_codes=h, chains=B,
    n_sweeps=S, timeout_s=3600.0)) for _ in range(R)]
svc.drain()
res = [t.result() for t in tickets]
assert all(r.status == "ok" for r in res), [r.status for r in res]
by_seq = {{r.launch_seq: r for r in res}}
healthy = [by_seq[i].exec_s for i in range(1, KILL)]        # skip compile
degraded = [by_seq[i].exec_s for i in range(KILL + 1, R)]   # skip replay
med = lambda xs: sorted(xs)[len(xs) // 2]
print(json.dumps({{
    "healthy_2dev_us_per_sweep": med(healthy) / S * 1e6,
    "degraded_1dev_us_per_sweep": med(degraded) / S * 1e6,
    "replay_recompile_ms": by_seq[KILL].exec_s * 1e3,
    "zero_drops": svc.metrics["completed"] == svc.metrics["admitted"] == R,
    "state": svc.state,
    "degradations": svc.metrics["degradations"],
}}))
"""


def bench_degraded(bshape, B, S, R) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{ROOT}/src"}
    script = _DEGRADED_WORKER.format(rows=bshape[0], cols=bshape[1],
                                     B=B, S=S, R=R)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["zero_drops"] and payload["state"] == "single", payload
    from repro.core.chimera import make_chimera
    g = make_chimera(*bshape)
    row = {"phase": "steady_state_degraded",
           "bucket": f"{bshape[0]}x{bshape[1]}", "N": int(g.n_nodes),
           "B": B, "S": S, "n_requests": R, "killed_shard": 1}
    row.update({k: payload[k] for k in
                ("healthy_2dev_us_per_sweep", "degraded_1dev_us_per_sweep",
                 "replay_recompile_ms", "degradations")})
    return row


def run(quick: bool = False) -> dict:
    if quick:
        split = bench_bucket_split((2, 2), B=8, S=8, iters=2)
        cache = bench_compile_cache((2, 2), B=8, S=8)
        degraded = bench_degraded((2, 2), B=4, S=8, R=6)
    else:
        # the paper-chip bucket (7x8 Chimera = 448 sites) at serving batch
        split = bench_bucket_split((7, 8), B=16, S=32, iters=3)
        cache = bench_compile_cache((7, 8), B=16, S=32)
        degraded = bench_degraded((4, 4), B=8, S=16, R=8)
    rows = split + [cache, degraded]
    results = {
        "note": "model-load vs invocation vs steady-state split for the "
                "repro.serve stack (docs/serving.md §Benchmark "
                "methodology); degraded row = scripted mid-stream shard "
                "kill on a forced 2-device host",
        "rows": rows,
    }

    steady = next(r for r in rows if r["phase"] == "steady_state")
    load = next(r for r in rows if r["phase"] == "model_load")
    emit("serving_steady_state", steady["us_per_sweep"],
         f"N={steady['N']} sweeps/s={steady['sweeps_per_sec']:.1f}")
    emit("serving_model_load_ms",
         load["session_build_ms"] + load["first_call_compile_ms"],
         f"program={load['program_ms']:.1f}ms")
    emit("serving_cache_hit_ms", cache["hit_ms"],
         f"miss={cache['miss_ms']:.0f}ms ({cache['speedup']:.0f}x)")
    emit("serving_program_swap_ms", cache["program_swap_ms"],
         f"recompile={cache['recompile_ms']:.0f}ms "
         f"({cache['swap_speedup']:.0f}x)")
    emit("serving_degraded_us_per_sweep",
         degraded["degraded_1dev_us_per_sweep"],
         f"healthy_2dev={degraded['healthy_2dev_us_per_sweep']:.0f}us")

    save_json("serving", results)
    if not quick:
        # tracked across PRs; merge-preserve the other benches' sections
        root = ROOT / "BENCH_kernel.json"
        merged = json.loads(root.read_text()) if root.exists() else {}
        merged["serving"] = results
        root.write_text(json.dumps(merged, indent=1))
    return results


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / CI smoke; skips the tracked root "
                         "file")
    args = ap.parse_args()
    run(quick=args.quick)
