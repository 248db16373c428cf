"""The paper's chip at lattice scale: anneal a large Chimera p-bit fabric
through a mesh-sharded `api.Session` — cell rows partition over the
device mesh and only the O(√N) chain-coupler boundary spins move between
devices (ppermute halo exchange), exactly the chip's inter-cell wires.

Nothing O(N²) is ever built: the machine is sparse-native
(`SparseMismatch`, O(D·N)) and the sharded engine keeps per-device slot
tables local.  Under the default barrier policy a sharded run reproduces
the single-device spin trajectory bit for bit (docs/sharding.md).

``--sync`` demos the first-class synchronization policies (`api.Sync`):

  * ``barrier`` — per-half-sweep halo exchange, the bit-exact default;
  * ``halo4``   — exchange every 4th half-sweep, 4-sweep launches;
  * ``async``   — PASS-style: launch-resident bands, double-buffered
                  (fire-and-forget) exchanges at launch boundaries only.

With a relaxed policy the script runs the barrier baseline too and prints
the measured sweeps/sec for both plus the energy-trace gap — the
sampling-quality cost is measured, never assumed away.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python examples/pbit_lattice_pod.py --sync async
(REPRO_EXAMPLE_QUICK=1 shrinks the lattice for the CI smoke job.)
"""
import argparse
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core.cd import PBitMachine
from repro.core.chimera import make_chimera
from repro.core.distributed import halo_bytes_per_sweep, sparse_energy
from repro.core.hardware import HardwareConfig
from repro.launch.mesh import halo_vs_hbm_seconds, make_line_mesh
from repro.runtime.compile_cache import use_compile_cache

SYNCS = {
    "barrier": api.Sync(),
    "halo4": api.Sync(halo_every=4, sweeps_per_launch=4),
    "async": api.Sync(halo_every=math.inf, mode="async",
                      sweeps_per_launch=4),
}

ap = argparse.ArgumentParser()
ap.add_argument("--sync", choices=sorted(SYNCS), default="barrier",
                help="shard synchronization policy (api.Sync)")
args = ap.parse_args()
use_compile_cache()

quick = bool(os.environ.get("REPRO_EXAMPLE_QUICK"))
side = 8 if quick else 32          # 32x32 cells = 8192 p-bits
n_sweeps = 60 if quick else 400
rec = 12 if quick else 40          # energy-trace segment (divisible by 4)
chains = 4 if quick else 16

graph = make_chimera(side, side)
n_dev = len(jax.devices())
mesh = make_line_mesh() if (n_dev > 1 or args.sync != "barrier") else None
print(f"lattice: {side}x{side} cells = {graph.n_nodes} p-bits, "
      f"{graph.n_edges} couplers over {n_dev} device(s), "
      f"sync={args.sync}")

# sparse-native chip instance: process variation sampled straight into the
# O(D·N) slot layout; mesh+partition+sync ride the machine into every
# Session (backend stays "sparse", so relaxed policies run the scan path)
machine = PBitMachine.create(
    graph, jax.random.PRNGKey(0), HardwareConfig(), sparse=True,
    noise="counter", w_scale=0.05, mesh=mesh,
    partition=api.Partition(rows="data") if mesh is not None else None)

# random SK instance on the physical couplers (one 8-bit code per edge)
rng = np.random.default_rng(1)
codes = jnp.asarray(rng.integers(-100, 101, graph.n_edges), jnp.int32)
betas = api.Anneal(0.05, 2.5, n_sweeps=n_sweeps).betas()
segs = betas.reshape(n_sweeps // rec, rec)


def run_policy(sync):
    """Anneal under one Sync policy; returns (sweeps/sec, energy trace)."""
    spec = machine.sampler_spec(
        chains=chains, sync=sync if mesh is not None else None)
    session = api.Session(spec)
    chip = session.program_edges(codes,
                                 jnp.zeros((graph.n_nodes,), jnp.int32))
    state = session.init_state(jax.random.PRNGKey(2))
    # energy trace: the record loop, one Session call per segment
    m, ns = state.m, state.noise_state
    trace = []
    for seg in segs:
        m, ns, _ = session.sample(chip, m, ns, seg)
        trace.append(float(sparse_energy(chip, m).mean()) / graph.n_nodes)
    e = np.asarray(sparse_energy(chip, m))
    # throughput: median of fresh whole-schedule calls (chaining
    # un-consumed sharded outputs across timed calls stalls the
    # forced-host runtime and would swamp the policy signal)
    out = session.sample(chip, state.m, state.noise_state, betas)
    jax.block_until_ready(out[0])  # warm-up: compile + first run
    ts = []
    for _ in range(3):
        t0 = time.time()
        out = session.sample(chip, state.m, state.noise_state, betas)
        jax.block_until_ready(out[0])
        ts.append(time.time() - t0)
    dt = sorted(ts)[1]
    return session, m, n_sweeps / dt, np.asarray(trace), e, dt


session, m, sps, trace, e, dt = run_policy(SYNCS[args.sync])
print(f"energy/spin after anneal: best {e.min() / graph.n_nodes:+.3f}, "
      f"mean {e.mean() / graph.n_nodes:+.3f} over {chains} chains")
print(f"{n_sweeps * chains * graph.n_nodes / dt / 1e6:.1f}M spin-updates/s "
      f"({sps:.1f} sweeps/s, {dt:.2f}s for {n_sweeps} sweeps)")

if args.sync != "barrier":
    _, _, sps_base, trace_base, e_base, _ = run_policy(SYNCS["barrier"])
    gap = np.abs(trace - trace_base)
    print(f"vs barrier baseline: {sps_base:.1f} sweeps/s "
          f"({sps / sps_base:.2f}x), energy-trace gap "
          f"mean {gap.mean():.4f} / max {gap.max():.4f} per spin "
          f"(baseline best {e_base.min() / graph.n_nodes:+.3f})")

plan = session.partition_plan
if plan is not None:
    sync = SYNCS[args.sync]
    halo = halo_bytes_per_sweep(plan, chains, sync=sync)
    # local HBM traffic/sweep/device: slot weights + spins once per sweep
    hbm = (2 * 6 * graph.n_nodes * 4 + 2 * chains * graph.n_nodes * 4) \
        // max(n_dev, 1)
    napkin = halo_vs_hbm_seconds(halo // max(n_dev - 1, 1), hbm,
                                 exchanges=sync.exchanges_per_sweep())
    print(f"halo traffic under sync={args.sync}: {halo:.0f} B/sweep total "
          f"({plan.n_boundary} boundary spins, "
          f"{sync.exchanges_per_sweep():.2f} exchanges/sweep); "
          f"TPUv5e napkin: ICI/HBM time ratio "
          f"{napkin['ici_over_hbm']:.3f} per device, "
          f"{napkin['ici_latency_share']:.0%} of ICI time is per-exchange "
          f"latency (the cost a larger halo_every amortizes)")
