"""Pallas kernel microbenchmark: sweep-resident fused engine vs unfused,
dense (N, N) matmul vs Chimera-native block-sparse (degree-6 slot gather).

Times the real kernels (CPU interpret mode — the TPU story is projected
from the HBM traffic + roofline model) and writes the perf trajectory to
``BENCH_kernel.json`` at the repo root so regressions across PRs are
visible in review diffs.

Reported per configuration:
  * measured CPU-interpret wall time, sweeps/sec and flips/ns for the jnp
    reference, the per-half-sweep Pallas kernel, and the fused engine at
    S=1 and S=S_RESIDENT sweeps per launch;
  * the modeled HBM bytes/sweep for each path and the fused-vs-half-sweep
    traffic reduction (the kernel's reason to exist);
  * projected TPU v5e sweeps/sec from the max(HBM-bound, MXU-bound) time;
  * dense-vs-sparse configs (N = 440, 2048, 8192): modeled FLOPs, weight
    bytes, VMEM residency feasibility, measured sparse-kernel flips/ns.
    The ≥8k-spin rows run *only* on the sparse path — the dense W no
    longer fits a 16 MB VMEM core, the sparse slot layout always does.
  * `sharded_sweep` (N = 440, 2048, 8192): the mesh-sharded scan path on
    1 vs 2 forced host devices, with the exact modeled halo bytes per
    sweep from the partition plan and the TPU ICI-vs-HBM napkin ratio
    (docs/sharding.md).  Never run concurrently with the test suite on
    a small box — timings distort.
  * `sync_policies` (N = 440, 2048; k in {1, 4, inf}): the first-class
    `api.Sync` policies on a forced 2-device host — measured us/sweep
    for the per-sweep-launch baseline (one 1-sweep Session call per
    sweep, the serving/record loop's shape), the same barrier policy as
    one resident S-sweep call, and the relaxed k=4 / launch-resident
    policies — plus each policy's modeled halo bytes per sweep
    (docs/sharding.md §Sync policies).

Usage: python benchmarks/bench_kernel.py [--quick]
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_json, timed, timer
from repro.core.chimera import make_chimera, make_chip_graph
from repro.kernels.pbit_update import pbit_half_sweep_pallas
from repro.kernels.ref import pbit_half_sweep_ref
from repro.kernels.sweep_fused import sweep_fused_pallas, sweep_sparse_pallas
from repro.launch.mesh import HBM_BW
from repro.launch.mesh import PEAK_FLOPS_BF16 as PEAK_FLOPS

S_RESIDENT = 16
VMEM_BYTES = 16 * 2 ** 20       # per-core VMEM the resident engine fits in
SPARSE_DEGREE = 6               # Chimera: 4 in-cell K4,4 + 2 chain couplers


def traffic_model(B: int, N: int, S: int) -> dict:
    """Modeled HBM bytes per full sweep for each execution path."""
    w = N * N * 4
    a = B * N * 4
    # jnp reference: matmul (W + m in + I out) then a ~5-op elementwise
    # chain re-reading/writing activations, twice per sweep (two colors),
    # plus host noise generation (write + read u)
    ref = 2 * (w + 2 * a + 5 * 2 * a) + 2 * 2 * a
    # per-half-sweep Pallas kernel: fused elementwise, but spins + noise
    # still cross HBM every half-sweep (m in, u in, m out) and noise is
    # generated outside the kernel (u write)
    half = 2 * (w + 3 * a) + 2 * a
    # fused S-sweep resident engine: W + spins in/out once per S sweeps;
    # noise never leaves the kernel; betas are S*B*4 per launch
    fused = (w + 2 * a) / S + B * 4
    return {
        "hbm_bytes_per_sweep_ref": ref,
        "hbm_bytes_per_sweep_halfsweep": half,
        "hbm_bytes_per_sweep_fused": fused,
        "traffic_reduction_vs_halfsweep": half / fused,
        "traffic_reduction_vs_ref": ref / fused,
    }


def projected_tpu_sweeps_per_sec(B: int, N: int, bytes_per_sweep: float
                                 ) -> float:
    flops_per_sweep = 2 * 2 * B * N * N  # two half-sweep matmuls
    t = max(bytes_per_sweep / HBM_BW, flops_per_sweep / PEAK_FLOPS)
    return 1.0 / t


def bench_config(B: int, N: int, iters: int = 3) -> dict:
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.integers(0, 2, (B, N)) * 2 - 1, jnp.float32)
    W = jnp.asarray(rng.normal(size=(N, N)) * 0.05, jnp.float32)
    h, g, o, rg, co = (jnp.asarray(rng.normal(size=N), jnp.float32)
                       for _ in range(5))
    g = 1.0 + 0.05 * g
    color = rng.integers(0, 2, N)
    mask0, mask1 = jnp.asarray(color == 0), jnp.asarray(color == 1)
    u = jnp.asarray(rng.uniform(-1, 1, (B, N)), jnp.float32)
    seedctr = jnp.asarray([1234, 0], jnp.uint32)

    out = {"B": B, "N": N, "S_resident": S_RESIDENT}
    out.update(traffic_model(B, N, S_RESIDENT))

    # -- jnp reference half-sweep (x2 per sweep)
    ref = jax.jit(lambda *a: pbit_half_sweep_ref(*a))
    t_ref = timer(ref, m, W, h, g, o, rg, co, mask0, 0.7, u, iters=iters)
    out["cpu_ref_half_us"] = t_ref * 1e6
    out["cpu_ref_sweeps_per_sec"] = 1.0 / (2 * t_ref)

    # -- per-half-sweep Pallas kernel (interpret mode on CPU)
    t_half = timer(
        lambda: pbit_half_sweep_pallas(m, W, h, g, o, rg, co, mask0, 0.7, u,
                                       interpret=True), iters=iters)
    out["cpu_halfsweep_kernel_us"] = t_half * 1e6
    out["cpu_halfsweep_sweeps_per_sec"] = 1.0 / (2 * t_half)

    # -- fused engine, 1 sweep and S_RESIDENT sweeps per launch
    for S in (1, S_RESIDENT):
        betas = jnp.full((S, B), 0.7, jnp.float32)
        t = timer(
            lambda b=betas: sweep_fused_pallas(
                m, W, h, g, o, rg, co, mask0, mask1, b, seedctr,
                noise_mode="counter", interpret=True)[0],
            iters=iters)
        key = "fused_s1" if S == 1 else f"fused_s{S}"
        sweeps_per_sec = S / t
        out[f"cpu_{key}_us_per_launch"] = t * 1e6
        out[f"cpu_{key}_sweeps_per_sec"] = sweeps_per_sec
        out[f"cpu_{key}_flips_per_ns"] = sweeps_per_sec * B * N * 1e-9

    _add_tpu_projection(B, N, out)
    return out


def _add_tpu_projection(B: int, N: int, out: dict) -> None:
    for key in ("halfsweep", "fused"):
        sps = projected_tpu_sweeps_per_sec(
            B, N, out[f"hbm_bytes_per_sweep_{key}"])
        out[f"tpu_projected_{key}_sweeps_per_sec"] = sps
        out[f"tpu_projected_{key}_flips_per_ns"] = sps * B * N * 1e-9


# ---------------------------------------------------------------------------
# api.Session dispatch: compile-once vs the legacy per-call path
# ---------------------------------------------------------------------------
def bench_session_dispatch(N: int = 440, B: int = 64, S: int = 8,
                           iters: int = 5) -> dict:
    """Measure what the unified API buys at the dispatch layer.

    The legacy path calls `pbit.gibbs_sample` as a plain Python function:
    every call re-resolves the backend (env read), rebuilds the sweep
    closure, and re-traces the scan before XLA's executable cache kicks
    in.  An `api.Session` jits the closure once at compile; steady-state
    calls replay the cached executable.  Both run the identical engine
    ("ref" backend, counter noise), so the delta is pure
    dispatch/trace overhead — the tax the CD loop, the tempering swap
    loop, and the serving path used to pay per call.
    """
    import jax.numpy as jnp

    from repro import api
    from repro.core import pbit
    from repro.core.cd import PBitMachine
    from repro.core.hardware import HardwareConfig

    g = _chimera_for(N)
    machine = PBitMachine.create(g, jax.random.PRNGKey(0),
                                 HardwareConfig(), noise="counter",
                                 backend="ref", w_scale=0.05)
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(-40, 40, g.n_edges), jnp.int32)
    h = jnp.zeros((g.n_nodes,), jnp.int32)
    session = machine.session(
        schedule=api.Constant(beta=0.7, n_sweeps=S), chains=B)
    chip = session.program_edges(codes, h)
    m0 = session.random_spins(jax.random.PRNGKey(1))
    ns = session.noise_state(jax.random.PRNGKey(2))
    state, step = machine.noise_fn(jax.random.PRNGKey(2), B)
    betas = jnp.full((S,), 0.7, jnp.float32)
    color = jnp.asarray(g.color)

    t_legacy = timer(
        lambda: pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                  backend="ref")[0], iters=iters)
    t_session = timer(lambda: session.sample(chip, m0, ns)[0], iters=iters)
    return {
        "N": N, "B": B, "S": S, "backend": "ref",
        "legacy_us_per_call": t_legacy * 1e6,
        "session_us_per_call": t_session * 1e6,
        "dispatch_overhead_us": (t_legacy - t_session) * 1e6,
        "speedup_per_call": t_legacy / t_session,
    }


# ---------------------------------------------------------------------------
# mesh-sharded sweep: 1 vs 2 host devices, measured + modeled halo bytes
# ---------------------------------------------------------------------------
_SHARDED_WORKER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json, time
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.chimera import make_chimera, make_chip_graph
    from repro.core.hardware import HardwareConfig

    rows = []
    for N, B, S in {configs}:
        g = make_chip_graph() if N == 440 else \\
            make_chimera(int(round((N / 8) ** 0.5)),
                         int(round((N / 8) ** 0.5)))
        mesh = jax.make_mesh((2,), ("data",))
        mach = PBitMachine.create(g, jax.random.PRNGKey(0),
                                  HardwareConfig.ideal(), sparse=True,
                                  noise="counter", mesh=mesh,
                                  partition=api.Partition(rows="data"))
        ses = mach.session(schedule=api.Constant(0.7, n_sweeps=S),
                           chains=B)
        rng = np.random.default_rng(N)
        chip = ses.program_edges(
            jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32),
            jnp.zeros((g.n_nodes,), jnp.int32))
        st = ses.init_state(jax.random.PRNGKey(1))
        m, ns, _ = ses.sample(chip, st.m, st.noise_state)
        jax.block_until_ready(m)              # compile + warm
        t0 = time.perf_counter()
        m, ns, _ = ses.sample(chip, m, ns)
        jax.block_until_ready(m)
        rows.append({{"N": N, "us_per_sweep":
                     (time.perf_counter() - t0) / S * 1e6}})
    print(json.dumps(rows))
""")


def _sharded_single_device_us(N: int, B: int, S: int) -> float:
    """Baseline: the same sparse scan path, one device, in-process."""
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.hardware import HardwareConfig

    g = _chimera_for(N)
    mach = PBitMachine.create(g, jax.random.PRNGKey(0),
                              HardwareConfig.ideal(), sparse=True,
                              noise="counter")
    ses = mach.session(schedule=api.Constant(0.7, n_sweeps=S), chains=B)
    rng = np.random.default_rng(N)
    chip = ses.program_edges(
        jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32),
        jnp.zeros((g.n_nodes,), jnp.int32))
    st = ses.init_state(jax.random.PRNGKey(1))
    _, (m, ns, _) = timed(ses.sample, chip, st.m, st.noise_state)
    t, _ = timed(ses.sample, chip, m, ns)
    return t / S * 1e6


def bench_sharded_sweep(quick: bool = False) -> dict:
    """The `sharded_sweep` section: per N, the modeled partition/halo
    numbers (exact, from the compile-time plan) plus measured sweep times
    on 1 and 2 forced host devices (2-dev in a subprocess — the device
    count is locked at first jax init).  On this 2-core CPU box the
    sharded time mostly measures shard_map overhead; the modeled halo
    bytes and the ICI/HBM ratio are the TPU-relevant outputs."""
    from repro.core.distributed import halo_bytes_per_sweep, \
        plan_row_partition
    from repro.launch.mesh import halo_vs_hbm_seconds

    shapes = {440: (64, 8), 2048: (16, 4), 8192: (8, 2)}
    if quick:
        shapes = {440: (16, 4), 2048: (8, 2), 8192: (4, 1)}
    rows = []
    for N, (B, S) in shapes.items():
        g = _chimera_for(N)
        plan = plan_row_partition(g, 2)
        halo = halo_bytes_per_sweep(plan, B)
        # per-device HBM stream per sweep: slot weights + spins, 2x/sweep
        hbm = (2 * 2 * SPARSE_DEGREE * N * 4 + 2 * B * N * 4) // 2
        row = {
            "N": N, "B": B, "S": S, "n_devices": 2,
            "n_boundary_spins": plan.n_boundary,
            "halo_bytes_per_sweep": halo,
            "halo_bytes_per_sweep_stats": halo_bytes_per_sweep(
                plan, B, refresh_for_moments=True),
            "dense_w_replication_bytes": 4 * N * N,
            **{f"tpu_{k}": v for k, v in halo_vs_hbm_seconds(
                halo // 2, hbm, exchanges=2.0).items()},
        }
        measure = not quick or N == 440
        if measure:
            row["cpu_1dev_us_per_sweep"] = _sharded_single_device_us(N, B, S)
        rows.append(row)

    measured = [(N, *shapes[N]) for N in shapes
                if not quick or N == 440]
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_WORKER.format(configs=measured)],
        capture_output=True, text=True, timeout=1200,
        cwd=Path(__file__).resolve().parent.parent,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    two_dev = {r["N"]: r["us_per_sweep"]
               for r in json.loads(out.stdout.strip().splitlines()[-1])}
    for row in rows:
        if row["N"] in two_dev:
            row["cpu_2dev_us_per_sweep"] = two_dev[row["N"]]
    return {"note": "sharded sparse scan path, rows partition over a "
                    "forced 2-device host mesh (docs/sharding.md)",
            "configs": rows}


# ---------------------------------------------------------------------------
# sync policies: barrier vs relaxed halo exchange on 2 forced host devices
# ---------------------------------------------------------------------------
_SYNC_WORKER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json, math, time
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.chimera import make_chimera, make_chip_graph
    from repro.core.hardware import HardwareConfig

    POLICIES = {{
        "1": api.Sync(),
        "4": api.Sync(halo_every=4, sweeps_per_launch=4),
        "inf": api.Sync(halo_every=math.inf, sweeps_per_launch=8),
    }}

    def time_calls(fn, m, ns, reps=5):
        jax.block_until_ready(fn(m, ns))         # compile + warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(m, ns))
            ts.append(time.perf_counter() - t0)
        # median of fresh-input calls: chaining un-consumed sharded
        # outputs back as inputs stalls the forced-host runtime for
        # ~100 ms/call and would swamp the policy signal
        return sorted(ts)[len(ts) // 2]

    rows = []
    for N, B, S in {configs}:
        g = make_chip_graph() if N == 440 else \\
            make_chimera(int(round((N / 8) ** 0.5)),
                         int(round((N / 8) ** 0.5)))
        mesh = jax.make_mesh((2,), ("data",))
        rng = np.random.default_rng(N)
        codes = jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32)
        h0 = jnp.zeros((g.n_nodes,), jnp.int32)
        for kname, sync in POLICIES.items():
            mach = PBitMachine.create(g, jax.random.PRNGKey(0),
                                      HardwareConfig.ideal(), sparse=True,
                                      noise="counter", mesh=mesh,
                                      partition=api.Partition(rows="data"),
                                      sync=sync)
            ses = mach.session(chains=B)
            chip = ses.program_edges(codes, h0)
            st = ses.init_state(jax.random.PRNGKey(1))
            betas = jnp.full((S,), 0.7, jnp.float32)
            t_call = time_calls(
                lambda m, ns: ses.sample(chip, m, ns, betas)[0],
                st.m, st.noise_state)
            row = {{"N": N, "halo_every": kname,
                    "sweeps_per_launch": sync.sweeps_per_launch,
                    "mode": sync.mode,
                    "cpu_us_per_sweep": t_call / S * 1e6}}
            if kname == "1":
                # the per-sweep-launch baseline: one 1-sweep Session call
                # per sweep, blocking on each result — the dispatch shape
                # of a serving / record loop that consumes every sweep,
                # which is exactly what the sweep-resident policies
                # amortize away
                beta1 = jnp.full((1,), 0.7, jnp.float32)

                def per_sweep(m, ns):
                    for _ in range(S):
                        m, ns, _ = ses.sample(chip, m, ns, beta1)
                        jax.block_until_ready(m)
                    return m
                t_ps = time_calls(per_sweep, st.m, st.noise_state)
                row["cpu_us_per_sweep_launch_baseline"] = t_ps / S * 1e6
            rows.append(row)
    print(json.dumps(rows))
""")


def bench_sync_policies(quick: bool = False) -> dict:
    """The `sync_policies` section: for N = 440 / 2048 and halo_every
    k in {1, 4, inf}, the modeled halo bytes per sweep under each policy
    and the measured 2-forced-host-device sweep times — the per-sweep-
    launch barrier baseline vs resident multi-sweep calls (the k=1
    resident call isolates dispatch amortization; the relaxed rows add
    the exchange savings).  Quick mode measures N=440 only."""
    import math as _math

    from repro import api
    from repro.core.distributed import halo_bytes_per_sweep, \
        plan_row_partition

    policies = {
        "1": api.Sync(),
        "4": api.Sync(halo_every=4, sweeps_per_launch=4),
        "inf": api.Sync(halo_every=_math.inf, sweeps_per_launch=8),
    }
    shapes = {440: (64, 16), 2048: (16, 16)}
    if quick:
        shapes = {440: (16, 8), 2048: (8, 8)}
    rows = []
    for N, (B, S) in shapes.items():
        g = _chimera_for(N)
        plan = plan_row_partition(g, 2)
        for kname, sync in policies.items():
            rows.append({
                "N": N, "B": B, "S": S, "n_devices": 2,
                "halo_every": kname,
                "sweeps_per_launch": sync.sweeps_per_launch,
                "mode": sync.mode,
                "exchanges_per_sweep": sync.exchanges_per_sweep(),
                "halo_bytes_per_sweep": halo_bytes_per_sweep(
                    plan, B, sync=sync),
            })

    measured = [(N, *shapes[N]) for N in shapes if not quick or N == 440]
    out = subprocess.run(
        [sys.executable, "-c", _SYNC_WORKER.format(configs=measured)],
        capture_output=True, text=True, timeout=1200,
        cwd=Path(__file__).resolve().parent.parent,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    timed = json.loads(out.stdout.strip().splitlines()[-1])
    by_key = {(r["N"], r["halo_every"]): r for r in timed}
    for row in rows:
        t = by_key.get((row["N"], row["halo_every"]))
        if t is not None:
            row["cpu_us_per_sweep"] = t["cpu_us_per_sweep"]
            if "cpu_us_per_sweep_launch_baseline" in t:
                row["cpu_us_per_sweep_launch_baseline"] = \
                    t["cpu_us_per_sweep_launch_baseline"]
    return {"note": "api.Sync policies on a forced 2-device host: "
                    "per-sweep-launch barrier baseline vs resident "
                    "multi-sweep calls (docs/sharding.md §Sync policies)",
            "configs": rows}


# ---------------------------------------------------------------------------
# Kernel-resident halo exchange vs host-exchange dispatch
# ---------------------------------------------------------------------------
_HALO_WORKER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json, time
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.chimera import make_chimera, make_chip_graph
    from repro.core.hardware import HardwareConfig

    def time_calls(fn, reps=5):
        jax.block_until_ready(fn())              # compile + warm
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    rows = []
    for N, B, S in {configs}:
        g = make_chip_graph() if N == 440 else \\
            make_chimera(int(round((N / 8) ** 0.5)),
                         int(round((N / 8) ** 0.5)))
        mesh = jax.make_mesh((2,), ("data",))
        mach = PBitMachine.create(g, jax.random.PRNGKey(0),
                                  HardwareConfig.ideal(), sparse=True,
                                  noise="counter")
        rng = np.random.default_rng(N)
        codes = jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32)
        h0 = jnp.zeros((g.n_nodes,), jnp.int32)
        ses0 = api.Session(mach.sampler_spec(chains=B))
        chip = ses0.program_edges(codes, h0)
        m0 = ses0.random_spins(jax.random.PRNGKey(1))
        ns = ses0.noise_state(jax.random.PRNGKey(2))
        betas = jnp.full((S,), 0.7, jnp.float32)

        def session(sync, backend):
            sp = mach.sampler_spec(
                chains=B, mesh=mesh, sync=sync,
                partition=api.Partition(rows="data"))
            return api.Session(sp.replace(backend=backend))

        for k in (1, 4):
            sync = api.Sync(halo_every=k, sweeps_per_launch=S)
            fz = session(sync, "fused_sparse")
            t_res = time_calls(
                lambda: fz.sample(chip, m0, ns, betas)[0])
            sc = session(sync, "sparse")
            t_scan = time_calls(
                lambda: sc.sample(chip, m0, ns, betas)[0])
            row = {{"N": N, "halo_every": k, "sweeps_per_launch": S,
                    "cpu_us_per_sweep_resident": t_res / S * 1e6,
                    "cpu_us_per_sweep_segment_scan": t_scan / S * 1e6}}
            if k == 1:
                # the host-exchange baseline the kernel-resident path
                # replaces: every exchange point ends the launch, so a
                # k=1 policy dispatches one 1-sweep launch per sweep and
                # pays the host round-trip on each boundary refresh
                ps = session(api.Sync(halo_every=1, sweeps_per_launch=1),
                             "sparse")
                beta1 = jnp.full((1,), 0.7, jnp.float32)

                def per_sweep():
                    m, n2 = m0, ns
                    for _ in range(S):
                        m, n2, _ = ps.sample(chip, m, n2, beta1)
                        jax.block_until_ready(m)
                    return m
                t_ps = time_calls(per_sweep)
                row["cpu_us_per_sweep_host_exchange_baseline"] = \\
                    t_ps / S * 1e6
                row["speedup_vs_host_exchange"] = t_ps / t_res
            rows.append(row)
    print(json.dumps(rows))
""")


def bench_halo_fused(quick: bool = False) -> dict:
    """The `halo_fused` section: kernel-resident halo exchange
    (docs/kernels.md §In-kernel halo exchange) vs the host-exchange
    paths, on a forced 2-device host.

    For N = 440 / 2048 and halo_every k in {1, 4}: the fused
    kernel-owned-exchange launch (one dispatch per S-sweep launch, the
    exchange points refreshed inside the jitted graph) against (a) at
    k=1 the host-exchange baseline — one 1-sweep launch per sweep,
    blocking on each, which is what a frequent-refresh policy was forced
    into before the kernel could own the exchange — and (b) the sparse
    segment-scan engine under the identical policy (single dispatch,
    host ppermute between segments).  The modeled halo bytes are
    identical for the kernel-resident and host paths — the policy fixes
    the transfer schedule; only who issues it changes."""
    from repro import api
    from repro.core.distributed import halo_bytes_per_sweep, \
        plan_row_partition

    shapes = {440: (16, 8), 2048: (8, 8)}
    if quick:
        shapes = {440: (8, 4)}
    rows = []
    for N, (B, S) in shapes.items():
        g = _chimera_for(N)
        plan = plan_row_partition(g, 2)
        for k in (1, 4):
            sync = api.Sync(halo_every=k, sweeps_per_launch=S)
            rows.append({
                "N": N, "B": B, "S": S, "n_devices": 2,
                "halo_every": k,
                "sweeps_per_launch": S,
                "exchanges_per_sweep": sync.exchanges_per_sweep(),
                # identical for kernel-resident and host exchange: the
                # Sync policy fixes the bytes, the kernel only moves
                # where the transfer is issued from
                "halo_bytes_per_sweep": halo_bytes_per_sweep(
                    plan, B, sync=sync),
            })

    measured = [(N, *shapes[N]) for N in shapes if not quick or N == 440]
    out = subprocess.run(
        [sys.executable, "-c", _HALO_WORKER.format(configs=measured)],
        capture_output=True, text=True, timeout=2400,
        cwd=Path(__file__).resolve().parent.parent,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    timed = json.loads(out.stdout.strip().splitlines()[-1])
    by_key = {(r["N"], r["halo_every"]): r for r in timed}
    for row in rows:
        t = by_key.get((row["N"], row["halo_every"]))
        if t is not None:
            for key in ("cpu_us_per_sweep_resident",
                        "cpu_us_per_sweep_segment_scan",
                        "cpu_us_per_sweep_host_exchange_baseline",
                        "speedup_vs_host_exchange"):
                if key in t:
                    row[key] = t[key]
    return {"note": "kernel-resident halo exchange vs host-exchange "
                    "dispatch on a forced 2-device host (docs/kernels.md "
                    "§In-kernel halo exchange); halo bytes are modeled "
                    "and identical for both paths",
            "configs": rows}


def _emit_halo(hf: dict) -> None:
    k1 = [r for r in hf["configs"]
          if r["N"] == 440 and r["halo_every"] == 1]
    if k1 and "speedup_vs_host_exchange" in k1[0]:
        r = k1[0]
        emit("kernel_halo_fused_speedup_N440_k1",
             r["speedup_vs_host_exchange"],
             f"resident={r['cpu_us_per_sweep_resident']:.0f}us/sweep, "
             f"host_exchange="
             f"{r['cpu_us_per_sweep_host_exchange_baseline']:.0f}us, "
             f"halo_bytes={r['halo_bytes_per_sweep']:.0f}")


# ---------------------------------------------------------------------------
# PSL compiler: embedding overhead + end-to-end correct-answer rate
# ---------------------------------------------------------------------------
def bench_psl_embed(quick: bool = False) -> dict:
    """The `psl_embed` section: the PSL compiler's (docs/psl.md)
    chain-embedding overhead and the end-to-end correct-answer rate of
    forward inference through an unmodified `api.Session`.

    Chain length is the scaling knob to watch: the clique-ladder
    embedder grows chains linearly with circuit size, and Gibbs mixing
    through a chain requires a coordinated all-member flip.  Measured:
    4-spin chains (adder2) and 8-spin chains (adder4) infer perfectly;
    14-spin chains (mult3) stop mixing — ~0% clause-valid samples at
    every schedule tried — so the mult3 row is *expected* to score ~0
    and is tracked here as the target for the connectivity-aware
    embedder (ROADMAP).
    """
    import time

    from repro import psl

    def adder_readout(n):
        def check(r, a, b):
            return r.infer("sum") + (r.infer("cout") << n) == a + b
        return check

    def mult_readout(n):
        def check(r, a, b):
            return r.infer("prod") == a * b
        return check

    cases = [
        ("adder2", psl.ripple_adder_circuit(2), adder_readout(2), 2,
         make_chimera(2, 2), {}),
        ("adder4", psl.ripple_adder_circuit(4), adder_readout(4), 4,
         make_chimera(4, 4), {}),
        ("mult3", psl.multiplier_circuit(3), mult_readout(3), 3,
         make_chip_graph(), {"n_sweeps": 600}),
    ]
    n_rows = 4 if quick else 8
    if quick:
        cases = cases[:1]

    rng = np.random.default_rng(0)
    rows = []
    for name, circuit, check, n_bits, g, kw in cases:
        if quick:
            kw = {**kw, "chains": 32, "n_sweeps": 200}
        t0 = time.perf_counter()
        cc = psl.compile_circuit(circuit, g, **kw)
        compile_ms = (time.perf_counter() - t0) * 1e3
        logical = cc.logical
        pairs = sorted({(int(a), int(b)) for a, b in
                        rng.integers(0, 1 << n_bits, (4 * n_rows, 2))}
                       )[:n_rows]
        key = jax.random.PRNGKey(0)
        correct, broken, valid, times = 0, [], [], []
        for a, b in pairs:
            key, sub = jax.random.split(key)
            t0 = time.perf_counter()
            r = cc.run_forward(sub, {"a": a, "b": b})
            times.append(time.perf_counter() - t0)
            correct += bool(check(r, a, b))
            s = r.summary()
            broken.append(s["broken_chain_fraction"])
            valid.append(s["clause_valid_fraction"])
        rows.append({
            "circuit": name,
            "n_logical_edges": logical.n_edges,
            **cc.embedding.stats(),
            "chains": cc.spec.chains,
            "n_sweeps": cc.spec.schedule.n_sweeps,
            "compile_ms": compile_ms,
            "rows_tested": len(pairs),
            "rows_correct": correct,
            "correct_rate": correct / len(pairs),
            "broken_chain_fraction": float(np.mean(broken)),
            "clause_valid_fraction": float(np.mean(valid)),
            # first call includes jit compile; steady state is the rest
            "sample_s_first": times[0],
            "sample_s_steady": float(np.mean(times[1:])) if times[1:]
            else times[0],
        })
    return {"note": "PSL compiler forward inference (docs/psl.md): "
                    "clique-ladder embedding stats + correct-answer "
                    "rate; mult3's 14-spin chains are the known mixing "
                    "cliff the ROADMAP embedder item targets",
            "configs": rows}


# ---------------------------------------------------------------------------
# dense vs Chimera-native block-sparse
# ---------------------------------------------------------------------------
def dense_vs_sparse_model(B: int, N: int, S: int,
                          D: int = SPARSE_DEGREE) -> dict:
    """Modeled FLOPs / bytes for the two weight layouts of the resident
    engine, plus VMEM-residency feasibility."""
    a = B * N * 4
    dense_w = N * N * 4                    # fp32 couplings
    sparse_w = 2 * D * N * 4               # fp32 slot weights + int32 table
    flops_dense = 2 * 2 * B * N * N        # two half-sweep matmuls
    flops_sparse = 2 * 2 * B * N * D       # two half-sweeps of D-slot FMAs
    # the resident engine needs W + one (block_b, N) spin tile (+ scratch
    # of the same order) simultaneously live in VMEM
    tile = 128 * N * 4
    return {
        "dense_weight_bytes": dense_w,
        "sparse_weight_bytes": sparse_w,
        "weight_bytes_reduction": dense_w / sparse_w,
        "flops_per_sweep_dense": flops_dense,
        "flops_per_sweep_sparse": flops_sparse,
        "flop_reduction": flops_dense / flops_sparse,
        "hbm_bytes_per_sweep_fused_dense": (dense_w + 2 * a) / S + B * 4,
        "hbm_bytes_per_sweep_fused_sparse": (sparse_w + 2 * a) / S + B * 4,
        "dense_vmem_resident_feasible": dense_w + 2 * tile <= VMEM_BYTES,
        "sparse_vmem_resident_feasible": sparse_w + 2 * tile <= VMEM_BYTES,
    }


def _chimera_for(N: int):
    if N == 440:
        return make_chip_graph()
    side = int(round((N / 8) ** 0.5))
    g = make_chimera(side, side)
    assert g.n_nodes == N, (g.n_nodes, N)
    return g


def bench_sparse_config(N: int, B: int, S: int, iters: int = 1,
                        measure: bool = True) -> dict:
    """Dense-vs-sparse comparison row; measures the sparse kernel (CPU
    interpret) on a real Chimera instance of N spins.  The dense resident
    engine is measured only where its W still fits VMEM."""
    out = {"B": B, "N": N, "S": S, "D": SPARSE_DEGREE, "layout": "chimera"}
    out.update(dense_vs_sparse_model(B, N, S))
    sps_flops = out["flops_per_sweep_sparse"]
    out["tpu_projected_sparse_sweeps_per_sec"] = 1.0 / max(
        out["hbm_bytes_per_sweep_fused_sparse"] / HBM_BW,
        sps_flops / PEAK_FLOPS)
    out["tpu_projected_sparse_flips_per_ns"] = (
        out["tpu_projected_sparse_sweeps_per_sec"] * B * N * 1e-9)
    if not measure:
        return out

    g = _chimera_for(N)
    nbr_idx, nbr_mask = g.neighbor_table()
    rng = np.random.default_rng(N)
    nbr_w = jnp.asarray(
        np.where(nbr_mask, rng.normal(size=nbr_idx.shape) * 0.05, 0.0),
        jnp.float32)
    idx = jnp.asarray(nbr_idx)
    m = jnp.asarray(rng.integers(0, 2, (B, N)) * 2 - 1, jnp.float32)
    h, gn, o, rg, co = (jnp.asarray(rng.normal(size=N) * 0.1, jnp.float32)
                        for _ in range(5))
    mask0 = jnp.asarray(g.color == 0)
    mask1 = jnp.asarray(g.color == 1)
    betas = jnp.full((S, B), 0.7, jnp.float32)
    seedctr = jnp.asarray([1234, 0], jnp.uint32)
    block_b = min(128, B)

    t = timer(
        lambda: sweep_sparse_pallas(
            m, idx, nbr_w, h, gn, o, rg, co, mask0, mask1, betas, seedctr,
            noise_mode="counter", block_b=block_b, interpret=True)[0],
        iters=iters)
    out["cpu_sparse_us_per_launch"] = t * 1e6
    out["cpu_sparse_sweeps_per_sec"] = S / t
    out["cpu_sparse_flips_per_ns"] = (S / t) * B * N * 1e-9
    return out


def _write_root_merge(results: dict) -> None:
    """Merge-preserve our sections into the tracked repo-root JSON:
    other benches own sections of this file (e.g. bench_variability's
    fault_yield) — only replace our own keys."""
    root = Path(__file__).resolve().parent.parent / "BENCH_kernel.json"
    merged = json.loads(root.read_text()) if root.exists() else {}
    merged.update(results)
    root.write_text(json.dumps(merged, indent=1))


def run(quick: bool = False, psl_only: bool = False,
        halo_only: bool = False) -> dict:
    if halo_only:
        # regenerate just the kernel-resident halo-exchange section
        # (cheap next to the full kernel sweeps) and merge it into the
        # tracked root JSON
        results = {"halo_fused": bench_halo_fused(quick)}
        _emit_halo(results["halo_fused"])
        save_json("halo_fused", results["halo_fused"])
        if not quick:
            _write_root_merge(results)
        return results

    if psl_only:
        # regenerate just the PSL section (it is far cheaper than the
        # kernel sweeps) and merge it into the tracked root JSON
        results = {"psl_embed": bench_psl_embed(quick)}
        for row in results["psl_embed"]["configs"]:
            emit(f"psl_{row['circuit']}_correct_rate", row["correct_rate"],
                 f"chain_len={row['chain_length']}, "
                 f"valid={row['clause_valid_fraction']:.2%}")
        if not quick:
            _write_root_merge(results)
        return results

    # chip scale is always measured; the paper-chip N=440 rounds to 512
    # lanes in-kernel.  The production-scale config is traffic-model only
    # in quick mode (interpret-mode matmuls at N=2048 take minutes).
    results = {"configs": []}
    results["configs"].append(bench_config(64 if quick else 256, 440,
                                           iters=1 if quick else 3))
    big = {"B": 256, "N": 2048, "S_resident": S_RESIDENT}
    big.update(traffic_model(256, 2048, S_RESIDENT))
    big["traffic_reduction_s1_vs_halfsweep"] = (
        traffic_model(256, 2048, 1)["traffic_reduction_vs_halfsweep"])
    _add_tpu_projection(256, 2048, big)
    results["configs"].append(big)

    # dense-vs-sparse rows: the chip graph, the largest dense-resident
    # lattice, and a 32x32 Chimera (8192 spins) that only the sparse slot
    # layout can keep VMEM-resident (dense W = 256 MB >> 16 MB)
    results["sparse_configs"] = [
        bench_sparse_config(440, 64 if quick else 256, S_RESIDENT,
                            iters=1 if quick else 3),
        bench_sparse_config(2048, 16 if quick else 64, 4,
                            iters=1, measure=not quick),
        bench_sparse_config(8192, 8, 2, iters=1, measure=not quick),
    ]

    # compile-once Session dispatch vs legacy per-call re-trace at N=440
    results["session_dispatch"] = bench_session_dispatch(
        440, 16 if quick else 64, 8, iters=3 if quick else 5)

    # mesh-sharded sweep: 1 vs 2 forced host devices + halo-bytes model
    results["sharded_sweep"] = bench_sharded_sweep(quick)

    # sync policies: barrier vs relaxed halo exchange, measured + modeled
    results["sync_policies"] = bench_sync_policies(quick)

    # kernel-resident halo exchange vs host-exchange dispatch
    results["halo_fused"] = bench_halo_fused(quick)

    # PSL compiler: embedding overhead + forward correct-answer rate
    results["psl_embed"] = bench_psl_embed(quick)

    chip = results["configs"][0]
    emit("kernel_session_dispatch_N440",
         results["session_dispatch"]["session_us_per_call"],
         f"legacy={results['session_dispatch']['legacy_us_per_call']:.0f}us"
         f" ({results['session_dispatch']['speedup_per_call']:.1f}x)")
    emit("kernel_fused_s16_cpu", chip["cpu_fused_s16_us_per_launch"],
         f"sweeps/s={chip['cpu_fused_s16_sweeps_per_sec']:.1f}")
    emit("kernel_traffic_reduction_B256_N2048",
         big["traffic_reduction_vs_halfsweep"],
         f"s1={big['traffic_reduction_s1_vs_halfsweep']:.2f}x")
    sp2048 = results["sparse_configs"][1]
    emit("kernel_sparse_flop_reduction_N2048", sp2048["flop_reduction"],
         f"weight_bytes={sp2048['weight_bytes_reduction']:.0f}x")
    sp8192 = results["sparse_configs"][2]
    emit("kernel_sparse_N8192_dense_resident",
         float(sp8192["dense_vmem_resident_feasible"]),
         f"sparse_resident={sp8192['sparse_vmem_resident_feasible']}")
    sh440 = results["sharded_sweep"]["configs"][0]
    emit("kernel_sharded_halo_bytes_N440",
         sh440["halo_bytes_per_sweep"],
         f"boundary={sh440['n_boundary_spins']} spins, "
         f"ici/hbm={sh440['tpu_ici_over_hbm']:.3f}")
    sy = {r["halo_every"]: r for r in results["sync_policies"]["configs"]
          if r["N"] == 440}
    emit("kernel_sync_resident_N440", sy["inf"].get("cpu_us_per_sweep", 0),
         f"per_sweep_launch_baseline="
         f"{sy['1'].get('cpu_us_per_sweep_launch_baseline', 0):.0f}us, "
         f"halo_bytes inf/k1={sy['inf']['halo_bytes_per_sweep']:.0f}/"
         f"{sy['1']['halo_bytes_per_sweep']:.0f}")
    _emit_halo(results["halo_fused"])
    for row in results["psl_embed"]["configs"]:
        emit(f"psl_{row['circuit']}_correct_rate", row["correct_rate"],
             f"chain_len={row['chain_length']}, "
             f"valid={row['clause_valid_fraction']:.2%}")

    save_json("kernel_pbit_update", results)
    if not quick:
        # perf trajectory tracked across PRs at the repo root; --quick runs
        # (CI smoke) use incomparable shapes and must not overwrite it
        _write_root_merge(results)
    return results


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / single iteration (CI smoke)")
    ap.add_argument("--psl-only", action="store_true",
                    help="regenerate only the psl_embed section")
    ap.add_argument("--halo-only", action="store_true",
                    help="regenerate only the halo_fused section")
    args = ap.parse_args()
    run(quick=args.quick, psl_only=args.psl_only, halo_only=args.halo_only)
