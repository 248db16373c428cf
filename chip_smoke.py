"""Chip smoke test: the p-bit sampler's main path on a TPU, end to end.

    python chip_smoke.py             # one chip, the paper chip's width
    python chip_smoke.py --chips 4   # the row-sharded lattice on 4 chips

One chip: four phases at N=440 (the 7x8 Chimera with one cell masked,
``configs/registry.py`` "pbit-chip-440"), each through the entry points a
user calls:

  sampling  `api.Session` with counter noise; "fused_sparse" against
            "sparse" and dense "fused" against "ref" under one seed —
            spins, noise state and moments must be bit-equal;
  cd        a few hardware-aware CD epochs (chip mismatch, full-adder
            task) via `Session.make_cd_step`; loss and KL must be finite;
  maxcut    `solve_maxcut` anneal; the cut must beat a random assignment;
  serving   `SamplerService` answers requests in the 2x2 and 7x8
            buckets, every one with status ok.

Four chips: only the row-sharded lattice ("pbit-pod-2m", 2M spins) on a
4-device row mesh under the barrier `Sync()`, compared bit for bit with
the single-device Session; then the fused exchange windows
("fused_sparse" with mid-launch exchanges: kernel windows with a swap
between them) against single device (halo_every=1) and against the
"sparse" segment scan (halo_every=4), on a lattice whose row bands fit
the kernel's VMEM.

Every phase prints one line; its seconds are smoke timings (first call
with compilation, then one warm call), not benchmark numbers.  Any
failed phase or comparison exits non-zero.  The last line of a passing
run is exactly {"ok": true, "device": {...}}.  The script refuses to run
anywhere but a TPU, and never falls back to interpret mode.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
N_CHIP = 440


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timed(fn):
    """(result, seconds) around ``fn()`` ending in block_until_ready."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def report(phase: str, sessions: dict, compared: str, setup_s: float,
           run_s: float) -> None:
    """One line per phase: backends and interpret flag as each Session
    resolved them, the comparison made, smoke timings."""
    ses = ", ".join(f"{k}: backend={s.backend} interpret={s.interpret}"
                    for k, s in sessions.items())
    print(f"[{phase}] {ses} | {compared} | smoke timing: setup "
          f"{setup_s:.3f} s (incl. compile), run {run_s:.3f} s",
          flush=True)


def _equal(a, b) -> bool:
    import numpy as np
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def _random_codes(graph, seed: int):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(-60, 61, graph.n_edges), jnp.int32),
            jnp.asarray(rng.integers(-15, 16, graph.n_nodes), jnp.int32))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def phase_sampling(graph, chains: int = 256, n_sweeps: int = 128) -> None:
    import jax

    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.hardware import HardwareConfig

    mach = PBitMachine.create(graph, jax.random.PRNGKey(SEED),
                              HardwareConfig(), noise="counter")
    spec = mach.sampler_spec(
        schedule=api.Anneal(0.1, 2.0, n_sweeps=n_sweeps), chains=chains)
    for kernel, reference in (("fused_sparse", "sparse"), ("fused", "ref")):
        t0 = time.perf_counter()
        ses = {b: api.Session(spec.replace(backend=b))
               for b in (kernel, reference)}
        chip = ses[reference].program_edges(*_random_codes(graph, SEED))
        m0 = ses[reference].random_spins(jax.random.PRNGKey(1))
        ns = ses[reference].noise_state(jax.random.PRNGKey(2))

        def run(s):
            return (s.sample(chip, m0, ns)[:2]
                    + s.stats(chip, m0, ns, n_sweeps, n_sweeps // 4))

        outs = {b: jax.block_until_ready(run(s)) for b, s in ses.items()}
        setup = time.perf_counter() - t0
        _, warm = timed(lambda: run(ses[kernel]))
        check(_equal(outs[kernel], outs[reference]),
              f"sampling: {kernel} != {reference} (spins, noise state, "
              f"moments)")
        report("sampling", ses,
               f"{kernel} == {reference} bit-exact: spins, noise state, "
               f"mean spin, edge correlations (N={graph.n_nodes}, "
               f"B={chains}, S={n_sweeps})", setup, warm)


def phase_cd(graph, chains: int = 256, epochs: int = 4) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import energy, tasks
    from repro.core.cd import CDConfig, PBitMachine, sample_visible_dist

    mach = PBitMachine.create(graph, jax.random.PRNGKey(SEED),
                              noise="counter")
    task = tasks.full_adder_task(graph)
    cfg = CDConfig(lr=6.0, cd_k=15, pos_sweeps=15, burn_in=3,
                   chains=chains, epochs=epochs)
    ses = mach.session(chains=chains)
    t0 = time.perf_counter()
    step = ses.make_cd_step(cfg, task.visible_idx)
    codes = jnp.asarray(energy.all_states(task.n_visible))
    Jm = jnp.zeros((graph.n_edges,), jnp.float32)
    hm = jnp.zeros((graph.n_nodes,), jnp.float32)
    m = ses.random_spins(jax.random.PRNGKey(1))
    ns = ses.noise_state(jax.random.PRNGKey(2))
    vel = (jnp.zeros_like(Jm), jnp.zeros_like(hm))
    key = jax.random.PRNGKey(3)
    losses, epoch_s = [], []
    for _ in range(epochs):
        key, kd = jax.random.split(key)
        data = codes[jax.random.choice(kd, codes.shape[0], (chains,),
                                       p=jnp.asarray(task.target_dist))]
        (Jm, hm, m, ns, vel, met), dt = timed(
            lambda: step(Jm, hm, data, m, ns, vel))
        epoch_s.append(dt)
        losses.append(float(met["corr_err"]))
    emp = sample_visible_dist(mach, Jm, hm, task.visible_idx, key,
                              chains=chains)
    kl = energy.kl_divergence(np.asarray(task.target_dist), emp)
    setup = time.perf_counter() - t0 - sum(epoch_s[1:])
    check(all(math.isfinite(x) for x in losses) and math.isfinite(kl),
          f"cd: non-finite loss {losses} or KL {kl}")
    report("cd", {"cd_step": ses},
           f"{epochs} epochs full adder on N={graph.n_nodes}, mismatched "
           f"chip: corr_err {losses[0]:.4f} -> {losses[-1]:.4f}, KL "
           f"{kl:.4f}, all finite", setup, epoch_s[-1])


def phase_maxcut(graph, chains: int = 64, n_sweeps: int = 200) -> None:
    import jax
    import numpy as np

    from repro.core.annealing import AnnealConfig
    from repro.core.cd import PBitMachine
    from repro.core.maxcut import random_chimera_maxcut, solve_maxcut

    mach = PBitMachine.create(graph, jax.random.PRNGKey(SEED),
                              noise="counter", w_scale=0.03)
    problem = random_chimera_maxcut(graph, jax.random.PRNGKey(4))
    cfg = AnnealConfig(n_sweeps=n_sweeps, beta_start=0.02, beta_end=3.0,
                       chains=chains)
    t0 = time.perf_counter()
    out = solve_maxcut(mach, problem, cfg, jax.random.PRNGKey(5))
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    solve_maxcut(mach, problem, cfg, jax.random.PRNGKey(5))
    warm = time.perf_counter() - t0
    rng = np.random.default_rng(SEED)
    rand_cut = problem.cut_value(rng.choice([-1.0, 1.0], graph.n_nodes))
    check(out["cut"] > rand_cut,
          f"maxcut: anneal cut {out['cut']} <= random {rand_cut}")
    ses = mach.session(schedule=cfg.to_schedule(), chains=chains)
    report("maxcut", {"anneal": ses},
           f"collect=True anneal (scan path), cut {out['cut']:.0f} > "
           f"random assignment {rand_cut:.0f} "
           f"(of {out['upper_bound']:.0f}; polished "
           f"{out['cut_polished']:.0f}; {problem.n_edges} edges)",
           setup, warm)


def phase_serving(n_per_bucket: int = 4, chains: int = 4,
                  n_sweeps: int = 100) -> None:
    import numpy as np

    from repro import api
    from repro.core.chimera import make_chimera, make_chip_graph
    from repro.serve import SampleRequest, SamplerService

    graphs = (make_chimera(2, 2), make_chip_graph())
    svc = SamplerService(seed=SEED, capacity_chains=16, noise="counter")
    rng = np.random.default_rng(SEED)
    reqs = [SampleRequest(
        tenant=f"tenant-{i % 3}", graph=g, chains=chains, n_sweeps=n_sweeps,
        J_codes=rng.integers(-40, 41, g.n_edges, dtype=np.int32),
        h_codes=rng.integers(-10, 11, g.n_nodes, dtype=np.int32))
        for g in graphs for i in range(n_per_bucket)]

    def serve():
        tickets = [svc.submit(r) for r in reqs]
        svc.drain()
        return [t.result() for t in tickets]

    t0 = time.perf_counter()
    results = serve()
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    serve()
    warm = time.perf_counter() - t0
    bad = [(r.tenant, r.status, r.error) for r in results
           if r.status != "ok"]
    check(not bad, f"serving: requests not ok: {bad}")
    for r, q in zip(results, reqs):
        check(r.spins.shape == (q.chains, q.graph.n_nodes)
              and bool(np.all(np.abs(r.spins) == 1.0)),
              f"serving: bad spins for {q.tenant}")
    buckets = sorted({r.bucket_shape for r in results})
    check(buckets == [(2, 2), (7, 8)], f"serving: buckets {buckets}")
    sessions = {f"{g.rows}x{g.cols}": svc.cache.get(
        api.spec_fingerprint(svc.bucket_spec(g))).session for g in graphs}
    report("serving", sessions,
           f"{len(results)} requests in buckets {buckets}, all ok, spins "
           f"+-1", setup, warm)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
def phase_sharded(graph, n_chips: int, chains: int, n_sweeps: int) -> None:
    """Row-sharded barrier `Sync()` "sparse" == the single-device Session."""
    import jax

    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.hardware import HardwareConfig
    from repro.launch.mesh import make_line_mesh

    mesh = make_line_mesh(n_chips)
    mach = PBitMachine.create(graph, jax.random.PRNGKey(SEED),
                              HardwareConfig(), sparse=True, noise="counter")
    t0 = time.perf_counter()
    ses0 = api.Session(mach.sampler_spec(chains=chains))
    ses1 = api.Session(mach.sampler_spec(
        chains=chains, mesh=mesh, partition=api.Partition(rows="data")))
    chip = ses0.program_edges(*_random_codes(graph, SEED))
    m0 = ses0.random_spins(jax.random.PRNGKey(1))
    ns = ses0.noise_state(jax.random.PRNGKey(2))
    betas = jax.numpy.linspace(0.3, 1.5, n_sweeps)

    def run(s):
        return (s.sample(chip, m0, ns, betas)[:2]
                + s.stats(chip, m0, ns, n_sweeps, 1))

    a = jax.block_until_ready(run(ses0))
    b = jax.block_until_ready(run(ses1))
    setup = time.perf_counter() - t0
    _, warm = timed(lambda: run(ses1))
    check(_equal(a, b), "sharded: Sync() sparse != single device")
    report("sharded", {"single": ses0, f"rows/{n_chips}": ses1},
           f"Sync() barrier rows-sharded == single device bit-exact: "
           f"spins, noise state, moments (N={graph.n_nodes}, B={chains}, "
           f"S={n_sweeps})", setup, warm)


def phase_halo_fused(graph, n_chips: int, chains: int) -> None:
    """Fused exchange windows: halo_every=1 against single device,
    halo_every=4 against the "sparse" segment scan."""
    import jax

    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.hardware import HardwareConfig
    from repro.launch.mesh import make_line_mesh

    mesh = make_line_mesh(n_chips)
    mach = PBitMachine.create(graph, jax.random.PRNGKey(SEED),
                              HardwareConfig(), sparse=True, noise="counter")
    ses0 = api.Session(mach.sampler_spec(chains=chains))
    chip = ses0.program_edges(*_random_codes(graph, SEED))
    m0 = ses0.random_spins(jax.random.PRNGKey(1))
    ns = ses0.noise_state(jax.random.PRNGKey(2))
    betas = jax.numpy.linspace(0.3, 1.5, 8)

    def sharded(sync, backend):
        sp = mach.sampler_spec(chains=chains, mesh=mesh, sync=sync,
                               partition=api.Partition(rows="data"))
        return api.Session(sp.replace(backend=backend))

    t0 = time.perf_counter()
    k1 = sharded(api.Sync(halo_every=1, sweeps_per_launch=4),
                 "fused_sparse")
    a = jax.block_until_ready(ses0.sample(chip, m0, ns, betas)[:2])
    b = jax.block_until_ready(k1.sample(chip, m0, ns, betas)[:2])
    setup = time.perf_counter() - t0
    _, warm = timed(lambda: k1.sample(chip, m0, ns, betas)[:2])
    check(_equal(a, b), "halo_fused: halo_every=1 fused != single device")
    k4 = {b_: sharded(api.Sync(halo_every=4, sweeps_per_launch=4), b_)
          for b_ in ("sparse", "fused_sparse")}
    c, d = (jax.block_until_ready(s.sample(chip, m0, ns, betas)[:2])
            for s in k4.values())
    check(_equal(c, d), "halo_fused: halo_every=4 fused != sparse scan")
    report("halo_fused", {"single": ses0, "k1": k1, "k4_scan":
                          k4["sparse"], "k4_fused": k4["fused_sparse"]},
           f"fused exchange windows: halo_every=1 == single device, "
           f"halo_every=4 == sparse segment scan, bit-exact spins + noise "
           f"state (N={graph.n_nodes}, B={chains}, S=8)", setup, warm)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip main path; 4: only the "
                         "row-sharded lattice path")
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        print("chip_smoke: refusing REPRO_PALLAS_INTERPRET=1 — this test "
              "runs compiled kernels on the chip", file=sys.stderr)
        return 2
    try:
        import jax

        from repro.core.chimera import make_chimera, make_chip_graph
        from repro.runtime.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the p-bit package: {e}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); this test runs only on a chip",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, compile "
          f"cache {use_compile_cache()}", flush=True)

    try:
        if args.chips == 1:
            graph = make_chip_graph()
            check(graph.n_nodes == N_CHIP, f"chip graph {graph.n_nodes}")
            phase_sampling(graph)
            phase_cd(graph)
            phase_maxcut(graph)
            phase_serving()
        else:
            from repro.configs.registry import PBIT_CONFIGS
            pod = PBIT_CONFIGS["pbit-pod-2m"]
            phase_sharded(make_chimera(pod["cell_rows"], pod["cell_cols"],
                                       masked_cells=pod["masked"]),
                          args.chips, chains=8, n_sweeps=4)
            # a row band the resident kernel holds in VMEM: 8 cell rows
            # x 64 cell columns per chip (4,096 spins)
            phase_halo_fused(make_chimera(8 * args.chips, 64), args.chips,
                             chains=8)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
