"""Lattice annealing on a row-sharded mesh: ``api.Session.sample``, closed
loop, one caller.

One standing spin glass (J codes in +-``j_max``, h codes in +-``h_max``,
drawn from the seed) is programmed once at set-up through
``Session.program_edges`` on a line mesh of the cell's chips, cell rows
split over them (``Partition(rows=...)``, ``Sync()``).  The chains anneal
geometrically from ``anneal.beta_start`` to ``anneal.beta_end`` over
``anneal.sweeps`` sweeps, cut into calls of ``sweeps`` sweeps; each call is
one ``Session.sample`` continuing the chains, and fetches only each chain's
count of +1 spins (an int32 reduced on the device), never the spins.  When
an anneal ends, the chains restart from spins and a noise seed drawn from
the seed.  Traffic keys: ``chains``, ``sweeps``, ``anneal``, ``j_max``,
``h_max``, ``check_calls`` (calls of the window whose counts the check
replays, drawn from the seed), ``check_chains`` (chains it replays).

The check replays those chains through ``bench/reference_lattice.py``
from the restart of every anneal it needs: the kept calls' counts, and the
full spins of the window's last call.
"""
from __future__ import annotations

import time

import numpy as np

import load
import reference as ref
import reference_lattice as rl
from load import jkey, keys, span

AXIS = "rows"


def anneal(tr: dict) -> np.ndarray:
    """The whole anneal's betas, (calls per anneal, sweeps per call)."""
    a = tr["anneal"]
    t = np.linspace(0.0, 1.0, a["sweeps"])
    b = a["beta_start"] * (a["beta_end"] / a["beta_start"]) ** t
    return b.astype(np.float32).reshape(-1, tr["sweeps"])


def line_mesh(devices):
    from jax.sharding import Mesh
    return Mesh(np.asarray(devices), (AXIS,))


def machine(cfg: dict, graph, mkey, mesh):
    """The cell's chip instance on ``mesh``, drawn in its bands."""
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.hardware import HardwareConfig
    return PBitMachine.create(
        graph, mkey, HardwareConfig(**load.hw_dict(cfg)), sparse=True,
        noise=cfg["noise"], backend=cfg["backend"],
        w_scale=float(cfg["w_scale"]), beta=float(cfg["beta"]), mesh=mesh,
        partition=api.Partition(rows=AXIS), sync=api.Sync())


def rehearse(cell, mach, sds, compile) -> None:
    """Compile the band programming, the window's call and the per-call
    count for a described v5e:2x2, the lattice split over the cell's
    chips (no run).

    ``mach`` and ``sds`` (one chip's) are not used: the cell builds its
    own machine on a mesh, and the Session's static tables are described
    by their shapes and shardings where a run would place them."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from repro import api
    from repro.core import distributed as dist
    from repro.core.cd import PBitMachine
    from repro.core.hardware import HardwareConfig, sample_mismatch_sparse

    def described(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    cfg, tr = cell.config, cell.traffic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = line_mesh(topo.devices[:cell.chips])
    part = api.Partition(rows=AXIS)
    g = load.program_graph(cfg)
    hw = HardwareConfig(**load.hw_dict(cfg))
    D = g.neighbor_table()[0].shape[0]
    mm = jax.tree_util.tree_map(
        described, jax.eval_shape(lambda k: sample_mismatch_sparse(
            k, g.n_nodes, D, hw), jax.random.PRNGKey(0)),
        dist.mismatch_shardings(mesh, part))
    put = dist.ShardedEngine._put
    dist.ShardedEngine._put = staticmethod(described)
    try:
        mach = PBitMachine(graph=g, hw=hw, mismatch=mm, noise=cfg["noise"],
                           backend=cfg["backend"],
                           w_scale=float(cfg["w_scale"]),
                           beta=float(cfg["beta"]), mesh=mesh,
                           partition=part, sync=api.Sync())
        ses = api.Session(mach.sampler_spec(chains=tr["chains"]).replace(
            interpret=False))
    finally:
        dist.ShardedEngine._put = put
    eng = ses._engine
    B, N, S = tr["chains"], g.n_nodes, tr["sweeps"]
    nodes = dist.band_sharding(mesh, part, 1, 0)
    program = ses._jit(ses._band_program_impl, "program_edges")
    args = (mm, eng.tables["edge_ids"],
            jax.ShapeDtypeStruct((N,), jnp.int32, sharding=nodes))
    compile(f"program_edges N={N} E={g.n_edges} over {cell.chips} chips",
            program, args)
    slots = dist.band_sharding(mesh, part, 2, 1)
    chip = jax.tree_util.tree_map(
        lambda x: described(x, slots if x.ndim == 2 else nodes),
        jax.eval_shape(program, *args))
    m = jax.ShapeDtypeStruct((B, N), jnp.float32, sharding=eng.spin_sharding)
    ns = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=eng.noise_sharding)
    betas = jax.ShapeDtypeStruct((S,), jnp.float32,
                                 sharding=eng.noise_sharding)
    compile(f"sample {ses.backend} N={N} B={B} S={S} over {cell.chips} "
            f"chips", ses._build_sample(False, False, True),
            (chip, m, ns, betas))
    compile(f"count B={B} N={N}", jax.jit(count), (m,))


def count(m):
    """Each chain's count of +1 spins, reduced on the device."""
    import jax.numpy as jnp
    return jnp.sum(m > 0, axis=1, dtype=jnp.int32)


class Generator:
    """One caller; calls continue the chains through a whole anneal."""

    def __init__(self, cell, seed: int):
        import jax
        import jax.numpy as jnp
        # the band-resident layout is what lets the lattice live on its
        # chips; a program without it cannot run this cell
        from repro.core.distributed import band_resident  # noqa: F401

        self.cell, self.seed = cell, seed
        self.phases = load.Phases()
        tr = cell.traffic
        self.B, self.S = tr["chains"], tr["sweeps"]
        self.betas_np = anneal(tr)
        self.per_anneal = self.betas_np.shape[0]
        self.mesh = line_mesh(jax.devices()[:cell.chips])
        self.mkey = jkey(seed, "chip")
        with self.phases("graph"):
            g = self.graph(cell.config)
            self.N, self.E = g.n_nodes, g.n_edges
        with self.phases("chip"):
            self.machine = machine(cell.config, g, self.mkey, self.mesh)
            jax.block_until_ready(self.machine.mismatch)
        with self.phases("plan_and_tables"):
            from repro import api
            self.session = api.Session(self.machine.sampler_spec(
                chains=self.B))
            jax.block_until_ready(self.session._engine.tables)
        with self.phases("program"):
            J, h = self.codes()
            self.chip = jax.block_until_ready(
                self.session.program_edges(J, h))
        self.betas = [jnp.asarray(b) for b in self.betas_np]
        self.count = jax.jit(count)
        rng = keys(seed, "check")
        self.chains = np.sort(rng.choice(self.B, tr["check_chains"],
                                         replace=False))
        self.kept = load.Reservoir(tr["check_calls"], rng)
        self.calls = self.k = 0
        self.m = self.ns = self.last = None
        for w in range(2):
            with self.phases(f"warm{w}"):
                self._call()
        plan = self.session.partition_plan
        from repro.core.distributed import halo_bytes_per_sweep
        self.halo = {"halo_bytes_per_sweep": halo_bytes_per_sweep(
            plan, self.B, sync=self.session.spec.sync_policy()),
            "exchanges_per_sweep":
                self.session.spec.sync_policy().exchanges_per_sweep(),
            "boundary_spins": plan.n_boundary, "bands": plan.n_shards}
        self.info = {"backend": self.session.backend,
                     "interpret": self.session.interpret,
                     "band_resident": self.session._engine.band_resident,
                     "spins": self.N, "couplers": self.E}

    _graphs: dict = {}

    @classmethod
    def graph(cls, cfg: dict):
        """The configuration's graph with its neighbour tables, built once
        per process: several seeds run in one process
        (``bench/control_lattice.py``) share it, as they share the
        compiled programs."""
        key = (cfg["cell_rows"], cfg["cell_cols"],
               tuple(map(tuple, cfg["masked_cells"])))
        if key not in cls._graphs:
            cls._graphs.clear()
            g = load.program_graph(cfg)
            g.neighbor_table()
            cls._graphs[key] = g
        return cls._graphs[key]

    def codes(self):
        rng = keys(self.seed, "program")
        tr = self.cell.traffic
        J = rng.integers(-tr["j_max"], tr["j_max"] + 1, self.E,
                         dtype=np.int32)
        h = rng.integers(-tr["h_max"], tr["h_max"] + 1, self.N,
                         dtype=np.int32)
        return J, h

    def restart_keys(self, a: int):
        return jkey(self.seed, "restart", a), jkey(self.seed, "noise", a)

    def _sample(self, m, ns, j: int):
        m, ns, _ = self.session.sample(self.chip, m, ns, self.betas[j])
        return m, ns

    def _call(self) -> np.ndarray:
        a, j = divmod(self.k, self.per_anneal)
        if j == 0:
            with span("restart"):
                km, kn = self.restart_keys(a)
                self.m = self.session.random_spins(km)
                self.ns = self.session.noise_state(kn)
        with span("sample"):
            self.m, self.ns = self._sample(self.m, self.ns, j)
        with span("fetch"):
            out = np.asarray(self.count(self.m))
        self.k += 1
        return out

    def run(self, seconds: float) -> None:
        self.longest = load.Longest()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            k = self.k
            with self.longest("call", k):
                out = self._call()
            self.calls += 1
            self.kept.offer((k, out[self.chains]))

    def layer_counters(self) -> dict:
        return {**self.halo, "longest_calls": self.longest.items}

    def counters(self) -> dict:
        return {"calls": self.calls, "sweeps": self.calls * self.S,
                "chains": self.B, "spins": self.N, "couplers": self.E,
                "chips": self.cell.chips}

    def work(self) -> tuple[float, float]:
        """(operations, HBM bytes) of one chip's share of the window's
        calls: its band's spins and couplers, every chain."""
        rf = load.roofline()
        c = self.cell.chips
        n, e, b, s = self.N // c, self.E // c, self.B, self.S
        return (self.calls * rf.sweep_ops(n, e, b, s),
                self.calls * rf.launch_bytes(n, e, b, s))

    def end_to_end(self, elapsed: float) -> dict:
        flips = self.calls * self.S * self.B * self.N
        return {"flips_per_ns": flips / (elapsed * 1e9)}

    def attempted(self) -> tuple[int, int]:
        return self.calls, 0

    def free(self) -> None:
        # the last call's spins stay on the device for the check: the
        # checked chains' rows only
        self.last = (self.k - 1, self.m[np.asarray(self.chains)])
        self.session = self.machine = self.chip = None
        self.m = self.ns = None

    def check(self, dtype=None) -> dict:
        """Replay the checked chains through the reference from the
        restart of each anneal the kept calls and the last call lie in:
        ``count_mismatch`` is the share of kept per-chain counts that
        differ, ``spin_mismatch`` the share of the last call's spins."""
        import jax
        import jax.numpy as jnp
        dtype = jnp.float32 if dtype is None else dtype
        cfg = self.cell.config
        mesh = rl.node_mesh(list(self.mesh.devices.flat))
        with jax.default_matmul_precision("highest"):
            g = rl.chimera(cfg["cell_rows"], cfg["cell_cols"],
                           cfg["masked_cells"])
            chip = rl.draw_chip(mesh, self.mkey, g, load.hw_dict(cfg))
            J, h = self.codes()
            prog = rl.program(mesh, g, chip, load.hw_dict(cfg),
                              float(cfg["w_scale"]), J, h)
            del chip
            slots = rl.place(mesh, rl.term_slots(g), 1)
            color = rl.place(mesh, g.color.astype(np.int32), 0)
            chains = rl.place(mesh, self.chains.astype(np.uint32), None)
            want = {}
            for k, counts in self.kept.items:
                want.setdefault(k // self.per_anneal, {})[k] = counts
            k_last, m_last = self.last
            want.setdefault(k_last // self.per_anneal, {})
            bad_c = tot_c = bad_s = tot_s = 0
            for a, calls in sorted(want.items()):
                km, kn = self.restart_keys(a)
                m = rl.spin_rows(mesh, km, self.B, self.N, self.chains)
                seed = ref.noise_seed(kn)
                ctr = jnp.uint32(0)
                last_j = max([k % self.per_anneal for k in calls]
                             + ([k_last % self.per_anneal]
                                if k_last // self.per_anneal == a else []))
                for j in range(last_j + 1):
                    k = a * self.per_anneal + j
                    m, ctr, cnt = rl.sweeps(
                        slots, color, prog, m, seed, ctr,
                        rl.place(mesh, self.betas_np[j], None), chains,
                        rows=cfg["cell_rows"], cols=cfg["cell_cols"],
                        dtype=dtype)
                    if k in calls:
                        bad_c += int(np.sum(np.asarray(cnt) != calls[k]))
                        tot_c += cnt.size
                    if k == k_last:
                        got = jax.device_put(m_last, m.sharding)
                        bad_s += int(jnp.sum(got != m))
                        tot_s += m.size
        return {"spin_mismatch": bad_s / max(tot_s, 1),
                "count_mismatch": bad_c / max(tot_c, 1)}



if __name__ == "__main__":
    # the compile rehearsal of a lattice cell, by hand (bench/
    # compile_rehearsal.py builds a one-chip machine for every cell):
    #   JAX_PLATFORMS=cpu PYTHONPATH=bench:src \
    #       python bench/generators/lattice.py pod33m.anneal
    import json
    import os
    import sys

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    import compile_rehearsal
    import harness

    jax.config.update("jax_enable_compilation_cache", False)
    log: list = []
    rehearse(harness.load_cell(sys.argv[1]), None, None,
             lambda what, fn, args: compile_rehearsal._compile(
                 what, fn, args, log))
    for entry in log:
        print(json.dumps(entry), flush=True)
    sys.exit(0 if all(x["ok"] for x in log) else 1)
