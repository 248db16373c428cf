"""Batch sampling: ``api.Session.sample_program``, closed loop, one caller.

Each call programs a fresh problem (J and h codes drawn from the seed),
anneals every chain geometrically from ``anneal.beta_start`` to
``anneal.beta_end`` over ``sweeps`` sweeps, and fetches the spins to the
host before the next call.  Traffic keys: ``chains``, ``sweeps``,
``anneal``, ``j_max``, ``h_max``, ``check_calls`` (calls of the window the
check replays, drawn from the seed).
"""
from __future__ import annotations

import time

import numpy as np

import load
import reference as ref
from load import jkey, keys, span


def anneal(tr: dict) -> np.ndarray:
    a = tr["anneal"]
    t = np.linspace(0.0, 1.0, tr["sweeps"])
    return (a["beta_start"] * (a["beta_end"] / a["beta_start"]) ** t
            ).astype(np.float32)


def rehearse(cell, mach, sds, compile) -> None:
    """Compile the window's call for a described chip (no run)."""
    import jax
    import jax.numpy as jnp
    from repro import api
    tr = cell.traffic
    n, e = mach.graph.n_nodes, mach.graph.n_edges
    spec = mach.sampler_spec(chains=tr["chains"])
    spec = spec.replace(interpret=False, backend=api.resolve_backend(spec))
    ses = api.Session(spec)
    mm = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                mach.mismatch)
    prog = api.Program(J_codes=sds((e,), jnp.int32),
                       h_codes=sds((n,), jnp.int32), mismatch=mm)
    compile(f"sample_program {ses.backend} N={n} B={tr['chains']} "
            f"S={tr['sweeps']}", ses._build_sample_program(False),
            (prog, sds((tr["chains"], n), jnp.float32),
             sds((2,), jnp.uint32), sds((tr["sweeps"],), jnp.float32)))


class Generator:
    """One caller; each call programs a fresh problem and fetches spins."""

    def __init__(self, cell, seed: int):
        import jax
        import jax.numpy as jnp
        from repro import api

        self.cell, self.seed = cell, seed
        self.phases = load.Phases()
        cfg, tr = cell.config, cell.traffic
        self.B, self.S = tr["chains"], tr["sweeps"]
        with self.phases("chip"):
            self.mkey = jkey(seed, "chip")
            self.machine = load.machine(cfg, self.mkey)
            g = self.machine.graph
            self.N, self.E = g.n_nodes, g.n_edges
            jax.block_until_ready(self.machine.mismatch)
        with self.phases("session"):
            self.session = api.Session(
                self.machine.sampler_spec(chains=self.B))
            self.betas_np = anneal(tr)
            self.betas = jnp.asarray(self.betas_np)
            self.m0 = jax.block_until_ready(
                ref.spins(jkey(seed, "m0"), self.B, self.N))
        self.kept = load.Reservoir(tr["check_calls"], keys(seed, "check"))
        self.calls = 0
        for k in (-2, -1):
            with self.phases(f"warm{k}"):
                self._call(k)
        self.info = {"backend": self.session.backend,
                     "interpret": self.session.interpret}

    def inputs(self, k: int):
        rng = keys(self.seed, "call", k)
        tr = self.cell.traffic
        J = rng.integers(-tr["j_max"], tr["j_max"] + 1, self.E,
                         dtype=np.int32)
        h = rng.integers(-tr["h_max"], tr["h_max"] + 1, self.N,
                         dtype=np.int32)
        ns = np.array([rng.integers(0, 2 ** 32), 0], np.uint32)
        return J, h, ns

    def _call(self, k: int) -> np.ndarray:
        import jax.numpy as jnp
        J, h, ns = self.inputs(k)
        with span("make_program"):
            # the chip instance rides in the program, so one executable
            # serves every chip drawn from every seed
            prog = self.session.make_program(
                jnp.asarray(J), jnp.asarray(h),
                mismatch=self.machine.mismatch)
        with span("sample_program"):
            m, _, _ = self.session.sample_program(
                prog, self.m0, jnp.asarray(ns), self.betas)
            out = np.asarray(m)
        return out

    def run(self, seconds: float) -> None:
        self.longest = load.Longest()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            k = self.calls
            with self.longest("call", k):
                out = self._call(k)
            self.calls += 1
            self.kept.offer((k, out))

    def layer_counters(self) -> dict:
        return {"longest_calls": self.longest.items}

    def counters(self) -> dict:
        return {"calls": self.calls, "sweeps": self.calls * self.S,
                "chains": self.B, "spins": self.N, "couplers": self.E}

    def work(self) -> tuple[float, float]:
        """(operations, HBM bytes) of the calls the window made."""
        rf = load.roofline()
        n, e, b, s = self.N, self.E, self.B, self.S
        return (self.calls * rf.sweep_ops(n, e, b, s),
                self.calls * rf.launch_bytes(n, e, b, s))

    def end_to_end(self, elapsed: float) -> dict:
        flips = self.calls * self.S * self.B * self.N
        return {"flips_per_ns": flips / (elapsed * 1e9)}

    def attempted(self) -> tuple[int, int]:
        return self.calls, 0

    def free(self) -> None:
        self.session = self.machine = None

    def check(self, dtype=None) -> dict:
        """Replay the kept calls through the reference: the chip instance
        re-drawn from the same key, the same codes, spins, noise seed and
        schedule; ``spin_mismatch`` is the share of spins that differ."""
        import jax.numpy as jnp
        dtype = jnp.float32 if dtype is None else dtype
        cfg = self.cell.config
        g = load.ref_graph(cfg)
        chip = ref.draw_chip(self.mkey, g, load.hw_dict(cfg), per_pair=True)
        nbr, color = jnp.asarray(g.nbr), jnp.asarray(g.color)
        programmer = load.ref_programmer(g, cfg, per_pair=True)
        bad = total = 0
        for k, out in self.kept.items:
            J, h, ns = self.inputs(k)
            prog = programmer(chip, jnp.asarray(J), jnp.asarray(h))
            m, *_ = ref.sweeps(nbr, color, prog, self.m0,
                               jnp.uint32(ns[0]), jnp.uint32(ns[1]),
                               self.betas, dtype=dtype)
            m = np.asarray(m)
            bad += int(np.sum(m != out))
            total += m.size
        return {"spin_mismatch": bad / max(total, 1)}
