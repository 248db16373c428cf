"""In-situ learning: ``core.cd.train_cd``, whole training runs back to back.

Each run trains the task of the traffic on the mismatched chip from its own
key, derived from the seed.  Traffic keys: ``task`` (the visible spins by
Chimera coordinates and the valid rows of the truth table), ``cd`` (the
``CDConfig``), ``eval_every``.  The evaluation that ``train_cd`` runs every
``eval_every`` epochs uses ``sample_visible_dist``'s own chains, sweeps and
burn-in; they are read from the program's signature, so the reference and
the operation count follow what ran.
"""
from __future__ import annotations

import dataclasses
import inspect
import math
import time

import numpy as np

import load
import reference as ref
from load import jkey, keys, leaf_norm_gap, rel_gap, span


def eval_settings() -> dict:
    """The chains, sweeps and burn-in of ``train_cd``'s evaluation."""
    from repro.core import cd as cd_mod
    p = inspect.signature(cd_mod.sample_visible_dist).parameters
    return {k: int(p[k].default) for k in ("chains", "sweeps", "burn_in")}


def rehearse(cell, mach, sds, compile) -> None:
    """Compile the CD step and the evaluation histogram."""
    import jax
    import jax.numpy as jnp
    from repro import api
    from repro.core import cd as cd_mod
    tr = cell.traffic
    n, e = mach.graph.n_nodes, mach.graph.n_edges
    f32 = jnp.float32
    c = cd_mod.CDConfig(**tr["cd"])
    spec = mach.sampler_spec(chains=c.chains)
    spec = spec.replace(interpret=False, backend=api.resolve_backend(spec))
    ses = api.Session(spec)
    vis = list(range(len(tr["task"]["visible"])))
    mm = jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype),
                                mach.mismatch)
    compile(f"cd_step {ses.backend} N={n} B={c.chains}",
            ses.make_cd_step(c, vis).with_mismatch,
            (mm, sds((e,), f32), sds((n,), f32),
             sds((c.chains, len(vis)), f32), sds((c.chains, n), f32),
             sds((2,), jnp.uint32), (sds((e,), f32), sds((n,), f32))))
    ev = eval_settings()
    hspec = mach.sampler_spec(
        schedule=api.Constant(beta=mach.beta, n_sweeps=ev["sweeps"]),
        chains=ev["chains"])
    hspec = hspec.replace(interpret=False, backend=api.resolve_backend(hspec))
    hses = api.Session(hspec)
    chip = hses.program_edges(jnp.zeros((e,), jnp.int32),
                              jnp.zeros((n,), jnp.int32))
    compile(f"eval visible_hist {hses.backend} B={ev['chains']} "
            f"S={ev['sweeps']}", hses._build_hist(vis, ev["burn_in"]),
            (jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), chip),
             sds((ev["chains"], n), f32), sds((2,), jnp.uint32),
             sds((ev["sweeps"],), f32)))


class Generator:
    """Whole training runs, each from its own key; the first epochs are
    also run in set-up, through the same call, for the step check."""

    def __init__(self, cell, seed: int):
        import jax
        from repro.core import cd as cd_mod

        self.cell, self.seed = cell, seed
        self.phases = load.Phases()
        cfg, tr = cell.config, cell.traffic
        with self.phases("chip"):
            self.mkey = jkey(seed, "chip")
            self.machine = load.machine(cfg, self.mkey)
            g = self.machine.graph
            self.N, self.E = g.n_nodes, g.n_edges
            jax.block_until_ready(self.machine.mismatch)
        lut = g.coord_lut()
        self.vis = np.array([lut[r, c, s, k]
                             for r, c, s, k in tr["task"]["visible"]])
        nv = len(self.vis)
        target = np.zeros(2 ** nv)
        for row in tr["task"]["rows"]:
            target[sum(1 << i for i, v in enumerate(row) if v > 0)] = 1.0
        self.target = target / target.sum()
        self.cfg = cd_mod.CDConfig(**tr["cd"])
        self.epochs = tr["cd"]["epochs"]
        self.eval = eval_settings()
        self.runs = 0
        self.kept = load.Reservoir(1, keys(seed, "check"))
        # the first steps, through the window's own call and compiled step
        self.first = {}
        for n in (1, 3):
            with self.phases(f"first{n}"):
                self.first[n] = self._train(
                    0, dataclasses.replace(self.cfg, epochs=n))
        self.info = {"backend": self.machine.session(
            chains=self.cfg.chains).backend, "eval": self.eval}

    def key(self, r: int):
        return jkey(self.seed, "run", r)

    def _train(self, r: int, cfg):
        from repro.core import cd as cd_mod
        with span("train_cd"):
            return cd_mod.train_cd(self.machine, self.vis, self.target, cfg,
                                   self.key(r),
                                   eval_every=self.cell.traffic["eval_every"])

    def run(self, seconds: float) -> None:
        self.longest = load.Longest()
        t0 = time.perf_counter()
        self.skipped = 0
        while time.perf_counter() - t0 < seconds:
            with self.longest("run", self.runs):
                res = self._train(self.runs, self.cfg)
            self.skipped += int(any(m.get("update_skipped", 0.0)
                                    for m in res.metric_history))
            self.kept.offer((self.runs, res))
            self.runs += 1

    def _evals_per_run(self) -> int:
        ev = self.cell.traffic["eval_every"]
        return sum((e + 1) % ev == 0 or e == self.epochs - 1
                   for e in range(self.epochs))

    def layer_counters(self) -> dict:
        return {"longest_runs": self.longest.items}

    def counters(self) -> dict:
        return {"runs": self.runs, "epochs": self.runs * self.epochs,
                "evals": self.runs * self._evals_per_run(),
                "chains": self.cfg.chains, "spins": self.N,
                "couplers": self.E}

    def work(self) -> tuple[float, float]:
        """(operations, HBM bytes) of the window's CD epochs and
        evaluations: two moment launches per epoch (positive phase,
        negative phase), one histogram launch per evaluation."""
        rf = load.roofline()
        n, e, b = self.N, self.E, self.cfg.chains
        epochs = self.runs * self.epochs
        ops = nbytes = 0
        for sw in (self.cfg.pos_sweeps, self.cfg.cd_k):
            meas = max(sw - self.cfg.burn_in, 0)
            ops += epochs * (rf.sweep_ops(n, e, b, sw)
                             + rf.moment_ops(n, e, b, meas))
            nbytes += epochs * rf.launch_bytes(n, e, b, sw, moments=True)
        ev = self.eval
        evals = self.runs * self._evals_per_run()
        ops += evals * rf.sweep_ops(n, e, ev["chains"], ev["sweeps"])
        nbytes += evals * rf.launch_bytes(n, e, ev["chains"], ev["sweeps"],
                                          hist_bins=2 ** len(self.vis))
        return ops, nbytes

    def end_to_end(self, elapsed: float) -> dict:
        return {"cd_epochs_per_s": self.runs * self.epochs / elapsed}

    def attempted(self) -> tuple[int, int]:
        return self.runs, self.skipped

    def free(self) -> None:
        self.machine = None

    def _reference(self, r: int, epochs: int, dtype):
        cfg, tr = self.cell.config, self.cell.traffic
        g = load.ref_graph(cfg)
        chip = ref.draw_chip(self.mkey, g, load.hw_dict(cfg), per_pair=True)
        return ref.train_cd(
            g, chip, load.hw_dict(cfg), float(cfg["w_scale"]),
            float(cfg["beta"]), self.vis, self.target, tr["cd"],
            self.key(r), epochs=epochs, eval_every=tr["eval_every"],
            eval_chains=self.eval["chains"], eval_sweeps=self.eval["sweeps"],
            eval_burn_in=self.eval["burn_in"], dtype=dtype)

    def check(self, dtype=None) -> dict:
        """The first three steps (losses, the first step's change, the
        change after three) and one window run drawn from the seed (every
        loss, the KL history, the final weights) against the reference."""
        import jax.numpy as jnp
        dtype = jnp.float32 if dtype is None else dtype
        r3 = self._reference(0, 3, dtype)
        p1, p3 = self.first[1], self.first[3]
        out = {
            "step_loss_gap": rel_gap([m["corr_err"]
                                      for m in p3.metric_history],
                                     r3["losses"]),
            "grad_norm_gap": leaf_norm_gap(
                (p1.J_edges, p1.hm), (r3["J1"], r3["h1"])),
            "change_norm_gap": leaf_norm_gap(
                (p3.J_edges, p3.hm), (r3["J3"], r3["h3"])),
        }
        if self.kept.items:
            r, res = self.kept.items[0]
            ref_run = self._reference(r, self.epochs, dtype)
            out["run_gap"] = max(
                rel_gap([m["corr_err"] for m in res.metric_history],
                        ref_run["losses"]),
                rel_gap([k for _, k in res.kl_history], ref_run["kl"]),
                leaf_norm_gap((res.J_edges, res.hm),
                              (ref_run["J"], ref_run["h"])))
        else:
            out["run_gap"] = math.inf
        return out
