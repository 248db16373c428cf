"""Readings for the limits of ``correct``: the program, and its control.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds 3 \
        [--control bf16]

Runs the cell once per seed in one process (one set-up's compilation
serves every seed) and prints, per seed, the numbers the cell compares.
Without ``--control`` the program runs as the benchmark runs it: these are
the lower readings.  With ``--control bf16`` the plain reference, computed
in bfloat16 (the precision below the configuration's float32), is put in
the program's place: ``api.Session.sample_program`` (batch sampling and the
service's launches) and ``core.cd.train_cd`` are replaced, and the harness
compares their output with the float32 reference as it would the
program's.  These are the upper readings.  The benchmark's own runs never
run the control.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _ref_chip(spec_graph, mismatch):
    import reference as ref
    g = ref.chimera(spec_graph.rows, spec_graph.cols,
                    spec_graph.masked_cells)
    chip = [mismatch.dac_bit_j, mismatch.dac_bit_h, mismatch.edge_gain,
            mismatch.tanh_gain, mismatch.tanh_offset, mismatch.rand_gain,
            mismatch.comp_offset, mismatch.leak]
    per_pair = type(mismatch).__name__ != "SparseMismatch"
    return g, chip, per_pair


def install(dtype_name: str = "bf16") -> None:
    """Put the reference, in ``dtype``, in the program's place."""
    import jax.numpy as jnp

    import load
    import reference as ref
    from repro import api
    from repro.core import cd as cd_mod

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype_name]

    def sample_program(self, prog, m, noise_state, betas=None, *,
                       collect=False):
        spec = self.spec
        mm = spec.mismatch if prog.mismatch is None else prog.mismatch
        g, chip, per_pair = _ref_chip(spec.graph, mm)
        p = ref.program(g, chip, dataclasses.asdict(spec.hw),
                        float(spec.w_scale), prog.J_codes, prog.h_codes,
                        per_pair=per_pair)
        b = betas if betas is not None else prog.betas
        b = self.default_betas if b is None else jnp.asarray(b, jnp.float32)
        ns = jnp.asarray(noise_state)
        m2, ctr, *_ = ref.sweeps(jnp.asarray(g.nbr), jnp.asarray(g.color),
                                 p, m, ns[0], ns[1], b, dtype=dtype)
        return m2, jnp.stack([ns[0], ctr]), None

    ev = load.generator_module(ROOT, "cd").eval_settings()

    def train_cd(machine, visible_idx, target_dist, cfg, key,
                 eval_every=10, verbose=False):
        g, chip, _ = _ref_chip(machine.graph, machine.mismatch)
        out = ref.train_cd(
            g, chip, dataclasses.asdict(machine.hw), float(machine.w_scale),
            float(machine.beta), visible_idx, target_dist,
            dataclasses.asdict(cfg), key, epochs=cfg.epochs,
            eval_every=eval_every, eval_chains=ev["chains"],
            eval_sweeps=ev["sweeps"], eval_burn_in=ev["burn_in"],
            dtype=dtype)
        evals = [e + 1 for e in range(cfg.epochs)
                 if (e + 1) % eval_every == 0 or e == cfg.epochs - 1]
        return types.SimpleNamespace(
            J_edges=out["J"], hm=out["h"],
            kl_history=list(zip(evals, out["kl"])),
            metric_history=[{"corr_err": x, "update_skipped": 0.0}
                            for x in out["losses"]])

    api.Session.sample_program = sample_program
    cd_mod.train_cd = train_cd


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", choices=("bf16",), default=None)
    args = ap.parse_args(argv)
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path.insert(0, str(ROOT / "src"))
    import harness
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    if args.control:
        install(args.control)
    for seed in (int(s) for s in args.seeds.split(",")):
        line = harness.run(args.workload, seed, args.seconds, False)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"],
                          "metrics": line["metrics"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
