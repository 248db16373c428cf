"""Readings for the limits of ``correct`` in lattice cells: the program,
and its control.

    python bench/control_lattice.py --workload <name> --seeds 1,2,3 \
        --seconds 3 [--control bf16]

As ``bench/control.py``, for cells whose generator sweeps a lattice split
over chips through ``api.Session.sample`` (``bench/generators/lattice.py``),
which ``bench/control.py`` does not reach.  Without ``--control`` the
program runs as the benchmark runs it: the lower readings.  With
``--control bf16`` every call's sweeps of the checked chains are the plain
reference's (``bench/reference_lattice.py``) computed in bfloat16, from
the spins the call was given, in the program's place; the other chains
are handed back as they came, since the check compares only the checked
ones.  The harness then compares as it always does: the upper readings.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def install(dtype_name: str = "bf16", root: Path = ROOT) -> None:
    """Put the reference, in ``dtype``, in the place of the program's
    sweeps of the checked chains (of the generator under ``root``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import load
    import reference_lattice as rl

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype_name]
    gen = load.generator_module(root, "lattice").Generator

    def reference(self):
        if getattr(self, "_control", None) is None:
            cfg, hw = self.cell.config, load.hw_dict(self.cell.config)
            mesh = rl.node_mesh(list(self.mesh.devices.flat))
            g = rl.chimera(cfg["cell_rows"], cfg["cell_cols"],
                           cfg["masked_cells"])
            chip = rl.draw_chip(mesh, self.mkey, g, hw)
            prog = rl.program(mesh, g, chip, hw, float(cfg["w_scale"]),
                              *self.codes())
            self._control = (mesh, prog, rl.place(mesh, rl.term_slots(g), 1),
                             rl.place(mesh, g.color.astype(np.int32), 0))
        return self._control

    def _sample(self, m, ns, j):
        mesh, prog, slots, color = reference(self)
        cfg = self.cell.config
        seed, ctr = (jnp.asarray(x) for x in np.asarray(ns))
        rows, _, _ = rl.sweeps(
            slots, color, prog,
            rl.place(mesh, np.asarray(m[self.chains]), 1), seed, ctr,
            rl.place(mesh, self.betas_np[j], None),
            rl.place(mesh, self.chains.astype(np.uint32), None),
            rows=cfg["cell_rows"], cols=cfg["cell_cols"], dtype=dtype)
        rows = jax.device_put(rows, NamedSharding(m.sharding.mesh, P()))
        return (m.at[self.chains].set(rows),
                ns + np.array([0, 2 * self.S], np.uint32))

    gen._sample = _sample


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
                          "metrics": line["metrics"],
                          "device": line["device"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
