"""Paper Fig 9a: simulated annealing of an SK spin glass, all 440 spins."""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, save_json
from repro.core.annealing import AnnealConfig, anneal, sk_instance
from repro.core.cd import PBitMachine
from repro.core.chimera import make_chip_graph
from repro.core.hardware import HardwareConfig


def run() -> dict:
    g = make_chip_graph()
    machine = PBitMachine.create(g, jax.random.PRNGKey(3),
                                 HardwareConfig(), beta=1.0, w_scale=0.02)
    J, h = sk_instance(g, jax.random.PRNGKey(4))
    cfg = AnnealConfig(n_sweeps=1000, beta_start=0.02, beta_end=3.0,
                       chains=64)
    t0 = time.perf_counter()
    out_a = anneal(machine, J, h, cfg, jax.random.PRNGKey(5),
                   record_every=50)
    dt = time.perf_counter() - t0
    out = {
        "sweeps": out_a["sweeps"].tolist(),
        "energy_mean": out_a["energy_mean"].tolist(),
        "energy_min": out_a["energy_min"].tolist(),
        "best_energy": out_a["best_energy"],
        "chains": cfg.chains,
        "seconds": dt,
        "sweeps_per_second_per_chain": cfg.n_sweeps * cfg.chains / dt,
    }
    save_json("fig9a_sk_annealing", out)
    emit("fig9a_sk_anneal_sweep", dt / cfg.n_sweeps * 1e6,
         f"best_E={out['best_energy']:.0f}")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    run()
