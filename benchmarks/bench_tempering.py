"""Beyond-paper: parallel tempering vs simulated annealing on the SK glass.

The chip exposes one global V_temp; a replica-exchange controller (R chips
or R passes + energy readout) is a natural system extension.  Equal sweep
budget per replica/chain.
"""
from __future__ import annotations

import time

import jax

from benchmarks.common import emit, save_json
from repro.core.annealing import AnnealConfig, anneal, sk_instance
from repro.core.cd import PBitMachine
from repro.core.chimera import make_chip_graph
from repro.core.hardware import HardwareConfig
from repro.core.tempering import PTConfig, parallel_tempering


def run() -> dict:
    g = make_chip_graph()
    machine = PBitMachine.create(g, jax.random.PRNGKey(3),
                                 HardwareConfig(), w_scale=0.02)
    J, h = sk_instance(g, jax.random.PRNGKey(4))

    sa = anneal(machine, J, h,
                AnnealConfig(n_sweeps=600, beta_start=0.02, beta_end=3.0,
                             chains=16),
                jax.random.PRNGKey(5))
    t0 = time.perf_counter()
    pt = parallel_tempering(
        machine, J, h,
        PTConfig(n_replicas=16, n_sweeps=600, swap_every=10),
        jax.random.PRNGKey(5))
    dt = time.perf_counter() - t0
    out = {
        "sa_best_energy": sa["best_energy"],
        "pt_best_energy": pt["best_energy"],
        "pt_swap_rate": pt["swap_rate"],
        "improvement_pct": 100.0 * (sa["best_energy"] - pt["best_energy"])
        / abs(sa["best_energy"]),
        "equal_budget_sweeps_x_chains": 600 * 16,
        "seconds": dt,
    }
    save_json("ext_parallel_tempering", out)
    emit("ext_pt_vs_sa_600sweeps", dt * 1e6,
         f"PT={pt['best_energy']:.0f};SA={sa['best_energy']:.0f}")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    run()
