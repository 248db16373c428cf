"""Paper Fig 9b: Max-Cut via annealing on the chip graph."""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, save_json
from repro.core.annealing import AnnealConfig
from repro.core.cd import PBitMachine
from repro.core.chimera import make_chip_graph
from repro.core.hardware import HardwareConfig
from repro.core.maxcut import random_chimera_maxcut, solve_maxcut


def run() -> dict:
    g = make_chip_graph()
    machine = PBitMachine.create(g, jax.random.PRNGKey(0),
                                 HardwareConfig(), beta=1.0, w_scale=0.03)
    prob = random_chimera_maxcut(g, jax.random.PRNGKey(1), edge_prob=0.8)
    cfg = AnnealConfig(n_sweeps=500, beta_start=0.05, beta_end=3.0,
                       chains=64)
    t0 = time.perf_counter()
    sol = solve_maxcut(machine, prob, cfg, jax.random.PRNGKey(2))
    dt = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    rand_cut = max(prob.cut_value(rng.choice([-1.0, 1.0], size=g.n_nodes))
                   for _ in range(64))
    out = {
        "n_nodes": int(g.n_nodes),
        "n_problem_edges": int(prob.n_edges),
        "cut_annealed": sol["cut"],
        "cut_polished": sol["cut_polished"],
        "cut_random_best_of_64": rand_cut,
        "upper_bound_total_weight": sol["upper_bound"],
        "fraction_of_ub": sol["cut_polished"] / sol["upper_bound"],
        "seconds": dt,
    }
    save_json("fig9b_maxcut", out)
    emit("fig9b_maxcut_solve", dt * 1e6,
         f"cut={out['cut_polished']:.0f}/"
         f"{out['upper_bound_total_weight']:.0f}")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    run()
