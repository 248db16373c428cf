"""Paper Fig 8a variability sweep + fault-yield curves.

``fig8a``: node-to-node variability — <m> vs bias-DAC sweep (unchanged).

``fault_yield``: the robustness benchmark.  For each fault rate we draw K
virtual chips (independent mismatch + independent `api.sample_faults`
draw), run short in-situ CD on the AND-gate task, and count the fraction
of chips whose KL to the target reaches the yield threshold.  This is the
manufacturing-yield question for a p-bit accelerator: how many fabricated
dies with stuck p-bits / dead couplers can hardware-aware learning still
train around?  Rows land in the tracked ``fault_yield`` section of the
repo-root ``BENCH_kernel.json`` (non-quick runs only; merge-preserving,
see bench_kernel.py).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_json
from repro import api
from repro.core import pbit, tasks
from repro.core.cd import CDConfig, PBitMachine, train_cd
from repro.core.chimera import make_chimera, make_chip_graph
from repro.core.hardware import HardwareConfig

BIASES = np.arange(-100, 101, 20)

YIELD_KL = 0.35          # a chip "yields" if CD reaches this KL
FAULT_RATES = (0.0, 0.05, 0.1, 0.2)


def run_fig8a() -> dict:
    g = make_chip_graph()
    machine = PBitMachine.create(g, jax.random.PRNGKey(8),
                                 HardwareConfig(), beta=1.0, w_scale=0.02)
    t0 = time.perf_counter()
    curves = []
    for b in BIASES:
        chip = machine.program(jnp.zeros((g.n_nodes, g.n_nodes), jnp.int32),
                               jnp.full((g.n_nodes,), int(b), jnp.int32))
        m0 = pbit.random_spins(jax.random.PRNGKey(0), 64, g.n_nodes)
        ns, nf = machine.noise_fn(jax.random.PRNGKey(1), 64)
        mean_s, _, _, _ = pbit.gibbs_stats(
            chip, jnp.asarray(g.color), m0, 1.0, 100, 20, ns, nf,
            jnp.asarray(g.edges))
        curves.append(np.asarray(mean_s))
    dt = time.perf_counter() - t0
    curves = np.stack(curves)            # (n_bias, 440)
    spread = curves.std(axis=1)
    out = {
        "biases": BIASES.tolist(),
        "mean_activation": curves.mean(axis=1).tolist(),
        "node_spread_per_bias": spread.tolist(),
        "max_node_spread": float(spread.max()),
        "n_nodes": int(g.n_nodes),
    }
    save_json("fig8a_variability", out)
    emit("fig8a_bias_sweep_point", dt / len(BIASES) * 1e6,
         f"max_spread={out['max_node_spread']:.3f}")
    return out


def run_fault_yield(quick: bool = False) -> dict:
    """Yield (fraction of virtual chips reaching YIELD_KL) vs fault rate."""
    g = make_chimera(1, 1)
    task = tasks.and_gate_task(g)
    n_chips = 2 if quick else 8
    rates = FAULT_RATES[:2] if quick else FAULT_RATES
    cfg = (CDConfig(epochs=6, chains=64, cd_k=4, pos_sweeps=4, burn_in=1)
           if quick else
           CDConfig(lr=6.0, cd_k=15, pos_sweeps=15, burn_in=3,
                    chains=256, epochs=50))
    rows = []
    t0 = time.perf_counter()
    for rate in rates:
        kls = []
        for chip_id in range(n_chips):
            faults = api.sample_faults(
                1000 * chip_id + int(rate * 1e4) + 1, g,
                stuck_rate=rate, dead_rate=rate,
                exclude_nodes=task.visible_idx)
            machine = PBitMachine.create(
                g, jax.random.PRNGKey(chip_id), HardwareConfig(),
                noise="counter", beta=1.0, w_scale=0.05, faults=faults)
            res = train_cd(machine, task.visible_idx, task.target_dist,
                           cfg, jax.random.PRNGKey(100 + chip_id),
                           eval_every=cfg.epochs)
            kls.append(float(res.kl_history[-1][1]))
        n_ok = sum(1 for k in kls if k < YIELD_KL)
        rows.append({"fault_rate": float(rate), "n_chips": n_chips,
                     "n_yielding": n_ok, "yield": n_ok / n_chips,
                     "kl_threshold": YIELD_KL,
                     "kls": [round(k, 4) for k in kls]})
        emit("fault_yield", (time.perf_counter() - t0) * 1e6,
             f"rate={rate} yield={n_ok}/{n_chips}")
    out = {"task": "and_gate", "graph": "chimera_1x1", "quick": quick,
           "epochs": cfg.epochs, "rows": rows}
    save_json("fault_yield", out)
    return out


def run(quick: bool = False) -> dict:
    results = {"fig8a": run_fig8a(), "fault_yield": run_fault_yield(quick)}
    if not quick:
        # tracked robustness trajectory: merge our section into the root
        # BENCH_kernel.json without clobbering bench_kernel's sections
        root = Path(__file__).resolve().parent.parent / "BENCH_kernel.json"
        merged = json.loads(root.read_text()) if root.exists() else {}
        merged["fault_yield"] = results["fault_yield"]
        root.write_text(json.dumps(merged, indent=1))
    return results


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small fleet / short training (CI smoke)")
    args = ap.parse_args()
    run(quick=args.quick)
