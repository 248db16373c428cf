"""Paper Fig 7b/7c: AND-gate Boltzmann learning on the mismatched chip.

Reports KL(target||model) and correlation error vs epoch, plus the central
hardware-aware-vs-transfer comparison (in-situ learning absorbs mismatch).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_json
from repro.core import energy, tasks
from repro.core.cd import CDConfig, PBitMachine, sample_visible_dist, train_cd
from repro.core.chimera import make_chimera
from repro.core.hardware import HardwareConfig

CFG = CDConfig(lr=6.0, cd_k=15, pos_sweeps=15, burn_in=3, chains=256,
               epochs=80)


def run() -> dict:
    g = make_chimera(1, 1)
    task = tasks.and_gate_task(g)
    chip_key = jax.random.PRNGKey(42)

    t0 = time.perf_counter()
    real = PBitMachine.create(g, chip_key, HardwareConfig(), beta=1.0,
                              w_scale=0.05)
    res_real = train_cd(real, task.visible_idx, task.target_dist, CFG,
                        jax.random.PRNGKey(7), eval_every=10)
    t_insitu = time.perf_counter() - t0

    ideal = PBitMachine.create(g, chip_key, HardwareConfig.ideal(),
                               beta=1.0, w_scale=0.05)
    res_ideal = train_cd(ideal, task.visible_idx, task.target_dist, CFG,
                         jax.random.PRNGKey(7), eval_every=CFG.epochs)

    kl_transfer = energy.kl_divergence(
        task.target_dist,
        sample_visible_dist(real, jnp.asarray(res_ideal.Jm),
                            jnp.asarray(res_ideal.hm), task.visible_idx,
                            jax.random.PRNGKey(3)))
    out = {
        "kl_vs_epoch": res_real.kl_history,
        "corr_err_first5": float(np.mean(
            [m["corr_err"] for m in res_real.metric_history[:5]])),
        "corr_err_last5": float(np.mean(
            [m["corr_err"] for m in res_real.metric_history[-5:]])),
        "kl_insitu_final": res_real.kl_history[-1][1],
        "kl_ideal_weights_on_mismatched_chip": kl_transfer,
        "epochs": CFG.epochs,
        "train_seconds": t_insitu,
    }
    save_json("fig7_and_gate", out)
    us = t_insitu / CFG.epochs * 1e6
    emit("fig7_and_gate_cd_epoch", us,
         f"KL_insitu={out['kl_insitu_final']:.3f};"
         f"KL_transfer={kl_transfer:.3f}")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    run()
