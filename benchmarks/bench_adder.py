"""Paper Fig 8b: full-adder distribution learning on the mismatched chip."""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, save_json
from repro.core import tasks
from repro.core.cd import CDConfig, PBitMachine, train_cd
from repro.core.chimera import make_chimera
from repro.core.hardware import HardwareConfig

CFG = CDConfig(lr=6.0, cd_k=15, pos_sweeps=15, burn_in=3, chains=256,
               epochs=100)


def run() -> dict:
    g = make_chimera(1, 2)
    machine = PBitMachine.create(g, jax.random.PRNGKey(9),
                                 HardwareConfig(), beta=1.0, w_scale=0.05)
    task = tasks.full_adder_task(g)
    t0 = time.perf_counter()
    res = train_cd(machine, task.visible_idx, task.target_dist, CFG,
                   jax.random.PRNGKey(1), eval_every=20)
    dt = time.perf_counter() - t0
    out = {
        "kl_vs_epoch": res.kl_history,
        "kl_final": res.kl_history[-1][1],
        "kl_uniform_baseline": float(np.log(32 / 8)),  # 8 valid rows of 32
        "epochs": CFG.epochs,
        "train_seconds": dt,
    }
    save_json("fig8b_full_adder", out)
    emit("fig8b_full_adder_cd_epoch", dt / CFG.epochs * 1e6,
         f"KL_final={out['kl_final']:.3f}")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    run()
