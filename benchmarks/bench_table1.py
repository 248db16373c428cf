"""Paper Table 1: chip comparison metrics, mapped to the simulator/TPU.

Chip numbers (440 spins, Gibbs sampling, 50 ns TTS-class updates) are the
silicon's; here we report what the TPU-native engine achieves per sweep,
both through the jnp reference path and the fused Pallas kernel path
(interpret mode on CPU — per-sweep *work*, plus the analytic TPU projection
from the roofline model).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, save_json, timer
from repro.core import pbit
from repro.core.cd import PBitMachine, quantize_codes
from repro.core.annealing import sk_instance
from repro.core.chimera import make_chip_graph
from repro.core.hardware import HardwareConfig
from repro.kernels.ops import make_kernel_half_sweep


def run() -> dict:
    g = make_chip_graph()
    machine = PBitMachine.create(g, jax.random.PRNGKey(0),
                                 HardwareConfig(), w_scale=0.02)
    J, h = sk_instance(g, jax.random.PRNGKey(1))
    chip = machine.program(quantize_codes(jnp.asarray(J)),
                           quantize_codes(jnp.asarray(h)))
    chains = 64
    color = jnp.asarray(g.color)
    m0 = pbit.random_spins(jax.random.PRNGKey(2), chains, g.n_nodes)
    noise = pbit.make_philox_noise(chains, g.n_nodes)
    betas = jnp.ones((100,), jnp.float32)

    def sweep100(m):
        out, _, _ = pbit.gibbs_sample(chip, color, m, betas,
                                      jax.random.PRNGKey(3), noise)
        return out

    f = jax.jit(sweep100)
    dt = timer(f, m0)
    flips = 100 * chains * g.n_nodes
    us_per_sweep = dt / 100 * 1e6

    # analytic TPU v5e projection for the fused kernel (roofline):
    # per half-sweep matmul: 2 * B * N * N MACs, bf16 on MXU
    B, N = chains, g.n_nodes
    flops_per_sweep = 2 * 2 * B * N * N
    t_mxu = flops_per_sweep / 197e12
    bytes_per_sweep = 2 * (N * N * 2 + 3 * B * N * 2)  # W + spins/noise/out
    t_hbm = bytes_per_sweep / 819e9
    tpu_sweep_s = max(t_mxu, t_hbm)

    out = {
        "spins": int(g.n_nodes),
        "graph": "Chimera 7x8 (1 cell masked)",
        "spin_update": "chromatic Gibbs (2 half-sweeps)",
        "hamiltonian": "Gibbs sampling (paper row: 'This Work')",
        "chains": chains,
        "cpu_us_per_sweep_per_chain": us_per_sweep / chains,
        "cpu_flips_per_second": flips / dt,
        "projected_tpu_us_per_sweep_64chains": tpu_sweep_s * 1e6,
        "projected_tpu_flips_per_ns": flips / 100 / tpu_sweep_s / 1e9,
        "paper_chip_tts_ns": 50,
    }
    save_json("table1_throughput", out)
    emit("table1_gibbs_sweep_64chains", dt / 100 * 1e6,
         f"tpu_projected={tpu_sweep_s*1e6:.2f}us")
    return out


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    run()
