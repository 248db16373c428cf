"""Jitted public wrappers around the Pallas kernels.

`make_kernel_half_sweep` adapts the per-half-sweep kernel to the sampler's
`half_sweep(m, chip, update_mask, beta, u)` signature (see core/pbit.py).
`sparse_half_sweep` is the same adapter for the Chimera-native fixed-degree
slot layout (jnp gather path — the "sparse" backend).
`fused_sweeps` adapts the sweep-resident engine (kernels/sweep_fused.py) —
dense or block-sparse — to the chip + graph-color view the backend API in
core/pbit.py works with, so the whole CD / annealing / tempering stack can
run through any kernel with one flag (see docs/kernels.md).
`fused_visible_hist` is the streaming visible-pattern histogram entry point
used by cd.sample_visible_dist.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.hardware import EffectiveChip
from repro.kernels.pbit_update import pbit_half_sweep_pallas
from repro.kernels.ref import pbit_half_sweep_ref, pbit_sparse_half_sweep_ref
from repro.kernels.sweep_fused import sweep_fused_pallas, sweep_sparse_pallas


def default_interpret() -> bool:
    """Interpret mode off the TPU (CPU tests), compiled kernels on it."""
    return jax.default_backend() != "tpu"


def make_kernel_half_sweep(block_b: int = 128, block_n: int = 128,
                           block_k: int = 512,
                           interpret: bool | None = None):
    interp = default_interpret() if interpret is None else interpret

    def half_sweep(m, chip: EffectiveChip, update_mask, beta, u):
        return pbit_half_sweep_pallas(
            m, chip.W, chip.h, chip.tanh_gain, chip.tanh_offset,
            chip.rand_gain, chip.comp_offset, update_mask, beta, u,
            block_b=block_b, block_n=block_n, block_k=block_k,
            interpret=interp)

    return half_sweep


def ref_half_sweep(m, chip: EffectiveChip, update_mask, beta, u):
    return pbit_half_sweep_ref(
        m, chip.W, chip.h, chip.tanh_gain, chip.tanh_offset,
        chip.rand_gain, chip.comp_offset, update_mask, beta, u)


def _require_sparse(chip: EffectiveChip) -> None:
    if chip.nbr_w is None or chip.nbr_idx is None:
        raise ValueError(
            "sparse backend needs a chip carrying the neighbor-table "
            "layout; program with neighbors=graph.neighbor_table()[0], use "
            "hardware.attach_sparse, or hardware.program_weights_sparse")


def sparse_half_sweep(m, chip: EffectiveChip, update_mask, beta, u):
    """jnp half-sweep on the fixed-degree slot layout (no dense W)."""
    _require_sparse(chip)
    return pbit_sparse_half_sweep_ref(
        m, chip.nbr_idx, chip.nbr_w, chip.h, chip.tanh_gain,
        chip.tanh_offset, chip.rand_gain, chip.comp_offset,
        update_mask, beta, u)


def _fused_common(chip, color, betas, B, noise_spec, clamp_mask, sparse):
    if noise_spec is None or noise_spec.kind not in ("counter", "lfsr"):
        kind = None if noise_spec is None else noise_spec.kind
        raise ValueError(
            f"fused backend needs in-kernel noise ('counter' or 'lfsr'), "
            f"got {kind!r}; build the noise fn with make_counter_noise or "
            f"make_lfsr_noise")
    if sparse:
        _require_sparse(chip)
    elif chip.W is None:
        raise ValueError(
            "dense fused backend needs a chip with a dense W; this chip is "
            "sparse-native (W=None) — use backend='fused_sparse' or "
            "'sparse'")
    betas = jnp.asarray(betas, jnp.float32)
    if betas.ndim == 1:
        betas = jnp.broadcast_to(betas[:, None], (betas.shape[0], B))
    mask0 = (color == 0)
    mask1 = (color == 1)
    if clamp_mask is not None:
        mask0 = mask0 & ~clamp_mask
        mask1 = mask1 & ~clamp_mask
    return betas, mask0, mask1


def fused_sweeps(
    m: jax.Array,
    chip: EffectiveChip,
    color: jax.Array,
    betas: jax.Array,               # (S,) or (S, B)
    noise_state: jax.Array,
    noise_spec,                     # core/pbit.py NoiseSpec
    clamp_mask: jax.Array | None = None,
    clamp_values: jax.Array | None = None,
    measured: jax.Array | None = None,
    *,
    sparse: bool = False,
    block_b: int = 128,
    interpret: bool | None = None,
):
    """Run S resident sweeps through the fused engine.

    Returns (m', noise_state') or, when ``measured`` is given,
    (m', noise_state', s_sum[N], c_sum) — raw sums over
    (chains x measured sweeps); divide by B * sum(measured).  c_sum is the
    (N, N) Gram matrix on the dense path and the (D, N) per-slot edge
    correlations on the sparse path (read edge (i, j) at
    ``c_sum[slot_of(i→j), i]``, see ChimeraGraph.edge_slots).
    """
    interp = default_interpret() if interpret is None else interpret
    betas, mask0, mask1 = _fused_common(
        chip, color, betas, m.shape[0], noise_spec, clamp_mask, sparse)
    kw = dict(
        clamp_mask=clamp_mask, clamp_values=clamp_values, measured=measured,
        noise_mode=noise_spec.kind, decimation=noise_spec.decimation,
        gather_perm=noise_spec.gather_perm,
        accumulate=measured is not None,
        block_b=block_b, interpret=interp)
    if sparse:
        return sweep_sparse_pallas(
            m, chip.nbr_idx, chip.nbr_w, chip.h, chip.tanh_gain,
            chip.tanh_offset, chip.rand_gain, chip.comp_offset,
            mask0, mask1, betas, noise_state, **kw)
    return sweep_fused_pallas(
        m, chip.W, chip.h, chip.tanh_gain, chip.tanh_offset,
        chip.rand_gain, chip.comp_offset, mask0, mask1, betas, noise_state,
        **kw)


def fused_visible_hist(
    m: jax.Array,
    chip: EffectiveChip,
    color: jax.Array,
    betas: jax.Array,
    noise_state: jax.Array,
    noise_spec,
    visible_idx,
    measured: jax.Array,            # (S,) histogram weights (burn-in mask)
    *,
    sparse: bool = False,
    block_b: int = 128,
    interpret: bool | None = None,
):
    """S resident sweeps + in-kernel visible-pattern histogram.

    Returns (m', noise_state', hist[2^nv]) — hist counts each measured
    sweep's visible bit pattern per chain (energy.empirical_visible_dist
    code order); the (S, B, N) trajectory never exists anywhere.
    """
    interp = default_interpret() if interpret is None else interpret
    betas, mask0, mask1 = _fused_common(
        chip, color, betas, m.shape[0], noise_spec, None, sparse)
    nv = int(len(visible_idx))
    kw = dict(
        measured=measured, visible_idx=jnp.asarray(visible_idx, jnp.int32),
        noise_mode=noise_spec.kind, decimation=noise_spec.decimation,
        gather_perm=noise_spec.gather_perm,
        collect_hist=True, n_visible=nv,
        block_b=block_b, interpret=interp)
    if sparse:
        return sweep_sparse_pallas(
            m, chip.nbr_idx, chip.nbr_w, chip.h, chip.tanh_gain,
            chip.tanh_offset, chip.rand_gain, chip.comp_offset,
            mask0, mask1, betas, noise_state, **kw)
    return sweep_fused_pallas(
        m, chip.W, chip.h, chip.tanh_gain, chip.tanh_offset,
        chip.rand_gain, chip.comp_offset, mask0, mask1, betas, noise_state,
        **kw)
