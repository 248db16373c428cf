"""Pallas TPU kernel: chain-batched Chimera-lattice half-sweep (SoA layout).

A standalone VPU kernel for the structure-of-arrays cell layout: for every
cell, the in-cell K44 coupling (4x4), the vertical/horizontal inter-cell
couplers, bias, tanh neuron and comparator — fused over a
(chains, rows, cols, 4) tile so spins, noise and couplings stream through
VMEM exactly once per half-sweep.

Layout choice (TPU-native): the trailing two dims are (cols*4) flattened to
a multiple of 128 lanes; chains ride the sublane dim.  The 4x4 cell einsum
is expressed as 4 shifted multiply-adds (k is tiny; an MXU matmul would
waste the 128x128 systolic array), so the kernel is pure VPU — matching the
chip, where the synapse is analog current summation, not a MAC array.

Halo handling: the caller passes spin planes already extended with their
neighbor rows/cols, so the kernel body is boundary-free.  Its original SoA
driver in core/distributed.py is retired (the sharded path runs the slot
layout, kernels/shard_sweep.py + docs/sharding.md); this kernel is the
starting point for the ROADMAP's sweep-resident *sharded* follow-on, where
the interior/boundary split lets S local sweeps fuse per launch.

Oracle: kernels/ref.py::lattice_vertical_update_ref; swept in
tests/test_kernels.py::test_lattice_kernel_*.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(mv_ref, mh_ref, mv_up_ref, mv_dn_ref,
            w_vh_ref, wv_up_ref, wv_dnin_ref, h_ref,
            gain_ref, u_ref, par_ref, out_ref, *, color: int, k: int):
    """Vertical-node update for one (chains, rows, cols*k) tile.

    I_v[b, r, c, i] = sum_j W_vh[r, c, i, j] * m_h[b, r, c, j]
                      + wv_dnin[r, c, i] * m_v_up[b, r, c, i]
                      + wv_up[r, c, i]   * m_v_dn[b, r, c, i] + h[r, c, i]
    m_v' = sgn(tanh(gain * I_v) + u) where cell parity == color.
    """
    mv = mv_ref[...]                    # (B, R, C, k)
    mh = mh_ref[...]
    acc = h_ref[...] + wv_dnin_ref[...] * mv_up_ref[...] + \
        wv_up_ref[...] * mv_dn_ref[...]
    # in-cell K_{k,k}: k shifted MALs instead of a 4-wide MXU matmul
    for j in range(k):
        acc = acc + w_vh_ref[..., j] * mh[..., j:j + 1]
    act = jnp.tanh(gain_ref[...] * acc)
    new = jnp.where(act + u_ref[...] >= 0.0, 1.0, -1.0)
    upd = (par_ref[...] == color)
    out_ref[...] = jnp.where(upd, new, mv).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("color", "block_r", "interpret"))
def lattice_vertical_update_pallas(
    m_v: jax.Array,        # (B, R, C, k) f32
    m_h: jax.Array,        # (B, R, C, k)
    m_v_up: jax.Array,     # (B, R, C, k) — neighbor spin from (r-1, c)
    m_v_dn: jax.Array,     # (B, R, C, k) — neighbor spin from (r+1, c)
    W_vh: jax.Array,       # (R, C, k, k)
    wv_up: jax.Array,      # (R, C, k) coupler into r from r+1
    wv_dnin: jax.Array,    # (R, C, k) coupler into r from r-1
    h: jax.Array,          # (R, C, k)
    gain: jax.Array,       # (R, C, k)  (beta folded in by the caller)
    u: jax.Array,          # (B, R, C, k) uniform noise
    parity: jax.Array,     # (R, C) int32 global cell parity
    *,
    color: int,
    block_r: int = 8,
    interpret: bool = True,
) -> jax.Array:
    """One fused vertical-node half-step of the chain-batched lattice."""
    B, R, C, k = m_v.shape
    assert R % block_r == 0, (R, block_r)
    grid = (R // block_r,)

    tile4 = lambda: pl.BlockSpec((B, block_r, C, k), lambda r: (0, r, 0, 0))
    tilew = lambda: pl.BlockSpec((block_r, C, k), lambda r: (r, 0, 0))

    in_specs = [
        tile4(), tile4(), tile4(), tile4(),                   # spins
        pl.BlockSpec((block_r, C, k, k), lambda r: (r, 0, 0, 0)),  # W_vh
        tilew(), tilew(), tilew(), tilew(),                   # couplers/bias/gain
        tile4(),                                              # noise
        pl.BlockSpec((B, block_r, C, 1), lambda r: (0, r, 0, 0)),  # parity
    ]
    par4 = jnp.broadcast_to(
        parity.astype(jnp.int32)[None, :, :, None], (B, R, C, 1))
    out = pl.pallas_call(
        functools.partial(_kernel, color=color, k=k),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((B, block_r, C, k), lambda r: (0, r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, R, C, k), m_v.dtype),
        interpret=interpret,
    )(m_v, m_h, m_v_up, m_v_dn, W_vh, wv_up, wv_dnin, h, gain, u, par4)
    return out
