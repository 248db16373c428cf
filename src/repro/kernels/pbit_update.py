"""Pallas TPU kernel: fused chromatic-Gibbs half-sweep (paper eqns 1+2).

One half-sweep is  m_c <- sgn( tanh(beta*g*(m @ W_c^T + h + o)) + rg*u + co )
for one color class.  On the chip this is a single analog settle; on TPU we
fuse the synapse matmul (MXU), the neuron nonlinearity (VPU) and the
comparator into one kernel so the (B, N) neuron currents never round-trip
through HBM.

Tiling: grid (B/tb, N/tn, N/tk) with a float32 VMEM accumulator; the K loop
(contraction over source spins) is the innermost, sequential grid dim.  All
tiles are MXU-aligned (multiples of 8x128 lanes; defaults 128/128/512).
Beta enters as a (B, 1) column so every chain can run its own inverse
temperature (parallel-tempering replicas) with no SMEM scalar plumbing;
scalars are broadcast to the column outside the kernel.

Validated in interpret mode against kernels/ref.py over shape/dtype sweeps
(tests/test_kernels.py); the on-silicon path is the same code with
interpret=False.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.util import pad_axis as _pad_to

from jax.experimental.pallas import tpu as pltpu


def _kernel(m_k_ref, w_ref, m_io_ref, h_ref, gain_ref, off_ref,
            rg_ref, co_ref, mask_ref, u_ref, beta_ref, out_ref, acc_ref,
            *, n_k: int):
    """Grid: (i: batch tiles, j: node tiles, k: contraction tiles)."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # synapse: partial current I[b, jtile] += m[b, ktile] @ W[jtile, ktile]^T
    acc_ref[...] += jax.lax.dot_general(
        m_k_ref[...], w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == n_k - 1)
    def _neuron():
        I = acc_ref[...] + h_ref[...]                      # (tb, tn)
        # beta is a per-chain column (tempering replicas run one beta each);
        # (tb, 1) * (1, tn) broadcasts to the tile
        act = jnp.tanh(beta_ref[...] * gain_ref[...] * (I + off_ref[...]))
        decision = act + rg_ref[...] * u_ref[...] + co_ref[...]
        new = jnp.where(decision >= 0.0, 1.0, -1.0)
        keep = mask_ref[...] != 0
        out_ref[...] = jnp.where(
            keep, new, m_io_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_b", "block_n", "block_k", "interpret"),
)
def pbit_half_sweep_pallas(
    m: jax.Array,
    W: jax.Array,
    h: jax.Array,
    gain: jax.Array,
    off: jax.Array,
    rand_gain: jax.Array,
    comp_off: jax.Array,
    update_mask: jax.Array,
    beta: jax.Array,
    u: jax.Array,
    *,
    block_b: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """Fused half-sweep.  Shapes/semantics identical to kernels/ref.py.

    Pads B to block_b and N to lcm-ish(block_n, block_k) multiples;
    zero-padded source spins contribute nothing to the matmul, and padded
    output nodes are masked off and sliced away.  ``beta`` may be a scalar
    or a (B,) per-chain vector (parallel-tempering replicas).
    """
    B, N = m.shape
    out_dtype = m.dtype
    nmult = max(block_n, block_k)

    beta_col = jnp.broadcast_to(
        jnp.asarray(beta, jnp.float32).reshape(-1, 1), (B, 1))
    bp = _pad_to(beta_col, block_b, 0)
    mp = _pad_to(_pad_to(m, block_b, 0), nmult, 1)
    Wp = _pad_to(_pad_to(W, nmult, 0), nmult, 1)
    up = _pad_to(_pad_to(u, block_b, 0), nmult, 1)
    row = lambda x, v=0.0: _pad_to(x.reshape(1, -1).astype(jnp.float32),
                                   nmult, 1, v)
    hp, gp, op_, rgp, cop = (row(x) for x in
                             (h, gain, off, rand_gain, comp_off))
    maskp = _pad_to(update_mask.reshape(1, -1).astype(jnp.int8), nmult, 1, 0)

    Bp, Np = mp.shape
    n_b, n_n, n_k = Bp // block_b, Np // block_n, Np // block_k

    vec = lambda: pl.BlockSpec((1, block_n), lambda i, j, k: (0, j))
    grid = (n_b, n_n, n_k)
    in_specs = [
            pl.BlockSpec((block_b, block_k), lambda i, j, k: (i, k)),  # m (matmul)
            pl.BlockSpec((block_n, block_k), lambda i, j, k: (j, k)),  # W
            pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),  # m (carry)
            vec(), vec(), vec(), vec(), vec(),                         # h,g,off,rg,co
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),        # mask (int8)
            pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j)),  # u
            pl.BlockSpec((block_b, 1), lambda i, j, k: (i, 0)),        # beta col
    ]
    out_specs = pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, j))
    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct((Bp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_b, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(mp, Wp, mp, hp, gp, op_, rgp, cop, maskp, up, bp)
    return out[:B, :N]
