"""LFSR random number generation, faithful to the chip.

The chip drives each Chimera unit cell with a 32-bit LFSR (clocked from 64
decimated random clocks derived from two 200 MHz LFSRs).  Each 32-bit LFSR
exposes only 4 unique bytes per cycle; the four *vertical* nodes of a cell
consume the bytes in normal bit order while the four *horizontal* nodes
consume the bit-reversed bytes (the paper's area-saving trick; measured to
cause no performance degradation — we test that claim in
tests/test_lfsr.py::test_reversed_byte_correlation).

We implement a Galois LFSR over uint32 with the maximal-length polynomial
x^32 + x^22 + x^2 + x + 1 (mask 0x80200003).  All ops vectorize over an
arbitrary leading shape of independent LFSR states, so (chains, cells) runs
as one fused update on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GALOIS_MASK_32 = np.uint32(0x80200003)  # x^32 + x^22 + x^2 + x + 1
_BYTE_REV = np.array(
    [int(f"{b:08b}"[::-1], 2) for b in range(256)], dtype=np.uint32
)


def seed_states(key: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    """Nonzero uint32 LFSR states of the given shape."""
    bits = jax.random.bits(key, shape, dtype=jnp.uint32)
    return jnp.where(bits == 0, jnp.uint32(0xDEADBEEF), bits)


def lfsr_step(state: jax.Array) -> jax.Array:
    """One Galois LFSR clock. state: uint32[...]"""
    lsb = state & jnp.uint32(1)
    shifted = state >> jnp.uint32(1)
    return jnp.where(lsb == 1, shifted ^ GALOIS_MASK_32, shifted)


def lfsr_step_n(state: jax.Array, n: int) -> jax.Array:
    """Advance every state by ``n`` clocks (unrolled; n is small/static)."""
    for _ in range(n):
        state = lfsr_step(state)
    return state


def cell_bytes(state: jax.Array) -> jax.Array:
    """Extract the 4 bytes of each 32-bit state. uint32[...] -> uint32[..., 4]."""
    shifts = jnp.array([0, 8, 16, 24], dtype=jnp.uint32)
    return (state[..., None] >> shifts) & jnp.uint32(0xFF)


def reverse_bytes_bits(b: jax.Array) -> jax.Array:
    """Bit-reverse each byte (uint32 values in [0,256))."""
    table = jnp.asarray(_BYTE_REV)
    return table[b]


def byte_to_uniform(b: jax.Array) -> jax.Array:
    """Map a byte to a mid-tread uniform in (-1, 1), as the 8-bit RNG DAC does.

    Converts through int32 (exact: the value is below 256) because Mosaic
    has no uint32 -> float32 cast.
    """
    return (b.astype(jnp.int32).astype(jnp.float32) - 127.5) / 128.0


def reverse_byte_bits_swar(b: jax.Array) -> jax.Array:
    """Bit-reverse each byte with shift/mask ops only (no table gather).

    Equivalent to ``reverse_bytes_bits`` but kernel-friendly: inside a Pallas
    TPU kernel a 256-entry table lookup is a gather, while this is three VPU
    shift/or rounds.  Used by the fused sweep engine's in-kernel LFSR.
    """
    b = ((b & jnp.uint32(0xF0)) >> jnp.uint32(4)) | \
        ((b & jnp.uint32(0x0F)) << jnp.uint32(4))
    b = ((b & jnp.uint32(0xCC)) >> jnp.uint32(2)) | \
        ((b & jnp.uint32(0x33)) << jnp.uint32(2))
    b = ((b & jnp.uint32(0xAA)) >> jnp.uint32(1)) | \
        ((b & jnp.uint32(0x55)) << jnp.uint32(1))
    return b


def cell_uniforms(state: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-cell uniforms for (vertical[..., 4], horizontal[..., 4]) nodes."""
    by = cell_bytes(state)
    return byte_to_uniform(by), byte_to_uniform(reverse_bytes_bits(by))


def flat_cell_uniforms(state: jax.Array) -> jax.Array:
    """Uniforms in the flat byte-major layout [v0..v3, h0..h3] x cells.

    state: uint32[..., C].  Returns float32[..., 8*C] where column
    ``k*C + cell`` is vertical byte k of ``cell`` and ``(4+k)*C + cell`` is
    the bit-reversed (horizontal) byte k.  The host reference; the fused
    kernel reads the same bytes per node (`node_byte_uniforms`).
    """
    parts = []
    for k in range(4):
        b = (state >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)
        parts.append(byte_to_uniform(b))
    for k in range(4):
        b = (state >> jnp.uint32(8 * k)) & jnp.uint32(0xFF)
        parts.append(byte_to_uniform(reverse_byte_bits_swar(b)))
    return jnp.concatenate(parts, axis=-1)


def node_byte_uniforms(state: jax.Array, byte_sel: jax.Array) -> jax.Array:
    """Per-node uniforms from per-node copies of the cell registers.

    state: uint32[..., N], node i holding its cell's register; byte_sel:
    uint32 broadcastable to state, ``perm // n_cells`` of
    ``node_gather_perm`` (0..3 vertical byte k, 4..7 bit-reversed byte
    k-4).  Equal to ``flat_cell_uniforms`` gathered by that perm, with
    shift/mask ops only — the fused kernel's in-kernel LFSR read.
    """
    b = (state >> ((byte_sel & jnp.uint32(3)) << jnp.uint32(3))) \
        & jnp.uint32(0xFF)
    b = jnp.where(byte_sel >= jnp.uint32(4), reverse_byte_bits_swar(b), b)
    return byte_to_uniform(b)


def node_gather_perm(vert_scatter, horiz_scatter, n_nodes: int) -> np.ndarray:
    """Inverse permutation: node id -> column of ``flat_cell_uniforms``.

    One precomputed gather replaces the two dynamic-update scatters the old
    ``lfsr_uniform_for_graph`` issued per noise step.
    """
    vert = np.asarray(vert_scatter)
    horiz = np.asarray(horiz_scatter)
    n_cells, k = vert.shape
    perm = np.zeros(n_nodes, dtype=np.int32)
    cells = np.arange(n_cells, dtype=np.int32)
    for kk in range(k):
        perm[vert[:, kk]] = kk * n_cells + cells
        perm[horiz[:, kk]] = (k + kk) * n_cells + cells
    return perm


def next_uniforms(state: jax.Array, decimation: int = 8
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Advance states ``decimation`` clocks and emit fresh cell uniforms.

    Returns (new_state, vert_u[..., 4], horiz_u[..., 4]).  The chip refreshes
    one byte-worth of entropy per sample (decimated clocking); decimation=8
    reproduces that.
    """
    state = lfsr_step_n(state, decimation)
    v, h = cell_uniforms(state)
    return state, v, h


def lfsr_uniform_for_graph(
    state: jax.Array,
    vert_scatter: jax.Array,
    horiz_scatter: jax.Array,
    n_nodes: int,
    decimation: int = 8,
    gather_perm: np.ndarray | jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Produce per-node uniforms for a Chimera graph.

    state: uint32[..., n_cells]; *_scatter: int32[n_cells, 4] node ids
    (vertical / horizontal nodes of each cell, compacted numbering).
    Returns (new_state, u[..., n_nodes]).

    One ``take`` with the precomputed inverse permutation replaces the old
    pair of ``.at[...].set`` scatters (each of which materialized a fresh
    (..., n_nodes) buffer per noise step).  Pass ``gather_perm`` (from
    ``node_gather_perm``) to skip rebuilding it per call.
    """
    state = lfsr_step_n(state, decimation)
    if gather_perm is None:
        # traceable fallback (scatter tables may be traced jax arrays);
        # precompute with node_gather_perm + pass gather_perm to skip it
        n_cells, k = vert_scatter.shape
        cols = jnp.arange(n_cells, dtype=jnp.int32)
        gather_perm = jnp.zeros((n_nodes,), jnp.int32)
        for kk in range(k):
            gather_perm = gather_perm.at[vert_scatter[:, kk]].set(
                kk * n_cells + cols)
            gather_perm = gather_perm.at[horiz_scatter[:, kk]].set(
                (k + kk) * n_cells + cols)
    flat = flat_cell_uniforms(state)
    u = jnp.take(flat, jnp.asarray(gather_perm), axis=-1)
    return state, u


# ---------------------------------------------------------------------------
# Counter-based (stateless) RNG — the fused kernel's "scale mode" noise
# ---------------------------------------------------------------------------
def mix32(x: jax.Array) -> jax.Array:
    """Avalanche finalizer (lowbias32 constants). uint32 -> uint32."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    return x


def counter_bits(seed: jax.Array, ctr: jax.Array,
                 row: jax.Array, col: jax.Array) -> jax.Array:
    """Stateless hash of (seed, step counter, chain row, node col) -> uint32.

    Pure uint32 shift/mul/xor arithmetic: the identical expression runs on
    the host (reference path) and inside the fused Pallas kernel, so the two
    are bit-exact by construction.
    """
    x = mix32(jnp.uint32(seed) ^ (jnp.uint32(ctr) * jnp.uint32(0x9E3779B9)))
    x = mix32(x
              ^ (row.astype(jnp.uint32) * jnp.uint32(0x85EBCA77))
              ^ (col.astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)))
    return x


def counter_uniform(seed: jax.Array, ctr: jax.Array,
                    row: jax.Array, col: jax.Array) -> jax.Array:
    """Counter-mode uniform in (-1, 1), quantized like the 8-bit RNG DAC."""
    return byte_to_uniform(counter_bits(seed, ctr, row, col)
                           & jnp.uint32(0xFF))
