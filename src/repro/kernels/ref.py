"""Pure-jnp oracles for every Pallas kernel in this package.

`field_decision_update` is THE half-sweep field-accumulation body: eqn 2
(tanh activation, additive RNG, comparator sign, masked write) in one
place.  The dense ref, the sparse ref, and the sharded halo path
(kernels/shard_sweep.py) all call it, so a change to the neuron model —
or to the sync-policy machinery that replays it per shard — edits exactly
one term list.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def field_decision_update(m, I, gain, off, rand_gain, comp_off,
                          update_mask, beta, u):
    """Eqn 2 on a precomputed neuron input I: the shared half-sweep tail.

    m/I/u: (B, N);  gain/off/rand_gain/comp_off: (N,);  update_mask: (N,)
    bool;  beta: scalar or (B,) per-chain inverse temperature.  Exact op
    order is load-bearing: every backend (ref, Pallas, sparse, sharded)
    reproduces this sequence term for term, which is what makes them
    bit-exact against each other.
    """
    beta = jnp.asarray(beta, jnp.float32)
    if beta.ndim == 1:
        beta = beta[:, None]
    act = jnp.tanh(beta * gain * (I + off))
    decision = act + rand_gain * u + comp_off
    new = jnp.where(decision >= 0.0, 1.0, -1.0).astype(m.dtype)
    return jnp.where(update_mask, new, m)


def pbit_half_sweep_ref(m, W, h, gain, off, rand_gain, comp_off,
                        update_mask, beta, u):
    """Fused chromatic-Gibbs half-sweep, reference semantics.

    m: (B, N) spins in {-1, +1};  W: (N, N) directional couplings
    (I_i = sum_j W[i, j] m_j);  h/gain/off/rand_gain/comp_off: (N,);
    update_mask: (N,) bool;  beta: scalar or (B,) per-chain inverse
    temperature (parallel tempering replicas);  u: (B, N) uniform noise.
    """
    I = jnp.matmul(m, W.T, precision=jax.lax.Precision.HIGHEST) + h
    return field_decision_update(m, I, gain, off, rand_gain, comp_off,
                                 update_mask, beta, u)


def scatter_edge_slots(codes, edges, slot_ij, slot_ji, degree, n_nodes):
    """Scatter (E,) edge-list values into the (D, N) slot layout, both
    directions: out[slot_ij[e], edges[e, 0]] = out[slot_ji[e], edges[e, 1]]
    = codes[e].

    This is the hot half of runtime weight streaming — it runs inside the
    compiled sampling/CD closures with ``codes`` as a traced operand
    (edges/slot tables are static), turning a program swap into one
    O(E) scatter instead of a retrace.  ``codes`` may carry leading batch
    axes (a stacked program fleet): the scatter applies to the trailing
    edge axis.
    """
    codes = jnp.asarray(codes)
    out = jnp.zeros(codes.shape[:-1] + (degree, n_nodes), codes.dtype)
    return (out.at[..., slot_ij, edges[:, 0]].set(codes)
            .at[..., slot_ji, edges[:, 1]].set(codes))


def sparse_neuron_input(m, nbr_idx, nbr_w, h):
    """Eqn 1 on the fixed-degree slot layout: I = Σ_d w_d ⊙ m[:, idx_d] + h.

    m: (B, M) gather source; nbr_idx/nbr_w: (D, N) neighbor table
    (ChimeraGraph.neighbor_table + hardware.attach_sparse).  The output is
    (B, N) — normally M == N, but the sharded engine passes the
    halo-extended source [local | halo_up | halo_dn] (M = N + 2H) with a
    table re-indexed into it, which is how one body serves both the
    single-device and the sharded path.  O(B·N·D) instead of the dense
    O(B·N²) matmul.  Slots accumulate in ascending-d order — the identical
    op order the sparse Pallas kernel uses, so ref and kernel agree bit for
    bit; with neighbors sorted ascending it also reproduces the dense
    sequential row reduction exactly (zeros are additive identities).
    """
    D = nbr_idx.shape[0]
    acc = jnp.zeros((m.shape[0], nbr_idx.shape[1]), jnp.float32)
    for d in range(D):
        acc = acc + nbr_w[d][None, :] * jnp.take(m, nbr_idx[d], axis=1)
    return acc + h


def pbit_sparse_half_sweep_ref(m, nbr_idx, nbr_w, h, gain, off, rand_gain,
                               comp_off, update_mask, beta, u):
    """`pbit_half_sweep_ref` with the degree-D gather replacing the matmul."""
    I = sparse_neuron_input(m, nbr_idx, nbr_w, h)
    return field_decision_update(m, I, gain, off, rand_gain, comp_off,
                                 update_mask, beta, u)


def halo_exchange_segments(ex_pts, n_half):
    """Exchange points -> half-sweep windows [(h0, h1), ...] of a launch.

    The segmentation rule of the sharded engine's fused loop: a launch
    of ``n_half`` half-sweeps splits at its `Sync.exchange_points()` into
    contiguous windows, each preceded by one halo refresh and run as one
    `fused_shard_sweeps` call (``half_offset=h0, n_half=h1 - h0``).  A
    launch with exchanges at its boundary only, ``(0,)``, is one window.
    """
    pts = tuple(ex_pts)
    if not pts or pts[0] != 0:
        raise ValueError(f"exchange points must start at 0, got {pts}")
    if any(not 0 <= p < n_half for p in pts):
        raise ValueError(
            f"exchange points {pts} outside the launch's {n_half} "
            f"half-sweeps")
    return tuple(zip(pts, pts[1:] + (n_half,)))
