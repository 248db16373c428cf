"""Device-local compute for the mesh-sharded sparse lattice.

The sharded execution layer (core/distributed.ShardedEngine) cuts the
Chimera cell grid into contiguous *row bands*, one per device along the
partition's rows axis.  Each device owns a padded (B, N_loc) spin block
plus the (D, N_loc) slice of the slot tables; the only non-local spins a
half-sweep ever reads are the chain-coupler boundary spins of the two row
neighbors — the ``halo_up`` / ``halo_dn`` blocks exchanged by
``jax.lax.ppermute`` in `halo_exchange`.

Both device-local sweep bodies are the SAME code as the single-device
backends:

  * `halo_half_sweep` is `kernels/ref.py::sparse_neuron_input` +
    `field_decision_update` with the gather source extended from the
    local block to [local | halo_up | halo_dn] — one shared term list,
    so a sharded half-sweep is *bit-exact* against the single-device
    sparse scan (and therefore the dense ref) for the same noise stream.
  * `fused_shard_sweeps` runs S *resident* sweeps on the same extended
    block through `kernels/sweep_fused.py::sweep_sparse_pallas`: halo
    columns are frozen (excluded from the update masks) and the
    in-kernel counter RNG is shifted to this shard's global
    (chain, node) coordinates via ``coord_offset``, so the kernel
    consumes exactly the columns of the noise stream the scan path
    would.  This is the per-shard engine behind launch-resident
    `api.Sync` policies (docs/sharding.md §Sync policies).  A policy
    with exchange points inside the launch runs it as half-sweep
    windows (``half_offset``/``n_half``), one per exchange point, with a
    ppermute between windows, all in one jitted graph (docs/kernels.md
    §Fused exchange windows).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ref import field_decision_update, sparse_neuron_input
from repro.kernels.sweep_fused import sweep_sparse_pallas


def halo_exchange(
    m_loc: jax.Array,
    send_up: jax.Array,
    send_dn: jax.Array,
    axis_name,
    n_shards: int,
) -> tuple[jax.Array, jax.Array]:
    """Exchange boundary spins with the row neighbors.

    m_loc: (B, N_loc) local spins; send_up/send_dn: (H,) local indices of
    the vertical nodes in the band's first/last cell row (padded with 0 —
    padding halo slots are never referenced by any neighbor table entry).
    Returns (halo_up, halo_dn), each (B, H): the down-boundary of the
    device above and the up-boundary of the device below.  Edge devices
    receive zeros (open lattice boundary, matching the dense path where
    those couplers simply do not exist).  O(B·H) bytes per device pair —
    the O(√N) inter-cell wires of the chip, nothing else ever moves.
    """
    up_src = jnp.take(m_loc, send_dn, axis=1)  # my last row -> device below
    dn_src = jnp.take(m_loc, send_up, axis=1)  # my first row -> device above
    if axis_name is None or n_shards <= 1:
        return jnp.zeros_like(up_src), jnp.zeros_like(dn_src)
    halo_up = jax.lax.ppermute(
        up_src, axis_name, [(i, i + 1) for i in range(n_shards - 1)])
    halo_dn = jax.lax.ppermute(
        dn_src, axis_name, [(i + 1, i) for i in range(n_shards - 1)])
    return halo_up, halo_dn


def halo_neuron_input(
    m_loc: jax.Array,
    halo_up: jax.Array,
    halo_dn: jax.Array,
    nbr_idx: jax.Array,
    nbr_w: jax.Array,
    h: jax.Array,
) -> jax.Array:
    """Eqn 1 on the local slot tables: I = Σ_d w_d ⊙ m_ext[:, idx_d] + h.

    nbr_idx: (D, N_loc) indices into the *extended* array
    [local | halo_up | halo_dn]; nbr_w: (D, N_loc) local slot weights.
    Literally `kernels/ref.py::sparse_neuron_input` on the extended
    gather source — the one shared accumulation body (ascending-d order,
    zero init, ``+ h`` last) that keeps the sharded path bit-exact vs the
    single-device backends.
    """
    m_ext = jnp.concatenate([m_loc, halo_up, halo_dn], axis=1)
    return sparse_neuron_input(m_ext, nbr_idx, nbr_w, h)


def halo_half_sweep(m_loc, halo_up, halo_dn, nbr_idx, nbr_w, h, gain, off,
                    rand_gain, comp_off, update_mask, beta, u):
    """The sparse half-sweep with the halo-extended gather source.

    m_loc/u: (B, N_loc); update_mask: (N_loc,) bool (padding lanes False);
    beta: scalar or (B,) per-chain inverse temperature.  The decision tail
    is the shared `kernels/ref.py::field_decision_update`.
    """
    I = halo_neuron_input(m_loc, halo_up, halo_dn, nbr_idx, nbr_w, h)
    return field_decision_update(m_loc, I, gain, off, rand_gain, comp_off,
                                 update_mask, beta, u)


def _halo_free(nbr_idx, pad2):
    """The ext-local table widened over the halo columns, which take no
    source (-1): they are never updated, and a table entry there would
    cost every slot group of the kernel's gather its own lane rotations."""
    return jnp.pad(jnp.asarray(nbr_idx, jnp.int32), ((0, 0), (0, pad2)),
                   constant_values=-1)


def fused_shard_sweeps(
    m_loc: jax.Array,            # (B, N_loc) local spins
    halo_up: jax.Array,          # (B, H) frozen for the whole call
    halo_dn: jax.Array,          # (B, H)
    nbr_idx: jax.Array,          # (D, N_loc) ext-local neighbor table
    nbr_w: jax.Array,            # (D, N_loc) slot weights
    h: jax.Array,
    gain: jax.Array,
    off: jax.Array,
    rand_gain: jax.Array,
    comp_off: jax.Array,
    mask0: jax.Array,            # (N_loc,) bool color-0 update set
    mask1: jax.Array,            # (N_loc,) bool
    betas: jax.Array,            # (S,) or (S, B) per-launch schedule slice
    noise_state: jax.Array,      # (2,) uint32 counter state
    row0: jax.Array,             # uint32 global id of this device's chain 0
    col0: jax.Array,             # uint32 global id of local node 0
    clamp_mask: jax.Array | None = None,    # (N_loc,) bool
    clamp_values: jax.Array | None = None,  # (B, N_loc)
    measured: jax.Array | None = None,      # (S,) moment weights
    *,
    block_b: int = 128,
    interpret: bool = True,
    half_offset: int = 0,
    n_half: int | None = None,
):
    """One sweep-resident launch on the halo-extended local block.

    Runs S full sweeps inside a single `sweep_sparse_pallas` call: spins
    stay in VMEM, counter noise is generated in-kernel at the shard's
    global (chain, node) coordinates, and (optionally) CD moments
    accumulate in the kernel's scratch.  Halo columns ride along in the
    extended array but are excluded from every update mask, so they stay
    frozen at the values of the exchange before the call — exactly the
    staleness the launch-resident `api.Sync` policies define.  Bands are
    contiguous global id ranges, so a single scalar ``col0`` places the
    whole block in the global noise grid.

    Returns (m', noise_state'), with ``measured``
    (m', noise_state', s_sum[N_loc], c_slots[D, N_ext]) — raw sums over
    (chains × measured sweeps); ``c_slots[d, i] = Σ m_i·m_ext[idx[d, i]]``
    with i ext-local (boundary edges read the frozen halo; halo columns
    read 0).

    ``half_offset``/``n_half`` run only that half-sweep window of the
    launch (`sweep_sparse_pallas` segmented-window contract): the
    engine's fused loop calls one window per halo segment of
    `kernels/ref.py::halo_exchange_segments`, re-exchanging halos in
    between, all inside one jitted graph.  Chained windows equal the
    unsplit launch bit for bit.
    """
    B, n_loc = m_loc.shape
    H = halo_up.shape[1]
    pad2 = 2 * H
    m_ext = jnp.concatenate([m_loc, halo_up, halo_dn], axis=1)
    zb = jnp.zeros((pad2,), bool)
    zf = jnp.zeros((pad2,), jnp.float32)

    def row(x):
        return jnp.concatenate([jnp.asarray(x, jnp.float32), zf])

    idx_e = _halo_free(nbr_idx, pad2)
    w_e = jnp.pad(jnp.asarray(nbr_w, jnp.float32), ((0, 0), (0, pad2)))
    betas = jnp.asarray(betas, jnp.float32)
    if betas.ndim == 1:
        betas = jnp.broadcast_to(betas[:, None], (betas.shape[0], B))
    cm_e = cv_e = None
    if clamp_mask is not None and clamp_values is not None:
        cm_e = jnp.concatenate([clamp_mask, zb])
        cv_e = jnp.pad(jnp.asarray(clamp_values, jnp.float32),
                       ((0, 0), (0, pad2)))
    coords = jnp.stack([jnp.asarray(row0, jnp.uint32),
                        jnp.asarray(col0, jnp.uint32)])
    outs = sweep_sparse_pallas(
        m_ext, idx_e, w_e, row(h), row(gain), row(off), row(rand_gain),
        row(comp_off), jnp.concatenate([mask0, zb]),
        jnp.concatenate([mask1, zb]), betas, noise_state,
        clamp_mask=cm_e, clamp_values=cv_e, measured=measured,
        coord_offset=coords, noise_mode="counter",
        accumulate=measured is not None, block_b=block_b,
        interpret=interpret, half_offset=half_offset, n_half=n_half)
    m_out = outs[0][:, :n_loc]
    if measured is None:
        return m_out, outs[1]
    return m_out, outs[1], outs[2][:n_loc], outs[3]
