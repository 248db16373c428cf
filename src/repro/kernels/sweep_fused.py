"""Pallas TPU kernel: sweep-resident sampling engine (dense + block-sparse).

The chip's figure of merit is flips per nanosecond: all 440 neurons settle
in parallel with per-cell LFSR noise generated *in place*.  The per-half-
sweep kernel (pbit_update.py) still round-trips spins and noise through HBM
twice per sweep and leaves moment accumulation to separate jnp ops.  This
kernel closes that gap: one invocation executes S full chromatic sweeps
(both color half-sweeps) with

  * spins resident in VMEM for the whole S-sweep block,
  * noise generated inside the kernel — either counter mode (a stateless
    uint32 hash shared bit-for-bit with the host reference in
    core/lfsr.py::counter_uniform) or chip-faithful mode (the Galois LFSR of
    core/lfsr.py advanced in-kernel, including the bit-reversed-byte sharing
    trick, bit-exact with the host LFSR stream),
  * optional on-line first/second moment accumulation (spin sums and either
    the full m^T m Gram matrix or, in sparse mode, the per-slot edge
    correlations) in VMEM scratch, so CD's `gibbs_stats` never materializes
    per-sweep state in HBM,
  * optional on-line visible-pattern histogramming (one-hot reduction over
    2^n_visible bins per sweep), so `sample_visible_dist` never collects a
    trajectory.

Two weight layouts share the kernel body:

  * dense  (`sweep_fused_pallas`)  — W (N, N) in VMEM, neuron input is a
    (tb, N) x (N, N) matmul.  W alone is 4·N² bytes, which bounds the
    resident engine to roughly N <= 1.5k fp32 on a 16 MB-VMEM core.
  * sparse (`sweep_sparse_pallas`) — the Chimera-native fixed-degree slot
    layout (ChimeraGraph.neighbor_table): nbr_idx/nbr_w (D, N) with D = 6
    on the chip's graph.  Neuron input is D gathers (built from the lane
    rotations of each slot group, `_gather_rows`) + multiply-adds, over
    row blocks of the tile small enough to stay in vector registers —
    2·B·N·D FLOPs instead of 2·B·N², and 8·D·N weight bytes instead of
    4·N², so ≥32k-spin lattices stay VMEM-resident.  Slots accumulate in
    ascending-neighbor order, making the result bit-exact against both the
    sparse jnp ref and (zeros being additive identities) the dense path.

`sweep_sparse_stream_pallas` adds runtime weight streaming to the sparse
engine: the NEXT program's (D, N)/(N,) weights ride the same launch,
stage into a second VMEM slot at grid step 0 (overlapping the current
program's S sweeps — the SpikeHard DMA model), and come back as staged
outputs aliased in place over the inputs, ready to be the next launch's
resident program.

Grid: (B/tb,) over batch tiles; each program owns its rows for all S
sweeps.  Moment/histogram scratch accumulates across the (sequential)
batch-tile grid and is flushed to the output on the last program, the same
revisiting pattern as the K-loop accumulator in pbit_update.py.

Validated bit-for-bit in interpret mode against a scan of the
kernels/ref.py oracles with host-side noise (tests/test_sweep_fused.py,
tests/test_sparse.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import lfsr as lfsr_mod
from repro.kernels.util import pad_axis as _pad_axis
from repro.kernels.util import round_up as _round_up

_VMEM = pltpu.VMEM
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)

NOISE_COUNTER = "counter"
NOISE_LFSR = "lfsr"

MAX_HIST_VISIBLE = 12  # one-hot reduction over 2^nv bins; keep it VMEM-sane
_GATHER_VREGS = 48     # vector registers (of 64) a row block's gather holds
_ROLLS_PER_STEP = 16   # lane rotations per step of the sparse gather loops


def _slot_groups(n_slots: int) -> tuple[tuple[int, ...], ...]:
    """Slots gathered together, each rotation rolled once for the group:
    the first and the second half of the slots, in slot order.

    Sharing a rotation saves lane rotations, the cross-lane unit's work,
    and costs each slot of the group a compare and a select per shared
    rotation.  On the chip graph the halves need 14 and 13 rotations, 27
    in all, against 53 one slot at a time and 19 for all six at once;
    compiled for a v5e, halves take the fewest bundles (docs/kernels.md).
    """
    h = -(-n_slots // 2)
    return tuple(g for g in (tuple(range(h)), tuple(range(h, n_slots))) if g)


def _lane_rotations(idx, n_cols: int, Np: int, groups):
    """Lane rotations that realize the column gathers ``idx``, per group
    of rows.

    idx: (R, W) int source columns; only the first ``n_cols`` columns are
    real, and idx < 0 means the lane takes no source.  Since
    ``jnp.roll(x, s, axis=-1)[:, i] == x[:, (i - s) mod Np]``, lane i of
    row r wants the rotation ``s = (i - idx[r, i]) mod Np``.  Returns

    * ``shifts``, int32 (R, W rounded up to 128): that s at every lane
      with a source, -1 at every other lane, so no rotation selects it;
    * ``table``, int32 (len(groups) * (1 + Np),): group g's row at offset
      ``g * (1 + Np)`` is ``[K_g, s_0, .., s_{K_g - 1}, s_{K_g - 1}, ..]``,
      the K_g distinct shifts its rows use, ascending, then the last one
      repeated (a repeat selects the same lanes again).  At most Np
      rotations exist, so a row never truncates.  On the chip graph the
      six slots alone use 10, 10, 10, 10, 10 and 3; all six, 19.
    """
    idx = jnp.asarray(idx, jnp.int32)
    R, W = idx.shape
    lane = jnp.arange(W, dtype=jnp.int32)[None, :]
    real = (idx >= 0) & (lane < n_cols)
    shifts = jnp.where(real, (lane - idx) % Np, -1)
    used = jnp.zeros((R, Np + 1), bool).at[
        jnp.arange(R)[:, None], jnp.where(real, shifts, Np)].set(True)[:, :Np]
    rows = []
    for g in groups:
        present = used[jnp.asarray(g)].any(0)
        listed = jnp.nonzero(present, size=Np, fill_value=Np)[0]
        last = jnp.max(jnp.where(present, jnp.arange(Np), 0))
        rows.append(jnp.concatenate([
            jnp.sum(present, dtype=jnp.int32)[None],
            jnp.where(listed < Np, listed, last).astype(jnp.int32)]))
    return _pad_axis(shifts, 128, 1, -1), jnp.concatenate(rows)


def _gather_rows(x, sh_ref, rot_ref, g: int, rows, per_step: int = 1):
    """In-kernel column gathers for the rows of group g of a
    `_lane_rotations` table: ``out[r][:, i] = x[:, idx[r, i]]``, 0 where
    lane i of row r has no source.

    Mosaic gathers only inside one 128-lane vreg, so a gather across a
    multi-vreg row is built from whole-row lane rotations instead: each
    of the group's rotations is rolled once, and every row keeps the
    lanes whose shift it is.  Pure selection — every output element is
    one input element, bit for bit.  x: (rows, Np); ``sh_ref``:
    `_lane_rotations`' shifts, (R, W) with W <= Np.  Each loop step
    issues ``per_step`` rotations (a divisor of 128, so the padded table
    row is never overrun).  Returns one (x rows, W) gather per row.
    """
    Np = x.shape[-1]
    base = g * (1 + Np)
    W = sh_ref.shape[-1]

    def step(j, gs):
        for u in range(per_step):
            s = rot_ref[base + 1 + j * per_step + u]
            xr = pltpu.roll(x, s, 1)[:, :W]
            # compare the shifts broadcast to the block: comparing the
            # (1, W) row and broadcasting its mask costs mask moves
            gs = tuple(jnp.where(jnp.broadcast_to(sh_ref[pl.ds(r, 1), :],
                                                  xr.shape) == s, xr, gr)
                       for r, gr in zip(rows, gs))
        return gs

    n_steps = (rot_ref[base] + per_step - 1) // per_step
    return jax.lax.fori_loop(
        0, n_steps, step,
        tuple(jnp.zeros((x.shape[0], W), x.dtype) for _ in rows))


def _slot_gathers(x, sh_ref, rot_ref, D: int, per_step: int = 1):
    """(slot, gather) pairs of x for the D slots, in slot order, each
    `_slot_groups` group gathered by one `_gather_rows`."""
    for gi, rows in enumerate(_slot_groups(D)):
        yield from zip(rows, _gather_rows(x, sh_ref, rot_ref, gi, rows,
                                          per_step))


def _noise_state_out(noise_in, n_half):
    """Counter state (seed, ctr) advanced by n_half ticks, as one (1, 2)
    vector (Mosaic cannot store scalars to VMEM)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2), 1)
    return noise_in + jnp.where(lane == 1, jnp.uint32(n_half),
                                jnp.uint32(0))


def _block_rows(tb: int, Np: int, D: int) -> int:
    """Rows of the half-sweep's row blocks on the sparse path.

    The gather walks the (tb, Np) tile in (hb, Np) row blocks whose
    working set — the spins, the rolled copy, a slot group's gathers and
    the accumulator: G + 3 slabs of (hb, Np) f32, G the larger
    `_slot_groups` group — takes at most `_GATHER_VREGS` vector
    registers, so it is not spilled to VMEM.  hb is a multiple of 8 that
    divides tb (the rows are independent chains), and 8 at the least.  A
    tile whose height is no multiple of 8 (a ``block_b`` that is none,
    over the whole batch) is one block."""
    if tb % 8:
        return tb
    slabs = max(len(g) for g in _slot_groups(D)) + 3
    per8 = slabs * (Np // 128)                # vregs per 8 rows
    return 8 * max((k for k in range(1, tb // 8 + 1)
                    if (tb // 8) % k == 0 and k * per8 <= _GATHER_VREGS),
                   default=1)


def _kernel(*refs, S: int, tb: int, hb: int, Np: int, n_b: int, B: int,
            noise_mode: str, has_clamp: bool, accumulate: bool,
            collect_hist: bool, decimation: int, sparse: bool, D: int,
            NBp: int, has_coords: bool, stream: bool = False,
            half_offset: int = 0, n_half: int | None = None):
    it = iter(refs)
    m0_ref = next(it)
    if sparse:
        sh_ref = next(it)                     # (Dp, Np) lane shifts
        w_ref = next(it)                      # (Dp, Np) slot weights
        rot_ref = next(it)                    # SMEM per-slot rotations
    else:
        w_ref = next(it)                      # (Np, Np) dense couplings
    h_ref, g_ref, off_ref, rg_ref, co_ref = (next(it) for _ in range(5))
    mask_refs = (next(it), next(it))
    betas_ref = next(it)
    clampm_ref = next(it) if has_clamp else None
    clampv_ref = next(it) if has_clamp else None
    meas_ref = next(it) if (accumulate or collect_hist) else None  # SMEM
    binw_ref = next(it) if collect_hist else None  # (1, Np) 2^k at vis k
    byte_ref = next(it) if noise_mode == NOISE_LFSR else None  # (1, Np)
    coords_ref = next(it) if has_coords else None
    noise_in_ref = next(it)
    if stream:
        next_w_ref = next(it)                 # (Dp, Np) next program weights
        next_h_ref = next(it)                 # (1, Np) next program biases
    m_out_ref = next(it)                      # f32 spins, updated in place
    noise_out_ref = next(it)
    if accumulate:
        ssum_out_ref, csum_out_ref = next(it), next(it)
    if collect_hist:
        hist_out_ref = next(it)
    if stream:
        staged_w_out_ref, staged_h_out_ref = next(it), next(it)
    bcol_ref = next(it)                       # (tb, 1) this sweep's betas
    if accumulate:
        ssum_ref, csum_ref = next(it), next(it)
        if sparse:
            corr_ref = next(it)               # (Dp, Np) one sweep's sums
    if collect_hist:
        hist_ref = next(it)
    if stream:
        slot_w_ref, slot_h_ref = next(it), next(it)

    i = pl.program_id(0)

    if accumulate:
        @pl.when(i == 0)
        def _zero_moments():
            ssum_ref[...] = jnp.zeros_like(ssum_ref)
            csum_ref[...] = jnp.zeros_like(csum_ref)
    if collect_hist:
        @pl.when(i == 0)
        def _zero_hist():
            hist_ref[...] = jnp.zeros_like(hist_ref)
    if stream:
        # double-buffered program upload (the SpikeHard DMA model): the
        # NEXT program's weights stream into the second VMEM slot up
        # front, before this launch's S resident sweeps touch the loop —
        # independent of the sweep dataflow, so the copy overlaps compute
        # on hardware.  Flushed to the staged outputs on the last block;
        # the host feeds them straight back as the following launch's
        # resident program (zero-copy: the next-program inputs alias the
        # staged outputs via input_output_aliases).
        @pl.when(i == 0)
        def _stage_next_program():
            slot_w_ref[...] = next_w_ref[...]
            slot_h_ref[...] = next_h_ref[...]

    # the spins (and the LFSR registers) live in their output blocks for
    # the whole launch; each half-sweep rewrites them one row block at a
    # time, so no tile-sized value is carried through the loops
    m_out_ref[...] = m0_ref[...].astype(jnp.float32)
    if noise_mode == NOISE_COUNTER:
        seed = noise_in_ref[0, 0]
        ctr0 = noise_in_ref[0, 1]
        # (row0, col0) shift the hash coordinates to this block's place in
        # the GLOBAL (chain, node) grid — the sharded engine passes its
        # chain offset / first global node id so every shard regenerates
        # exactly its columns of the single-device stream
        row0 = coords_ref[0, 0] if has_coords else jnp.uint32(0)
        col0 = coords_ref[0, 1] if has_coords else jnp.uint32(0)
    else:
        # (tb, Np) LFSR states replicated onto each cell's nodes: every
        # node reads its own byte in place, so no gather is needed
        noise_out_ref[...] = noise_in_ref[...]

    def neuron_current(m):
        """Eqn 1 over a row block: matmul (dense) or D slot gathers, each
        multiply-added in slot order once its group is gathered."""
        if sparse:
            acc = jnp.zeros(m.shape, jnp.float32)
            for d, g in _slot_gathers(m, sh_ref, rot_ref, D,
                                      _ROLLS_PER_STEP):
                acc = acc + w_ref[pl.ds(d, 1), :] * g
            return acc + h_ref[...]
        return jax.lax.dot_general(
            m, w_ref[...], dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) + h_ref[...]

    def row_blocks(body, carry):
        """Run ``body(rows, r0, carry)`` over the tile's (hb, Np) row
        blocks; ``rows`` slices the block, ``r0`` is its first row."""
        def step(r, carry):
            r0 = pl.multiple_of(r * hb, hb)
            return body(pl.ds(r0, hb), r0, carry)
        return jax.lax.fori_loop(0, tb // hb, step, carry)

    # Launch-relative half-sweep window.  The fused-exchange engine splits
    # one logical launch into segments at halo exchange points, so a
    # segment may start mid-sweep (odd half_offset: the color-1 half that
    # FINISHES sweep half_offset//2) and end mid-sweep (a trailing color-0
    # half whose sweep the next segment completes).  The noise counter
    # advances by LOCAL halves — the engine threads noise_state between
    # segments, so ctr0 already encodes half_offset — while betas /
    # measured keep full-launch sweep indices.  Defaults (half_offset=0,
    # n_half=None) reproduce the classic whole-launch loop exactly.
    n_half_eff = 2 * S if n_half is None else n_half
    lead = half_offset % 2
    n_full = max(n_half_eff - lead, 0) // 2
    tail = max(n_half_eff - lead, 0) % 2
    s0 = (half_offset + lead) // 2

    def impose_clamp():
        if has_clamp:
            m_out_ref[...] = jnp.where(clampm_ref[...] != 0, clampv_ref[...],
                                       m_out_ref[...])

    def half_update(s_idx, c, half_j):
        """One color half-sweep of (launch-relative) sweep s_idx."""
        bcol_ref[...] = betas_ref[pl.ds(s_idx, 1), :].reshape(tb, 1)

        def block(rows, r0, carry):
            m = m_out_ref[rows, :]
            if noise_mode == NOISE_COUNTER:
                shape = (hb, Np)
                u = lfsr_mod.counter_uniform(
                    seed, ctr0 + half_j,
                    jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
                    + (i * tb + r0).astype(jnp.uint32) + row0,
                    jax.lax.broadcasted_iota(jnp.uint32, shape, 1) + col0)
            else:
                st = lfsr_mod.lfsr_step_n(noise_out_ref[rows, :], decimation)
                noise_out_ref[rows, :] = st
                u = lfsr_mod.node_byte_uniforms(st, byte_ref[...])
            I = neuron_current(m)
            act = jnp.tanh(bcol_ref[rows, :] * g_ref[...]
                           * (I + off_ref[...]))
            decision = act + rg_ref[...] * u + co_ref[...]
            new = jnp.where(decision >= 0.0, 1.0, -1.0)
            m_out_ref[rows, :] = jnp.where(mask_refs[c][...] != 0, new, m)
            return carry

        row_blocks(block, 0)

    def sweep_stats(s_idx):
        """Accumulate moments/histogram after sweep s_idx completes.
        Every per-sweep sum is of +-1 products, so it is exact in any
        order; it is scaled by the sweep's weight once, whole."""
        wgt = meas_ref[s_idx]                                   # scalar
        if accumulate and sparse:
            def block(rows, r0, spin_sum):
                # padded batch rows update like real chains; keep them out
                # of the statistics
                row_ids = (jax.lax.broadcasted_iota(jnp.int32, (hb, 1), 0)
                           + i * tb + r0)
                mv = jnp.where(row_ids < B, m_out_ref[rows, :], 0.0)
                for d, g in _slot_gathers(mv, sh_ref, rot_ref, D,
                                          _ROLLS_PER_STEP):
                    corr_ref[pl.ds(d, 1), :] += jnp.sum(mv * g, axis=0,
                                                        keepdims=True)
                return spin_sum + jnp.sum(mv, axis=0, keepdims=True)

            corr_ref[...] = jnp.zeros_like(corr_ref)
            spin_sum = row_blocks(block, jnp.zeros((1, Np), jnp.float32))
            ssum_ref[...] += wgt * spin_sum
            for d in range(D):
                csum_ref[pl.ds(d, 1), :] += wgt * corr_ref[pl.ds(d, 1), :]
        if not (collect_hist or accumulate and not sparse):
            return
        m = m_out_ref[...]
        row_ids = (jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0) + i * tb)
        if accumulate and not sparse:
            mv = jnp.where(row_ids < B, m, 0.0)
            ssum_ref[...] += wgt * jnp.sum(mv, axis=0, keepdims=True)
            csum_ref[...] += wgt * jax.lax.dot_general(
                mv, mv, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # m^T m
        if collect_hist:
            # visible node k carries weight 2^k, every other node 0
            codes = jnp.sum(jnp.where(m > 0, binw_ref[...], 0),
                            axis=1, keepdims=True)               # (tb, 1)
            bin_ids = jax.lax.broadcasted_iota(jnp.int32, (tb, NBp), 1)
            onehot = ((codes == bin_ids)
                      & (row_ids < B)).astype(jnp.float32)
            hist_ref[...] += wgt * jnp.sum(onehot, axis=0, keepdims=True)

    if lead:
        # clamp re-imposition is idempotent (clamped nodes are excluded
        # from the color masks), so repeating it at a mid-sweep segment
        # boundary is bit-identical to the unsplit launch
        impose_clamp()
        half_update(half_offset // 2, 1, jnp.uint32(0))
        if accumulate or collect_hist:
            sweep_stats(half_offset // 2)

    def one_sweep(jj, carry):
        impose_clamp()
        for c in (0, 1):
            hj = (jnp.uint32(lead) + jnp.uint32(2) * jj.astype(jnp.uint32)
                  + jnp.uint32(c))
            half_update(s0 + jj, c, hj)
        if accumulate or collect_hist:
            sweep_stats(s0 + jj)
        return carry

    jax.lax.fori_loop(0, n_full, one_sweep, 0)
    if tail:
        impose_clamp()
        half_update(s0 + n_full, 0, jnp.uint32(lead + 2 * n_full))

    if noise_mode == NOISE_COUNTER:
        noise_out_ref[...] = _noise_state_out(noise_in_ref[...], n_half_eff)

    if accumulate:
        @pl.when(i == n_b - 1)
        def _flush_moments():
            ssum_out_ref[...] = ssum_ref[...]
            csum_out_ref[...] = csum_ref[...]
    if collect_hist:
        @pl.when(i == n_b - 1)
        def _flush_hist():
            hist_out_ref[...] = hist_ref[...]
    if stream:
        @pl.when(i == n_b - 1)
        def _flush_staged_program():
            staged_w_out_ref[...] = slot_w_ref[...]
            staged_h_out_ref[...] = slot_h_ref[...]


def _launch(
    m, dense_W, nbr_idx, nbr_w, h, gain, off, rand_gain, comp_off,
    mask0, mask1, betas, noise_state, clamp_mask, clamp_values, measured,
    visible_idx, *, sparse, noise_mode, decimation, gather_perm,
    accumulate, collect_hist, n_visible, block_b, interpret,
    coord_offset=None, next_nbr_w=None, next_h=None,
    half_offset=0, n_half=None,
):
    """Shared plumbing for the dense and sparse sweep-resident engines."""
    B, N = m.shape
    S = betas.shape[0]
    # normalize the half-sweep window: n_half=None means "to launch end"
    n_half = 2 * S - half_offset if n_half is None else n_half
    if not (0 <= half_offset and 0 <= n_half
            and half_offset + n_half <= 2 * S):
        raise ValueError(
            f"half-sweep window [{half_offset}, {half_offset + n_half}) "
            f"falls outside the launch's 2*S={2 * S} half-sweeps")
    out_dtype = m.dtype
    stream = next_nbr_w is not None
    if stream:
        if not sparse or noise_mode != NOISE_COUNTER:
            raise ValueError(
                "program streaming runs on the sparse counter-noise "
                "engine (the launch-resident serving configuration)")
        if next_h is None:
            raise ValueError("next_nbr_w without next_h")
        if accumulate or collect_hist or measured is not None:
            raise ValueError(
                "program streaming excludes in-kernel moment/histogram "
                "accumulation — a swapped program invalidates the "
                "accumulators mid-grid")
    # clamp_mask alone (freeze nodes at their current spins) is fully
    # handled by excluding the nodes from mask0/mask1; the kernel only
    # needs the clamp inputs when values are re-imposed every sweep
    has_clamp = clamp_mask is not None and clamp_values is not None
    accumulate = accumulate and measured is not None
    collect_hist = collect_hist and measured is not None
    if noise_mode not in (NOISE_COUNTER, NOISE_LFSR):
        raise ValueError(f"unknown noise_mode {noise_mode!r}")
    if collect_hist:
        if visible_idx is None:
            raise ValueError("collect_hist needs visible_idx")
        if not (0 < n_visible <= MAX_HIST_VISIBLE):
            raise ValueError(
                f"collect_hist supports 1..{MAX_HIST_VISIBLE} visible "
                f"nodes, got {n_visible}")
    if sparse:
        D = nbr_idx.shape[0]
    NB = 2 ** n_visible if collect_hist else 0

    if S == 0:  # empty schedule: identity, like a zero-length scan
        outs = [m, jnp.asarray(noise_state, jnp.uint32)]
        if accumulate:
            c_shape = (D, N) if sparse else (N, N)
            outs += [jnp.zeros((N,), jnp.float32),
                     jnp.zeros(c_shape, jnp.float32)]
        if collect_hist:
            outs.append(jnp.zeros((NB,), jnp.float32))
        if stream:
            outs += [jnp.asarray(next_nbr_w, jnp.float32),
                     jnp.asarray(next_h, jnp.float32)]
        return tuple(outs)

    Np = _round_up(N, 128)
    tb = min(block_b, _round_up(B, 8))
    Bp = _round_up(B, tb)
    n_b = Bp // tb

    mp = _pad_axis(_pad_axis(m, tb, 0), 128, 1)
    row = lambda x, v=0.0: _pad_axis(
        jnp.asarray(x).reshape(1, -1).astype(jnp.float32), 128, 1, v)
    hp, gp, op_, rgp, cop = (row(x) for x in
                             (h, gain, off, rand_gain, comp_off))
    m0p = _pad_axis(jnp.asarray(mask0).reshape(1, -1).astype(jnp.int8),
                    128, 1, 0)
    m1p = _pad_axis(jnp.asarray(mask1).reshape(1, -1).astype(jnp.int8),
                    128, 1, 0)
    betasp = _pad_axis(jnp.asarray(betas, jnp.float32), tb, 1)

    vec = lambda: pl.BlockSpec((1, Np), lambda i: (0, 0))
    in_specs = [pl.BlockSpec((tb, Np), lambda i: (i, 0))]       # m
    args = [mp]
    if sparse:
        Dp = _round_up(D, 8)
        wp = _pad_axis(_pad_axis(
            jnp.asarray(nbr_w, jnp.float32), Dp, 0), 128, 1)
        shifts, rotations = _lane_rotations(nbr_idx, N, Np, _slot_groups(D))
        in_specs += [pl.BlockSpec((Dp, Np), lambda i: (0, 0)),  # lane shifts
                     pl.BlockSpec((Dp, Np), lambda i: (0, 0)),  # nbr_w
                     _SMEM]                                     # rotations
        args += [_pad_axis(shifts, Dp, 0, -1), wp, rotations]
    else:
        Wp = _pad_axis(_pad_axis(dense_W, 128, 0), 128, 1)
        in_specs.append(pl.BlockSpec((Np, Np), lambda i: (0, 0)))  # W
        args.append(Wp)
    in_specs += [vec(), vec(), vec(), vec(), vec(),             # h,g,off,rg,co
                 vec(), vec(),                                  # color masks
                 pl.BlockSpec((S, tb), lambda i: (0, i))]       # betas
    args += [hp, gp, op_, rgp, cop, m0p, m1p, betasp]

    if has_clamp:
        in_specs.append(vec())
        args.append(_pad_axis(
            jnp.asarray(clamp_mask).reshape(1, -1).astype(jnp.int8),
            128, 1, 0))
        in_specs.append(pl.BlockSpec((tb, Np), lambda i: (i, 0)))
        args.append(_pad_axis(_pad_axis(
            jnp.asarray(clamp_values, jnp.float32), tb, 0), 128, 1))
    if accumulate or collect_hist:
        in_specs.append(_SMEM)
        args.append(jnp.asarray(measured, jnp.float32).reshape(S))
    NBp = 0
    if collect_hist:
        NBp = _round_up(NB, 128)
        binw = jnp.zeros((Np,), jnp.int32).at[
            jnp.asarray(visible_idx, jnp.int32)].add(
            jnp.asarray(2 ** np.arange(n_visible, dtype=np.int32)))
        in_specs.append(vec())
        args.append(binw.reshape(1, Np))

    has_coords = coord_offset is not None
    if has_coords:
        if noise_mode != NOISE_COUNTER:
            raise ValueError(
                "coord_offset shifts the counter hash's (chain, node) "
                "coordinates; the lfsr mode carries its cell band in the "
                "state instead")
        in_specs.append(pl.BlockSpec((1, 2), lambda i: (0, 0)))
        args.append(jnp.asarray(coord_offset, jnp.uint32).reshape(1, 2))
    if noise_mode == NOISE_COUNTER:
        in_specs.append(pl.BlockSpec((1, 2), lambda i: (0, 0)))
        args.append(jnp.asarray(noise_state, jnp.uint32).reshape(1, 2))
        noise_out_shape = jax.ShapeDtypeStruct((1, 2), jnp.uint32)
        noise_out_spec = pl.BlockSpec((1, 2), lambda i: (0, 0))
    else:
        if gather_perm is None:
            raise ValueError("lfsr noise_mode needs gather_perm "
                             "(see core/lfsr.py::node_gather_perm)")
        # flat LFSR column perm[i] = byte * C + cell: node i reads byte
        # `byte` (4..7: bit-reversed) of its cell's register.  The kernel
        # steps one register copy per node, so each node reads its byte
        # in place; every copy of a cell evolves identically, and the
        # cell's first node hands its copy back.
        C = noise_state.shape[-1]
        p = np.asarray(gather_perm, np.int64)
        node_cell, node_byte = p % C, p // C
        first = np.full(C, -1, np.int64)
        first[node_cell[::-1]] = np.arange(N)[::-1]
        if (first < 0).any():
            raise ValueError("gather_perm leaves an LFSR cell without nodes")
        in_specs.append(vec())
        args.append(_pad_axis(
            jnp.asarray(node_byte, jnp.uint32).reshape(1, N), 128, 1))
        stp = _pad_axis(_pad_axis(
            jnp.asarray(noise_state, jnp.uint32)[:, node_cell],
            tb, 0, 1), 128, 1, 1)
        in_specs.append(pl.BlockSpec((tb, Np), lambda i: (i, 0)))
        args.append(stp)
        noise_out_shape = jax.ShapeDtypeStruct((Bp, Np), jnp.uint32)
        noise_out_spec = pl.BlockSpec((tb, Np), lambda i: (i, 0))

    aliases = {}
    if stream:
        # the next program rides the SAME launch as the current sweeps:
        # two O(D·N) operands appended after the noise state, aliased to
        # the staged outputs (in-place buffer handoff — the upload costs
        # no extra HBM round-trip, matching the chip's SPI-write-during-
        # anneal overlap)
        i_next = len(args)
        in_specs += [pl.BlockSpec((Dp, Np), lambda i: (0, 0)),
                     pl.BlockSpec((1, Np), lambda i: (0, 0))]
        args += [_pad_axis(_pad_axis(
            jnp.asarray(next_nbr_w, jnp.float32), Dp, 0), 128, 1),
            row(next_h)]
        aliases = {i_next: 2, i_next + 1: 3}

    out_shape = [jax.ShapeDtypeStruct((Bp, Np), jnp.float32),
                 noise_out_shape]
    out_specs = [pl.BlockSpec((tb, Np), lambda i: (i, 0)), noise_out_spec]
    scratch = [_VMEM((tb, 1), jnp.float32)]
    if accumulate:
        c_shape = (Dp, Np) if sparse else (Np, Np)
        out_shape += [jax.ShapeDtypeStruct((1, Np), jnp.float32),
                      jax.ShapeDtypeStruct(c_shape, jnp.float32)]
        out_specs += [pl.BlockSpec((1, Np), lambda i: (0, 0)),
                      pl.BlockSpec(c_shape, lambda i: (0, 0))]
        scratch += [_VMEM((1, Np), jnp.float32), _VMEM(c_shape, jnp.float32)]
        if sparse:
            scratch.append(_VMEM((Dp, Np), jnp.float32))
    if collect_hist:
        out_shape.append(jax.ShapeDtypeStruct((1, NBp), jnp.float32))
        out_specs.append(pl.BlockSpec((1, NBp), lambda i: (0, 0)))
        scratch.append(_VMEM((1, NBp), jnp.float32))
    if stream:
        out_shape += [jax.ShapeDtypeStruct((Dp, Np), jnp.float32),
                      jax.ShapeDtypeStruct((1, Np), jnp.float32)]
        out_specs += [pl.BlockSpec((Dp, Np), lambda i: (0, 0)),
                      pl.BlockSpec((1, Np), lambda i: (0, 0))]
        scratch += [_VMEM((Dp, Np), jnp.float32),
                    _VMEM((1, Np), jnp.float32)]

    outs = pl.pallas_call(
        functools.partial(
            _kernel, S=S, tb=tb,
            hb=_block_rows(tb, Np, D) if sparse else tb, Np=Np, n_b=n_b, B=B,
            noise_mode=noise_mode, has_clamp=has_clamp,
            accumulate=accumulate, collect_hist=collect_hist,
            decimation=decimation, sparse=sparse,
            D=D if sparse else 0, NBp=NBp, has_coords=has_coords,
            stream=stream, half_offset=half_offset, n_half=n_half),
        grid=(n_b,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)

    result = [outs[0][:B, :N].astype(out_dtype)]
    if noise_mode == NOISE_COUNTER:
        result.append(outs[1].reshape(2))
    else:
        result.append(outs[1][:B, first])
    k = 2
    if accumulate:
        result.append(outs[k][0, :N])
        result.append(outs[k + 1][:D, :N] if sparse else outs[k + 1][:N, :N])
        k += 2
    if collect_hist:
        result.append(outs[k][0, :NB])
        k += 1
    if stream:
        result.append(outs[k][:D, :N])
        result.append(outs[k + 1][0, :N])
    return tuple(result)


@functools.partial(
    jax.jit,
    static_argnames=("noise_mode", "decimation", "gather_perm", "accumulate",
                     "collect_hist", "n_visible", "block_b", "interpret"),
)
def sweep_fused_pallas(
    m: jax.Array,                 # (B, N) spins in {-1, +1}
    W: jax.Array,                 # (N, N) directional couplings
    h: jax.Array,
    gain: jax.Array,
    off: jax.Array,
    rand_gain: jax.Array,
    comp_off: jax.Array,
    mask0: jax.Array,             # (N,) bool — color-0 update set (minus clamps)
    mask1: jax.Array,             # (N,) bool — color-1 update set (minus clamps)
    betas: jax.Array,             # (S, B) per-sweep, per-chain inverse temps
    noise_state: jax.Array,       # counter: (2,) uint32; lfsr: (B, C) uint32
    clamp_mask: jax.Array | None = None,     # (N,) bool
    clamp_values: jax.Array | None = None,   # (B, N)
    measured: jax.Array | None = None,       # (S,) statistic weights, or None
    visible_idx: jax.Array | None = None,    # (n_visible,) histogram nodes
    coord_offset: jax.Array | None = None,   # (2,) uint32 (row0, col0)
    *,
    noise_mode: str = NOISE_COUNTER,
    decimation: int = 8,
    gather_perm: tuple | None = None,   # node -> flat LFSR column (length N)
    accumulate: bool = False,
    collect_hist: bool = False,
    n_visible: int = 0,
    block_b: int = 128,
    interpret: bool = True,
):
    """Run S resident sweeps, dense layout.

    Returns ``(m', noise_state'[, s_sum, c_sum][, hist])``.
    s_sum: (N,) sum of spins over (chains x measured sweeps); c_sum: (N, N)
    accumulated Gram matrix sum_meas m^T m — extract edge correlations as
    ``c_sum[e0, e1]``.  hist: (2^n_visible,) weighted counts of visible bit
    patterns (energy.empirical_visible_dist code order).  All need dividing
    by their sample counts.  ``coord_offset`` (counter mode only) shifts
    the in-kernel hash to global (chain, node) coordinates — the sharded
    per-shard launch passes (chain0, node0) so each shard regenerates its
    own columns of the single-device noise stream.
    """
    return _launch(
        m, W, None, None, h, gain, off, rand_gain, comp_off, mask0, mask1,
        betas, noise_state, clamp_mask, clamp_values, measured, visible_idx,
        sparse=False, noise_mode=noise_mode, decimation=decimation,
        gather_perm=gather_perm, accumulate=accumulate,
        collect_hist=collect_hist, n_visible=n_visible, block_b=block_b,
        interpret=interpret, coord_offset=coord_offset)


@functools.partial(
    jax.jit,
    static_argnames=("noise_mode", "decimation", "gather_perm", "accumulate",
                     "collect_hist", "n_visible", "block_b", "interpret",
                     "half_offset", "n_half"),
)
def sweep_sparse_pallas(
    m: jax.Array,                 # (B, N) spins in {-1, +1}
    nbr_idx: jax.Array,           # (D, N) int32 neighbor table
    nbr_w: jax.Array,             # (D, N) per-slot couplings
    h: jax.Array,
    gain: jax.Array,
    off: jax.Array,
    rand_gain: jax.Array,
    comp_off: jax.Array,
    mask0: jax.Array,
    mask1: jax.Array,
    betas: jax.Array,             # (S, B)
    noise_state: jax.Array,
    clamp_mask: jax.Array | None = None,
    clamp_values: jax.Array | None = None,
    measured: jax.Array | None = None,
    visible_idx: jax.Array | None = None,
    coord_offset: jax.Array | None = None,
    *,
    noise_mode: str = NOISE_COUNTER,
    decimation: int = 8,
    gather_perm: tuple | None = None,
    accumulate: bool = False,
    collect_hist: bool = False,
    n_visible: int = 0,
    block_b: int = 128,
    interpret: bool = True,
    half_offset: int = 0,
    n_half: int | None = None,
):
    """Run S resident sweeps on the Chimera-native fixed-degree layout.

    Same contract as `sweep_fused_pallas` except weights are the (D, N)
    slot layout and the second-moment output is the per-slot edge
    correlation ``c_slots[d, i] = Σ m_i · m_{nbr_idx[d, i]}`` instead of a
    Gram matrix — read edge (i, j) at ``c_slots[slot_of(i→j), i]`` (see
    ChimeraGraph.edge_slots).  Never materializes anything O(N²).

    ``half_offset``/``n_half`` select a half-sweep window of the launch:
    run ``n_half`` color half-sweeps starting at (launch-relative) half
    ``half_offset``, with betas/measured still indexed by full-launch
    sweep number.  The fused-exchange engine uses this to split one
    logical launch at halo exchange points inside a single jitted graph;
    chaining windows (threading ``noise_state`` between calls) is
    bit-identical to the unsplit launch, and per-window moment partials
    sum exactly to the whole-launch moments.
    """
    return _launch(
        m, None, nbr_idx, nbr_w, h, gain, off, rand_gain, comp_off,
        mask0, mask1, betas, noise_state, clamp_mask, clamp_values,
        measured, visible_idx,
        sparse=True, noise_mode=noise_mode, decimation=decimation,
        gather_perm=gather_perm, accumulate=accumulate,
        collect_hist=collect_hist, n_visible=n_visible, block_b=block_b,
        interpret=interpret, coord_offset=coord_offset,
        half_offset=half_offset, n_half=n_half)


@functools.partial(
    jax.jit,
    static_argnames=("decimation", "block_b", "interpret",
                     "half_offset", "n_half"),
)
def sweep_sparse_stream_pallas(
    m: jax.Array,                 # (B, N) spins in {-1, +1}
    nbr_idx: jax.Array,           # (D, N) int32 neighbor table
    nbr_w: jax.Array,             # (D, N) CURRENT program's slot weights
    h: jax.Array,                 # (N,)   CURRENT program's biases
    gain: jax.Array,
    off: jax.Array,
    rand_gain: jax.Array,
    comp_off: jax.Array,
    mask0: jax.Array,
    mask1: jax.Array,
    betas: jax.Array,             # (S, B)
    noise_state: jax.Array,       # (2,) uint32 counter state
    next_nbr_w: jax.Array,        # (D, N) NEXT program's slot weights
    next_h: jax.Array,            # (N,)   NEXT program's biases
    clamp_mask: jax.Array | None = None,
    clamp_values: jax.Array | None = None,
    coord_offset: jax.Array | None = None,
    *,
    decimation: int = 8,
    block_b: int = 128,
    interpret: bool = True,
    half_offset: int = 0,
    n_half: int | None = None,
):
    """`sweep_sparse_pallas` with a double-buffered program upload: run S
    resident sweeps of the CURRENT program while the NEXT program's
    weights stream into a second VMEM slot.

    Returns ``(m', noise_state', staged_w, staged_h)`` where
    ``staged_w``/``staged_h`` are the next program, already device-
    resident: feed them back as this call's ``nbr_w``/``h`` on the next
    launch.  The next-program inputs alias the staged outputs
    (`input_output_aliases`), so the handoff is an in-place buffer swap,
    and the stage copy runs at grid step 0 — independent of the sweep
    loop, overlapping compute on hardware (the SpikeHard DMA model: the
    chip accepts the next problem's SPI write while the current anneal
    runs).  Counter noise only, no in-kernel accumulation (a swapped
    program would invalidate mid-grid moments).  Per-program results are
    bit-identical to serialized `sweep_sparse_pallas` launches — the
    benchmark ``weight_streaming`` section measures the upload overlap.
    """
    return _launch(
        m, None, nbr_idx, nbr_w, h, gain, off, rand_gain, comp_off,
        mask0, mask1, betas, noise_state, clamp_mask, clamp_values,
        None, None,
        sparse=True, noise_mode=NOISE_COUNTER, decimation=decimation,
        gather_perm=None, accumulate=False, collect_hist=False,
        n_visible=0, block_b=block_b, interpret=interpret,
        coord_offset=coord_offset, next_nbr_w=next_nbr_w, next_h=next_h,
        half_offset=half_offset, n_half=n_half)


# ---------------------------------------------------------------------------
# Kernel-resident halo exchange (hardware RDMA path)
# ---------------------------------------------------------------------------
#
# One resident launch per shard refreshes its halos MID-FLIGHT: at every
# `Sync.exchange_points()` half-sweep the kernel gathers its O(√N) boundary
# spins into a VMEM send buffer and `pltpu.make_async_remote_copy`s them
# into the row neighbor's second halo VMEM slot, double-buffered on
# exchange parity exactly like the PR-9 program stream.  `mode="barrier"`
# waits for the incoming copy before the next half-sweep consumes it;
# `mode="async"` installs the PREVIOUS exchange's values and lets the
# in-flight copy overlap the segment's compute — the same staleness
# contract as the host engine's pend-buffer.  Host CI cannot run RDMA:
# interpret mode runs the bit-exact emulation instead
# (ShardedEngine's fused-resident-exchange loop shape: the same launch
# split at exchange points via `half_offset`/`n_half`, ppermute between
# segments, one jitted graph).  This kernel compiles only for TPU meshes
# (tests/test_tpu_compile.py).

_HALO_UP, _HALO_DN = 0, 1  # recv-buffer direction slots


def _exchange_kernel(*refs, S, tb, Np, B, n_loc, H, Hp, segments, mode,
                     has_clamp, accumulate, D, axis_name, n_row,
                     collective_id, stream):
    it = iter(refs)
    m0_ref = next(it)                         # (tb, Np) [local|hu|hd]
    sh_ref, w_ref = next(it), next(it)        # (Dp, Np) lane shifts, weights
    rot_ref = next(it)                        # SMEM per-slot rotations
    h_ref, g_ref, off_ref, rg_ref, co_ref = (next(it) for _ in range(5))
    mask0_ref, mask1_ref = next(it), next(it)
    betas_ref = next(it)                      # (S, tb)
    send_ref = next(it)                       # (2, Hp) boundary lane shifts
    srot_ref = next(it)                       # SMEM send rotations
    inst_ref = next(it)                       # (1, Np) halo lane shifts
    irot_ref = next(it)                       # SMEM install rotations
    clampm_ref = next(it) if has_clamp else None
    clampv_ref = next(it) if has_clamp else None
    meas_ref = next(it) if accumulate else None
    coords_ref = next(it)
    noise_in_ref = next(it)
    if stream:
        next_w_ref, next_h_ref = next(it), next(it)
    m_out_ref = next(it)
    noise_out_ref = next(it)
    if accumulate:
        ssum_out_ref, csum_out_ref = next(it), next(it)
    if stream:
        staged_w_out_ref, staged_h_out_ref = next(it), next(it)
    sbuf_ref = next(it)                       # (2, 2, tb, Hp) send slots
    rbuf_ref = next(it)                       # (2, 2, tb, Hp) recv slots
    send_sem = next(it)                       # DMA (2, 2) [dir, parity]
    recv_sem = next(it)                       # DMA (2, 2)
    if accumulate:
        ssum_ref, csum_ref = next(it), next(it)
    if stream:
        slot_w_ref, slot_h_ref = next(it), next(it)

    my = jax.lax.axis_index(axis_name)
    up_ok = my > 0                  # row above exists
    dn_ok = my < n_row - 1          # row below exists
    n_nbr = up_ok.astype(jnp.int32) + dn_ok.astype(jnp.int32)

    if accumulate:
        ssum_ref[...] = jnp.zeros_like(ssum_ref)
        csum_ref[...] = jnp.zeros_like(csum_ref)
    if stream:
        # double-buffered program upload staged up front, overlapping the
        # resident sweeps (shared launch with the halo refresh)
        slot_w_ref[...] = next_w_ref[...]
        slot_h_ref[...] = next_h_ref[...]

    hrow, grow = h_ref[...], g_ref[...]
    offrow, rgrow, corow = off_ref[...], rg_ref[...], co_ref[...]
    masks = (mask0_ref[...] != 0, mask1_ref[...] != 0)
    seed = noise_in_ref[0, 0]
    ctr0 = noise_in_ref[0, 1]
    row0 = coords_ref[0, 0]
    col0 = coords_ref[0, 1]
    rows = jax.lax.broadcasted_iota(jnp.uint32, (tb, Np), 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.uint32, (tb, Np), 1) + col0

    # neighbor barrier before the first RDMA: nobody writes into a peer
    # still draining its previous launch
    barrier = pltpu.get_barrier_semaphore()

    @pl.when(up_ok)
    def _sig_up():
        pltpu.semaphore_signal(
            barrier, inc=1, device_id={axis_name: my - 1},
            device_id_type=pltpu.DeviceIdType.MESH)

    @pl.when(dn_ok)
    def _sig_dn():
        pltpu.semaphore_signal(
            barrier, inc=1, device_id={axis_name: my + 1},
            device_id_type=pltpu.DeviceIdType.MESH)

    pltpu.semaphore_wait(barrier, n_nbr)

    def neuron_current(m):
        acc = jnp.zeros((tb, Np), jnp.float32)
        for d, g in _slot_gathers(m, sh_ref, rot_ref, D):
            acc = acc + w_ref[pl.ds(d, 1), :] * g
        return acc + hrow

    def copy(direction, parity):
        """My outgoing boundary copy: direction 0 sends my first-row
        boundary UP (it becomes that neighbor's halo_dn), 1 sends my
        last-row boundary DOWN (that neighbor's halo_up).  The same
        descriptor waits the matching local semaphores."""
        slot = _HALO_DN if direction == 0 else _HALO_UP
        return pltpu.make_async_remote_copy(
            src_ref=sbuf_ref.at[direction, parity],
            dst_ref=rbuf_ref.at[slot, parity],
            send_sem=send_sem.at[direction, parity],
            recv_sem=recv_sem.at[slot, parity],
            device_id={axis_name: my - 1 if direction == 0 else my + 1},
            device_id_type=pltpu.DeviceIdType.MESH)

    def start_exchange(m, parity):
        """Gather boundary spins and fire both neighbor RDMAs."""
        up, dn = _gather_rows(m, send_ref, srot_ref, 0, (0, 1))
        sbuf_ref[0, parity] = up
        sbuf_ref[1, parity] = dn

        @pl.when(up_ok)
        def _send_up():
            copy(0, parity).start()

        @pl.when(dn_ok)
        def _send_dn():
            copy(1, parity).start()

    halo_lanes = inst_ref[...] >= 0

    def install_halos(m, parity):
        """Wait the incoming copies of `parity` and refresh halo columns."""
        @pl.when(up_ok)
        def _wait_up():
            copy(1, parity).wait_recv()    # lands in my _HALO_UP slot

        @pl.when(dn_ok)
        def _wait_dn():
            copy(0, parity).wait_recv()    # lands in my _HALO_DN slot
        hu = jnp.where(up_ok, rbuf_ref[_HALO_UP, parity], 0.0)
        hd = jnp.where(dn_ok, rbuf_ref[_HALO_DN, parity], 0.0)
        src = jnp.concatenate(
            [hu, hd] + ([jnp.zeros((tb, Np - 2 * Hp), jnp.float32)]
                        if Np > 2 * Hp else []), axis=1)
        (halos,) = _gather_rows(src, inst_ref, irot_ref, 0, (0,))
        return jnp.where(halo_lanes, halos, m)

    def wait_sends(parity):
        @pl.when(up_ok)
        def _ws_up():
            copy(0, parity).wait_send()

        @pl.when(dn_ok)
        def _ws_dn():
            copy(1, parity).wait_send()

    def half_update(m, s_idx, c, half_j):
        ctr = ctr0 + half_j
        u = lfsr_mod.counter_uniform(seed, ctr, rows, cols)
        beta_col = betas_ref[pl.ds(s_idx, 1), :].reshape(tb, 1)
        act = jnp.tanh(beta_col * grow * (neuron_current(m) + offrow))
        decision = act + rgrow * u + corow
        new = jnp.where(decision >= 0.0, 1.0, -1.0)
        return jnp.where(masks[c], new, m)

    def impose_clamp(m):
        if has_clamp:
            return jnp.where(clampm_ref[...] != 0, clampv_ref[...], m)
        return m

    def sweep_stats(m, s_idx):
        wgt = meas_ref[s_idx]
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0)
        mv = jnp.where(row_ids < B, m, 0.0)
        ssum_ref[...] += wgt * jnp.sum(mv, axis=0, keepdims=True)
        for d, g in _slot_gathers(mv, sh_ref, rot_ref, D):
            corr = jnp.sum(mv * g, axis=0, keepdims=True)
            csum_ref[pl.ds(d, 1), :] += wgt * corr

    m = m0_ref[...].astype(jnp.float32)
    n_ex = len(segments)
    for e, (h0, h1) in enumerate(segments):
        parity = e % 2
        if e >= 2:
            # reusing this parity's send slots: previous copy must be out
            wait_sends(parity)
        start_exchange(m, parity)
        if mode == "barrier":
            m = install_halos(m, parity)
        elif e > 0:
            # async: consume the PREVIOUS exchange's values; exchange e
            # stays in flight behind this segment's compute
            m = install_halos(m, (e - 1) % 2)
        # run the [h0, h1) half-sweep window (lead / full / tail — the
        # same structure as _kernel's segmented window)
        lead = h0 % 2
        n_full = (h1 - h0 - lead) // 2
        tail = (h1 - h0 - lead) % 2
        s0 = (h0 + lead) // 2
        if lead:
            m = impose_clamp(m)
            m = half_update(m, h0 // 2, 1, jnp.uint32(h0))
            if accumulate:
                sweep_stats(m, h0 // 2)

        def one_sweep(jj, m, s0=s0, base=h0 + lead):
            m = impose_clamp(m)
            for c in (0, 1):
                hj = (jnp.uint32(base)
                      + jnp.uint32(2) * jj.astype(jnp.uint32)
                      + jnp.uint32(c))
                m = half_update(m, s0 + jj, c, hj)
            if accumulate:
                sweep_stats(m, s0 + jj)
            return m

        m = jax.lax.fori_loop(0, n_full, one_sweep, m)
        if tail:
            m = impose_clamp(m)
            m = half_update(m, s0 + n_full, 0, jnp.uint32(h1 - 1))

    # drain every DMA still in flight before the kernel exits
    if mode != "barrier":
        # async: the final exchange is the NEXT launch's first consume
        # (the engine's pend buffer) — install it into the halo columns
        # so m_out carries it across the launch boundary
        m = install_halos(m, (n_ex - 1) % 2)
    for parity in range(min(n_ex, 2)):
        # sends not yet retired by the e>=2 slot-reuse waits: the last
        # exchange on each parity
        wait_sends(parity)

    m_out_ref[...] = m.astype(m_out_ref.dtype)
    noise_out_ref[...] = _noise_state_out(noise_in_ref[...], 2 * S)
    if accumulate:
        ssum_out_ref[...] = ssum_ref[...]
        csum_out_ref[...] = csum_ref[...]
    if stream:
        staged_w_out_ref[...] = slot_w_ref[...]
        staged_h_out_ref[...] = slot_h_ref[...]


def sweep_sparse_exchange_pallas(
    m_ext: jax.Array,             # (B, N_ext) [local | halo_up | halo_dn]
    nbr_idx: jax.Array,           # (D, N_ext) ext-local neighbor table
    nbr_w: jax.Array,             # (D, N_ext)
    h: jax.Array,
    gain: jax.Array,
    off: jax.Array,
    rand_gain: jax.Array,
    comp_off: jax.Array,
    mask0: jax.Array,             # (N_ext,) halo columns excluded
    mask1: jax.Array,
    betas: jax.Array,             # (S, B)
    noise_state: jax.Array,       # (2,) uint32
    send_up: jax.Array,           # (H,) local cols of the first-row verts
    send_dn: jax.Array,           # (H,) local cols of the last-row verts
    clamp_mask: jax.Array | None = None,
    clamp_values: jax.Array | None = None,
    measured: jax.Array | None = None,
    coord_offset: jax.Array | None = None,
    next_nbr_w: jax.Array | None = None,
    next_h: jax.Array | None = None,
    *,
    n_loc: int,
    halo: int,
    ex_pts: tuple,                # launch-relative half-sweep indices
    mode: str = "barrier",
    axis_name: str = "row",
    n_row: int,
    collective_id: int = 7,
    interpret: bool = False,
):
    """S resident sweeps with IN-KERNEL halo refresh at every exchange
    point — the hardware twin of the engine's fused-resident-exchange
    emulation (identical noise counters, identical exchange-point
    staleness); `chip_smoke.py --chips 4` checks the match on 4 chips.

    Must run under ``shard_map`` over a mesh whose ``axis_name`` axis
    holds the ``n_row`` row bands.  Single batch tile (the exchange needs the whole
    shard's boundary at once).  Raises in interpret mode: host CI runs
    the segmented emulation (`ShardedEngine._local_sweeps`), which this
    kernel must match bit-for-bit on hardware.
    """
    if interpret:
        raise NotImplementedError(
            "in-kernel RDMA halo exchange needs a real TPU mesh; "
            "interpret mode runs the bit-exact segmented emulation "
            "(ShardedEngine's fused-resident-exchange loop shape)")
    from repro.kernels.ref import halo_exchange_segments

    B, N = m_ext.shape
    S = betas.shape[0]
    H = halo
    D = nbr_idx.shape[0]
    segments = halo_exchange_segments(ex_pts, 2 * S)
    accumulate = measured is not None
    has_clamp = clamp_mask is not None and clamp_values is not None
    stream = next_nbr_w is not None
    if stream and accumulate:
        raise ValueError("program streaming excludes in-kernel moments")

    Np = _round_up(N, 128)
    Hp = _round_up(max(H, 1), 128)
    tb = _round_up(B, 8)
    Dp = _round_up(D, 8)
    if 2 * Hp > Np:
        raise ValueError(f"halo width {H} too wide for a {N}-column shard")
    # halo installs gather from the [recv_up | recv_dn] buffers: ext
    # column n_loc + j reads up-lane j, n_loc + H + j reads dn-lane j
    inst = np.full((1, Np), -1, np.int32)
    inst[0, n_loc:n_loc + H] = np.arange(H)
    inst[0, n_loc + H:n_loc + 2 * H] = Hp + np.arange(H)

    row = lambda x: _pad_axis(
        jnp.asarray(x).reshape(1, -1).astype(jnp.float32), 128, 1)
    mp = _pad_axis(_pad_axis(m_ext, tb, 0), 128, 1)
    shifts, rotations = _lane_rotations(nbr_idx, N, Np, _slot_groups(D))
    send_shifts, send_rotations = _lane_rotations(
        jnp.stack([jnp.asarray(send_up, jnp.int32),
                   jnp.asarray(send_dn, jnp.int32)]), H, Np, ((0, 1),))
    inst_shifts, inst_rotations = _lane_rotations(inst, Np, Np, ((0,),))
    wp = _pad_axis(_pad_axis(jnp.asarray(nbr_w, jnp.float32), Dp, 0),
                   128, 1)
    m0p = _pad_axis(jnp.asarray(mask0).reshape(1, -1).astype(jnp.int8),
                    128, 1, 0)
    m1p = _pad_axis(jnp.asarray(mask1).reshape(1, -1).astype(jnp.int8),
                    128, 1, 0)
    betasp = _pad_axis(jnp.asarray(betas, jnp.float32), tb, 1)

    full = lambda shape: pl.BlockSpec(shape, lambda: tuple(
        0 for _ in shape))
    in_specs = [full((tb, Np)), full((Dp, Np)), full((Dp, Np)), _SMEM]
    args = [mp, _pad_axis(shifts, Dp, 0, -1), wp, rotations]
    in_specs += [full((1, Np))] * 7 + [full((S, tb)), full((2, Hp)), _SMEM]
    args += [row(h), row(gain), row(off), row(rand_gain), row(comp_off),
             m0p, m1p, betasp, send_shifts, send_rotations,
             inst_shifts, inst_rotations]
    in_specs += [full((1, Np)), _SMEM]
    if has_clamp:
        in_specs += [full((1, Np)), full((tb, Np))]
        args += [_pad_axis(jnp.asarray(clamp_mask).reshape(1, -1)
                           .astype(jnp.int8), 128, 1, 0),
                 _pad_axis(_pad_axis(
                     jnp.asarray(clamp_values, jnp.float32), tb, 0),
                     128, 1)]
    if accumulate:
        in_specs.append(_SMEM)
        args.append(jnp.asarray(measured, jnp.float32).reshape(S))
    in_specs.append(full((1, 2)))
    args.append(jnp.zeros((1, 2), jnp.uint32) if coord_offset is None
                else jnp.asarray(coord_offset, jnp.uint32).reshape(1, 2))
    in_specs.append(full((1, 2)))
    args.append(jnp.asarray(noise_state, jnp.uint32).reshape(1, 2))
    if stream:
        in_specs += [full((Dp, Np)), full((1, Np))]
        args += [_pad_axis(_pad_axis(
            jnp.asarray(next_nbr_w, jnp.float32), Dp, 0), 128, 1),
            row(next_h)]

    out_shape = [jax.ShapeDtypeStruct((tb, Np), m_ext.dtype),
                 jax.ShapeDtypeStruct((1, 2), jnp.uint32)]
    out_specs = [full((tb, Np)), full((1, 2))]
    if accumulate:
        out_shape += [jax.ShapeDtypeStruct((1, Np), jnp.float32),
                      jax.ShapeDtypeStruct((Dp, Np), jnp.float32)]
        out_specs += [full((1, Np)), full((Dp, Np))]
    if stream:
        out_shape += [jax.ShapeDtypeStruct((Dp, Np), jnp.float32),
                      jax.ShapeDtypeStruct((1, Np), jnp.float32)]
        out_specs += [full((Dp, Np)), full((1, Np))]

    scratch = [_VMEM((2, 2, tb, Hp), jnp.float32),   # send slots
               _VMEM((2, 2, tb, Hp), jnp.float32),   # recv slots
               pltpu.SemaphoreType.DMA((2, 2)),
               pltpu.SemaphoreType.DMA((2, 2))]
    if accumulate:
        scratch += [_VMEM((1, Np), jnp.float32), _VMEM((Dp, Np),
                                                       jnp.float32)]
    if stream:
        scratch += [_VMEM((Dp, Np), jnp.float32), _VMEM((1, Np),
                                                        jnp.float32)]

    # stream excludes accumulate, so staged outputs sit at 2/3
    aliases = {len(args) - 2: 2, len(args) - 1: 3} if stream else {}
    outs = pl.pallas_call(
        functools.partial(
            _exchange_kernel, S=S, tb=tb, Np=Np, B=B, n_loc=n_loc, H=H,
            Hp=Hp, segments=segments, mode=mode, has_clamp=has_clamp,
            accumulate=accumulate, D=D, axis_name=axis_name, n_row=n_row,
            collective_id=collective_id, stream=stream),
        grid=(),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(), has_side_effects=True,
            collective_id=collective_id),
        interpret=False,
    )(*args)

    result = [outs[0][:B, :N], outs[1].reshape(2)]
    k = 2
    if accumulate:
        result += [outs[k][0, :N], outs[k + 1][:D, :N]]
        k += 2
    if stream:
        result += [outs[k][:D, :N], outs[k + 1][0, :N]]
    return tuple(result)
