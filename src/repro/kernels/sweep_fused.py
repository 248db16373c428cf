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
            NBp: int, has_coords: bool, half_offset: int = 0,
            n_half: int | None = None):
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
    m_out_ref = next(it)                      # f32 spins, updated in place
    noise_out_ref = next(it)
    if accumulate:
        ssum_out_ref, csum_out_ref = next(it), next(it)
    if collect_hist:
        hist_out_ref = next(it)
    bcol_ref = next(it)                       # (tb, 1) this sweep's betas
    if accumulate:
        ssum_ref, csum_ref = next(it), next(it)
        if sparse:
            corr_ref = next(it)               # (Dp, Np) one sweep's sums
    if collect_hist:
        hist_ref = next(it)

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


def _launch(
    m, dense_W, nbr_idx, nbr_w, h, gain, off, rand_gain, comp_off,
    mask0, mask1, betas, noise_state, clamp_mask, clamp_values, measured,
    visible_idx, *, sparse, noise_mode, decimation, gather_perm,
    accumulate, collect_hist, n_visible, block_b, interpret,
    coord_offset=None, half_offset=0, n_half=None,
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

    outs = pl.pallas_call(
        functools.partial(
            _kernel, S=S, tb=tb,
            hb=_block_rows(tb, Np, D) if sparse else tb, Np=Np, n_b=n_b, B=B,
            noise_mode=noise_mode, has_clamp=has_clamp,
            accumulate=accumulate, collect_hist=collect_hist,
            decimation=decimation, sparse=sparse,
            D=D if sparse else 0, NBp=NBp, has_coords=has_coords,
            half_offset=half_offset, n_half=n_half),
        grid=(n_b,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=scratch,
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
