"""Analog hardware model of the chip's non-idealities.

The paper's area-efficiency choices (standard-cell analog pitch-matched to
digital, shared 1 V supply, MOS R-2R DACs with no output-resistance
enhancement, un-matched current mirrors) buy density at the cost of
process-variation mismatch.  This module is the physics model of those
non-idealities; `program_weights` compiles digital 8-bit weights through it
into the *effective* analog quantities the sampler sees.

Modeled effects (all per chip *instance*, sampled from a PRNG key):
  * R-2R DAC per-bit branch mismatch       -> nonmonotonic INL/DNL in J & h
  * DAC output-resistance / supply droop   -> soft compression of large currents
  * Gilbert-multiplier gain error per edge *direction* -> asymmetric W[i,j] != W[j,i]
  * disabled-coupler leakage (enable bit leaks a small current)
  * WTA-tanh gain (beta) variation and input offset per node
  * RNG-DAC amplitude mismatch per node
  * comparator input offset per node

Setting ``HardwareConfig.ideal()`` zeroes every sigma, giving a bit-exact
textbook p-bit (used as the oracle in tests).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.chimera import ChimeraGraph

WMIN, WMAX = -128, 127  # 8-bit signed DAC codes


def quantize_codes(w: jax.Array, lsb: float = 1.0) -> jax.Array:
    """Float master weights -> signed 8-bit DAC codes."""
    return jnp.clip(jnp.round(w / lsb), WMIN, WMAX).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class HardwareConfig:
    """Process-variation sigmas (fraction of nominal unless noted)."""

    sigma_dac_bit: float = 0.04      # per-R-2R-branch current mismatch
    sigma_edge_gain: float = 0.05    # Gilbert multiplier gain, per direction
    sigma_tanh_gain: float = 0.08    # WTA tanh beta spread per node
    sigma_tanh_offset: float = 2.0   # input-referred offset, LSB units
    sigma_rand_gain: float = 0.05    # RNG DAC amplitude spread per node
    sigma_comp_offset: float = 0.02  # comparator offset, fraction of FS
    leak_frac: float = 0.004         # disabled-coupler leakage, fraction of FS
    compression: float = 3e-3        # soft saturation: I/(1+compression*|I|/FS)

    @staticmethod
    def ideal() -> "HardwareConfig":
        return HardwareConfig(0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0)

    def is_ideal(self) -> bool:
        return all(
            getattr(self, f.name) == 0.0 for f in dataclasses.fields(self)
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Mismatch:
    """Sampled per-instance variation (a pytree of arrays)."""

    dac_bit_j: jax.Array      # (N, N, 8) per-bit branch error for J DACs
    dac_bit_h: jax.Array      # (N, 8)
    edge_gain: jax.Array      # (N, N) directional multiplier gain error
    tanh_gain: jax.Array      # (N,)   multiplicative beta error
    tanh_offset: jax.Array    # (N,)   additive input offset (weight LSB units)
    rand_gain: jax.Array      # (N,)
    comp_offset: jax.Array    # (N,)
    leak: jax.Array           # (N, N) leakage of disabled couplers

    def tree_flatten(self):
        fields = dataclasses.fields(self)
        return tuple(getattr(self, f.name) for f in fields), None

    @classmethod
    def tree_unflatten(cls, aux: Any, children):
        return cls(*children)


def sample_mismatch(
    key: jax.Array, n_nodes: int, cfg: HardwareConfig
) -> Mismatch:
    """Draw one chip instance's process variation."""
    ks = jax.random.split(key, 8)
    n = n_nodes

    def g(k, shape, sigma):
        if sigma == 0.0:
            return jnp.zeros(shape, dtype=jnp.float32)
        return sigma * jax.random.normal(k, shape, dtype=jnp.float32)

    return Mismatch(
        dac_bit_j=g(ks[0], (n, n, 8), cfg.sigma_dac_bit),
        dac_bit_h=g(ks[1], (n, 8), cfg.sigma_dac_bit),
        edge_gain=g(ks[2], (n, n), cfg.sigma_edge_gain),
        tanh_gain=g(ks[3], (n,), cfg.sigma_tanh_gain),
        tanh_offset=g(ks[4], (n,), cfg.sigma_tanh_offset),
        rand_gain=g(ks[5], (n,), cfg.sigma_rand_gain),
        comp_offset=g(ks[6], (n,), cfg.sigma_comp_offset),
        leak=jnp.abs(g(ks[7], (n, n), cfg.leak_frac)),
    )


def _bits(w_mag: jax.Array) -> jax.Array:
    """Binary expansion of |code| in [0, 128]. Returns float (..., 8)."""
    shifts = jnp.arange(8, dtype=jnp.int32)
    return ((w_mag[..., None].astype(jnp.int32) >> shifts) & 1).astype(
        jnp.float32
    )


def dac_transfer(code: jax.Array, bit_err: jax.Array) -> jax.Array:
    """R-2R DAC: signed 8-bit code -> analog current (weight-LSB units).

    Sign-magnitude current steering with per-branch mismatch:
      I = sign(code) * sum_b bit_b(|code|) * 2^b * (1 + eps_b)
    """
    sign = jnp.sign(code.astype(jnp.float32))
    mag = jnp.abs(code.astype(jnp.int32))
    weights = (2.0 ** jnp.arange(8, dtype=jnp.float32)) * (1.0 + bit_err)
    return sign * jnp.sum(_bits(mag) * weights, axis=-1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EffectiveChip:
    """Digital weights compiled through the analog model — what physics sees.

    W is *directional*: W[i, j] is the current injected into node i per unit
    spin m_j (the shared-edge DAC value times node-i's multiplier gain), so
    in general W != W.T under mismatch, exactly as on silicon.

    ``nbr_idx``/``nbr_w`` are the Chimera-native fixed-degree slot layout
    (see ChimeraGraph.neighbor_table): ``nbr_w[d, i] = W[i, nbr_idx[d, i]]``.
    A chip may carry both views (dense programming + `attach_sparse`), or
    only the sparse one (`program_weights_sparse`, W=None) for lattices
    where the dense (N, N) matrix cannot exist at all.
    """

    W: jax.Array | None     # (N, N) effective couplings, weight-LSB units
    h: jax.Array            # (N,)  effective biases
    tanh_gain: jax.Array    # (N,)  multiplicative on beta
    tanh_offset: jax.Array  # (N,)  additive current offset
    rand_gain: jax.Array    # (N,)
    comp_offset: jax.Array  # (N,)
    nbr_idx: jax.Array | None = None  # (D, N) int32 neighbor table
    nbr_w: jax.Array | None = None    # (D, N) per-slot couplings

    def tree_flatten(self):
        fields = dataclasses.fields(self)
        return tuple(getattr(self, f.name) for f in fields), None

    @classmethod
    def tree_unflatten(cls, aux: Any, children):
        return cls(*children)

    @property
    def n_nodes(self) -> int:
        return self.h.shape[-1]

    @property
    def degree(self) -> int:
        """Slot count D of the sparse layout (0 when dense-only)."""
        return 0 if self.nbr_idx is None else int(self.nbr_idx.shape[0])


def program_weights(
    J: jax.Array,
    h: jax.Array,
    enable: jax.Array,
    mism: Mismatch,
    cfg: HardwareConfig,
    adjacency: jax.Array | None = None,
    neighbors: jax.Array | None = None,
) -> EffectiveChip:
    """Compile digital (int8) weights into effective analog quantities.

    J: (N, N) symmetric int8 codes; h: (N,) int8 codes;
    enable: (N, N) bool coupler-enable bits; adjacency: (N, N) bool physical
    couplers (no current path at all where False); neighbors: optional
    (D, N) neighbor table — when given, the sparse slot view is attached to
    the returned chip (a gather of the final W, bit-identical entries).
    """
    J = jnp.asarray(J)
    n = J.shape[0]
    Wdac = dac_transfer(J, mism.dac_bit_j)           # shared per-edge DAC
    Wdir = Wdac * (1.0 + mism.edge_gain)             # per-direction multiplier
    # enable bit: disabled couplers leak a small fraction of full scale
    Wdir = jnp.where(enable, Wdir, jnp.sign(Wdir) * mism.leak * 128.0)
    if adjacency is not None:
        Wdir = jnp.where(adjacency, Wdir, 0.0)
    Wdir = Wdir * (1.0 - jnp.eye(n, dtype=Wdir.dtype))  # no self coupling
    # soft compression from finite DAC output resistance / supply droop
    if cfg.compression > 0.0:
        Wdir = Wdir / (1.0 + cfg.compression * jnp.abs(Wdir))
    h_eff = dac_transfer(h, mism.dac_bit_h)
    chip = EffectiveChip(
        W=Wdir.astype(jnp.float32),
        h=h_eff.astype(jnp.float32),
        tanh_gain=1.0 + mism.tanh_gain,
        tanh_offset=mism.tanh_offset,
        rand_gain=1.0 + mism.rand_gain,
        comp_offset=mism.comp_offset,
    )
    if neighbors is not None:
        chip = attach_sparse(chip, neighbors)
    return chip


def attach_sparse(chip: EffectiveChip, nbr_idx: jax.Array) -> EffectiveChip:
    """Gather the dense W into the (D, N) slot layout.

    ``nbr_w[d, i] = W[i, nbr_idx[d, i]]`` — bit-identical entries, so the
    sparse backends sample the exact same physics as the dense ones.
    Self-pointing padding slots read the (zero) diagonal.
    """
    idx = jnp.asarray(nbr_idx)
    rows = jnp.arange(chip.n_nodes)[None, :]
    nbr_w = chip.W[rows, idx].astype(jnp.float32)
    return dataclasses.replace(chip, nbr_idx=idx.astype(jnp.int32),
                               nbr_w=nbr_w)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SparseMismatch:
    """Per-instance variation in the fixed-degree slot layout.

    Pair fields are (D, N) — one entry per physical coupler *direction*
    (slot d of node i), exactly the entries the dense (N, N) model carries
    on the Chimera adjacency; everything off-graph, which the dense model
    samples and then masks to zero, is simply never sampled.  O(D·N)
    memory, so chip instances exist at lattice sizes where the dense
    Mismatch (N² and N²·8 arrays) cannot.
    """

    dac_bit_j: jax.Array      # (D, N, 8) per-bit branch error for J DACs
    dac_bit_h: jax.Array      # (N, 8)
    edge_gain: jax.Array      # (D, N) directional multiplier gain error
    tanh_gain: jax.Array      # (N,)
    tanh_offset: jax.Array    # (N,)
    rand_gain: jax.Array      # (N,)
    comp_offset: jax.Array    # (N,)
    leak: jax.Array           # (D, N) leakage of disabled couplers

    def tree_flatten(self):
        fields = dataclasses.fields(self)
        return tuple(getattr(self, f.name) for f in fields), None

    @classmethod
    def tree_unflatten(cls, aux: Any, children):
        return cls(*children)

    @classmethod
    def from_dense(cls, mism: "Mismatch", nbr_idx: jax.Array
                   ) -> "SparseMismatch":
        """Reproduce a *given* dense chip instance in the slot layout.

        Gathers exactly the on-graph entries of the dense draw, so a
        sparse-native machine built from this carries bit-identical
        mismatch to the dense machine: programming the same codes yields
        bit-identical ``nbr_w``, and the sparse backends then sample the
        identical spin trajectory (asserted at chip scale in
        tests/test_sparse.py::test_sparse_machine_reproduces_dense_chip).
        The dense (N², N²·8) arrays exist only as the *input* — the
        result is O(D·N), ready for lattice-scale sharded sampling.
        """
        idx = jnp.asarray(nbr_idx)
        rows = jnp.arange(mism.tanh_gain.shape[0])[None, :]
        return cls(
            dac_bit_j=mism.dac_bit_j[rows, idx],
            dac_bit_h=mism.dac_bit_h,
            edge_gain=mism.edge_gain[rows, idx],
            tanh_gain=mism.tanh_gain,
            tanh_offset=mism.tanh_offset,
            rand_gain=mism.rand_gain,
            comp_offset=mism.comp_offset,
            leak=mism.leak[rows, idx],
        )


def sample_mismatch_sparse(
    key: jax.Array, n_nodes: int, degree: int, cfg: HardwareConfig,
    out_shardings: SparseMismatch | None = None,
) -> SparseMismatch:
    """Draw one chip instance's process variation, slot layout (O(D·N)).

    ``out_shardings`` (a `SparseMismatch` of shardings, e.g.
    `core.distributed.mismatch_shardings`) draws each device's part of
    the instance on that device.  The draw is partitionable, and the
    scaling by sigma runs as its own op as in the unsharded draw, so the
    values equal the unsharded draw's bit for bit."""
    ks = jax.random.split(key, 8)
    n, d = n_nodes, degree
    shards = ([None] * 8 if out_shardings is None
              else jax.tree_util.tree_leaves(out_shardings))

    def g(i, shape, sigma):
        s = shards[i]
        if sigma == 0.0:
            if s is None:
                return jnp.zeros(shape, dtype=jnp.float32)
            return jax.jit(lambda: jnp.zeros(shape, jnp.float32),
                           out_shardings=s)()
        if s is None:
            return sigma * jax.random.normal(ks[i], shape, dtype=jnp.float32)
        return sigma * jax.jit(
            lambda k: jax.random.normal(k, shape, dtype=jnp.float32),
            out_shardings=s)(ks[i])

    return SparseMismatch(
        dac_bit_j=g(0, (d, n, 8), cfg.sigma_dac_bit),
        dac_bit_h=g(1, (n, 8), cfg.sigma_dac_bit),
        edge_gain=g(2, (d, n), cfg.sigma_edge_gain),
        tanh_gain=g(3, (n,), cfg.sigma_tanh_gain),
        tanh_offset=g(4, (n,), cfg.sigma_tanh_offset),
        rand_gain=g(5, (n,), cfg.sigma_rand_gain),
        comp_offset=g(6, (n,), cfg.sigma_comp_offset),
        leak=jnp.abs(g(7, (d, n), cfg.leak_frac)),
    )


def gather_mismatch(mism: Mismatch, nbr_idx: jax.Array) -> SparseMismatch:
    """Dense (N, N) mismatch -> (D, N) slot layout.

    Alias of `SparseMismatch.from_dense` (kept for existing call sites)."""
    return SparseMismatch.from_dense(mism, nbr_idx)


def program_weights_sparse(
    J_slots: jax.Array,
    h: jax.Array,
    enable_slots: jax.Array,
    mism: SparseMismatch,
    cfg: HardwareConfig,
    nbr_idx: jax.Array,
    nbr_mask: jax.Array,
) -> EffectiveChip:
    """Sparse-native programming: slot codes -> EffectiveChip with W=None.

    J_slots/enable_slots: (D, N) int8 codes / enable bits in the neighbor
    table layout; nbr_mask marks physical couplers (padding slots carry no
    current path, mirroring the dense adjacency mask).  The elementwise
    analog chain is applied in the same order as `program_weights`, so with
    a gathered dense mismatch the resulting nbr_w is bit-identical to
    gathering the densely programmed W.  Never touches O(N²) memory.
    """
    J = jnp.asarray(J_slots)
    Wdac = dac_transfer(J, mism.dac_bit_j)
    Wdir = Wdac * (1.0 + mism.edge_gain)
    Wdir = jnp.where(enable_slots, Wdir, jnp.sign(Wdir) * mism.leak * 128.0)
    Wdir = jnp.where(nbr_mask, Wdir, 0.0)
    if cfg.compression > 0.0:
        Wdir = Wdir / (1.0 + cfg.compression * jnp.abs(Wdir))
    h_eff = dac_transfer(h, mism.dac_bit_h)
    return EffectiveChip(
        W=None,
        h=h_eff.astype(jnp.float32),
        tanh_gain=1.0 + mism.tanh_gain,
        tanh_offset=mism.tanh_offset,
        rand_gain=1.0 + mism.rand_gain,
        comp_offset=mism.comp_offset,
        nbr_idx=jnp.asarray(nbr_idx, jnp.int32),
        nbr_w=Wdir.astype(jnp.float32),
    )


def ideal_chip(J: jax.Array, h: jax.Array,
               adjacency: jax.Array | None = None,
               neighbors: jax.Array | None = None) -> EffectiveChip:
    """Zero-mismatch chip from float or int weights (the textbook p-bit)."""
    J = jnp.asarray(J, dtype=jnp.float32)
    n = J.shape[0]
    W = J * (1.0 - jnp.eye(n, dtype=jnp.float32))
    if adjacency is not None:
        W = jnp.where(adjacency, W, 0.0)
    ones = jnp.ones((n,), dtype=jnp.float32)
    chip = EffectiveChip(
        W=W,
        h=jnp.asarray(h, dtype=jnp.float32),
        tanh_gain=ones,
        tanh_offset=0.0 * ones,
        rand_gain=ones,
        comp_offset=0.0 * ones,
    )
    if neighbors is not None:
        chip = attach_sparse(chip, neighbors)
    return chip


def measure_node_transfer(
    chip_sampler,
    bias_codes: np.ndarray,
    **kw,
) -> np.ndarray:
    """Paper Fig. 8a: sweep the bias DAC and record <m> per node.

    `chip_sampler(bias_code) -> mean_spin[N]` is provided by callers; kept
    here for discoverability.  See benchmarks/bench_variability.py.
    """
    return np.stack([np.asarray(chip_sampler(b, **kw)) for b in bias_codes])
