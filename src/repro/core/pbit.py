"""The p-bit sampling engine (paper eqns 1 & 2), vectorized + batched.

Eqn 1:  I_i = sum_{j != i} J_ij m_j + h_i        (current summation)
Eqn 2:  m_i = sgn( tanh(beta I_i) + U(-1, +1) )  (stochastic neuron)

(The paper's eqn 1 prints "h_i m_i"; the standard p-bit bias term — and the
chip's bias-DAC current path, which does not multiply by m_i — is "+ h_i".
We implement "+ h_i" and note the typo here.)

On silicon all 440 neurons update asynchronously in parallel.  The exact
digital emulation for a 2-colorable graph (Chimera is — see chimera.py) is
*chromatic Gibbs*: update color class 0 in parallel, then class 1, each with
fresh noise.  Each half-sweep is one (B, N) x (N, N) matmul — MXU food.

`half_sweep` runs through an `EffectiveChip` (hardware.py) so every analog
non-ideality is in the loop; with `HardwareConfig.ideal()` it reduces to the
textbook equations, which tests/test_pbit.py verifies against exact
enumeration of the Boltzmann distribution.

Execution backends (see docs/kernels.md):
  * "ref"    — pure jnp chromatic half-sweeps under `lax.scan` (default).
  * "pallas" — the tiled per-half-sweep Pallas kernel (kernels/pbit_update).
  * "fused"  — the sweep-resident engine (kernels/sweep_fused): S sweeps per
               kernel launch, spins in VMEM, noise generated in-kernel, CD
               moments accumulated on-line.  Needs "counter" or "lfsr" noise.
  * "sparse" — jnp scan like "ref", but eqn 1 is the Chimera-native
               fixed-degree gather (≤6 neighbors/node) instead of the dense
               matmul.  Needs a chip carrying the slot layout
               (hardware.attach_sparse / program_weights_sparse).
  * "fused_sparse" — the sweep-resident engine on the slot layout: D
               lane-gathers replace the (B,N)x(N,N) matmul and the moment
               scratch shrinks from the (N,N) Gram to (D,N) per-slot edge
               correlations, which is what lets ≥32k-spin lattices stay
               VMEM-resident.  Needs "counter" or "lfsr" noise.
Selected per call via the ``backend=`` argument, or globally via the
REPRO_PBIT_BACKEND environment variable (used when backend is None/"auto").

This module is the *engine* layer.  Workload code builds samplers through
`repro.api` (a declarative SamplerSpec compiled into a Session) which
resolves backend/interpret/noise/schedule once and calls in here with
everything explicit; the free functions keep their legacy env-consulting
defaults as deprecation shims (docs/api.md has the migration table).

Multi-device execution sits one layer up: a spec carrying ``mesh=`` +
``partition=`` compiles into `core/distributed.ShardedEngine`, which runs
the "sparse" slot-layout scan per device shard with ppermute halo
exchange of the chain-coupler boundary spins (docs/sharding.md).  The
noise sources here are the single-device references the sharded engine
must match bit for bit: "counter" regenerates from the global
(chain, node) coordinate hash and "lfsr" from the per-cell register
band, so any shard can reproduce exactly its columns of the global
stream — which is why sharded specs require one of those two kinds.
"""
from __future__ import annotations

import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lfsr as lfsr_mod
from repro.core.chimera import ChimeraGraph
from repro.core.hardware import EffectiveChip

NoiseFn = Callable[[jax.Array], tuple[jax.Array, jax.Array]]

BACKENDS = ("ref", "pallas", "fused", "sparse", "fused_sparse")
FUSED_BACKENDS = ("fused", "fused_sparse")


def resolve_backend(backend: str | None = None) -> str:
    """Map None/"auto" to the env default; validate explicit choices."""
    if backend in (None, "auto"):
        backend = os.environ.get("REPRO_PBIT_BACKEND", "ref")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; pick from {BACKENDS}")
    return backend


class NoiseSpec(NamedTuple):
    """Static description of a noise source, attached to step fns as
    ``step.spec`` so the fused kernel can regenerate the same stream
    in-kernel (see kernels/sweep_fused.py)."""

    kind: str                        # "philox" | "counter" | "lfsr"
    decimation: int = 8
    gather_perm: tuple | None = None  # node -> flat LFSR column (static)


# ---------------------------------------------------------------------------
# Noise sources
# ---------------------------------------------------------------------------
def make_philox_noise(batch: int, n_nodes: int, quantize: bool = True
                      ) -> NoiseFn:
    """Host-side counter noise (scale mode): state is a PRNG key.

    Not reproducible inside the fused kernel — use `make_counter_noise` for
    a bit-exact host/kernel pair.
    """

    def step(key: jax.Array) -> tuple[jax.Array, jax.Array]:
        key, sub = jax.random.split(key)
        if quantize:  # mimic the 8-bit RNG DAC's discrete levels
            b = jax.random.randint(sub, (batch, n_nodes), 0, 256)
            u = (b.astype(jnp.float32) - 127.5) / 128.0
        else:
            u = jax.random.uniform(
                sub, (batch, n_nodes), minval=-1.0, maxval=1.0)
        return key, u

    step.spec = NoiseSpec(kind="philox")
    return step


def make_counter_noise(batch: int, n_nodes: int
                       ) -> tuple[Callable[[jax.Array], jax.Array], NoiseFn]:
    """Stateless-hash noise, bit-exact between host and the fused kernel.

    State is uint32[2] = (seed, step counter); every step consumes one
    counter tick and hashes (seed, ctr, chain, node) — the scale-mode
    equivalent of the chip's per-cell LFSRs, quantized like the 8-bit RNG
    DAC.  Returns (init_fn(key) -> state, step_fn).
    """
    rows = jnp.arange(batch, dtype=jnp.uint32)[:, None]
    cols = jnp.arange(n_nodes, dtype=jnp.uint32)[None, :]

    def init(key: jax.Array) -> jax.Array:
        seed = jax.random.bits(key, (1,), jnp.uint32)[0]
        return jnp.stack([seed, jnp.uint32(0)])

    def step(state: jax.Array) -> tuple[jax.Array, jax.Array]:
        u = lfsr_mod.counter_uniform(state[0], state[1], rows, cols)
        return state + jnp.array([0, 1], jnp.uint32), u

    step.spec = NoiseSpec(kind="counter")
    return init, step


def make_lfsr_noise(graph: ChimeraGraph, batch: int, decimation: int = 8
                    ) -> tuple[Callable[[jax.Array], jax.Array], NoiseFn]:
    """Chip-faithful noise: one 32-bit LFSR per unit cell.

    Returns (init_fn(key) -> state, step_fn(state) -> (state, u[batch, N])).
    Vertical nodes read the register bytes; horizontal nodes read the
    bit-reversed bytes (paper's sharing trick).  Per-node mapping is one
    gather through the precomputed inverse permutation (shared with the
    fused kernel's in-kernel LFSR path).
    """
    cells = sorted(
        {(int(r), int(c)) for r, c in zip(graph.node_r, graph.node_c)}
    )
    vert = np.stack([graph.cell_nodes(r, c, side=0) for r, c in cells])
    horiz = np.stack([graph.cell_nodes(r, c, side=1) for r, c in cells])
    perm = lfsr_mod.node_gather_perm(vert, horiz, graph.n_nodes)
    perm_j = jnp.asarray(perm)
    n_cells = len(cells)

    def init(key: jax.Array) -> jax.Array:
        return lfsr_mod.seed_states(key, (batch, n_cells))

    def step(state: jax.Array) -> tuple[jax.Array, jax.Array]:
        return lfsr_mod.lfsr_uniform_for_graph(
            state, None, None, graph.n_nodes, decimation, gather_perm=perm_j)

    step.spec = NoiseSpec(kind="lfsr", decimation=decimation,
                          gather_perm=tuple(int(x) for x in perm))
    return init, step


# ---------------------------------------------------------------------------
# Core update
# ---------------------------------------------------------------------------
def neuron_input(m: jax.Array, chip: EffectiveChip) -> jax.Array:
    """Eqn 1 for every node: I = m @ W^T + h.  m: (B, N) in {-1, +1}."""
    if chip.W is None:
        raise ValueError(
            "this chip carries only the sparse slot layout (W=None); use a "
            "sparse backend ('sparse' or 'fused_sparse'), e.g. "
            "PBitMachine(backend='sparse') or REPRO_PBIT_BACKEND=sparse")
    # f32 weights at full precision: the TPU's default matmul precision
    # would round the analog couplings to bf16
    return jnp.matmul(m, chip.W.T,
                      precision=jax.lax.Precision.HIGHEST) + chip.h


def half_sweep(
    m: jax.Array,
    chip: EffectiveChip,
    update_mask: jax.Array,
    beta: jax.Array,
    u: jax.Array,
) -> jax.Array:
    """Parallel update of the nodes selected by ``update_mask`` (eqn 2).

    ``beta`` may be a scalar or a (B,) per-chain vector (tempering ladder).
    """
    beta = jnp.asarray(beta, jnp.float32)
    if beta.ndim == 1:
        beta = beta[:, None]
    I = neuron_input(m, chip)
    act = jnp.tanh(beta * chip.tanh_gain * (I + chip.tanh_offset))
    decision = act + chip.rand_gain * u + chip.comp_offset
    new = jnp.where(decision >= 0.0, 1.0, -1.0).astype(m.dtype)
    return jnp.where(update_mask, new, m)


class SweepCarry(NamedTuple):
    m: jax.Array
    noise_state: jax.Array


def make_sweep_fn(
    chip: EffectiveChip,
    color: jax.Array,
    noise_fn: NoiseFn,
    clamp_mask: jax.Array | None = None,
    clamp_values: jax.Array | None = None,
    kernel: Callable | None = None,
    flip_fn: Callable[[jax.Array], jax.Array] | None = None,
):
    """Build one full Gibbs sweep (two chromatic half-sweeps).

    clamp_mask: (N,) bool — nodes held at clamp_values (B, N) (CD positive
    phase).  `kernel`, if given, replaces the jnp half-sweep with the Pallas
    fused implementation (same signature, see kernels/ops.py).

    flip_fn(noise_state) -> (B, N) bool is the transient-fault hook
    (api.Faults.flip_prob): just-updated spins where it reads True are
    inverted after their half-sweep.  It receives the noise state *before*
    the half-sweep's draw, so the flip stream is addressed by the same
    (seed, counter) coordinates as the sampling stream without consuming
    it; clamped/stuck nodes never flip (the update mask gates it).
    """
    hs = kernel if kernel is not None else half_sweep
    masks = [(color == c) for c in (0, 1)]
    if clamp_mask is not None:
        masks = [mk & (~clamp_mask) for mk in masks]

    def sweep(carry: SweepCarry, beta: jax.Array) -> SweepCarry:
        m, ns = carry.m, carry.noise_state
        if clamp_values is not None:
            m = jnp.where(clamp_mask, clamp_values, m)
        for mk in masks:
            ns0 = ns
            ns, u = noise_fn(ns)
            m = hs(m, chip, mk, beta, u)
            if flip_fn is not None:
                m = jnp.where(mk & flip_fn(ns0), -m, m)
        return SweepCarry(m, ns)

    return sweep


def _resolve_kernel(backend: str, kernel: Callable | None,
                    interpret: bool | None = None) -> Callable | None:
    """Half-sweep implementation for the scan-based backends."""
    if kernel is not None:
        return kernel
    if backend == "pallas":
        from repro.kernels import ops as kernel_ops
        return kernel_ops.make_kernel_half_sweep(interpret=interpret)
    if backend in ("sparse", "fused_sparse"):
        # "fused_sparse" lands here only on the collect=True fallback
        from repro.kernels import ops as kernel_ops
        return kernel_ops.sparse_half_sweep
    return None  # "ref" (and "fused" fallbacks) use the jnp half_sweep


def gibbs_sample(
    chip: EffectiveChip,
    color: jax.Array,
    init_m: jax.Array,
    betas: jax.Array,
    noise_state: jax.Array,
    noise_fn: NoiseFn,
    clamp_mask: jax.Array | None = None,
    clamp_values: jax.Array | None = None,
    collect: bool = False,
    kernel: Callable | None = None,
    backend: str | None = None,
    interpret: bool | None = None,
    flip_fn: Callable | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """Run n_sweeps sweeps.  Returns (final_m, noise_state, traj|None).

    betas: (n_sweeps,) shared schedule or (n_sweeps, B) per-chain inverse
    temperatures (parallel-tempering replicas).
    traj (if collect): (n_sweeps, B, N) spin states after every sweep.
    backend: "ref" | "pallas" | "fused" (None/"auto" -> REPRO_PBIT_BACKEND
    env var, default "ref").  The fused engine runs every sweep inside one
    kernel launch; it cannot emit per-sweep trajectories, so ``collect``
    falls back to the scan path.
    interpret: Pallas interpret mode for the kernel backends (None -> off
    on a TPU, on elsewhere; api.Session resolves it once at compile and
    passes it explicitly).
    """
    backend = resolve_backend(backend)
    # an explicit kernel= always wins (custom half-sweep injection): the
    # fused engine could not honor it, so fall through to the scan path —
    # same for a flip_fn fault hook, which runs between half-sweeps
    if backend in FUSED_BACKENDS and not collect and kernel is None \
            and flip_fn is None:
        from repro.kernels import ops as kernel_ops
        m, ns = kernel_ops.fused_sweeps(
            init_m, chip, color, betas, noise_state,
            getattr(noise_fn, "spec", None),
            clamp_mask=clamp_mask, clamp_values=clamp_values,
            sparse=(backend == "fused_sparse"), interpret=interpret)
        return m, ns, None

    sweep = make_sweep_fn(chip, color, noise_fn, clamp_mask, clamp_values,
                          _resolve_kernel(backend, kernel, interpret),
                          flip_fn=flip_fn)

    def body(carry, beta):
        nxt = sweep(carry, beta)
        return nxt, (nxt.m if collect else None)

    (final, traj) = jax.lax.scan(
        body, SweepCarry(init_m, noise_state), betas)
    return final.m, final.noise_state, traj


def gibbs_stats(
    chip: EffectiveChip,
    color: jax.Array,
    init_m: jax.Array,
    beta: float,
    n_sweeps: int,
    burn_in: int,
    noise_state: jax.Array,
    noise_fn: NoiseFn,
    edges: jax.Array,
    clamp_mask: jax.Array | None = None,
    clamp_values: jax.Array | None = None,
    kernel: Callable | None = None,
    backend: str | None = None,
    interpret: bool | None = None,
    flip_fn: Callable | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Accumulate first/second moments on-line (no trajectory storage).

    Returns (mean_spin[N], mean_edge_corr[E], final_m, noise_state), with
    moments averaged over chains and post-burn-in sweeps — exactly the
    statistics contrastive divergence needs.  With backend="fused" (or
    "fused_sparse") the whole phase (every sweep AND the moment
    accumulation) is one kernel launch: per-sweep spins never touch HBM;
    edge correlations are read out of the accumulated m^T m Gram matrix
    (dense) or the (D, N) per-slot correlation table (sparse).
    """
    backend = resolve_backend(backend)
    e0, e1 = edges[:, 0], edges[:, 1]
    betas = jnp.full((n_sweeps,), beta, dtype=jnp.float32)
    denom = jnp.maximum(n_sweeps - burn_in, 1).astype(jnp.float32)

    if backend in FUSED_BACKENDS and kernel is None and flip_fn is None:
        from repro.kernels import ops as kernel_ops
        sparse = backend == "fused_sparse"
        measured = (jnp.arange(n_sweeps) >= burn_in).astype(jnp.float32)
        m, ns, s_sum, c_sum = kernel_ops.fused_sweeps(
            init_m, chip, color, betas, noise_state,
            getattr(noise_fn, "spec", None),
            clamp_mask=clamp_mask, clamp_values=clamp_values,
            measured=measured, sparse=sparse, interpret=interpret)
        scale = denom * init_m.shape[0]
        if sparse:
            # edge (i, j) lives at slot row d with nbr_idx[d, i] == j
            slot = jnp.argmax(chip.nbr_idx[:, e0] == e1[None, :], axis=0)
            c_edge = c_sum[slot, e0]
        else:
            c_edge = c_sum[e0, e1]
        return s_sum / scale, c_edge / scale, m, ns

    sweep = make_sweep_fn(chip, color, noise_fn, clamp_mask, clamp_values,
                          _resolve_kernel(backend, kernel, interpret),
                          flip_fn=flip_fn)

    def body(carry, inp):
        state, s_sum, c_sum = carry
        beta_t, is_measured = inp
        state = sweep(state, beta_t)
        w = is_measured.astype(jnp.float32)
        s_sum = s_sum + w * state.m.mean(axis=0)
        corr = (state.m[:, e0] * state.m[:, e1]).mean(axis=0)
        c_sum = c_sum + w * corr
        return (state, s_sum, c_sum), None

    measured = (jnp.arange(n_sweeps) >= burn_in)
    init = (
        SweepCarry(init_m, noise_state),
        jnp.zeros((init_m.shape[1],), jnp.float32),
        jnp.zeros((edges.shape[0],), jnp.float32),
    )
    (state, s_sum, c_sum), _ = jax.lax.scan(body, init, (betas, measured))
    return s_sum / denom, c_sum / denom, state.m, state.noise_state


def gibbs_visible_hist(
    chip: EffectiveChip,
    color: jax.Array,
    init_m: jax.Array,
    betas: jax.Array,
    burn_in: int,
    noise_state: jax.Array,
    noise_fn: NoiseFn,
    visible_idx: np.ndarray,
    backend: str | None = None,
    interpret: bool | None = None,
    clamp_mask: jax.Array | None = None,
    clamp_values: jax.Array | None = None,
    flip_fn: Callable | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Free-run and histogram the visible bit patterns, streaming.

    Returns (counts[2^nv], final_m, noise_state): counts[c] is the number
    of (chain, post-burn-in sweep) samples whose visible spins encode c
    (energy.empirical_visible_dist code order).  The scan backends fold the
    histogram into the sweep loop; the fused backends accumulate it inside
    the kernel — either way the (sweeps, B, N) trajectory never
    materializes, unlike the old `gibbs_sample(collect=True)` route.

    clamp_mask/clamp_values freeze nodes through the run (stuck-at-spin
    faults; conditioned histograms) — the in-kernel histogram takes no
    clamps, so a clamped (or flip-injected) call uses the scan path.
    """
    backend = resolve_backend(backend)
    visible_idx = np.asarray(visible_idx)
    nv = int(visible_idx.shape[0])
    n_sweeps = betas.shape[0]
    measured = (jnp.arange(n_sweeps) >= burn_in).astype(jnp.float32)

    if backend in FUSED_BACKENDS and clamp_mask is None and flip_fn is None:
        from repro.kernels import ops as kernel_ops
        from repro.kernels.sweep_fused import MAX_HIST_VISIBLE
        spec = getattr(noise_fn, "spec", None)
        # host noise (philox) or an oversized visible set cannot histogram
        # in-kernel: fall back to the scan path, like collect=True used to
        if (spec is not None and spec.kind in ("counter", "lfsr")
                and nv <= MAX_HIST_VISIBLE):
            m, ns, hist = kernel_ops.fused_visible_hist(
                init_m, chip, color, betas, noise_state, spec, visible_idx,
                measured, sparse=(backend == "fused_sparse"),
                interpret=interpret)
            return hist, m, ns

    sweep = make_sweep_fn(chip, color, noise_fn, clamp_mask, clamp_values,
                          _resolve_kernel(backend, None, interpret),
                          flip_fn=flip_fn)
    vis = jnp.asarray(visible_idx)
    pow2 = jnp.asarray(2 ** np.arange(nv), jnp.int32)

    def body(carry, inp):
        state, hist = carry
        beta_t, w = inp
        state = sweep(state, beta_t)
        codes = jnp.sum((state.m[:, vis] > 0).astype(jnp.int32) * pow2,
                        axis=1)
        # scatter-add, not a (B, 2^nv) one-hot: this path is the fallback
        # for visible sets too wide for the in-kernel histogram
        return (state, hist.at[codes].add(w)), None

    init = (SweepCarry(init_m, noise_state),
            jnp.zeros((2 ** nv,), jnp.float32))
    (state, hist), _ = jax.lax.scan(body, init, (betas, measured))
    return hist, state.m, state.noise_state


def random_spins(key: jax.Array, batch: int, n_nodes: int) -> jax.Array:
    return jnp.where(
        jax.random.bernoulli(key, 0.5, (batch, n_nodes)), 1.0, -1.0
    ).astype(jnp.float32)
