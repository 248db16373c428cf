"""Sweep-resident fused engine vs the scan-of-half-sweeps oracle.

The fused kernel must be *bit-exact* (interpret mode) against running the
same sweeps through kernels/ref.py half-sweeps with host-generated noise,
for both in-kernel noise modes:
  * counter — the stateless hash of core/lfsr.py::counter_uniform,
  * lfsr    — the chip's Galois LFSR, advanced inside the kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lfsr, pbit
from repro.core.chimera import make_chimera
from repro.core.hardware import ideal_chip
from repro.kernels.ops import ref_half_sweep
from repro.kernels.pbit_update import pbit_half_sweep_pallas
from repro.kernels.ref import pbit_half_sweep_ref, pbit_sparse_half_sweep_ref
from repro.kernels.sweep_fused import sweep_fused_pallas, sweep_sparse_pallas


def _chip_problem(seed=0, rows=2, cols=3, scale=0.3):
    g = make_chimera(rows, cols)
    rng = np.random.default_rng(seed)
    n = g.n_nodes
    J = np.zeros((n, n), np.float32)
    vals = rng.normal(size=g.n_edges) * scale
    J[g.edges[:, 0], g.edges[:, 1]] = vals
    J[g.edges[:, 1], g.edges[:, 0]] = vals
    h = (rng.normal(size=n) * 0.2).astype(np.float32)
    chip = ideal_chip(J, h, jnp.asarray(g.adjacency()))
    return g, chip


def _noise(kind, g, batch, key):
    if kind == "lfsr":
        init, step = pbit.make_lfsr_noise(g, batch)
    else:
        init, step = pbit.make_counter_noise(batch, g.n_nodes)
    return init(key), step


def _scan_oracle(chip, g, m0, betas, state, step):
    """Scan of kernels/ref.py half-sweeps with host-side noise."""
    color = g.color
    m = m0
    for s in range(betas.shape[0]):
        for c in (0, 1):
            state, u = step(state)
            m = pbit_half_sweep_ref(
                m, chip.W, chip.h, chip.tanh_gain, chip.tanh_offset,
                chip.rand_gain, chip.comp_offset, jnp.asarray(color == c),
                betas[s], u)
    return m, state


@pytest.mark.parametrize("n_sweeps", [1, 4, 16])
@pytest.mark.parametrize("kind", ["counter", "lfsr"])
def test_fused_matches_ref_oracle(n_sweeps, kind):
    g, chip = _chip_problem(seed=n_sweeps)
    B = 10
    m0 = pbit.random_spins(jax.random.PRNGKey(0), B, g.n_nodes)
    state, step = _noise(kind, g, B, jax.random.PRNGKey(1))
    rng = np.random.default_rng(n_sweeps)
    betas = jnp.asarray(rng.uniform(0.2, 1.5, (n_sweeps, B)), jnp.float32)

    m_ref, state_ref = _scan_oracle(chip, g, m0, betas, state, step)
    spec = step.spec
    m_k, state_k = sweep_fused_pallas(
        m0, chip.W, chip.h, chip.tanh_gain, chip.tanh_offset,
        chip.rand_gain, chip.comp_offset,
        jnp.asarray(g.color == 0), jnp.asarray(g.color == 1),
        betas, state, noise_mode=spec.kind, decimation=spec.decimation,
        gather_perm=spec.gather_perm, block_b=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m_ref))
    np.testing.assert_array_equal(np.asarray(state_k),
                                  np.asarray(state_ref))


@pytest.mark.parametrize("kind", ["counter", "lfsr"])
def test_gibbs_sample_backend_fused_vs_ref(kind):
    """Same result through the public backend API, multiple batch tiles."""
    g, chip = _chip_problem(seed=7)
    B = 12
    color = jnp.asarray(g.color)
    m0 = pbit.random_spins(jax.random.PRNGKey(2), B, g.n_nodes)
    betas = jnp.linspace(0.3, 2.0, 9)
    state, step = _noise(kind, g, B, jax.random.PRNGKey(3))
    m_r, ns_r, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                     backend="ref")
    m_f, ns_f, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                     backend="fused")
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(ns_f), np.asarray(ns_r))


def test_fused_clamp_holds_and_matches_ref():
    g, chip = _chip_problem(seed=3)
    B, n = 6, g.n_nodes
    color = jnp.asarray(g.color)
    clamp_mask = jnp.zeros((n,), bool).at[jnp.array([0, 9, 17])].set(True)
    rng = np.random.default_rng(0)
    clamp_values = jnp.asarray(
        np.tile(rng.integers(0, 2, (1, n)) * 2 - 1, (B, 1)), jnp.float32)
    m0 = pbit.random_spins(jax.random.PRNGKey(4), B, n)
    betas = jnp.ones((8,), jnp.float32)
    state, step = _noise("counter", g, B, jax.random.PRNGKey(5))
    m_r, _, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                  clamp_mask=clamp_mask,
                                  clamp_values=clamp_values, backend="ref")
    m_f, _, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                  clamp_mask=clamp_mask,
                                  clamp_values=clamp_values, backend="fused")
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_r))
    held = np.asarray(m_f)[:, np.asarray(clamp_mask)]
    np.testing.assert_array_equal(
        held, np.asarray(clamp_values)[:, np.asarray(clamp_mask)])


def test_fused_clamp_mask_only_matches_ref():
    """clamp_mask without clamp_values freezes nodes at their current
    spins — same semantics as the scan backends."""
    g, chip = _chip_problem(seed=19, rows=1, cols=2)
    B, n = 5, g.n_nodes
    color = jnp.asarray(g.color)
    clamp_mask = jnp.zeros((n,), bool).at[jnp.array([1, 4])].set(True)
    m0 = pbit.random_spins(jax.random.PRNGKey(10), B, n)
    betas = jnp.ones((6,), jnp.float32)
    state, step = _noise("counter", g, B, jax.random.PRNGKey(11))
    m_r, _, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                  clamp_mask=clamp_mask, backend="ref")
    m_f, _, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                  clamp_mask=clamp_mask, backend="fused")
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(m_f)[:, [1, 4]],
                                  np.asarray(m0)[:, [1, 4]])


@pytest.mark.parametrize("kind", ["counter", "lfsr"])
def test_fused_moments_match_gibbs_stats(kind):
    """Fused in-VMEM moment accumulation == jnp gibbs_stats (fp tolerance:
    only the summation order differs)."""
    g, chip = _chip_problem(seed=11, rows=1, cols=2)
    B, n_sweeps, burn_in = 16, 40, 8
    color = jnp.asarray(g.color)
    edges = jnp.asarray(g.edges)
    m0 = pbit.random_spins(jax.random.PRNGKey(6), B, g.n_nodes)
    state, step = _noise(kind, g, B, jax.random.PRNGKey(7))

    s_r, c_r, m_r, ns_r = pbit.gibbs_stats(
        chip, color, m0, 1.0, n_sweeps, burn_in, state, step, edges,
        backend="ref")
    s_f, c_f, m_f, ns_f = pbit.gibbs_stats(
        chip, color, m0, 1.0, n_sweeps, burn_in, state, step, edges,
        backend="fused")
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_r))
    np.testing.assert_array_equal(np.asarray(ns_f), np.asarray(ns_r))
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_r),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_f), np.asarray(c_r),
                               rtol=0, atol=1e-5)


def test_in_kernel_lfsr_bitexact_states():
    """The in-kernel Galois LFSR stream is the host stream, bit for bit."""
    g, chip = _chip_problem(seed=13)
    B = 4
    state, step = _noise("lfsr", g, B, jax.random.PRNGKey(8))
    m0 = pbit.random_spins(jax.random.PRNGKey(9), B, g.n_nodes)
    betas = jnp.ones((5, B), jnp.float32)
    spec = step.spec
    _, state_k = sweep_fused_pallas(
        m0, chip.W, chip.h, chip.tanh_gain, chip.tanh_offset,
        chip.rand_gain, chip.comp_offset,
        jnp.asarray(g.color == 0), jnp.asarray(g.color == 1),
        betas, state, noise_mode="lfsr", gather_perm=spec.gather_perm,
        block_b=8, interpret=True)
    # 5 sweeps x 2 half-sweeps x 8 decimation clocks
    expect = lfsr.lfsr_step_n(state, 5 * 2 * 8)
    np.testing.assert_array_equal(np.asarray(state_k), np.asarray(expect))
    assert (np.asarray(state_k) != 0).all()


@pytest.mark.parametrize("B,N,block_b,slots", [
    pytest.param(3, 77, 8, False, id="3-77-8"),
    pytest.param(17, 130, 8, False, id="17-130-8"),
    pytest.param(64, 440, 32, False, id="64-440-32"),
    # the slot layout on random degree-6 tables (hundreds of lane
    # rotations a slot group): rows walked in blocks of 16 (512 lanes)
    # or 8 (1,024 lanes), with chains that fill no block or tile evenly
    pytest.param(37, 440, 32, True, id="slots-37-440-32"),
    pytest.param(20, 1000, 128, True, id="slots-20-1000-128"),
    # a 12-chain tile is no multiple of 8: one block of all its rows
    pytest.param(12, 440, 12, True, id="slots-12-440-12"),
])
def test_fused_counter_odd_shapes(B, N, block_b, slots):
    """Non-aligned shapes pad cleanly; counter mode works off-Chimera."""
    rng = np.random.default_rng(B + N)
    m0 = jnp.asarray(rng.integers(0, 2, (B, N)) * 2 - 1, jnp.float32)
    W = jnp.asarray(rng.normal(size=(N, N)) * 0.2, jnp.float32)
    h, g, o, rg, co = (jnp.asarray(rng.normal(size=N) * 0.3, jnp.float32)
                       for _ in range(5))
    color = rng.integers(0, 2, N)
    mask0, mask1 = jnp.asarray(color == 0), jnp.asarray(color == 1)
    betas = jnp.asarray(rng.uniform(0.2, 1.5, (3, B)), jnp.float32)
    state = jnp.asarray([42, 5], jnp.uint32)
    if slots:
        # neighbours ascending per node, a fifth of the slots unused
        # (pointing home, weight 0)
        idx = np.sort(rng.integers(0, N, (6, N)), axis=0)
        idx = np.where(rng.random((6, N)) < 0.2, np.arange(N), idx)
        w = np.where(idx == np.arange(N), 0.0, rng.normal(size=(6, N)) * 0.3)
        idx, w = jnp.asarray(idx, jnp.int32), jnp.asarray(w, jnp.float32)

        def half_sweep(m, mk, beta, u):
            return pbit_sparse_half_sweep_ref(m, idx, w, h, g, o, rg, co, mk,
                                              beta, u)
    else:
        def half_sweep(m, mk, beta, u):
            return pbit_half_sweep_ref(m, W, h, g, o, rg, co, mk, beta, u)

    rows = jnp.arange(B, dtype=jnp.uint32)[:, None]
    cols = jnp.arange(N, dtype=jnp.uint32)[None, :]
    m, ctr = m0, 5
    for s in range(3):
        for c, mk in ((0, mask0), (1, mask1)):
            u = lfsr.counter_uniform(jnp.uint32(42), jnp.uint32(ctr), rows,
                                     cols)
            m = half_sweep(m, mk, betas[s], u)
            ctr += 1
    if slots:
        m_k, state_k = sweep_sparse_pallas(
            m0, idx, w, h, g, o, rg, co, mask0, mask1, betas, state,
            noise_mode="counter", block_b=block_b, interpret=True)
    else:
        m_k, state_k = sweep_fused_pallas(
            m0, W, h, g, o, rg, co, mask0, mask1, betas, state,
            noise_mode="counter", block_b=block_b, interpret=True)
    np.testing.assert_array_equal(np.asarray(m_k), np.asarray(m))
    assert int(state_k[1]) == ctr


def test_fused_bf16_spins():
    """±1 spins are exact in bf16; fused output matches the f32 oracle."""
    g, chip = _chip_problem(seed=17, rows=1, cols=2)
    B = 8
    m0 = pbit.random_spins(jax.random.PRNGKey(1), B, g.n_nodes)
    betas = jnp.ones((4, B), jnp.float32)
    state = jnp.asarray([7, 0], jnp.uint32)
    m_f32, _ = sweep_fused_pallas(
        m0, chip.W, chip.h, chip.tanh_gain, chip.tanh_offset,
        chip.rand_gain, chip.comp_offset,
        jnp.asarray(g.color == 0), jnp.asarray(g.color == 1),
        betas, state, noise_mode="counter", block_b=8, interpret=True)
    m_bf, _ = sweep_fused_pallas(
        m0.astype(jnp.bfloat16), chip.W, chip.h, chip.tanh_gain,
        chip.tanh_offset, chip.rand_gain, chip.comp_offset,
        jnp.asarray(g.color == 0), jnp.asarray(g.color == 1),
        betas, state, noise_mode="counter", block_b=8, interpret=True)
    assert m_bf.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(m_bf, np.float32),
                                  np.asarray(m_f32))


def test_fused_requires_kernel_noise():
    g, chip = _chip_problem(seed=1, rows=1, cols=1)
    B = 4
    m0 = pbit.random_spins(jax.random.PRNGKey(0), B, g.n_nodes)
    step = pbit.make_philox_noise(B, g.n_nodes)
    with pytest.raises(ValueError, match="counter|lfsr"):
        pbit.gibbs_sample(chip, jnp.asarray(g.color), m0, jnp.ones((3,)),
                          jax.random.PRNGKey(1), step, backend="fused")


def test_counter_noise_matches_boltzmann():
    """Counter-mode noise is good enough to sample the exact distribution."""
    from repro.core import energy

    g, chip = _chip_problem(seed=21, rows=1, cols=1, scale=0.7)
    init, step = pbit.make_counter_noise(512, 8)
    state = init(jax.random.PRNGKey(2))
    m0 = pbit.random_spins(jax.random.PRNGKey(0), 512, 8)
    betas = jnp.ones((400,), jnp.float32)
    _, _, traj = pbit.gibbs_sample(
        chip, jnp.asarray(g.color), m0, betas, state, step, collect=True)
    samples = np.asarray(traj[100:]).reshape(-1, 8)
    emp = energy.empirical_visible_dist(samples, np.arange(8))
    W = np.asarray(chip.W)
    exact = energy.exact_boltzmann(
        (W + W.T) / 2.0, np.asarray(chip.h), 1.0)
    assert energy.kl_divergence(exact, emp) < 0.08


def test_vector_beta_half_sweep_kernels():
    """(B,) beta column == per-row scalar calls, for ref and Pallas."""
    rng = np.random.default_rng(5)
    B, N = 6, 200
    m = jnp.asarray(rng.integers(0, 2, (B, N)) * 2 - 1, jnp.float32)
    W = jnp.asarray(rng.normal(size=(N, N)) * 0.1, jnp.float32)
    h, g, o, rg, co = (jnp.asarray(rng.normal(size=N), jnp.float32)
                       for _ in range(5))
    mask = jnp.asarray(rng.integers(0, 2, N).astype(bool))
    u = jnp.asarray(rng.uniform(-1, 1, (B, N)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.1, 2.0, B), jnp.float32)

    per_row = jnp.concatenate([
        pbit_half_sweep_ref(m[i:i + 1], W, h, g, o, rg, co, mask,
                            beta[i], u[i:i + 1])
        for i in range(B)])
    vec_ref = pbit_half_sweep_ref(m, W, h, g, o, rg, co, mask, beta, u)
    vec_pal = pbit_half_sweep_pallas(m, W, h, g, o, rg, co, mask, beta, u,
                                     block_b=8, block_n=128, block_k=128,
                                     interpret=True)
    np.testing.assert_array_equal(np.asarray(vec_ref), np.asarray(per_row))
    np.testing.assert_array_equal(np.asarray(vec_pal), np.asarray(per_row))


def test_tempering_runs_through_shared_backend():
    """PT through the shared API: fused == ref, bit for bit."""
    from repro.core.annealing import sk_instance
    from repro.core.cd import PBitMachine
    from repro.core.hardware import HardwareConfig
    from repro.core.tempering import PTConfig, parallel_tempering

    g = make_chimera(2, 2)
    J, h = sk_instance(g, jax.random.PRNGKey(1))
    cfg = PTConfig(n_replicas=8, n_sweeps=60, swap_every=10)
    results = {}
    for backend in ("ref", "fused"):
        machine = PBitMachine.create(
            g, jax.random.PRNGKey(0), HardwareConfig(), w_scale=0.02,
            noise="counter", backend=backend)
        results[backend] = parallel_tempering(
            machine, J, h, cfg, jax.random.PRNGKey(2))
    np.testing.assert_array_equal(results["ref"]["best_state"],
                                  results["fused"]["best_state"])
    assert results["ref"]["best_energy"] == results["fused"]["best_energy"]
    np.testing.assert_array_equal(results["ref"]["final_order"],
                                  results["fused"]["final_order"])
