"""Chimera spin-glass lattices through `api.Session`: the slot layout
against a dense reference, and an anneal that lowers the energy.

A lattice is a `PBitMachine` on `make_chimera` with ideal hardware,
programmed by `program_edges` from edge codes drawn like an SK instance
(couplings ~ N(0, 0.8) at the default coupling LSB), and scored by
`core.distributed.sparse_energy`.  Sampling the slot chip must match the
dense reference bit for bit.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core.cd import PBitMachine
from repro.core.chimera import make_chimera
from repro.core.distributed import sparse_energy
from repro.core.hardware import HardwareConfig


def lattice_codes(g, seed, sigma=16.0):
    """SK-style edge codes (zero biases): N(0, sigma) codes, rounded and
    clipped to the 8-bit DAC range."""
    rng = np.random.default_rng(seed)
    J = np.clip(np.rint(rng.normal(0.0, sigma, g.n_edges)), -127, 127)
    return (jnp.asarray(J, jnp.int32),
            jnp.zeros((g.n_nodes,), jnp.int32))


def lattice_machine(g, sparse=True, **kw):
    kw.setdefault("backend", "sparse" if sparse else "ref")
    return PBitMachine.create(g, jax.random.PRNGKey(0),
                              HardwareConfig.ideal(), sparse=sparse,
                              noise="counter", **kw)


def anneal(ses, chip, key, betas, record_every, score=None):
    """Sample ``betas`` in segments of ``record_every`` sweeps; return the
    final spins and each segment's mean energy (``score(m)``, per chain;
    `sparse_energy` on ``chip`` by default)."""
    if score is None:
        score = functools.partial(sparse_energy, chip)
    k1, k2 = jax.random.split(key)
    m, ns = ses.random_spins(k1), ses.noise_state(k2)
    energies = []
    for seg in jnp.reshape(betas, (-1, record_every)):
        m, ns, _ = ses.sample(chip, m, ns, seg)
        energies.append(float(score(m).mean()))
    return m, np.asarray(energies)


def test_lattice_slot_chip_matches_dense_reference():
    """The slot chip's weights are a gather of the densely programmed W,
    sampling the slot chip (backend "sparse") is bit-exact against the
    dense ref, and `sparse_energy` equals the dense quadratic form."""
    g = make_chimera(3, 2)
    J, h = lattice_codes(g, 0)
    B = 4
    ses_s = api.Session(lattice_machine(g).sampler_spec(chains=B))
    ses_d = api.Session(lattice_machine(g, sparse=False).sampler_spec(
        chains=B))
    chip_s = ses_s.program_edges(J, h)
    chip_d = ses_d.program_edges(J, h)
    assert chip_s.W is None

    nbr_idx = np.asarray(chip_s.nbr_idx)
    rows = np.arange(g.n_nodes)[None, :]
    np.testing.assert_array_equal(np.asarray(chip_s.nbr_w),
                                  np.asarray(chip_d.W)[rows, nbr_idx])
    np.testing.assert_array_equal(np.asarray(chip_s.h),
                                  np.asarray(chip_d.h))
    np.testing.assert_array_equal(np.asarray(chip_s.tanh_gain),
                                  np.asarray(chip_d.tanh_gain))

    # full Gibbs parity under one noise stream
    m0 = ses_s.random_spins(jax.random.PRNGKey(1))
    ns = ses_s.noise_state(jax.random.PRNGKey(2))
    betas = jnp.linspace(0.3, 1.2, 7)
    m_s, _, _ = ses_s.sample(chip_s, m0, ns, betas)
    m_d, _, _ = ses_d.sample(chip_d, m0, ns, betas)
    np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_d))

    # energy parity vs the explicit dense quadratic form
    W_sym = 0.5 * (np.asarray(chip_d.W) + np.asarray(chip_d.W).T)
    m_np = np.asarray(m_s)
    e_dense = (-0.5 * np.einsum("bi,ij,bj->b", m_np, W_sym, m_np)
               - m_np @ np.asarray(chip_d.h))
    np.testing.assert_allclose(np.asarray(sparse_energy(chip_s, m_s)),
                               e_dense, rtol=1e-5)


def test_chain_batched_anneal_energy_decreases():
    g = make_chimera(6, 6)
    chains = 8
    ses = api.Session(lattice_machine(g).sampler_spec(chains=chains))
    chip = ses.program_edges(*lattice_codes(g, 0))
    m, e = anneal(ses, chip, jax.random.PRNGKey(1),
                  jnp.linspace(0.05, 2.5, 80), record_every=20)
    assert m.shape == (chains, g.n_nodes)
    assert e[-1] < 0 and e[-1] < 0.8 * e[0]
