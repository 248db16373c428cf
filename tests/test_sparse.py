"""Chimera-native block-sparse compute path vs the dense reference.

The fixed-degree slot layout (ChimeraGraph.neighbor_table) must be
*bit-exact* against the dense path on Chimera graphs: neighbors accumulate
in ascending order, so the degree-≤6 gather reproduces the dense row
reduction term for term (zeros are additive identities), and the sparse
Pallas kernel runs the identical op sequence as the sparse jnp ref.
Covers masked graphs, clamped CD phases, per-chain (S, B) tempering betas,
and all three noise kinds (philox / counter / lfsr).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import pbit, tasks
from repro.core.cd import CDConfig, PBitMachine, make_cd_step
from repro.core.chimera import make_chimera, make_chip_graph
from repro.core.hardware import (
    HardwareConfig,
    attach_sparse,
    gather_mismatch,
    ideal_chip,
    program_weights,
    program_weights_sparse,
    sample_mismatch,
)
from repro.kernels import ops as kernel_ops
from repro.kernels.sweep_fused import (
    _gather_rows,
    _lane_rotations,
    _slot_groups,
)

SPARSE_BACKENDS = ("sparse", "fused_sparse")


def _graph(rows=2, cols=3, masked=((0, 1),)):
    return make_chimera(rows, cols, masked_cells=masked)


_GRAPHS = {"small": _graph, "chip": make_chip_graph}


def _tiles(monkeypatch, block_b):
    """Run the fused engines at chain tiles of ``block_b`` (None: the
    engines' default)."""
    if block_b is not None:
        for name in ("fused_sweeps", "fused_visible_hist"):
            monkeypatch.setattr(kernel_ops, name, functools.partial(
                getattr(kernel_ops, name), block_b=block_b))


def _chip(g, seed=0, scale=0.3):
    rng = np.random.default_rng(seed)
    n = g.n_nodes
    J = np.zeros((n, n), np.float32)
    vals = rng.normal(size=g.n_edges) * scale
    J[g.edges[:, 0], g.edges[:, 1]] = vals
    J[g.edges[:, 1], g.edges[:, 0]] = vals
    h = (rng.normal(size=n) * 0.2).astype(np.float32)
    nbr_idx, _ = g.neighbor_table()
    return ideal_chip(J, h, jnp.asarray(g.adjacency()),
                      neighbors=jnp.asarray(nbr_idx))


def _noise(kind, g, batch, key):
    if kind == "lfsr":
        init, step = pbit.make_lfsr_noise(g, batch)
        return init(key), step
    if kind == "counter":
        init, step = pbit.make_counter_noise(batch, g.n_nodes)
        return init(key), step
    return key, pbit.make_philox_noise(batch, g.n_nodes)


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------
def test_neighbor_table_covers_chip_graph():
    g = make_chip_graph()
    nbr_idx, nbr_mask = g.neighbor_table()
    assert nbr_idx.shape[0] == 6  # 4 in-cell K4,4 + 2 chain couplers
    assert nbr_mask.sum() == 2 * g.n_edges  # every coupler, both directions
    # real slots list each node's neighbors ascending; padding points home
    n = g.n_nodes
    for i in (0, 17, n - 1):
        nbrs = nbr_idx[nbr_mask[:, i], i]
        assert (np.diff(nbrs) > 0).all()
        assert (nbr_idx[~nbr_mask[:, i], i] == i).all()
    # each edge is findable from both endpoints
    sij, sji = g.edge_slots(nbr_idx)
    assert (nbr_idx[sij, g.edges[:, 0]] == g.edges[:, 1]).all()
    assert (nbr_idx[sji, g.edges[:, 1]] == g.edges[:, 0]).all()


def test_attach_sparse_gathers_dense_weights():
    g = _graph()
    chip = _chip(g, seed=5)
    nbr_idx = np.asarray(chip.nbr_idx)
    W = np.asarray(chip.W)
    want = W[np.arange(g.n_nodes)[None, :], nbr_idx]
    np.testing.assert_array_equal(np.asarray(chip.nbr_w), want)


@pytest.mark.parametrize("graph,per_slot,union", [
    pytest.param(make_chip_graph, [10, 10, 10, 10, 10, 3], 19, id="chip"),
    pytest.param(functools.partial(make_chimera, 4, 5,
                                   masked_cells=((1, 2), (3, 0))),
                 None, None, id="masked"),
    pytest.param(functools.partial(make_chimera, 16, 16), None, None,
                 id="c16"),
])
def test_lane_rotations_reproduce_gather(graph, per_slot, union):
    """Each slot's own lane rotations, alone or shared within the
    kernel's slot groups, rebuild ``jnp.take(m, nbr_idx[d], axis=1)``
    exactly inside a kernel (one rotation a step, or 16 with the padded
    table)."""
    g = graph()
    idx = np.asarray(g.neighbor_table()[0])
    D, n = idx.shape
    Np = -(-n // 128) * 128
    slots = tuple((d,) for d in range(D))
    _, table = _lane_rotations(idx, n, Np, slots)
    counts = np.asarray(table).reshape(D, 1 + Np)[:, 0]
    if per_slot is not None:
        assert counts.tolist() == per_slot
        _, everything = _lane_rotations(idx, n, Np, (tuple(range(D)),))
        assert int(everything[0]) == union
    rng = np.random.default_rng(n)
    m = jnp.asarray(rng.normal(size=(8, Np)), jnp.float32)  # all distinct
    want = np.stack([np.take(np.asarray(m)[:, :n], idx[d], axis=1)
                     for d in range(D)])
    for groups, per_step in ((slots, 1), (_slot_groups(D), 16)):
        shifts, table = _lane_rotations(idx, n, Np, groups)

        def kernel(x_ref, sh_ref, rot_ref, o_ref, groups=groups,
                   per_step=per_step):
            for gi, rows in enumerate(groups):
                gathers = _gather_rows(x_ref[...], sh_ref, rot_ref, gi,
                                       rows, per_step)
                for r, gr in zip(rows, gathers):
                    o_ref[r] = gr

        out = pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((D, 8, Np), jnp.float32),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            interpret=True)(m, shifts, table)
        np.testing.assert_array_equal(np.asarray(out)[:, :, :n], want)


# ---------------------------------------------------------------------------
# bit-exact sampling parity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["philox", "counter", "lfsr"])
@pytest.mark.parametrize("masked", [(), ((0, 1), (1, 2))])
def test_sparse_ref_matches_dense_ref(kind, masked):
    """Scan backend "sparse" == "ref", per-chain (S, B) tempering betas."""
    g = _graph(masked=masked)
    chip = _chip(g, seed=len(masked))
    B = 10
    m0 = pbit.random_spins(jax.random.PRNGKey(0), B, g.n_nodes)
    state, step = _noise(kind, g, B, jax.random.PRNGKey(1))
    rng = np.random.default_rng(7)
    betas = jnp.asarray(rng.uniform(0.2, 1.8, (9, B)), jnp.float32)
    color = jnp.asarray(g.color)
    m_d, ns_d, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                     backend="ref")
    m_s, ns_s, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                     backend="sparse")
    np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_d))
    np.testing.assert_array_equal(np.asarray(ns_s), np.asarray(ns_d))


@pytest.mark.parametrize("kind,graph,B,block_b", [
    pytest.param("counter", "small", 12, None, id="counter"),
    pytest.param("lfsr", "small", 12, None, id="lfsr"),
    # the chip graph's 512-lane rows are walked in 16- or 8-row blocks:
    # 36 chains in 32-chain tiles leave a last tile of 4 chains and 28
    # padded rows; 40 chains in one tile split into five 8-row blocks
    pytest.param("counter", "chip", 36, 32, id="counter-chip-B36-tile32"),
    pytest.param("lfsr", "chip", 40, 128, id="lfsr-chip-B40-tile40"),
    pytest.param("counter", "chip", 20, 8, id="counter-chip-B20-tile8"),
])
def test_fused_sparse_matches_ref(kind, graph, B, block_b, monkeypatch):
    """Sweep-resident sparse kernel == dense ref, multiple batch tiles."""
    _tiles(monkeypatch, block_b)
    g = _GRAPHS[graph]()
    chip = _chip(g, seed=11)
    m0 = pbit.random_spins(jax.random.PRNGKey(2), B, g.n_nodes)
    state, step = _noise(kind, g, B, jax.random.PRNGKey(3))
    betas = jnp.linspace(0.3, 2.0, 9)
    color = jnp.asarray(g.color)
    m_d, ns_d, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                     backend="ref")
    m_f, ns_f, _ = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                     backend="fused_sparse")
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_d))
    np.testing.assert_array_equal(np.asarray(ns_f), np.asarray(ns_d))


@pytest.mark.parametrize("kind,graph,B,block_b", [
    pytest.param("philox", "small", 8, None, id="philox"),
    pytest.param("counter", "small", 8, None, id="counter"),
    pytest.param("lfsr", "small", 8, None, id="lfsr"),
    # moments summed over uneven row blocks and padded rows (see
    # test_fused_sparse_matches_ref)
    pytest.param("counter", "chip", 36, 32, id="counter-chip-B36-tile32"),
    pytest.param("lfsr", "chip", 40, 128, id="lfsr-chip-B40-tile40"),
])
def test_sparse_clamped_stats_match(kind, graph, B, block_b, monkeypatch):
    """Clamped (CD positive phase) gibbs_stats: spins bit-exact, moments
    exact on the scan path and fp-tolerance on the fused kernel."""
    _tiles(monkeypatch, block_b)
    g = (_graph(rows=1, cols=2, masked=()) if graph == "small"
         else _GRAPHS[graph]())
    chip = _chip(g, seed=13)
    n = g.n_nodes
    color = jnp.asarray(g.color)
    edges = jnp.asarray(g.edges)
    clamp_mask = jnp.zeros((n,), bool).at[jnp.array([0, 5, 9])].set(True)
    rng = np.random.default_rng(1)
    clamp_values = jnp.asarray(
        np.tile(rng.integers(0, 2, (1, n)) * 2 - 1, (B, 1)), jnp.float32)
    m0 = pbit.random_spins(jax.random.PRNGKey(4), B, n)
    state, step = _noise(kind, g, B, jax.random.PRNGKey(5))

    s_d, c_d, m_d, ns_d = pbit.gibbs_stats(
        chip, color, m0, 1.0, 24, 4, state, step, edges,
        clamp_mask=clamp_mask, clamp_values=clamp_values, backend="ref")
    s_s, c_s, m_s, ns_s = pbit.gibbs_stats(
        chip, color, m0, 1.0, 24, 4, state, step, edges,
        clamp_mask=clamp_mask, clamp_values=clamp_values, backend="sparse")
    np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_d))
    np.testing.assert_array_equal(np.asarray(s_s), np.asarray(s_d))
    np.testing.assert_array_equal(np.asarray(c_s), np.asarray(c_d))
    if kind == "philox":
        return  # the fused engines need in-kernel noise
    s_f, c_f, m_f, ns_f = pbit.gibbs_stats(
        chip, color, m0, 1.0, 24, 4, state, step, edges,
        clamp_mask=clamp_mask, clamp_values=clamp_values,
        backend="fused_sparse")
    np.testing.assert_array_equal(np.asarray(m_f), np.asarray(m_d))
    np.testing.assert_array_equal(np.asarray(ns_f), np.asarray(ns_d))
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_d),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_f), np.asarray(c_d),
                               rtol=0, atol=1e-5)


def test_sparse_requires_layout():
    g = _graph(rows=1, cols=1, masked=())
    chip = ideal_chip(np.zeros((8, 8), np.float32), np.zeros(8))  # no slots
    m0 = pbit.random_spins(jax.random.PRNGKey(0), 4, 8)
    init, step = pbit.make_counter_noise(4, 8)
    with pytest.raises(ValueError, match="neighbor"):
        pbit.gibbs_sample(chip, jnp.asarray(g.color), m0, jnp.ones((2,)),
                          init(jax.random.PRNGKey(1)), step,
                          backend="sparse")


# ---------------------------------------------------------------------------
# sparse-native programming (no O(N²) anywhere)
# ---------------------------------------------------------------------------
def test_program_weights_sparse_matches_dense_gather():
    """Slot-native programming through a gathered dense mismatch is
    bit-identical to gathering the densely programmed chip."""
    g = _graph()
    n = g.n_nodes
    hw = HardwareConfig()
    mism = sample_mismatch(jax.random.PRNGKey(8), n, hw)
    nbr_idx, nbr_mask = g.neighbor_table()
    rng = np.random.default_rng(2)
    J = np.zeros((n, n), np.int32)
    vals = rng.integers(-100, 100, g.n_edges)
    J[g.edges[:, 0], g.edges[:, 1]] = vals
    J[g.edges[:, 1], g.edges[:, 0]] = vals
    h = rng.integers(-50, 50, n).astype(np.int32)
    enable = np.abs(J) > 0

    dense = program_weights(jnp.asarray(J), jnp.asarray(h),
                            jnp.asarray(enable), mism, hw,
                            adjacency=jnp.asarray(g.adjacency()),
                            neighbors=jnp.asarray(nbr_idx))
    rows = np.arange(n)[None, :]
    sparse = program_weights_sparse(
        jnp.asarray(J[rows, nbr_idx]), jnp.asarray(h),
        jnp.asarray(enable[rows, nbr_idx]), gather_mismatch(mism, nbr_idx),
        hw, jnp.asarray(nbr_idx), jnp.asarray(nbr_mask))
    assert sparse.W is None
    np.testing.assert_array_equal(np.asarray(sparse.nbr_w),
                                  np.asarray(dense.nbr_w))
    np.testing.assert_array_equal(np.asarray(sparse.h), np.asarray(dense.h))


def test_sparse_native_machine_ideal_matches_dense():
    """An ideal sparse-native machine (SparseMismatch, W never built)
    samples the exact same dynamics as the dense machine."""
    g = _graph(rows=1, cols=2, masked=())
    n = g.n_nodes
    rng = np.random.default_rng(3)
    codes_e = jnp.asarray(rng.integers(-40, 40, g.n_edges), jnp.int32)
    h_codes = jnp.asarray(rng.integers(-10, 10, n), jnp.int32)
    kw = dict(noise="counter", w_scale=0.05)
    mach_s = PBitMachine.create(g, jax.random.PRNGKey(0),
                                HardwareConfig.ideal(), sparse=True, **kw)
    mach_d = PBitMachine.create(g, jax.random.PRNGKey(0),
                                HardwareConfig.ideal(), **kw)
    assert mach_s.sparse_native and not mach_d.sparse_native
    chip_s = mach_s.program_edges(codes_e, h_codes)
    chip_d = mach_d.program_edges(codes_e, h_codes)
    assert chip_s.W is None
    B = 8
    m0 = pbit.random_spins(jax.random.PRNGKey(4), B, n)
    state, step = mach_s.noise_fn(jax.random.PRNGKey(5), B)
    betas = jnp.ones((12, B), jnp.float32)
    color = jnp.asarray(g.color)
    m_s, _, _ = pbit.gibbs_sample(chip_s, color, m0, betas, state, step,
                                  backend="fused_sparse")
    m_d, _, _ = pbit.gibbs_sample(chip_d, color, m0, betas, state, step,
                                  backend="ref")
    np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_d))


def test_sparse_machine_reproduces_dense_chip():
    """ROADMAP item closed: a sparse-native machine reproduces a *given*
    dense machine's mismatch bit-for-bit at chip scale (440 spins, real
    process-variation sigmas) — `machine.to_sparse()` gathers the dense
    draw into the slot layout; same codes => identical couplings and an
    identical spin trajectory for the same noise stream."""
    g = make_chip_graph()
    mach_d = PBitMachine.create(g, jax.random.PRNGKey(3), HardwareConfig(),
                                noise="counter", backend="ref")
    mach_s = mach_d.to_sparse()
    assert mach_s.sparse_native and mach_s.backend == "sparse"

    rng = np.random.default_rng(5)
    codes_e = jnp.asarray(rng.integers(-80, 80, g.n_edges), jnp.int32)
    h_codes = jnp.asarray(rng.integers(-30, 30, g.n_nodes), jnp.int32)
    chip_d = mach_d.program_edges(codes_e, h_codes)
    chip_s = mach_s.program_edges(codes_e, h_codes)
    assert chip_s.W is None and chip_s.nbr_w.shape == (6, 440)
    np.testing.assert_array_equal(np.asarray(chip_s.nbr_w),
                                  np.asarray(chip_d.nbr_w))
    np.testing.assert_array_equal(np.asarray(chip_s.h),
                                  np.asarray(chip_d.h))

    B, S = 4, 6
    ses_d = mach_d.session(chains=B)
    ses_s = mach_s.session(chains=B)
    m0 = ses_d.random_spins(jax.random.PRNGKey(6))
    ns = ses_d.noise_state(jax.random.PRNGKey(7))
    betas = jnp.linspace(0.4, 1.6, S)
    m_d, ns_d, _ = ses_d.sample(chip_d, m0, ns, betas)
    m_s, ns_s, _ = ses_s.sample(chip_s, m0, ns, betas)
    np.testing.assert_array_equal(np.asarray(m_s), np.asarray(m_d))
    np.testing.assert_array_equal(np.asarray(ns_s), np.asarray(ns_d))


def test_large_lattice_sparse_only_smoke():
    """16x16 Chimera (2048 spins) end-to-end on the sparse-native path —
    the layout whose dense (N, N) form would already crowd a VMEM core."""
    g = make_chimera(16, 16)
    assert g.n_nodes == 2048
    mach = PBitMachine.create(g, jax.random.PRNGKey(0),
                              HardwareConfig.ideal(), sparse=True,
                              noise="counter", backend="fused_sparse")
    rng = np.random.default_rng(4)
    codes_e = jnp.asarray(rng.integers(-30, 30, g.n_edges), jnp.int32)
    chip = mach.program_edges(codes_e, jnp.zeros((g.n_nodes,), jnp.int32))
    assert chip.W is None and chip.nbr_w.shape == (6, 2048)
    B = 4
    m0 = pbit.random_spins(jax.random.PRNGKey(1), B, g.n_nodes)
    state, step = mach.noise_fn(jax.random.PRNGKey(2), B)
    m, ns, _ = pbit.gibbs_sample(chip, jnp.asarray(g.color), m0,
                                 jnp.ones((2, B), jnp.float32), state, step,
                                 backend="fused_sparse")
    assert set(np.unique(np.asarray(m))) <= {-1.0, 1.0}


# ---------------------------------------------------------------------------
# CD: edge-list master weights
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["sparse", "fused_sparse"])
def test_cd_step_matches_dense_backend(backend):
    """The edge-list CD update is bit-identical across dense/sparse scan
    backends (same noise stream) and fp-identical on the fused kernel."""
    g = _graph(rows=1, cols=2, masked=())
    task = tasks.and_gate_task(g)
    cfg = CDConfig(lr=4.0, cd_k=6, pos_sweeps=6, burn_in=2, chains=16,
                   epochs=2)
    outs = {}
    for be in ("ref", backend):
        machine = PBitMachine.create(g, jax.random.PRNGKey(0),
                                     HardwareConfig(), noise="counter",
                                     backend=be)
        step = make_cd_step(machine, cfg, task.visible_idx)
        Jm = jnp.zeros((g.n_edges,), jnp.float32)
        hm = jnp.zeros((g.n_nodes,), jnp.float32)
        m = pbit.random_spins(jax.random.PRNGKey(1), cfg.chains, g.n_nodes)
        ns, _ = machine.noise_fn(jax.random.PRNGKey(2), cfg.chains)
        vel = (jnp.zeros((g.n_edges,)), jnp.zeros((g.n_nodes,)))
        dv = jnp.asarray(
            np.tile([[1.0, -1.0, 1.0]], (cfg.chains, 1)), jnp.float32)
        for _ in range(3):
            Jm, hm, m, ns, vel, _ = step(Jm, hm, dv, m, ns, vel)
        outs[be] = (np.asarray(Jm), np.asarray(hm), np.asarray(m))
    tol = 0.0 if backend == "sparse" else 2e-5
    np.testing.assert_allclose(outs[backend][0], outs["ref"][0],
                               rtol=0, atol=tol)
    np.testing.assert_allclose(outs[backend][1], outs["ref"][1],
                               rtol=0, atol=tol)
    np.testing.assert_array_equal(outs[backend][2], outs["ref"][2])


# ---------------------------------------------------------------------------
# streaming visible histogram
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,graph,B,block_b", [
    pytest.param("ref", "small", 16, None, id="ref"),
    pytest.param("sparse", "small", 16, None, id="sparse"),
    pytest.param("fused", "small", 16, None, id="fused"),
    pytest.param("fused_sparse", "small", 16, None, id="fused_sparse"),
    # five 8-row blocks in a 40-chain tile, then 24-chain tiles of three
    pytest.param("fused_sparse", "chip", 40, 128,
                 id="fused_sparse-chip-B40-tile40"),
    pytest.param("fused_sparse", "chip", 40, 24,
                 id="fused_sparse-chip-B40-tile24"),
])
def test_streaming_hist_matches_trajectory(backend, graph, B, block_b,
                                           monkeypatch):
    """gibbs_visible_hist == histogramming the collected trajectory, for
    every backend (the fused ones accumulate in-kernel)."""
    from repro.core import energy

    _tiles(monkeypatch, block_b)
    g = (_graph(rows=1, cols=2, masked=()) if graph == "small"
         else _GRAPHS[graph]())
    chip = _chip(g, seed=21)
    sweeps, burn_in = 40, 8
    vis = np.array([0, 3, 9])
    color = jnp.asarray(g.color)
    m0 = pbit.random_spins(jax.random.PRNGKey(6), B, g.n_nodes)
    state, step = _noise("counter", g, B, jax.random.PRNGKey(7))
    betas = jnp.full((sweeps,), 1.0, jnp.float32)

    hist, m_h, ns_h = pbit.gibbs_visible_hist(
        chip, color, m0, betas, burn_in, state, step, vis, backend=backend)
    _, _, traj = pbit.gibbs_sample(chip, color, m0, betas, state, step,
                                   collect=True, backend="ref")
    samples = np.asarray(traj[burn_in:]).reshape(-1, g.n_nodes)
    want = energy.empirical_visible_dist(samples, vis) * len(samples)
    np.testing.assert_array_equal(np.asarray(hist), want)
    assert float(np.asarray(hist).sum()) == (sweeps - burn_in) * B


# ---------------------------------------------------------------------------
# satellite: MaxCut float32 weight storage
# ---------------------------------------------------------------------------
def test_maxcut_weights_float32_and_cut_consistency():
    from repro.core.maxcut import random_chimera_maxcut

    g = _graph()
    prob = random_chimera_maxcut(g, jax.random.PRNGKey(0), weighted=True)
    assert prob.weights.dtype == np.float32
    assert prob.edges.dtype == np.int32
    rng = np.random.default_rng(0)
    m = rng.integers(0, 2, g.n_nodes) * 2 - 1
    # regression: float32 storage must not change the cut value — integer
    # weights are exact in float32, so f32 and f64 evaluation agree exactly
    cut64 = float(np.sum(prob.weights.astype(np.float64)
                         * (1.0 - m[prob.edges[:, 0]] * m[prob.edges[:, 1]])
                         / 2.0))
    assert prob.cut_value(m) == cut64
