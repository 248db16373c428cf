"""Mesh-sharded Sessions: partition plan, validation, bit-exactness.

The contract (docs/sharding.md): a sharded Session reproduces the
single-device spin trajectory *exactly* for the same noise stream —
rows partitioning (ppermute halo exchange of the chain-coupler boundary
spins), chains partitioning (psum-reduced edge-list moments), and their
2-D composition — with halo traffic O(boundary), never O(N²).

Multi-device cases run in subprocesses with a forced host platform
(XLA_FLAGS device count must be set before jax initializes); both sides
of every parity check are jitted (jit-vs-eager may differ by 1 ulp).
The CI `sharded` job runs this file as its own matrix entry.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import pbit
from repro.core.cd import PBitMachine
from repro.core.chimera import make_chimera, make_chip_graph
from repro.core.distributed import halo_bytes_per_sweep, plan_row_partition
from repro.core.hardware import HardwareConfig

ROOT = Path(__file__).resolve().parent.parent
SUBPROC_ENV = {"PYTHONPATH": f"{ROOT}/src", "PATH": "/usr/bin:/bin",
               "HOME": "/root", "JAX_PLATFORMS": "cpu"}


# ---------------------------------------------------------------------------
# partition plan (pure numpy — no devices involved)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_shards", [1, 2, 3])
def test_plan_covers_chip_graph(n_shards):
    g = make_chip_graph()   # 7x8, one masked cell -> uneven bands
    p = plan_row_partition(g, n_shards, with_lfsr=True)
    # every node owned exactly once
    owned = p.part_ids[p.valid]
    assert sorted(owned.tolist()) == list(range(g.n_nodes))
    # inverse map round-trips
    flat = p.part_ids.reshape(-1)
    assert np.array_equal(flat[p.inv_ids], np.arange(g.n_nodes))
    # local neighbor tables reproduce the global one through the halo
    nbr_g, _ = g.neighbor_table()
    H, n_loc = p.halo, p.n_loc
    for d in range(n_shards):
        ext = np.full((n_loc + 2 * H,), -1, np.int64)
        ext[:n_loc] = p.part_ids[d]
        if d > 0:
            ext[n_loc:n_loc + H] = p.part_ids[d - 1][p.send_dn[d - 1]]
        if d < n_shards - 1:
            ext[n_loc + H:] = p.part_ids[d + 1][p.send_up[d + 1]]
        got = ext[p.nbr_idx[d][:, p.valid[d]]]
        np.testing.assert_array_equal(got, nbr_g[:, p.part_ids[d][p.valid[d]]])
    # each edge accounted exactly once
    assert np.unique(p.edge_inv).size == g.n_edges
    # boundary is O(cols * k), not O(N): verticals of internal cut rows
    # (the masked cell sits in row 6, never on a cut for these shardings)
    assert p.n_boundary == 2 * (n_shards - 1) * 4 * g.cols


def test_halo_bytes_model_is_o_boundary():
    g = make_chimera(16, 16)      # 2048 spins
    p = plan_row_partition(g, 4)
    B = 64
    halo = halo_bytes_per_sweep(p, B)
    dense_w = 4 * g.n_nodes ** 2
    assert halo == 2 * p.n_boundary * B * 4
    # O(√N·B) halo vs the O(N²) a dense-W exchange would move
    assert halo * 10 < dense_w


# ---------------------------------------------------------------------------
# spec validation
# ---------------------------------------------------------------------------
def _spec(g, mesh=None, partition=None, chains=8, **kw):
    kw.setdefault("noise", "counter")
    kw.setdefault("backend", "sparse")
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), HardwareConfig(),
                              **kw)
    return mach.sampler_spec(chains=chains, mesh=mesh, partition=partition)


def test_partition_validation_errors():
    g = make_chimera(2, 2)
    mesh = jax.make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="mesh=None"):
        _spec(g, partition=api.Partition()).validate()
    with pytest.raises(ValueError, match="not in mesh axes"):
        _spec(g, mesh=mesh, partition=api.Partition(rows="rows")).validate()
    with pytest.raises(ValueError, match="counter"):
        _spec(g, mesh=mesh, noise="philox").validate()
    # fused_sparse under the default per-half-sweep barrier is legal now
    # that the kernel owns the halo refresh (PR 10); the infeasible
    # window S < halo_every < 2S still raises, naming the nearest fix
    _spec(g, mesh=mesh, backend="fused_sparse").validate()
    with pytest.raises(ValueError, match="nearest legal Sync"):
        _spec(g, mesh=mesh, backend="fused_sparse").replace(
            sync=api.Sync(halo_every=6, sweeps_per_launch=4)).validate()
    with pytest.raises(ValueError, match="disjoint"):
        _spec(g, mesh=mesh,
              partition=api.Partition(rows="data",
                                      chains="data")).validate()
    with pytest.raises(ValueError, match="shards nothing"):
        _spec(g, mesh=mesh,
              partition=api.Partition(rows=None, chains=None)).validate()
    class FakeMesh:
        axis_names = ("data",)
        shape = {"data": 2}
    with pytest.raises(ValueError, match="not divisible"):
        _spec(g, mesh=FakeMesh(),
              partition=api.Partition(rows=None, chains="data"),
              chains=7).validate()
    # a sharded spec resolves to the sparse scan path, env var or not
    assert api.resolve_backend(
        _spec(g, mesh=mesh, backend="auto")) == "sparse"


def test_too_many_row_shards_raises():
    g = make_chimera(2, 2)

    class FakeMesh:
        axis_names = ("data",)
        shape = {"data": 3}
    with pytest.raises(ValueError, match="cell rows"):
        _spec(g, mesh=FakeMesh()).validate()


# ---------------------------------------------------------------------------
# single-device mesh: the whole engine machinery, bit-exact vs plain
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("noise", ["counter", "lfsr"])
def test_one_shard_engine_bit_exact(noise):
    g = make_chimera(3, 2, masked_cells=((1, 1),))
    mesh = jax.make_mesh((1,), ("data",))
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), HardwareConfig(),
                              noise=noise, backend="sparse")
    rng = np.random.default_rng(1)
    codes = jnp.asarray(rng.integers(-50, 50, g.n_edges), jnp.int32)
    h = jnp.asarray(rng.integers(-10, 10, g.n_nodes), jnp.int32)
    B, S = 8, 6
    ses0 = api.Session(mach.sampler_spec(chains=B))
    ses1 = api.Session(mach.sampler_spec(
        chains=B, mesh=mesh, partition=api.Partition(rows="data")))
    assert ses1.backend == "sparse" and ses1._engine is not None
    chip = ses0.program_edges(codes, h)
    m0 = ses0.random_spins(jax.random.PRNGKey(2))
    ns = ses0.noise_state(jax.random.PRNGKey(3))
    betas = jnp.linspace(0.3, 1.5, S)
    a = ses0.sample(chip, m0, ns, betas, collect=True)
    b = ses1.sample(chip, m0, ns, betas, collect=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(ses0.stats(chip, m0, ns, 10, 2),
                    ses1.stats(chip, m0, ns, 10, 2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    vis = np.array([0, 3, 9])
    ha = ses0.visible_hist(chip, m0, ns, vis, 2, betas)
    hb = ses1.visible_hist(chip, m0, ns, vis, 2, betas)
    np.testing.assert_array_equal(np.asarray(ha[0]), np.asarray(hb[0]))


# ---------------------------------------------------------------------------
# forced multi-device host platform (subprocess)
# ---------------------------------------------------------------------------
def _run_forced(script: str, n_dev: int, timeout: int = 540) -> dict:
    head = (f"import os\nos.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={n_dev}'\n")
    out = subprocess.run(
        [sys.executable, "-c", head + textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=SUBPROC_ENV,
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_COMMON = """
    import jax, json
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.chimera import make_chimera, make_chip_graph
    from repro.core.hardware import HardwareConfig

    def chip_for(mach, ses, g, seed):
        rng = np.random.default_rng(seed)
        return ses.program_edges(
            jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32),
            jnp.asarray(rng.integers(-15, 15, g.n_nodes), jnp.int32))
"""


def test_two_device_rows_bit_exact():
    """Chip graph (440 spins, masked cell) + a masked non-square grid:
    2-device rows sharding == single device, spins/moments/hist, both
    noise kinds, including collect trajectories and clamped stats."""
    rec = _run_forced(_COMMON + """
    mesh = jax.make_mesh((2,), ("data",))
    checks = 0
    for g in (make_chip_graph(),
              make_chimera(3, 2, masked_cells=((0, 1), (2, 0)))):
        for noise in ("counter", "lfsr"):
            mach = PBitMachine.create(g, jax.random.PRNGKey(0),
                                      HardwareConfig(), noise=noise,
                                      backend="sparse")
            B, S = 4, 5
            ses0 = api.Session(mach.sampler_spec(chains=B))
            ses1 = api.Session(mach.sampler_spec(
                chains=B, mesh=mesh, partition=api.Partition(rows="data")))
            chip = chip_for(mach, ses0, g, 1)
            m0 = ses0.random_spins(jax.random.PRNGKey(2))
            ns = ses0.noise_state(jax.random.PRNGKey(3))
            betas = jnp.linspace(0.3, 1.5, S)
            a = ses0.sample(chip, m0, ns, betas, collect=True)
            b = ses1.sample(chip, m0, ns, betas, collect=True)
            for x, y in zip(a, b):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            cm = jnp.zeros((g.n_nodes,), bool).at[
                jnp.array([0, 5, g.n_nodes - 1])].set(True)
            cv = jnp.tile(jnp.asarray([[1.0]]), (B, g.n_nodes))
            for x, y in zip(
                    ses0.stats(chip, m0, ns, 8, 2, clamp_mask=cm,
                               clamp_values=cv),
                    ses1.stats(chip, m0, ns, 8, 2, clamp_mask=cm,
                               clamp_values=cv)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            # clamp_mask without clamp_values (exclusion-only clamping)
            for x, y in zip(ses0.stats(chip, m0, ns, 8, 2, clamp_mask=cm),
                            ses1.stats(chip, m0, ns, 8, 2, clamp_mask=cm)):
                np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
            vis = np.array([0, 3, 9, 11])
            ha = ses0.visible_hist(chip, m0, ns, vis, 2, betas)
            hb = ses1.visible_hist(chip, m0, ns, vis, 2, betas)
            np.testing.assert_array_equal(np.asarray(ha[0]),
                                          np.asarray(hb[0]))
            checks += 1
    print(json.dumps({"checks": checks}))
    """, n_dev=2)
    assert rec["checks"] == 4


def test_two_device_chains_cd_bit_exact():
    """Chains-sharded CD: per-device Gibbs phases + one (E,) gradient
    psum per phase reproduce the single-device weight trajectory exactly
    (power-of-two chains)."""
    rec = _run_forced(_COMMON + """
    from repro.core import tasks
    from repro.core.cd import CDConfig
    mesh = jax.make_mesh((2,), ("data",))
    g = make_chimera(2, 2)
    results = {}
    for noise in ("counter", "lfsr"):
        mach = PBitMachine.create(g, jax.random.PRNGKey(0),
                                  HardwareConfig(), noise=noise,
                                  backend="sparse")
        B = 16
        ses0 = api.Session(mach.sampler_spec(chains=B))
        ses1 = api.Session(mach.sampler_spec(
            chains=B, mesh=mesh,
            partition=api.Partition(rows=None, chains="data")))
        task = tasks.and_gate_task(g)
        cfg = CDConfig(lr=4.0, cd_k=5, pos_sweeps=5, burn_in=1, chains=B,
                       epochs=2)
        outs = {}
        for name, ses in (("single", ses0), ("sharded", ses1)):
            step = ses.make_cd_step(cfg, task.visible_idx)
            Jm = jnp.zeros((g.n_edges,), jnp.float32)
            hm = jnp.zeros((g.n_nodes,), jnp.float32)
            m = ses.random_spins(jax.random.PRNGKey(1))
            ns = ses.noise_state(jax.random.PRNGKey(2))
            vel = (jnp.zeros((g.n_edges,)), jnp.zeros((g.n_nodes,)))
            dv = jnp.asarray(np.tile([[1.0, -1.0, 1.0]], (B, 1)),
                             jnp.float32)
            for _ in range(3):
                Jm, hm, m, ns, vel, _ = step(Jm, hm, dv, m, ns, vel)
            outs[name] = [np.asarray(x) for x in (Jm, hm, m)]
        for x, y in zip(outs["single"], outs["sharded"]):
            np.testing.assert_array_equal(x, y)
        # (S, B) tempered betas chains-sharded through sample AND
        # visible_hist (per-chain beta columns must shard with the
        # chains), plus exclusion-only clamping (clamp_mask, no values)
        rng = np.random.default_rng(7)
        betas = jnp.asarray(rng.uniform(0.2, 1.8, (6, B)), jnp.float32)
        chip = chip_for(mach, ses0, g, 4)
        m0 = ses0.random_spins(jax.random.PRNGKey(3))
        ns0 = ses0.noise_state(jax.random.PRNGKey(4))
        a = ses0.sample(chip, m0, ns0, betas)
        b = ses1.sample(chip, m0, ns0, betas)
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        vis = np.array([0, 3, 9])
        ha = ses0.visible_hist(chip, m0, ns0, vis, 2, betas)
        hb = ses1.visible_hist(chip, m0, ns0, vis, 2, betas)
        np.testing.assert_array_equal(np.asarray(ha[0]), np.asarray(hb[0]))
        cmask = jnp.zeros((g.n_nodes,), bool).at[
            jnp.array([0, 5])].set(True)
        for x, y in zip(ses0.stats(chip, m0, ns0, 8, 2, clamp_mask=cmask),
                        ses1.stats(chip, m0, ns0, 8, 2,
                                   clamp_mask=cmask)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        results[noise] = True
    print(json.dumps(results))
    """, n_dev=2)
    assert rec == {"counter": True, "lfsr": True}


def test_four_device_2d_rows_x_chains():
    """2x2 mesh: rows AND chains sharded at once, stats bit-exact."""
    rec = _run_forced(_COMMON + """
    mesh = jax.make_mesh((2, 2), ("r", "c"))
    g = make_chimera(4, 2, masked_cells=((3, 1),))
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), HardwareConfig(),
                              noise="counter", backend="sparse")
    B = 8
    ses0 = api.Session(mach.sampler_spec(chains=B))
    ses1 = api.Session(mach.sampler_spec(
        chains=B, mesh=mesh, partition=api.Partition(rows="r", chains="c")))
    chip = chip_for(mach, ses0, g, 2)
    m0 = ses0.random_spins(jax.random.PRNGKey(5))
    ns = ses0.noise_state(jax.random.PRNGKey(6))
    for x, y in zip(ses0.stats(chip, m0, ns, 8, 2),
                    ses1.stats(chip, m0, ns, 8, 2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    betas = jnp.linspace(0.4, 1.4, 6)
    a = ses0.sample(chip, m0, ns, betas)
    b = ses1.sample(chip, m0, ns, betas)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    print(json.dumps({"ok": True}))
    """, n_dev=4)
    assert rec["ok"]


def test_lattice_anneal_sharded_matches_single():
    """A lattice anneal through a row-sharded Session is bit-identical to
    the single-device run (same key => same counter stream), not merely
    the same energy scale."""
    rec = _run_forced("""
    import sys
    sys.path.insert(0, "tests")
    import jax, json
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.chimera import make_chimera
    from repro.core.distributed import sparse_energy
    from test_lattice import anneal, lattice_codes, lattice_machine
    g = make_chimera(4, 4)
    codes = lattice_codes(g, 0)
    betas = jnp.linspace(0.1, 2.0, 20)
    ses1 = api.Session(lattice_machine(g).sampler_spec(chains=2))
    chip1 = ses1.program_edges(*codes)
    m1, e1 = anneal(ses1, chip1, jax.random.PRNGKey(1), betas, 10)
    mesh = jax.make_mesh((2,), ("data",))
    ses2 = api.Session(lattice_machine(g).sampler_spec(
        chains=2, mesh=mesh, partition=api.Partition(rows="data")))
    # both runs scored on one device: equal spins give equal energies
    m2, e2 = anneal(ses2, ses2.program_edges(*codes), jax.random.PRNGKey(1),
                    betas, 10, score=lambda m: sparse_energy(
                        chip1, jnp.asarray(np.asarray(m))))
    ok_m = bool(np.array_equal(np.asarray(m1), np.asarray(m2)))
    ok_e = bool(np.array_equal(e1, e2))
    print(json.dumps({"m": ok_m, "e": ok_e, "e_last": float(e1[-1])}))
    """, n_dev=2)
    assert rec["m"] and rec["e"]
    assert rec["e_last"] < 0
