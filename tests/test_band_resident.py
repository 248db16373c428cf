"""Lattices that live in their row bands: host construction at lattice
scale, state born split over the devices, and the band-resident sharded
sample (docs/sharding.md, "Band-resident layout").

The graph is built vectorized; a plain loop construction is the
reference here.  Multi-device cases run in subprocesses with a forced host
platform of four devices, as tests/test_shard_session.py does; both sides
of a programming parity check are jitted (jit-vs-eager may differ by one
ulp on the CPU).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import api
from repro.core.cd import PBitMachine
from repro.core.chimera import make_chimera
from repro.core.hardware import HardwareConfig

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
SUBPROC_ENV = {"PYTHONPATH": f"{ROOT}/src:{BENCH}", "PATH": "/usr/bin:/bin",
               "HOME": str(Path.home()), "JAX_PLATFORMS": "cpu"}

GRIDS = [(1, 1, ()), (2, 3, ()), (3, 2, ((1, 1),)), (4, 4, ((0, 0), (3, 3))),
         (5, 3, ((2, 0), (2, 2), (4, 1))), (7, 8, ((6, 7),)),
         (6, 1, ((0, 0),)), (1, 6, ((0, 5),))]


def loop_chimera(rows, cols, k=4, masked_cells=()):
    """The construction the vectorized one replaced: Python loops, a set
    of edges, and a sort."""
    masked = {(int(r), int(c)) for r, c in masked_cells}

    def raw_id(r, c, s, kk):
        return (((r * cols) + c) * 2 + s) * k + kk

    compact = -np.ones(rows * cols * 2 * k, dtype=np.int64)
    nodes, nid = [], 0
    for r in range(rows):
        for c in range(cols):
            if (r, c) in masked:
                continue
            for s in range(2):
                for kk in range(k):
                    compact[raw_id(r, c, s, kk)] = nid
                    nodes.append((r, c, s, kk, (r + c + s) % 2))
                    nid += 1
    edges = set()

    def add(a, b):
        ca, cb = compact[a], compact[b]
        if ca >= 0 and cb >= 0:
            edges.add((min(ca, cb), max(ca, cb)))

    for r in range(rows):
        for c in range(cols):
            if (r, c) in masked:
                continue
            for i in range(k):
                for j in range(k):
                    add(raw_id(r, c, 0, i), raw_id(r, c, 1, j))
            if r + 1 < rows and (r + 1, c) not in masked:
                for i in range(k):
                    add(raw_id(r, c, 0, i), raw_id(r + 1, c, 0, i))
            if c + 1 < cols and (r, c + 1) not in masked:
                for j in range(k):
                    add(raw_id(r, c, 1, j), raw_id(r, c + 1, 1, j))
    nodes = np.array(nodes, np.int32).reshape(-1, 5)
    return nodes, np.array(sorted(edges), np.int32).reshape(-1, 2)


def lexsort_table(edges, n):
    """Neighbor table and edge slots by a full lexsort of both
    directions: the O(E log E) construction the fast one replaced."""
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    deg = np.bincount(src, minlength=n)
    D = max(int(deg.max()) if deg.size else 0, 1)
    slot = np.arange(src.size) - np.concatenate([[0], np.cumsum(deg)[:-1]])[
        src]
    nbr = np.tile(np.arange(n, dtype=np.int32), (D, 1))
    ok = np.zeros((D, n), bool)
    nbr[slot, src], ok[slot, src] = dst, True
    e0, e1 = edges[:, 0], edges[:, 1]
    s_ij = np.argmax(nbr[:, e0] == e1[None, :], axis=0)
    s_ji = np.argmax(nbr[:, e1] == e0[None, :], axis=0)
    return nbr, ok, s_ij, s_ji


@pytest.mark.parametrize("rows,cols,masked", GRIDS)
def test_vectorized_chimera_matches_loop_construction(rows, cols, masked):
    g = make_chimera(rows, cols, masked_cells=masked)
    nodes, edges = loop_chimera(rows, cols, masked_cells=masked)
    assert g.n_nodes == nodes.shape[0]
    for col, name in enumerate(("node_r", "node_c", "node_side", "node_k",
                                "color")):
        got = getattr(g, name)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, nodes[:, col])
    assert g.edges.dtype == np.int32
    np.testing.assert_array_equal(g.edges, edges)
    nbr, ok, s_ij, s_ji = lexsort_table(edges, g.n_nodes)
    got = g.neighbor_table()
    np.testing.assert_array_equal(got[0], nbr)
    np.testing.assert_array_equal(got[1], ok)
    for a, b in zip(g.edge_slots(), (s_ij, s_ji)):
        np.testing.assert_array_equal(a, b)
    # a table that is not the graph's own still gets its slots
    for a, b in zip(g.edge_slots(nbr.copy()), (s_ij, s_ji)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("rows,cols,masked", GRIDS[1:6])
def test_reference_lattice_graph_matches_reference(rows, cols, masked):
    sys.path.insert(0, str(BENCH))
    import reference as ref
    import reference_lattice as rl
    a, b = ref.chimera(rows, cols, masked), rl.chimera(rows, cols, masked)
    for f in ("coords", "edges", "color", "nbr", "nbr_ok"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_fused_sparse_needs_a_band_that_fits_vmem():
    """A pinned fused_sparse whose row band cannot fit the per-shard
    kernel's VMEM raises at Session build; auto picks the scan there and
    the kernel where the band fits."""
    mesh = jax.make_mesh((1,), ("data",))
    sync = api.Sync(halo_every=4, sweeps_per_launch=4)

    def spec(g, chains, backend):
        m = PBitMachine.create(g, jax.random.PRNGKey(0), HardwareConfig(),
                               sparse=True, noise="counter",
                               backend=backend)
        return m.sampler_spec(chains=chains, mesh=mesh, sync=sync,
                              partition=api.Partition(rows="data"))

    big = make_chimera(16, 64)                    # 8192 spins in one band
    with pytest.raises(ValueError, match="VMEM"):
        api.Session(spec(big, 128, "fused_sparse"))
    assert api.resolve_backend(spec(big, 128, "auto")) == "sparse"
    assert api.resolve_backend(spec(make_chimera(2, 2), 8, "auto")) \
        == "fused_sparse"


def _run_forced(script: str, n_dev: int = 4, timeout: int = 540) -> dict:
    head = (f"import os\nos.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={n_dev}'\n")
    out = subprocess.run(
        [sys.executable, "-c", head + textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=SUBPROC_ENV,
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_SETUP = """
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.chimera import make_chimera
    from repro.core.hardware import HardwareConfig
    from repro.launch.mesh import make_line_mesh

    g = make_chimera(16, 8)                  # 1024 spins, 4 bands of 256
    B, n = 16, g.n_nodes
    mesh = make_line_mesh(4)
    part = api.Partition(rows="data")
    key = jax.random.PRNGKey(11)
    kw = dict(sparse=True, noise="counter", backend="sparse")
    m0 = PBitMachine.create(g, key, HardwareConfig(), **kw)
    m1 = PBitMachine.create(g, key, HardwareConfig(), mesh=mesh,
                            partition=part, **kw)
    ses0 = api.Session(m0.sampler_spec(chains=B))
    ses1 = api.Session(m1.sampler_spec(chains=B))
    rng = np.random.default_rng(5)
    J = rng.integers(-60, 61, g.n_edges).astype(np.int32)
    h = rng.integers(-15, 16, n).astype(np.int32)

    def bands(x, axis):
        \"\"\"Each device's shard is exactly its band of the node axis.\"\"\"
        assert x.sharding.mesh.devices.size == 4, x.sharding
        full = np.asarray(x)
        for s in x.addressable_shards:
            d = list(mesh.devices.flat).index(s.device)
            sl = [slice(None)] * full.ndim
            sl[axis] = slice(d * n // 4, (d + 1) * n // 4)
            np.testing.assert_array_equal(np.asarray(s.data),
                                          full[tuple(sl)])
        return full
"""


def test_band_placement_equals_single_device():
    """Spins, noise state, chip instance, program and the engine's tables
    are born split over the devices, each holding its band, and equal
    the single-device Session's arrays bit for bit."""
    rec = _run_forced(_SETUP + """
    eq = np.testing.assert_array_equal
    mm = jax.tree_util.tree_leaves(m1.mismatch)
    axes = (1, 0, 1, 0, 0, 0, 0, 1)
    for a, b, ax in zip(mm, jax.tree_util.tree_leaves(m0.mismatch), axes):
        eq(bands(a, ax), np.asarray(b))
    m_a = ses1.random_spins(jax.random.PRNGKey(3))
    eq(bands(m_a, 1), np.asarray(ses0.random_spins(jax.random.PRNGKey(3))))
    eq(np.asarray(ses1.noise_state(jax.random.PRNGKey(4))),
       np.asarray(ses0.noise_state(jax.random.PRNGKey(4))))
    c1 = ses1.program_edges(J, h)
    c0 = jax.jit(ses0.program_edges)(jnp.asarray(J), jnp.asarray(h))
    for f, ax in (("nbr_w", 1), ("nbr_idx", 1), ("h", 0), ("tanh_gain", 0),
                  ("tanh_offset", 0), ("rand_gain", 0), ("comp_offset", 0)):
        eq(bands(getattr(c1, f), ax), np.asarray(getattr(c0, f)))
    assert c1.W is None
    # in-jit programming from a Program takes the same band path
    prog = ses1.make_program(J, h)
    c2 = jax.jit(lambda t, p: ses1._program_in_jit(
        t, m1.mismatch, p.J_codes, p.h_codes))(ses1._tables, prog)
    eq(np.asarray(c2.nbr_w), np.asarray(c0.nbr_w))
    # the per-band tables: shard d is band d of the plan
    eng, plan = ses1._engine, ses1.partition_plan
    assert eng.band_resident
    for k in ("nbr", "send_up", "send_dn", "upd", "cols"):
        t = eng.tables[k]
        for s in t.addressable_shards:
            d = list(mesh.devices.flat).index(s.device)
            eq(np.asarray(s.data)[0], np.asarray(
                {"nbr": plan.nbr_idx, "send_up": plan.send_up,
                 "send_dn": plan.send_dn, "upd": plan.upd_masks,
                 "cols": plan.part_ids}[k])[d])
    assert "part_ids" not in eng.tables and "inv_ids" not in eng.tables
    print(json.dumps({"ok": True}))
    """)
    assert rec["ok"]


def test_band_resident_sample_matches_single_device_and_reference():
    """The band-resident sample, spins donated call to call, equals the
    single-device Session and bench/reference_lattice.py bit for bit; the
    halo exchange is what carries the bands' boundaries, and the bands,
    whole cell rows, sweep without gathers."""
    rec = _run_forced(_SETUP + """
    import reference as ref
    import reference_lattice as rl
    hw = {f: float(getattr(HardwareConfig(), f))
          for f in HardwareConfig.__dataclass_fields__}
    betas = [jnp.linspace(0.2, 1.0, 4), jnp.linspace(1.0, 2.0, 4)]
    c1 = ses1.program_edges(J, h)
    c0 = jax.jit(ses0.program_edges)(jnp.asarray(J), jnp.asarray(h))
    ma = ses1.random_spins(jax.random.PRNGKey(3))
    mb = ses0.random_spins(jax.random.PRNGKey(3))
    na = ses1.noise_state(jax.random.PRNGKey(4))
    nb = ses0.noise_state(jax.random.PRNGKey(4))
    first = ma
    for b in betas:
        ma, na, _ = ses1.sample(c1, ma, na, b)
        mb, nb, _ = ses0.sample(c0, mb, nb, b)
        assert ma.sharding == ses1._engine.spin_sharding
        np.testing.assert_array_equal(bands(ma, 1), np.asarray(mb))
        np.testing.assert_array_equal(np.asarray(na), np.asarray(nb))
    donated = first.is_deleted()
    foreign = ses0.random_spins(jax.random.PRNGKey(3))
    ses1.sample(c1, foreign, na, betas[0])
    kept = not foreign.is_deleted()
    # bands of whole cell rows sweep by shifted views, without gathers,
    # in chunks of cell rows where a band is large
    grid = ses1._engine.grid
    from repro.core import distributed
    distributed.GRID_CHUNK_BYTES = 4 * B * 8 * 8 * 3     # 3 of 4 rows
    mc = ses1.random_spins(jax.random.PRNGKey(3))
    nc = ses1.noise_state(jax.random.PRNGKey(4))
    ses2 = api.Session(m1.sampler_spec(chains=B))
    for b in betas:
        mc, nc, _ = ses2.sample(c1, mc, nc, b)
    np.testing.assert_array_equal(np.asarray(mc), np.asarray(mb))
    # the plain reference, every chain, from the same key and codes
    rmesh = rl.node_mesh(jax.devices())
    rg = rl.chimera(16, 8)
    chip = rl.draw_chip(rmesh, key, rg, hw)
    prog = rl.program(rmesh, rg, chip, hw, 0.05, J, h)
    chains = np.arange(B)
    m = rl.spin_rows(rmesh, jax.random.PRNGKey(3), B, n, chains)
    seed, ctr = ref.noise_seed(jax.random.PRNGKey(4)), jnp.uint32(0)
    slots = rl.place(rmesh, rl.term_slots(rg), 1)
    color = rl.place(rmesh, rg.color.astype(np.int32), 0)
    for b in betas:
        m, ctr, cnt = rl.sweeps(slots, color, prog, m, seed, ctr,
                                rl.place(rmesh, b, None),
                                rl.place(rmesh, chains.astype(np.uint32),
                                         None), rows=16, cols=8)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(mb))
    np.testing.assert_array_equal(np.asarray(cnt),
                                  np.sum(np.asarray(mb) > 0, axis=1))
    print(json.dumps({"donated": bool(donated), "kept": bool(kept),
                      "grid": bool(grid)}))
    """)
    assert rec == {"donated": True, "kept": True, "grid": True}
