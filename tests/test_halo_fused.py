"""Fused exchange windows (docs/kernels.md §Fused exchange windows).

A fused launch refreshes halos mid-launch: the engine splits it at the
`Sync.exchange_points()` half-sweeps into `half_offset`/`n_half` windows
of the resident kernel with a ppermute between windows, one jitted graph,
on CPU hosts and TPU meshes alike.  This file pins its contracts:

  * the half-sweep-window kernel parameters chain bit-exactly (a launch
    split at arbitrary cuts equals the unsplit launch, spins + noise +
    moments);
  * fused exchange windows under `Sync(halo_every=1, mode="barrier")`
    equal the single-device Session bit for bit on a forced 2-device
    host, chained program streams included;
  * relaxed policies (halo_every=k, async) equal the existing sparse
    segment-scan engine bit for bit under the same seeds;
  * `plan_row_partition` memoizes (serving's shard-loss re-plan hits the
    cache), `Sync.exchange_points()` edge semantics are pinned, and the
    ICI napkin model carries a per-exchange latency term.
"""
import json
import math
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.cd import PBitMachine
from repro.core.chimera import make_chimera
from repro.core.distributed import (
    clear_plan_cache,
    plan_cache_stats,
    plan_row_partition,
)
from repro.core.hardware import HardwareConfig
from repro.kernels.ref import halo_exchange_segments

ROOT = Path(__file__).resolve().parent.parent
SUBPROC_ENV = {"PYTHONPATH": f"{ROOT}/src", "PATH": "/usr/bin:/bin",
               "HOME": "/root", "JAX_PLATFORMS": "cpu"}


def _run_forced(script: str, n_dev: int, timeout: int = 540) -> dict:
    head = (f"import os\nos.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={n_dev}'\n")
    out = subprocess.run(
        [sys.executable, "-c", head + textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=SUBPROC_ENV,
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# plan memoization (serving's re-plan path)
# ---------------------------------------------------------------------------
def test_plan_row_partition_memoized():
    g = make_chimera(6, 2)
    clear_plan_cache()
    p3 = plan_row_partition(g, 3)
    assert plan_cache_stats() == {"hits": 0, "misses": 1}
    # the degrade ladder: shard dies, re-plan over the survivors...
    p2 = plan_row_partition(g, 2)
    assert plan_cache_stats() == {"hits": 0, "misses": 2}
    # ...and any later Session compile on the same (graph, n_shards)
    # hits the cache — including a re-degrade back through 2 shards
    assert plan_row_partition(g, 2) is p2
    assert plan_row_partition(g, 3) is p3
    assert plan_cache_stats() == {"hits": 2, "misses": 2}
    # the key separates lfsr plans (they carry the cell permutation)...
    plan_row_partition(g, 2, with_lfsr=True)
    assert plan_cache_stats()["misses"] == 3
    # ...and distinct graphs (masked cells change the partition)
    plan_row_partition(make_chimera(6, 2, masked_cells=((1, 1),)), 2)
    assert plan_cache_stats()["misses"] == 4
    # invalid shard counts raise without polluting the cache
    with pytest.raises(ValueError):
        plan_row_partition(g, 7)
    assert plan_cache_stats()["misses"] == 4
    clear_plan_cache()
    assert plan_cache_stats() == {"hits": 0, "misses": 0}


# ---------------------------------------------------------------------------
# Sync edge-case semantics (pinned before the kernel path consumes them)
# ---------------------------------------------------------------------------
def test_exchange_points_property_grid():
    for S in range(1, 7):
        for k in list(range(1, 10)) + [math.inf]:
            sync = api.Sync(halo_every=k, sweeps_per_launch=S)
            pts = sync.exchange_points()
            if k == math.inf:
                expect = (0,)
            else:
                expect = tuple(h for h in range(2 * S) if h % k == 0)
            assert pts == expect, (k, S)
            assert pts[0] == 0  # a launch boundary always refreshes
            assert sync.kernel_fusible == (pts == (0,))
            assert sync.exchanges_per_sweep() == len(pts) / S
            # the bit-exact moment refresh only exists at k=1 barrier
            extra = 1.0 if sync.bit_exact else 0.0
            assert sync.exchanges_per_sweep(refresh_for_moments=True) \
                == len(pts) / S + extra


def test_exchange_points_edges():
    # halo_every > 2*sweeps_per_launch: only the launch boundary
    assert api.Sync(halo_every=5,
                    sweeps_per_launch=2).exchange_points() == (0,)
    # non-dividing halo_every: points land mid-sweep
    assert api.Sync(halo_every=3,
                    sweeps_per_launch=2).exchange_points() == (0, 3)
    # halo_every=1 with S=1: both halves of the single sweep
    assert api.Sync(halo_every=1,
                    sweeps_per_launch=1).exchange_points() == (0, 1)
    assert api.Sync(halo_every=1,
                    sweeps_per_launch=1).exchanges_per_sweep() == 2.0


def test_fused_compatible_windows():
    # exchange windows: any halo_every <= sweeps_per_launch
    assert api.Sync(halo_every=1, sweeps_per_launch=4).fused_compatible
    assert api.Sync(halo_every=4, sweeps_per_launch=4).fused_compatible
    assert api.Sync(halo_every=1, sweeps_per_launch=1).fused_compatible
    # launch-boundary-only exchange stays fusible
    assert api.Sync(halo_every=math.inf,
                    sweeps_per_launch=8).fused_compatible
    assert api.Sync(halo_every=8, sweeps_per_launch=4).fused_compatible
    # the infeasible window: S < halo_every < 2S
    assert not api.Sync(halo_every=5, sweeps_per_launch=4).fused_compatible
    assert not api.Sync(halo_every=6, sweeps_per_launch=4).fused_compatible
    assert not api.Sync(halo_every=3, sweeps_per_launch=2).fused_compatible


def test_halo_exchange_segments_helper():
    assert halo_exchange_segments((0,), 8) == ((0, 8),)
    assert halo_exchange_segments((0, 4), 8) == ((0, 4), (4, 8))
    assert halo_exchange_segments(tuple(range(4)), 4) \
        == ((0, 1), (1, 2), (2, 3), (3, 4))
    with pytest.raises(ValueError, match="start at 0"):
        halo_exchange_segments((1, 2), 4)
    with pytest.raises(ValueError, match="start at 0"):
        halo_exchange_segments((), 4)
    with pytest.raises(ValueError, match="outside"):
        halo_exchange_segments((0, 9), 8)


# ---------------------------------------------------------------------------
# the half-sweep-window kernel contract (in-process, interpret mode)
# ---------------------------------------------------------------------------
def _sparse_setup(seed=1, B=6, S=6):
    g = make_chimera(2, 2)
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), sparse=True,
                              noise="counter")
    ses = api.Session(mach.sampler_spec(chains=B, interpret=True))
    rng = np.random.default_rng(seed)
    chip = ses.program_edges(
        jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32),
        jnp.asarray(rng.integers(-15, 15, g.n_nodes), jnp.int32))
    m0 = ses.random_spins(jax.random.PRNGKey(2))
    masks = (jnp.asarray(g.color == 0), jnp.asarray(g.color == 1))
    betas = jnp.broadcast_to(jnp.linspace(0.3, 1.5, S)[:, None], (S, B))
    ns0 = jnp.asarray([42, 0], jnp.uint32)
    return g, ses, chip, m0, masks, betas, ns0


@pytest.mark.parametrize("cuts", [(0, 1), (0, 3, 4), (0, 2, 5, 9, 11)],
                         ids=lambda c: "c" + "-".join(map(str, c)))
def test_window_chaining_matches_single_launch(cuts):
    """`half_offset`/`n_half` windows of `sweep_sparse_pallas` chained at
    arbitrary half-sweep cuts == the unsplit launch, bit for bit (spins,
    noise state, in-kernel moments)."""
    from repro.kernels.sweep_fused import sweep_sparse_pallas

    _, _, chip, m0, masks, betas, ns0 = _sparse_setup()
    S = betas.shape[0]
    meas = jnp.ones((S,), jnp.float32)
    kw = dict(noise_mode="counter", accumulate=True, block_b=8,
              interpret=True)
    args = (chip.nbr_idx, chip.nbr_w, chip.h, chip.tanh_gain,
            chip.tanh_offset, chip.rand_gain, chip.comp_offset, *masks,
            betas)
    whole = sweep_sparse_pallas(m0, *args, ns0, measured=meas, **kw)
    m_c, ns_c = m0, ns0
    ssum = jnp.zeros_like(whole[2])
    csum = jnp.zeros_like(whole[3])
    for h0, h1 in halo_exchange_segments(tuple(cuts), 2 * S):
        m_c, ns_c, s_w, c_w = sweep_sparse_pallas(
            m_c, *args, ns_c, measured=meas, half_offset=h0,
            n_half=h1 - h0, **kw)
        ssum, csum = ssum + s_w, csum + c_w
    np.testing.assert_array_equal(np.asarray(m_c), np.asarray(whole[0]))
    np.testing.assert_array_equal(np.asarray(ns_c), np.asarray(whole[1]))
    np.testing.assert_array_equal(np.asarray(ssum), np.asarray(whole[2]))
    np.testing.assert_array_equal(np.asarray(csum), np.asarray(whole[3]))


def test_window_validation():
    from repro.kernels.sweep_fused import sweep_sparse_pallas

    _, _, chip, m0, masks, betas, ns0 = _sparse_setup()
    with pytest.raises(ValueError, match="half-sweep window"):
        sweep_sparse_pallas(
            m0, chip.nbr_idx, chip.nbr_w, chip.h, chip.tanh_gain,
            chip.tanh_offset, chip.rand_gain, chip.comp_offset, *masks,
            betas, ns0, noise_mode="counter", block_b=8, interpret=True,
            half_offset=10, n_half=4)


# ---------------------------------------------------------------------------
# one-shard fused-exchange Sessions (halos structurally zero => bit-exact)
# ---------------------------------------------------------------------------
EX_POLICIES = [
    api.Sync(halo_every=1, sweeps_per_launch=4),
    api.Sync(halo_every=2, sweeps_per_launch=2),
    api.Sync(halo_every=4, mode="async", sweeps_per_launch=4),
]


@pytest.mark.parametrize("sync", EX_POLICIES,
                         ids=lambda s: f"k{s.halo_every}-{s.mode}"
                                       f"-L{s.sweeps_per_launch}")
def test_one_shard_fused_exchange_bit_exact(sync):
    g = make_chimera(3, 2, masked_cells=((1, 1),))
    mesh = jax.make_mesh((1,), ("data",))
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), HardwareConfig(),
                              noise="counter", backend="sparse")
    B, S = 8, 8
    ses0 = api.Session(mach.sampler_spec(chains=B))
    sp = mach.sampler_spec(chains=B, mesh=mesh, interpret=True,
                           partition=api.Partition(rows="data"), sync=sync)
    ses1 = api.Session(sp.replace(backend="fused_sparse"))
    assert ses1.backend == "fused_sparse"
    rng = np.random.default_rng(1)
    chip = ses0.program_edges(
        jnp.asarray(rng.integers(-50, 50, g.n_edges), jnp.int32),
        jnp.asarray(rng.integers(-10, 10, g.n_nodes), jnp.int32))
    m0 = ses0.random_spins(jax.random.PRNGKey(2))
    ns = ses0.noise_state(jax.random.PRNGKey(3))
    betas = jnp.linspace(0.3, 1.5, S)
    a = ses0.sample(chip, m0, ns, betas)
    b = ses1.sample(chip, m0, ns, betas)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    for x, y in zip(ses0.stats(chip, m0, ns, 8, 2),
                    ses1.stats(chip, m0, ns, 8, 2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# forced 2-device host: the acceptance contracts
# ---------------------------------------------------------------------------
def test_two_device_fused_exchange_k1_bit_exact():
    """Fused exchange windows under Sync(halo_every=1, barrier)
    == the single-device Session bit for bit — spins, noise state, AND
    moments — including a chained program stream (two programs through
    sample_program on the same executable)."""
    rec = _run_forced("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.chimera import make_chimera
    from repro.core.hardware import HardwareConfig

    g = make_chimera(2, 2)
    mesh = jax.make_mesh((2,), ("data",))
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), HardwareConfig(),
                              noise="counter", backend="sparse")
    B = 8
    ses0 = api.Session(mach.sampler_spec(chains=B))
    sp = mach.sampler_spec(chains=B, mesh=mesh, interpret=True,
                           partition=api.Partition(rows="data"),
                           sync=api.Sync(halo_every=1, sweeps_per_launch=4))
    ses1 = api.Session(sp.replace(backend="fused_sparse"))
    rng = np.random.default_rng(5)
    codes = jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32)
    h = jnp.asarray(rng.integers(-15, 15, g.n_nodes), jnp.int32)
    chip = ses0.program_edges(codes, h)
    m0 = ses0.random_spins(jax.random.PRNGKey(2))
    ns = ses0.noise_state(jax.random.PRNGKey(3))
    betas = jnp.linspace(0.3, 1.5, 8)

    rec = {"backend": ses1.backend}
    a, b = ses0.sample(chip, m0, ns, betas), ses1.sample(chip, m0, ns, betas)
    rec["spins"] = bool(np.array_equal(np.asarray(a[0]), np.asarray(b[0])))
    rec["noise"] = bool(np.array_equal(np.asarray(a[1]), np.asarray(b[1])))
    sa, sb = ses0.stats(chip, m0, ns, 8, 2), ses1.stats(chip, m0, ns, 8, 2)
    rec["moments"] = bool(all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(sa, sb)))

    # chained program stream: two programs back to back, state threaded
    rng2 = np.random.default_rng(9)
    codes2 = jnp.asarray(rng2.integers(-60, 60, g.n_edges), jnp.int32)
    h2 = jnp.asarray(rng2.integers(-15, 15, g.n_nodes), jnp.int32)
    ok = True
    m_a, ns_a, m_b, ns_b = m0, ns, m0, ns
    for J, hh in ((codes, h), (codes2, h2)):
        m_a, ns_a, _ = ses0.sample_program(
            ses0.make_program(J, hh), m_a, ns_a, betas)
        m_b, ns_b, _ = ses1.sample_program(
            ses1.make_program(J, hh), m_b, ns_b, betas)
        ok = ok and np.array_equal(np.asarray(m_a), np.asarray(m_b)) \
            and np.array_equal(np.asarray(ns_a), np.asarray(ns_b))
    rec["program_chain"] = bool(ok)
    print(json.dumps(rec))
    """, 2)
    assert rec["backend"] == "fused_sparse"
    assert rec["spins"] and rec["noise"] and rec["moments"]
    assert rec["program_chain"]


def test_two_device_fused_exchange_relaxed_matches_segment_scan():
    """Relaxed policies (halo_every=k barrier, async) through the
    fused exchange windows == the existing sparse segment-scan engine bit
    for bit under the same seeds (spins and noise state)."""
    rec = _run_forced("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.chimera import make_chimera
    from repro.core.hardware import HardwareConfig

    g = make_chimera(2, 2)
    mesh = jax.make_mesh((2,), ("data",))
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), HardwareConfig(),
                              noise="counter", backend="sparse")
    B = 8
    ses0 = api.Session(mach.sampler_spec(chains=B))
    rng = np.random.default_rng(5)
    chip = ses0.program_edges(
        jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32),
        jnp.asarray(rng.integers(-15, 15, g.n_nodes), jnp.int32))
    m0 = ses0.random_spins(jax.random.PRNGKey(2))
    ns = ses0.noise_state(jax.random.PRNGKey(3))
    betas = jnp.linspace(0.3, 1.5, 8)

    def run(sync, backend):
        sp = mach.sampler_spec(chains=B, mesh=mesh, interpret=True,
                               partition=api.Partition(rows="data"),
                               sync=sync)
        return api.Session(sp.replace(backend=backend)).sample(
            chip, m0, ns, betas)

    rec = {}
    for name, sync in (
            ("k4_barrier", api.Sync(halo_every=4, sweeps_per_launch=4)),
            ("k4_async", api.Sync(halo_every=4, mode="async",
                                  sweeps_per_launch=4)),
            ("k2_barrier", api.Sync(halo_every=2, sweeps_per_launch=2))):
        sc = run(sync, "sparse")
        fu = run(sync, "fused_sparse")
        rec[name] = bool(
            np.array_equal(np.asarray(sc[0]), np.asarray(fu[0]))
            and np.array_equal(np.asarray(sc[1]), np.asarray(fu[1])))
    print(json.dumps(rec))
    """, 2)
    assert rec["k4_barrier"]
    assert rec["k4_async"]
    assert rec["k2_barrier"]


# ---------------------------------------------------------------------------
# the ICI napkin model's latency term
# ---------------------------------------------------------------------------
def test_halo_napkin_latency_term():
    from repro.launch.mesh import ICI_BW, ICI_LAT_S, halo_vs_hbm_seconds

    halo, hbm = 4096, 10 * 2 ** 20
    base = halo_vs_hbm_seconds(halo, hbm)
    assert base["ici_latency_s"] == 0.0
    assert base["ici_latency_share"] == 0.0
    assert base["ici_s"] == pytest.approx(halo / ICI_BW)
    two = halo_vs_hbm_seconds(halo, hbm, exchanges=2.0)
    assert two["ici_latency_s"] == pytest.approx(2.0 * ICI_LAT_S)
    assert two["ici_s"] == pytest.approx(halo / ICI_BW + 2.0 * ICI_LAT_S)
    assert 0.0 < two["ici_latency_share"] < 1.0
    # small halos are latency-bound: the fixed cost dominates the wire
    # time — what a sparser exchange schedule amortizes
    small = halo_vs_hbm_seconds(128, hbm, exchanges=2.0)
    assert small["ici_latency_share"] > 0.9
    assert small["ici_over_hbm"] > base["ici_over_hbm"]
