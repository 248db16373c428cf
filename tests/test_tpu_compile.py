"""Compile rehearsals: every Pallas kernel of the main path, built for a
described (not attached) TPU v5e at the chip's full width (N=440, B=128),
and the sharded engine's fused exchange windows on a described 4-chip
row mesh.

Interpret mode runs any jnp inside a kernel; Mosaic does not (cross-vreg
lane gathers, uint32 -> float32 casts, scalar stores to VMEM).  These
tests compile each kernel with the TPU compiler, which refuses what the
chip would refuse, without a chip.  They run nothing, so they say nothing
about results — the interpret-mode parity tests judge bit-exactness.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import pbit
from repro.core.chimera import make_chip_graph
from repro.kernels import sweep_fused
from repro.kernels.pbit_update import pbit_half_sweep_pallas

B, S = 128, 8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the cache but cannot
    # be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_graph():
    g = make_chip_graph()
    assert g.n_nodes == 440
    return g


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _common(sh, n, d=None):
    """(m, [nbr_idx, nbr_w | W], h, gain, off, rg, co, mask0, mask1, betas)"""
    f32 = functools.partial(_shape, sh, dtype=jnp.float32)
    m = f32((B, n))
    weights = ([_shape(sh, (d, n), jnp.int32), f32((d, n))] if d
               else [f32((n, n))])
    rows = [f32((n,)) for _ in range(5)]
    masks = [_shape(sh, (n,), jnp.bool_) for _ in range(2)]
    return [m, *weights, *rows, *masks, f32((S, B))]


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant", ["plain", "accumulate", "hist", "lfsr"])
def test_sweep_sparse_compiles(one_chip, chip_graph, variant):
    n = chip_graph.n_nodes
    d = chip_graph.neighbor_table()[0].shape[0]
    args = _common(one_chip, n, d)
    kw = dict(interpret=False)
    if variant == "lfsr":
        spec = pbit.make_lfsr_noise(chip_graph, B)[1].spec
        args.append(_shape(one_chip, (B, chip_graph.n_cells), jnp.uint32))
        kw.update(noise_mode="lfsr", gather_perm=spec.gather_perm)
    else:
        args.append(_shape(one_chip, (2,), jnp.uint32))
    if variant in ("accumulate", "hist"):
        args += [None, None, _shape(one_chip, (S,), jnp.float32)]
        kw.update(accumulate=True)
    if variant == "hist":
        args.append(_shape(one_chip, (3,), jnp.int32))
        kw.update(accumulate=False, collect_hist=True, n_visible=3)
    _assert_kernel(sweep_fused.sweep_sparse_pallas.lower(*args, **kw)
                   .compile())


# The scoped VMEM the kernel needed before its gather moved into row
# blocks, as the least `vmem_limit_bytes` it compiled under (4 KiB steps):
# the sample cell's call, and a band tile near `band_vmem_feasible`'s
# calibrated limit (1,024 spins + 128 halo lanes x 128 chains).
@pytest.mark.parametrize("chains,n,sweeps,band,before", [
    pytest.param(256, 440, 512, False, 5_443_584, id="sample"),
    pytest.param(128, 1152, 8, True, 12_349_440, id="band"),
])
def test_sweep_sparse_scoped_vmem_no_larger(one_chip, monkeypatch, chains, n,
                                            sweeps, band, before):
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(pltpu, "CompilerParams", functools.partial(
        pltpu.CompilerParams, vmem_limit_bytes=before))
    jax.clear_caches()     # no trace from another limit
    sh = functools.partial(_shape, one_chip)
    args = [sh((chains, n), jnp.float32), sh((6, n), jnp.int32),
            sh((6, n), jnp.float32), *[sh((n,), jnp.float32)] * 5,
            *[sh((n,), jnp.bool_)] * 2, sh((sweeps, chains), jnp.float32),
            sh((2,), jnp.uint32)]
    if band:
        args += [None, None, None, None, sh((2,), jnp.uint32)]
    _assert_kernel(sweep_fused.sweep_sparse_pallas.lower(
        *args, interpret=False).compile())


@pytest.mark.parametrize("noise", ["counter", "lfsr"])
def test_sweep_fused_dense_compiles(one_chip, chip_graph, noise):
    args = _common(one_chip, chip_graph.n_nodes)
    kw = dict(interpret=False, noise_mode=noise)
    if noise == "lfsr":
        spec = pbit.make_lfsr_noise(chip_graph, B)[1].spec
        args.append(_shape(one_chip, (B, chip_graph.n_cells), jnp.uint32))
        kw.update(gather_perm=spec.gather_perm)
    else:
        args.append(_shape(one_chip, (2,), jnp.uint32))
    _assert_kernel(sweep_fused.sweep_fused_pallas.lower(*args, **kw)
                   .compile())


def test_pbit_half_sweep_compiles(one_chip, chip_graph):
    n = chip_graph.n_nodes
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    args = [f32((B, n)), f32((n, n)), *[f32((n,)) for _ in range(5)],
            _shape(one_chip, (n,), jnp.bool_), f32((B,)), f32((B, n))]
    _assert_kernel(pbit_half_sweep_pallas.lower(*args, interpret=False)
                   .compile())


# Half-sweep windows of one launch (`half_offset`, `n_half`), as the
# sharded engine cuts a launch at its exchange points: a window that
# starts on a sweep's second half, one that stops after a first half, and
# one that does both.
@pytest.mark.parametrize("half_offset,n_half", [
    pytest.param(3, 2 * S - 3, id="starts-mid-sweep"),
    pytest.param(0, 5, id="ends-mid-sweep"),
    pytest.param(3, 6, id="both"),
])
def test_sweep_sparse_window_compiles(one_chip, chip_graph, half_offset,
                                      n_half):
    n = chip_graph.n_nodes
    d = chip_graph.neighbor_table()[0].shape[0]
    u32 = functools.partial(_shape, one_chip, (2,), jnp.uint32)
    args = _common(one_chip, n, d) + [
        u32(), None, None, _shape(one_chip, (S,), jnp.float32), None, u32()]
    _assert_kernel(sweep_fused.sweep_sparse_pallas.lower(
        *args, interpret=False, accumulate=True, half_offset=half_offset,
        n_half=n_half).compile())


# The sharded engine's fused loop on a 4-chip row mesh: each launch of 4
# sweeps splits at halo_every=3 into windows [0, 3), [3, 6), [6, 8) with
# a ppermute swap before each, under shard_map.  Built by the Session and
# its `ShardedEngine`, with the static tables described where a run would
# place them.
@pytest.mark.parametrize("what", ["sample", "stats"])
@pytest.mark.parametrize("mode", ["barrier", "async"])
def test_sharded_fused_windows_compile(topo, monkeypatch, mode, what):
    from jax.sharding import Mesh

    from repro import api
    from repro.core import distributed as dist
    from repro.core.cd import PBitMachine
    from repro.core.chimera import make_chimera
    from repro.core.hardware import (EffectiveChip, HardwareConfig,
                                     sample_mismatch_sparse)

    def described(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("row",))
    part = api.Partition(rows="row")
    g = make_chimera(16, 8)            # four bands of 256 spins
    n, d = g.n_nodes, g.neighbor_table()[0].shape[0]
    hw = HardwareConfig()
    mm = jax.tree_util.tree_map(
        described, jax.eval_shape(lambda k: sample_mismatch_sparse(
            k, n, d, hw), jax.random.PRNGKey(0)),
        dist.mismatch_shardings(mesh, part))
    monkeypatch.setattr(dist.ShardedEngine, "_put", staticmethod(described))
    sync = api.Sync(halo_every=3, mode=mode, sweeps_per_launch=4)
    mach = PBitMachine(graph=g, hw=hw, mismatch=mm, noise="counter",
                       backend="fused_sparse", mesh=mesh, partition=part,
                       sync=sync)
    ses = api.Session(mach.sampler_spec(chains=B).replace(interpret=False))
    assert ses.backend == "fused_sparse"
    assert dist.halo_exchange_segments(sync.exchange_points(), 8) == (
        (0, 3), (3, 6), (6, 8))
    eng = ses._engine
    nodes = dist.band_sharding(mesh, part, 1, 0)
    slots = dist.band_sharding(mesh, part, 2, 1)
    f32 = functools.partial(_shape, nodes, (n,), jnp.float32)
    chip = EffectiveChip(
        W=None, h=f32(), tanh_gain=f32(), tanh_offset=f32(), rand_gain=f32(),
        comp_offset=f32(), nbr_idx=_shape(slots, (d, n), jnp.int32),
        nbr_w=_shape(slots, (d, n), jnp.float32))
    m = _shape(eng.spin_sharding, (B, n), jnp.float32)
    ns = _shape(eng.noise_sharding, (2,), jnp.uint32)
    if what == "sample":
        fn = ses._build_sample(False, False)
        args = (chip, m, ns, _shape(eng.noise_sharding, (2 * 4,),
                                    jnp.float32))
    else:
        fn = ses._build_stats(2 * 4, 2, 1.0, False)
        args = (chip, m, ns)
    _assert_kernel(fn.lower(*args).compile())
