"""Compile rehearsals: every Pallas kernel of the main path, built for a
described (not attached) TPU v5e at the chip's full width (N=440, B=128).

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
from jax.sharding import NamedSharding, PartitionSpec as P
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


def test_sweep_sparse_stream_compiles(one_chip, chip_graph):
    n = chip_graph.n_nodes
    d = chip_graph.neighbor_table()[0].shape[0]
    args = _common(one_chip, n, d)
    args += [_shape(one_chip, (2,), jnp.uint32),
             _shape(one_chip, (d, n), jnp.float32),
             _shape(one_chip, (n,), jnp.float32)]
    _assert_kernel(sweep_fused.sweep_sparse_stream_pallas.lower(
        *args, interpret=False).compile())


def test_pbit_half_sweep_compiles(one_chip, chip_graph):
    n = chip_graph.n_nodes
    f32 = functools.partial(_shape, one_chip, dtype=jnp.float32)
    args = [f32((B, n)), f32((n, n)), *[f32((n,)) for _ in range(5)],
            _shape(one_chip, (n,), jnp.bool_), f32((B,)), f32((B, n))]
    _assert_kernel(pbit_half_sweep_pallas.lower(*args, interpret=False)
                   .compile())


def test_exchange_kernel_compiles_on_four_chips(topo, chip_graph):
    """The in-kernel RDMA halo exchange under shard_map on a 4-chip row
    mesh: each shard holds a chip-width band plus its two halos."""
    from jax.sharding import Mesh

    n_row, n_loc, H, d = 4, 440, 32, 6
    n_ext = n_loc + 2 * H
    mesh = Mesh(np.asarray(topo.devices).reshape(n_row), ("row",))

    def local(m, idx, w, rows, masks, betas, ns, coords, send):
        out = sweep_fused.sweep_sparse_exchange_pallas(
            m[0], idx[0], w[0], *rows[0], masks[0, 0], masks[0, 1],
            betas[0], ns[0], send[0, 0], send[0, 1],
            coord_offset=coords[0], n_loc=n_loc, halo=H, ex_pts=(0, 4, 8),
            mode="barrier", axis_name="row", n_row=n_row)
        return out[0][None], out[1][None]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct((n_row, *shape), dtype,
                                    sharding=NamedSharding(mesh, P("row")))

    args = [sds((B, n_ext), jnp.float32), sds((d, n_ext), jnp.int32),
            sds((d, n_ext), jnp.float32), sds((5, n_ext), jnp.float32),
            sds((2, n_ext), jnp.bool_), sds((S, B), jnp.float32),
            sds((2,), jnp.uint32), sds((2,), jnp.uint32),
            sds((2, H), jnp.int32)]
    fn = jax.shard_map(local, mesh=mesh, in_specs=(P("row"),) * len(args),
                       out_specs=(P("row"), P("row")), check_vma=False)
    compiled = jax.jit(fn).lower(*args).compile()
    _assert_kernel(compiled)
