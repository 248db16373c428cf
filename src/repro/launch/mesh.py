"""Production mesh definitions (TPU v5e pods).

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the "pod"
axis carries only data parallelism (gradient all-reduce over DCI/optical),
"model" stays intra-pod where ICI bandwidth lives.

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis `AxisType.Auto`.

    `jax.make_mesh` gives Explicit axes by default, under which a gather
    over a sharded operand must name its output sharding.  The sharded
    engine lays data out with `shard_map` specs and leaves the rest to
    the compiler, so it runs every user mesh as an Auto mesh.
    """
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def _mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

# TPU v5e hardware constants (roofline + napkin math)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per axis neighbor)
ICI_LAT_S = 1e-6                # per-transfer ICI latency (hop setup cost)
HBM_BYTES = 16 * 2**30          # 16 GiB per chip


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (possibly fake) local devices exist."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _mesh((data, model), ("data", "model"))


def make_line_mesh(n: int | None = None, axis: str = "data") -> Mesh:
    """1-D mesh over n local devices — the shape the sharded p-bit
    lattice wants (cell rows partition over one axis; see
    docs/sharding.md).  n=None uses every local device."""
    n = len(jax.devices()) if n is None else n
    return _mesh((n,), (axis,))


def halo_vs_hbm_seconds(halo_bytes: int, hbm_bytes: int,
                        exchanges: float = 0.0) -> dict:
    """Napkin math for one sharded sweep (docs/sharding.md): time on the
    ICI link moving the halo vs time streaming the local state+weights
    from HBM.  Ratio << 1 means the halo exchange hides entirely behind
    the local half-sweep — the regime the O(√N) boundary guarantees.

    ``exchanges`` is the policy's per-sweep transfer count
    (`Sync.exchanges_per_sweep()`); each transfer pays a fixed
    ``ICI_LAT_S`` hop-setup latency on top of the bandwidth term.  Small
    halos are latency-bound — the cost a sparser exchange schedule
    (larger ``halo_every``) amortizes — ``ici_latency_share`` says how
    much of the ICI time that fixed cost is."""
    t_bw = halo_bytes / ICI_BW
    t_lat = exchanges * ICI_LAT_S
    t_ici = t_bw + t_lat
    t_hbm = hbm_bytes / HBM_BW
    return {"ici_s": t_ici, "hbm_s": t_hbm,
            "ici_latency_s": t_lat,
            "ici_latency_share": t_lat / max(t_ici, 1e-30),
            "ici_over_hbm": t_ici / max(t_hbm, 1e-30)}


def n_chips(mesh: Mesh) -> int:
    out = 1
    for v in mesh.shape.values():
        out *= v
    return out
