"""Mesh-sharded sparse lattice: row partitioning, halo exchange, engine.

The paper's chip tiles a 7x8 Chimera cell grid with only inter-cell wires
crossing tile boundaries — exactly the communication pattern a device mesh
wants.  This module is the sharded execution layer behind
``api.SamplerSpec(mesh=..., partition=api.Partition(...))``:

  * `plan_row_partition` cuts the cell grid into contiguous *row bands*
    (one per device along the partition's rows axis) and precomputes, in
    numpy at Session compile: the padded per-device node slices, the
    (D, N_loc) neighbor tables re-indexed into [local | halo_up | halo_dn],
    the boundary send lists (the O(√N) chain-coupler spins), the
    per-device edge lists for moment accumulation, and the LFSR cell
    bands for chip-faithful noise.
  * `ShardedEngine` compiles the plan plus the spec's `api.Sync` policy
    into `shard_map`-wrapped launch loops: at each exchange point a
    device ppermutes its boundary spins to its row neighbors
    (`kernels/shard_sweep.py`), regenerates its own noise columns from
    the *global* (chain, node) coordinates, and runs the slot-layout
    sweeps locally — no dense W, no global gather, ever.  Under the
    default barrier policy (exchange every half-sweep) spins are
    bit-exact vs the single-device scan backends for the same noise
    stream; relaxed policies (halo_every=k, PASS-style async double
    buffering, launch-resident fused kernels) are deterministic, seeded
    approximations measured against it (docs/sharding.md §Sync
    policies).  The Gibbs-chain axis shards the same way (CD's
    embarrassingly parallel dimension); the (E,) edge-list moments are
    psum-reduced once per phase.  Chips enter every engine entry point as
    *traced operands* (`_chip_parts` is pure jnp on static tables), so
    runtime weight streaming works through the sharded path unchanged:
    one compiled executable per (graph-shape, partition, sync) bucket
    serves every `api.Program` (`Session.sample_program`).

The old structure-of-arrays pod lattice (`LatticeSpec`/`make_sk_lattice`)
remains as the O(N) *instance generator* for SK-style lattices, but its
private update loop is gone: `lattice_to_chip` converts the SoA couplings
into the shared `EffectiveChip` slot layout and `make_lattice_anneal`
drives the same `api.Session` engine every other workload uses.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import lfsr as lfsr_mod
from repro.core.chimera import ChimeraGraph, make_chimera
from repro.core.hardware import EffectiveChip, HardwareConfig
from repro.kernels.ref import halo_exchange_segments, sparse_neuron_input
from repro.kernels.shard_sweep import (
    fused_shard_exchange_resident,
    fused_shard_sweeps,
    halo_exchange,
    halo_half_sweep,
)
from repro.launch.mesh import auto_axes


# ---------------------------------------------------------------------------
# Partition plan (numpy, built once at Session compile)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RowPartition:
    """Static plan: Chimera cell rows -> n_shards contiguous row bands.

    All arrays are numpy; shard-varying tables carry a leading
    (n_shards,) dim and are fed to `shard_map` as sharded inputs (never
    baked into the traced closure, which would replicate them).
    Padding entries (bands own unequal node counts on masked grids) point
    at real in-bounds nodes and are masked out of updates/scatters.
    """

    n_shards: int
    n_loc: int                 # padded nodes per band
    halo: int                  # padded boundary spins per direction
    node_starts: np.ndarray    # (n_shards + 1,) global node range bounds
    part_ids: np.ndarray       # (n_shards, n_loc) global node id
    valid: np.ndarray          # (n_shards, n_loc) bool
    inv_ids: np.ndarray        # (N,) global node -> shard * n_loc + p
    nbr_idx: np.ndarray        # (n_shards, D, n_loc) ext-local indices
    send_up: np.ndarray        # (n_shards, halo) local idx -> device above
    send_dn: np.ndarray        # (n_shards, halo) local idx -> device below
    n_boundary: int            # true boundary spins over internal cuts
    upd_masks: np.ndarray      # (n_shards, 2, n_loc) color masks & valid
    e_loc: int                 # padded edges per band
    edge_e0: np.ndarray        # (n_shards, e_loc) ext-local endpoint 0
    edge_e1: np.ndarray        # (n_shards, e_loc) ext-local endpoint 1
    edge_inv: np.ndarray       # (E,) global edge -> shard * e_loc + q
    # LFSR cell bands (built only when the spec's noise is "lfsr")
    c_loc: int = 0
    cell_ids: np.ndarray | None = None   # (n_shards, c_loc) global cell
    cell_valid: np.ndarray | None = None
    cell_inv: np.ndarray | None = None   # (n_cells,) -> shard * c_loc + q
    lfsr_perm: np.ndarray | None = None  # (n_shards, n_loc) local flat col


# plan_row_partition memo: serving's shard-loss re-plan and every compile-
# cache miss used to redo the full numpy plan; a ChimeraGraph is a pure
# function of (rows, cols, k, masked_cells), so those four plus the shard
# count key the plan exactly.  Plans are frozen dataclasses of read-only
# tables — every consumer treats them as immutable, so sharing one
# instance across Sessions is safe.
_PLAN_CACHE: dict = {}
PLAN_CACHE_STATS = {"hits": 0, "misses": 0}


def plan_cache_stats() -> dict:
    """Copy of the `plan_row_partition` memo hit/miss counters."""
    return dict(PLAN_CACHE_STATS)


def clear_plan_cache() -> None:
    """Drop memoized plans and zero the counters (tests)."""
    _PLAN_CACHE.clear()
    PLAN_CACHE_STATS["hits"] = 0
    PLAN_CACHE_STATS["misses"] = 0


def plan_row_partition(graph: ChimeraGraph, n_shards: int,
                       with_lfsr: bool = False) -> RowPartition:
    """Cut the cell grid into contiguous row bands (see RowPartition).

    Memoized on (graph identity, n_shards, with_lfsr): a degraded-mesh
    re-plan (`surviving_mesh` shrinking n_shards back to a previously
    planned size) and repeat Session compiles hit the cache instead of
    re-running the numpy planner (`plan_cache_stats()` exposes the
    counters).
    """
    key = (graph.rows, graph.cols, graph.k, tuple(graph.masked_cells),
           int(n_shards), bool(with_lfsr))
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        PLAN_CACHE_STATS["hits"] += 1
        return plan
    plan = _plan_row_partition(graph, n_shards, with_lfsr)
    PLAN_CACHE_STATS["misses"] += 1
    _PLAN_CACHE[key] = plan
    return plan


def _plan_row_partition(graph: ChimeraGraph, n_shards: int,
                        with_lfsr: bool = False) -> RowPartition:
    if n_shards < 1 or n_shards > graph.rows:
        raise ValueError(
            f"cannot cut {graph.rows} cell rows into {n_shards} bands")
    base, rem = divmod(graph.rows, n_shards)
    counts = [base + (d < rem) for d in range(n_shards)]
    r_start = np.concatenate([[0], np.cumsum(counts)])       # (n_shards+1,)
    node_r = np.asarray(graph.node_r)
    node_side = np.asarray(graph.node_side)
    # nodes are numbered by (r, c, side, k): each band owns a contiguous
    # id range regardless of cell masking
    node_starts = np.searchsorted(node_r, r_start).astype(np.int64)
    n_loc = max(1, int(np.max(np.diff(node_starts))))
    N = graph.n_nodes
    owner = np.searchsorted(node_starts[1:], np.arange(N), side="right")

    # boundary send lists: vertical (side-0) nodes of each band's first /
    # last cell row — the only nodes chain couplers carry across a cut
    ids_all = np.arange(N)
    send_up_ids, send_dn_ids = [], []
    for d in range(n_shards):
        sel = slice(node_starts[d], node_starts[d + 1])
        ids = ids_all[sel]
        vert = node_side[sel] == 0
        send_up_ids.append(ids[vert & (node_r[sel] == r_start[d])])
        send_dn_ids.append(ids[vert & (node_r[sel] == r_start[d + 1] - 1)])
    H = max(1, max((len(x) for x in send_up_ids + send_dn_ids), default=1))
    n_boundary = sum(len(send_dn_ids[d]) for d in range(n_shards - 1)) \
        + sum(len(send_up_ids[d]) for d in range(1, n_shards))

    nbr_g, _ = graph.neighbor_table()
    D = nbr_g.shape[0]
    part_ids = np.zeros((n_shards, n_loc), np.int32)
    valid = np.zeros((n_shards, n_loc), bool)
    local_nbr = np.zeros((n_shards, D, n_loc), np.int32)
    send_up = np.zeros((n_shards, H), np.int32)
    send_dn = np.zeros((n_shards, H), np.int32)
    for d in range(n_shards):
        s, e = int(node_starts[d]), int(node_starts[d + 1])
        n_d = e - s
        part_ids[d] = min(s, N - 1)
        part_ids[d, :n_d] = np.arange(s, e)
        valid[d, :n_d] = True
        send_up[d, :len(send_up_ids[d])] = send_up_ids[d] - s
        send_dn[d, :len(send_dn_ids[d])] = send_dn_ids[d] - s
        g_nbr = nbr_g[:, s:e].astype(np.int64)       # (D, n_d) global ids
        own = owner[g_nbr]
        loc = (g_nbr - s).astype(np.int64)           # local by default
        if d > 0:
            up = own == d - 1
            pos = np.searchsorted(send_dn_ids[d - 1], g_nbr[up])
            if not np.array_equal(send_dn_ids[d - 1][pos], g_nbr[up]):
                raise AssertionError("cross-band neighbor not on boundary")
            loc[up] = n_loc + pos
        if d < n_shards - 1:
            dn = own == d + 1
            pos = np.searchsorted(send_up_ids[d + 1], g_nbr[dn])
            if not np.array_equal(send_up_ids[d + 1][pos], g_nbr[dn]):
                raise AssertionError("cross-band neighbor not on boundary")
            loc[dn] = n_loc + H + pos
        if np.any(np.abs(own - d) > 1):
            raise AssertionError("neighbor more than one row band away")
        local_nbr[d, :, :n_d] = loc
    inv_ids = (owner * n_loc
               + (np.arange(N) - node_starts[owner])).astype(np.int32)

    color = np.asarray(graph.color)[part_ids]
    upd_masks = np.stack([(color == c) & valid for c in (0, 1)], axis=1)

    # per-band edge lists (owner = endpoint-0's band; endpoint 1 is local
    # or in the halo of the band below)
    e0g, e1g = graph.edges[:, 0].astype(np.int64), \
        graph.edges[:, 1].astype(np.int64)
    e_own = owner[e0g]
    e_loc = max(1, int(np.bincount(e_own, minlength=n_shards).max()))
    edge_e0 = np.zeros((n_shards, e_loc), np.int32)
    edge_e1 = np.zeros((n_shards, e_loc), np.int32)
    edge_inv = np.zeros((graph.n_edges,), np.int32)
    for d in range(n_shards):
        s = int(node_starts[d])
        sel = np.nonzero(e_own == d)[0]
        edge_e0[d, :len(sel)] = e0g[sel] - s
        le1 = e1g[sel] - s
        far = owner[e1g[sel]] == d + 1
        if np.any(far):
            pos = np.searchsorted(send_up_ids[d + 1], e1g[sel][far])
            le1[far] = n_loc + H + pos
        edge_e1[d, :len(sel)] = le1
        edge_inv[sel] = d * e_loc + np.arange(len(sel))

    kw: dict[str, Any] = {}
    if with_lfsr:
        kw = _plan_lfsr_cells(graph, n_shards, r_start, part_ids, valid,
                              node_starts)
    return RowPartition(
        n_shards=n_shards, n_loc=n_loc, halo=H, node_starts=node_starts,
        part_ids=part_ids, valid=valid, inv_ids=inv_ids, nbr_idx=local_nbr,
        send_up=send_up, send_dn=send_dn, n_boundary=int(n_boundary),
        upd_masks=upd_masks, e_loc=e_loc, edge_e0=edge_e0, edge_e1=edge_e1,
        edge_inv=edge_inv, **kw)


def _plan_lfsr_cells(graph, n_shards, r_start, part_ids, valid, node_starts):
    """Band the per-cell LFSRs the same way (cells sort by (r, c), exactly
    the order core/pbit.make_lfsr_noise enumerates them)."""
    cells = sorted(
        {(int(r), int(c)) for r, c in zip(graph.node_r, graph.node_c)})
    n_cells = len(cells)
    vert = np.stack([graph.cell_nodes(r, c, side=0) for r, c in cells])
    horiz = np.stack([graph.cell_nodes(r, c, side=1) for r, c in cells])
    perm_g = lfsr_mod.node_gather_perm(vert, horiz, graph.n_nodes)
    cell_rows = np.array([r for r, _ in cells])
    cell_starts = np.searchsorted(cell_rows, r_start)
    c_loc = max(1, int(np.max(np.diff(cell_starts))))
    cell_ids = np.zeros((n_shards, c_loc), np.int32)
    cell_valid = np.zeros((n_shards, c_loc), bool)
    lfsr_perm = np.zeros(part_ids.shape, np.int32)
    for d in range(n_shards):
        s, e = int(cell_starts[d]), int(cell_starts[d + 1])
        cell_ids[d] = min(s, n_cells - 1)
        cell_ids[d, :e - s] = np.arange(s, e)
        cell_valid[d, :e - s] = True
        pg = perm_g[part_ids[d]]
        kk, cell = pg // n_cells, pg % n_cells
        lp = kk * c_loc + (cell - s)
        lfsr_perm[d] = np.where(valid[d], lp, 0)
    cell_own = np.searchsorted(cell_starts[1:], np.arange(n_cells),
                               side="right")
    cell_inv = (cell_own * c_loc
                + (np.arange(n_cells) - cell_starts[cell_own])).astype(
                    np.int32)
    return dict(c_loc=c_loc, cell_ids=cell_ids, cell_valid=cell_valid,
                cell_inv=cell_inv, lfsr_perm=lfsr_perm)


def halo_bytes_per_sweep(plan: RowPartition, chains: int,
                         refresh_for_moments: bool = False,
                         sync=None):
    """Total float32 bytes crossing internal band cuts per full sweep.

    Under the default barrier policy: two half-sweeps, each moving every
    internal boundary spin in both directions, for every chain; +1
    exchange per sweep when moments are accumulated (the post-sweep
    refresh for boundary-edge correlations).  An `api.Sync` policy scales
    the multiplier by its exchange schedule — ``halo_every=k`` divides it
    by ~k, a launch-resident policy (``sweeps_per_launch=S`` with
    launch-boundary-only exchange) by 2S (docs/sharding.md §Sync
    policies; the relaxed policies drop the moment refresh, so the result
    may be fractional).  O(boundary) = O(√N · n_shards) either way —
    compare 4·N² bytes to replicate a dense W.
    """
    if sync is None:
        from repro.api.spec import Sync
        sync = Sync()
    return sync.exchanges_per_sweep(refresh_for_moments) \
        * plan.n_boundary * chains * 4


def surviving_mesh(mesh: Mesh, dead_ids) -> Mesh | None:
    """Re-plan a 1-D row mesh onto the devices that outlived a shard loss.

    The serving degradation ladder (`repro.serve.degrade`) calls this when
    heartbeats or the fault harness declare devices dead: survivors keep
    the original axis name, so every `Partition(rows=axis)` in cached
    specs stays valid and `plan_row_partition` simply re-cuts the row
    bands over the smaller device count.  Returns ``None`` when fewer
    than two devices survive — the caller then drops ``mesh=`` entirely
    and falls back to the bit-exact single-device path rather than paying
    halo-exchange overhead on a one-device "mesh".
    """
    dead = {int(d) for d in dead_ids}
    survivors = [d for d in np.asarray(mesh.devices).reshape(-1)
                 if int(d.id) not in dead]
    if not survivors:
        raise RuntimeError(
            f"no devices survive: mesh {tuple(int(d.id) for d in np.asarray(mesh.devices).reshape(-1))} "
            f"all marked dead ({sorted(dead)})")
    if len(survivors) < 2:
        return None
    axis = mesh.axis_names[0]
    return Mesh(np.asarray(survivors), (axis,))


# ---------------------------------------------------------------------------
# The sharded engine (compiled into api.Session closures)
# ---------------------------------------------------------------------------
class ShardedEngine:
    """Plan + mesh + sync policy -> device-local sweep implementations.

    Built once at `api.Session` compile when the spec carries a mesh.
    The public impls (`sample` / `stats` / `visible_hist`) keep the exact
    array contracts of the single-device engine (global (B, N) spins,
    global noise state) — the Session's closures call them unchanged, so
    every workload (CD, annealing, tempering, Max-Cut) shards without
    modification.

    The `api.Sync` policy is compiled into a *launch loop*: the sweep
    schedule is cut into launches of ``sweeps_per_launch`` sweeps, the
    scan runs over launches, and the L sweeps inside a launch unroll with
    the policy's exchange points placed statically — no collective ever
    sits behind a traced conditional.  Halo buffers (and, in async mode,
    the in-flight double buffer) thread through the scan carry, so
    between exchange points every band samples against a *stale* halo —
    the deterministic, seeded emulation of the chip's clockless fabric.
    ``Sync()`` (barrier, halo_every=1) reproduces the single-device
    trajectory bit for bit; under a launch-resident counter-noise policy
    the whole launch runs inside the sweep-resident Pallas kernel
    (`kernels/shard_sweep.py::fused_shard_sweeps`, backend
    "fused_sparse").
    """

    def __init__(self, graph: ChimeraGraph, mesh: Mesh, partition,
                 noise: str, decimation: int, chains: int, *,
                 sync=None, backend: str = "sparse",
                 interpret: bool = True, faults=None):
        if sync is None:
            from repro.api.spec import Sync
            sync = Sync()
        self.graph = graph
        self.mesh = auto_axes(mesh)
        self.noise = noise
        self.decimation = decimation
        self.chains = chains
        self.sync = sync
        self.interpret = interpret
        # discrete fault injection (api.Faults).  Stuck spins arrive as
        # clamp args from the Session; what the engine itself owns are
        # the per-half-sweep hooks, regenerated per shard from *global*
        # coordinates so the sharded trajectory reproduces the
        # single-device fault draw bit for bit under the barrier policy:
        # transient flips (salted counter hash of global (chain, node))
        # and stuck LFSR register bits (per-cell masks gathered into the
        # shard's cell band).
        self.faults = faults
        self._fused = backend == "fused_sparse"
        self.rows_axes = partition.rows_axes
        self.chain_axes = partition.chain_axes
        self.n_row = int(np.prod([mesh.shape[a] for a in self.rows_axes],
                                 dtype=np.int64)) if self.rows_axes else 1
        self.n_chain = int(np.prod([mesh.shape[a] for a in self.chain_axes],
                                   dtype=np.int64)) if self.chain_axes else 1
        if chains % self.n_chain:
            raise ValueError(f"chains={chains} not divisible by the "
                             f"chain-axis size {self.n_chain}")
        self.b_loc = chains // self.n_chain
        # fused-resident-exchange: with mid-launch exchange points the
        # KERNEL owns the halo refresh.  On a real TPU mesh (single named
        # rows axis, compiled mode) one RDMA launch runs the whole
        # schedule; everywhere else (interpret mode, CPU hosts, or
        # REPRO_HALO_EMULATE=1) the engine emulates the same launch
        # bit-exactly: half-sweep windows of the resident kernel with a
        # ppermute between windows, inside one jitted graph.
        self._fused_exchange = self._fused and not sync.kernel_fusible
        self._halo_rdma = bool(
            self._fused_exchange and not interpret
            and jax.default_backend() == "tpu"
            and len(self.rows_axes) == 1
            and not os.environ.get("REPRO_HALO_EMULATE"))
        self.plan = plan_row_partition(graph, self.n_row,
                                       with_lfsr=(noise == "lfsr"))
        p = self.plan
        self._row_name = (self.rows_axes[0] if len(self.rows_axes) == 1
                          else (tuple(self.rows_axes) or None))
        self._chain_name = (self.chain_axes[0] if len(self.chain_axes) == 1
                            else (tuple(self.chain_axes) or None))
        # P-spec dimension entries (None = replicated over that dim)
        self._r = tuple(self.rows_axes) if self.rows_axes else None
        self._c = tuple(self.chain_axes) if self.chain_axes else None
        self._part_ids = jnp.asarray(p.part_ids)
        self._inv_ids = jnp.asarray(p.inv_ids)
        self._edge_inv = jnp.asarray(p.edge_inv)
        self._dev = {
            "nbr": jnp.asarray(p.nbr_idx),
            "send_up": jnp.asarray(p.send_up),
            "send_dn": jnp.asarray(p.send_dn),
            "upd": jnp.asarray(p.upd_masks),
            "cols": jnp.asarray(p.part_ids.astype(np.uint32)),
            "edge_e0": jnp.asarray(p.edge_e0),
            "edge_e1": jnp.asarray(p.edge_e1),
        }
        if noise == "lfsr":
            self._dev["lfsr_perm"] = jnp.asarray(p.lfsr_perm)
            self._cell_ids = jnp.asarray(p.cell_ids)
            self._cell_inv = jnp.asarray(p.cell_inv)
            if faults is not None and faults.lfsr_stuck:
                n_cells = graph.n_nodes // 8
                s0 = np.zeros((n_cells,), np.uint32)
                s1 = np.zeros((n_cells,), np.uint32)
                for cell, m0, m1 in faults.lfsr_stuck:
                    s0[int(cell)] |= np.uint32(m0)
                    s1[int(cell)] |= np.uint32(m1)
                self._dev["lfsr_s0"] = jnp.asarray(s0[p.cell_ids])
                self._dev["lfsr_s1"] = jnp.asarray(s1[p.cell_ids])
        if self._fused:
            # per-edge slot row into the kernel's (D, N_ext) correlation
            # scratch: edge q of band b lives at c_slots[edge_slot[b, q],
            # edge_e0[b, q]] (endpoint 0 is always local)
            es = np.zeros((p.n_shards, p.e_loc), np.int32)
            for b in range(p.n_shards):
                hit = p.nbr_idx[b][:, p.edge_e0[b]] == p.edge_e1[b][None, :]
                es[b] = np.argmax(hit, axis=0)
            self._dev["edge_slot"] = jnp.asarray(es)

    # -- spec helpers ----------------------------------------------------
    def _dev_specs(self):
        specs = {
            "nbr": P(self._r, None, None),
            "send_up": P(self._r, None),
            "send_dn": P(self._r, None),
            "upd": P(self._r, None, None),
            "cols": P(self._r, None),
            "edge_e0": P(self._r, None),
            "edge_e1": P(self._r, None),
        }
        if self.noise == "lfsr":
            specs["lfsr_perm"] = P(self._r, None)
            if "lfsr_s0" in self._dev:
                specs["lfsr_s0"] = P(self._r, None)
                specs["lfsr_s1"] = P(self._r, None)
        if self._fused:
            specs["edge_slot"] = P(self._r, None)
        return specs

    def _chip_specs(self):
        return {"w": P(self._r, None, None),
                **{k: P(self._r, None)
                   for k in ("h", "gain", "off", "rg", "co")}}

    def _shard_map(self, fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    # -- global <-> parts layout ----------------------------------------
    def _chip_parts(self, chip: EffectiveChip) -> dict:
        """Slice the chip into per-device (n_shards, ...) shard layouts.

        Pure jnp gathers on static index tables, so this runs *inside*
        the Session's jitted closures with the chip as a traced operand —
        which is what threads runtime weight streaming through the
        sharded engine for free: a `Program` programmed in-jit
        (`Session.sample_program`) flows through here into the
        shard_map'd sweep as sharded input, and a swapped program is a
        new operand value, never a recompile.
        """
        if chip.nbr_w is None or chip.nbr_idx is None:
            raise ValueError(
                "sharded execution needs a chip carrying the slot layout "
                "(program through the Session — e.g. Session.make_program "
                "+ sample_program — or hardware.attach_sparse)")
        ids = self._part_ids
        return {
            "w": jnp.moveaxis(chip.nbr_w[:, ids], 1, 0),
            "h": chip.h[ids],
            "gain": chip.tanh_gain[ids],
            "off": chip.tanh_offset[ids],
            "rg": chip.rand_gain[ids],
            "co": chip.comp_offset[ids],
        }

    def _m_parts(self, m: jax.Array) -> jax.Array:
        return jnp.moveaxis(jnp.take(m, self._part_ids, axis=1), 1, 0)

    def _m_global(self, parts: jax.Array) -> jax.Array:
        flat = jnp.moveaxis(parts, 0, 1).reshape(parts.shape[1], -1)
        return jnp.take(flat, self._inv_ids, axis=1)

    def _ns_parts(self, ns: jax.Array):
        if self.noise == "lfsr":
            return jnp.moveaxis(jnp.take(ns, self._cell_ids, axis=1), 1, 0)
        return ns  # counter: replicated uint32[2]

    def _ns_global(self, ns, parts):
        if self.noise == "lfsr":
            flat = jnp.moveaxis(parts, 0, 1).reshape(parts.shape[1], -1)
            return jnp.take(flat, self._cell_inv, axis=1)
        return parts

    def _ns_spec(self):
        return P(self._r, self._c, None) if self.noise == "lfsr" else P()

    # -- device-local pieces --------------------------------------------
    def _chain_offset(self):
        """Global id of this device's first chain (uint32)."""
        idx = jnp.uint32(0)
        for ax in self.chain_axes:
            idx = idx * jnp.uint32(self.mesh.shape[ax]) \
                + jax.lax.axis_index(ax).astype(jnp.uint32)
        return idx * jnp.uint32(self.b_loc)

    def _noise_step(self, dev):
        """Device-local step fn regenerating the *global* noise stream's
        columns for this shard — bit-exact vs core/pbit's host noise."""
        if self.noise == "counter":
            cols = dev["cols"][0][None, :]

            def step(st, chain0):
                rows = chain0 + jnp.arange(self.b_loc, dtype=jnp.uint32)
                u = lfsr_mod.counter_uniform(st[0], st[1], rows[:, None],
                                             cols)
                return st + jnp.array([0, 1], jnp.uint32), u
            return step

        perm = dev["lfsr_perm"][0]
        s0 = dev["lfsr_s0"][0] if "lfsr_s0" in dev else None
        s1 = dev["lfsr_s1"][0] if "lfsr_s1" in dev else None

        def step(st, chain0):
            st = lfsr_mod.lfsr_step_n(st, self.decimation)
            if s0 is not None:
                # stuck register bits (api.Faults.lfsr_stuck): forced
                # after every decimated clock, before the read — same
                # order as the Session's single-device wrapper
                st = (st & ~s0) | s1
            u = jnp.take(lfsr_mod.flat_cell_uniforms(st), perm, axis=-1)
            return st, u
        return step

    def _flip_step(self, dev):
        """Transient-flip draw for this shard: Bernoulli(flip_prob) per
        (chain, node) per half-sweep from a salted counter stream over
        global coordinates (None when the fault model has no flips)."""
        f = self.faults
        if f is None or f.flip_prob <= 0.0:
            return None
        from repro.api.faults import FLIP_SALT
        cols = dev["cols"][0][None, :]
        thresh = jnp.uint32(round(float(f.flip_prob) * 65536.0))
        salt = jnp.uint32((int(f.flip_seed) ^ FLIP_SALT) & 0xFFFFFFFF)

        def flip(st, chain0):
            rows = chain0 + jnp.arange(self.b_loc, dtype=jnp.uint32)
            bits = lfsr_mod.counter_bits(st[0] ^ salt, st[1],
                                         rows[:, None], cols)
            return ((bits >> jnp.uint32(16)) & jnp.uint32(0xFFFF)) < thresh
        return flip

    def _local_sweeps(self, clamped, collect, accumulate, hist_w):
        """The per-device launch loop.  Returns
        run(dev, chip, m, ns, betas, measured?, cm?, cv?) -> mode outputs
        — ``dev`` is the *sharded* plan-table argument shard_map hands
        each device (never a closure capture, which would replicate
        device 0's tables everywhere).

        The sync policy shapes the loop at trace time: every halo
        exchange sits at a statically-placed exchange point, and halos
        are reused (stale) from the carry in between — no collective ever
        hides behind a traced conditional.  Async mode double-buffers the
        exchange: the values consumed at an exchange point were sent at
        the previous one, so the ppermute overlaps the intervening
        interior compute.  Four loop shapes, picked at compile:

          * fused — launch-resident counter-noise policies with
            launch-boundary-only exchange run each launch as one
            `fused_shard_sweeps` Pallas call (sample and stats paths;
            collect/hist fall back to the segment scan).
          * fused-resident-exchange — fused backends whose policy has
            mid-launch exchange points: the kernel owns the halo
            refresh.  TPU meshes run one `fused_shard_exchange_resident`
            RDMA launch per schedule chunk; interpret/CPU hosts run the
            bit-exact emulation — the same launch split at the exchange
            points into `half_offset`/`n_half` windows of the resident
            kernel with a ppermute between windows, all inside one
            jitted graph (no host round-trip).  Replaces the segment
            scan whenever the fused kernel is active (see
            docs/kernels.md, "In-kernel halo exchange").
          * segment scan — exchanges uniformly spaced at full-sweep
            boundaries (``halo_every`` even or inf): outer scan over
            inter-exchange segments, inner scan over the uniform sweeps
            between them.  Keeps the compiled body one-sweep-sized —
            Python-unrolling S sweeps makes XLA's CPU pipeline blow up
            super-linearly in S.
          * unrolled launch — odd ``halo_every`` (exchange points inside
            a sweep, e.g. the k=1 barrier's two per sweep): scan over
            launches with the L sweeps unrolled statically.  L=1
            reproduces the pre-policy engine graph exactly.
        """
        n_loc = self.plan.n_loc
        sync = self.sync
        L = sync.sweeps_per_launch
        k = sync.halo_every
        ex_pts = sync.exchange_points()
        async_ = sync.mode == "async"
        k1_exact = sync.bit_exact
        use_fused = self._fused and not collect and hist_w is None
        fused_ex = use_fused and ex_pts != (0,)
        if use_fused or ex_pts == (0,):
            seg_sweeps = L                  # exchange at launch starts only
        elif isinstance(k, int) and k % 2 == 0 and (2 * L) % k == 0:
            seg_sweeps = k // 2             # uniform inter-exchange segments
        else:
            seg_sweeps = None               # unrolled launch body

        def run(dev, chip, m, ns, betas, measured=None, cm=None, cv=None,
                vis_idx=None, vis_w=None):
            send_up, send_dn = dev["send_up"][0], dev["send_dn"][0]
            nbr = dev["nbr"][0]

            def exchange(m):
                return halo_exchange(m, send_up, send_dn, self._row_name,
                                     self.n_row)

            nstep = self._noise_step(dev)
            fstep = self._flip_step(dev)
            w, h = chip["w"][0], chip["h"][0]
            gain, off = chip["gain"][0], chip["off"][0]
            rg, co = chip["rg"][0], chip["co"][0]
            chain0 = self._chain_offset()
            masks = [dev["upd"][0, c] for c in (0, 1)]
            if clamped:
                masks = [mk & ~cm for mk in masks]

            S_total = int(betas.shape[0])
            if S_total % L:
                raise ValueError(
                    f"this Session's sync policy fuses sweeps_per_launch="
                    f"{L} sweeps per launch, which must divide the "
                    f"schedule length (got {S_total} sweeps); pad the "
                    f"schedule or change the Sync policy")

            def swap(m, hu, hd, pend):
                """One exchange point: barrier consumes the fresh values;
                async consumes the in-flight buffer and refills it."""
                fresh = exchange(m)
                if async_:
                    return pend[0], pend[1], fresh
                return fresh[0], fresh[1], pend

            def sweep_stats(m, ru, rd, w_t, accs):
                """Per-sweep moment / histogram accumulation against the
                halo view (ru, rd) the policy defines."""
                accs = list(accs)
                if accumulate:
                    m_ext = jnp.concatenate([m, ru, rd], axis=1)
                    corr = m_ext[:, dev["edge_e0"][0]] \
                        * m_ext[:, dev["edge_e1"][0]]
                    if self.n_chain == 1:
                        # dense-identical accumulation order (any B)
                        accs[0] = accs[0] + w_t * jnp.mean(m, axis=0)
                        accs[1] = accs[1] + w_t * jnp.mean(corr, axis=0)
                    else:
                        # raw ±1 sums; psum + one division at the end —
                        # bit-exact vs dense for power-of-two chains
                        accs[0] = accs[0] + w_t * jnp.sum(m, axis=0)
                        accs[1] = accs[1] + w_t * jnp.sum(corr, axis=0)
                else:  # histogram
                    bits = (jnp.take(m, vis_idx, axis=1) > 0).astype(
                        jnp.int32)
                    code = jnp.sum(bits * vis_w[None, :], axis=1)
                    if self.n_row > 1:
                        code = jax.lax.psum(code, self._row_name)
                    accs[0] = accs[0].at[code].add(w_t)
                return accs

            def launch(carry, xs_t):
                """Fused kernel launch (boundary-only or kernel-resident
                exchange), or L statically-unrolled sweeps (the
                odd-``halo_every`` non-fused shapes, incl. k=1)."""
                m, ns, hu, hd = carry[0], carry[1], carry[2], carry[3]
                base = 4
                pend = ()
                if async_:
                    pend, base = (carry[4], carry[5]), 6
                accs = list(carry[base:])
                betas_t = xs_t[0]
                meas_t = xs_t[1] if len(xs_t) > 1 else None
                outs = []

                if use_fused and not fused_ex:
                    if clamped and cv is not None:
                        m = jnp.where(cm, cv, m)
                    hu, hd, pend = swap(m, hu, hd, pend)
                    kwc = {}
                    if clamped and cv is not None:
                        kwc = dict(clamp_mask=cm, clamp_values=cv)
                    res = fused_shard_sweeps(
                        m, hu, hd, nbr, w, h, gain, off, rg, co,
                        masks[0], masks[1], betas_t, ns, chain0,
                        dev["cols"][0][0],
                        measured=meas_t if accumulate else None,
                        interpret=self.interpret, **kwc)
                    m, ns = res[0], res[1]
                    if accumulate:
                        s_k = res[2]
                        c_k = res[3][dev["edge_slot"][0],
                                     dev["edge_e0"][0]]
                        if self.n_chain == 1:
                            b = jnp.float32(m.shape[0])
                            s_k, c_k = s_k / b, c_k / b
                        accs[0] = accs[0] + s_k
                        accs[1] = accs[1] + c_k
                elif fused_ex:
                    # fused-resident-exchange: the kernel owns the halo
                    # refresh.  k=1 barrier (bit_exact) keeps the host
                    # post-sweep stats refresh, so the kernel only
                    # sweeps; every other policy accumulates in-kernel.
                    if clamped and cv is not None:
                        m = jnp.where(cm, cv, m)
                    kwc = {}
                    if clamped and cv is not None:
                        kwc = dict(clamp_mask=cm, clamp_values=cv)
                    exact_stats = accumulate and k1_exact
                    kern_meas = meas_t \
                        if (accumulate and not exact_stats) else None
                    if self._halo_rdma and not exact_stats:
                        # one RDMA launch per chunk; halos refresh via
                        # remote async copies inside the kernel.  Async
                        # consumes the pend buffer at point 0 and the
                        # kernel's drained final exchange refills it.
                        hu_in, hd_in = pend if async_ else (hu, hd)
                        res = fused_shard_exchange_resident(
                            m, hu_in, hd_in, nbr, w, h, gain, off, rg,
                            co, masks[0], masks[1], betas_t, ns, chain0,
                            dev["cols"][0][0], send_up, send_dn,
                            measured=kern_meas, ex_pts=ex_pts,
                            mode=sync.mode, axis_name=self._row_name,
                            n_row=self.n_row, **kwc)
                        m, ns, hu, hd = res[0], res[1], res[2], res[3]
                        if async_:
                            pend = (hu, hd)
                        if kern_meas is not None:
                            s_k = res[4]
                            c_k = res[5][dev["edge_slot"][0],
                                         dev["edge_e0"][0]]
                            if self.n_chain == 1:
                                b = jnp.float32(m.shape[0])
                                s_k, c_k = s_k / b, c_k / b
                            accs[0] = accs[0] + s_k
                            accs[1] = accs[1] + c_k
                    else:
                        # bit-exact emulation: split the launch at the
                        # exchange points into half-sweep windows of the
                        # same resident kernel, ppermute between them —
                        # one jitted graph, no host round-trip
                        s_l = c_l = None
                        if kern_meas is not None:
                            s_l = jnp.zeros((n_loc,), jnp.float32)
                            c_l = jnp.zeros(
                                (dev["edge_e0"].shape[1],), jnp.float32)
                        for h0, h1 in halo_exchange_segments(
                                ex_pts, 2 * L):
                            hu, hd, pend = swap(m, hu, hd, pend)
                            res = fused_shard_sweeps(
                                m, hu, hd, nbr, w, h, gain, off, rg,
                                co, masks[0], masks[1], betas_t, ns,
                                chain0, dev["cols"][0][0],
                                measured=kern_meas,
                                interpret=self.interpret,
                                half_offset=h0, n_half=h1 - h0, **kwc)
                            m, ns = res[0], res[1]
                            if kern_meas is not None:
                                s_l = s_l + res[2]
                                c_l = c_l + res[3][dev["edge_slot"][0],
                                                   dev["edge_e0"][0]]
                            if exact_stats and h1 % 2 == 0:
                                # post-sweep refresh for boundary edges
                                # — part of the bit-exact contract
                                ru, rd = exchange(m)
                                accs = sweep_stats(
                                    m, ru, rd, meas_t[h1 // 2 - 1],
                                    accs)
                        if kern_meas is not None:
                            if self.n_chain == 1:
                                b = jnp.float32(m.shape[0])
                                s_l, c_l = s_l / b, c_l / b
                            accs[0] = accs[0] + s_l
                            accs[1] = accs[1] + c_l
                else:
                    for s in range(L):
                        beta_t = betas_t[s]
                        if clamped and cv is not None:
                            m = jnp.where(cm, cv, m)
                        for c in (0, 1):
                            if 2 * s + c in ex_pts:
                                hu, hd, pend = swap(m, hu, hd, pend)
                            ns0 = ns
                            ns, u = nstep(ns, chain0)
                            m = halo_half_sweep(m, hu, hd, nbr, w, h,
                                                gain, off, rg, co,
                                                masks[c], beta_t, u)
                            if fstep is not None:
                                m = jnp.where(
                                    masks[c] & fstep(ns0, chain0), -m, m)
                        if accumulate:
                            if k1_exact:
                                # post-sweep refresh for boundary edges —
                                # part of the bit-exact contract
                                ru, rd = exchange(m)
                            else:
                                # relaxed policies read the (stale) halo
                                # the sweep itself saw
                                ru, rd = hu, hd
                            accs = sweep_stats(m, ru, rd, meas_t[s], accs)
                        elif hist_w is not None:
                            accs = sweep_stats(m, hu, hd, meas_t[s], accs)
                        elif collect:
                            outs.append(m)

                new_carry = (m, ns, hu, hd) + (pend if async_ else ()) \
                    + tuple(accs)
                return new_carry, (jnp.stack(outs) if collect else None)

            def segment(carry, xs_t):
                """One inter-exchange segment: swap once, then an inner
                scan over the uniform exchange-free sweeps — keeps the
                compiled body one-sweep-sized instead of unrolling."""
                m, ns, hu, hd = carry[0], carry[1], carry[2], carry[3]
                base = 4
                pend = ()
                if async_:
                    pend, base = (carry[4], carry[5]), 6
                accs = tuple(carry[base:])
                betas_t = xs_t[0]
                meas_t = xs_t[1] if len(xs_t) > 1 else None
                if clamped and cv is not None:
                    m = jnp.where(cm, cv, m)   # boundary sent post-clamp
                hu, hd, pend = swap(m, hu, hd, pend)

                def sweep_body(c2, xs_s):
                    m, ns = c2[0], c2[1]
                    accs2 = tuple(c2[2:])
                    beta_t = xs_s[0]
                    if clamped and cv is not None:
                        m = jnp.where(cm, cv, m)
                    for c in (0, 1):
                        ns0 = ns
                        ns, u = nstep(ns, chain0)
                        m = halo_half_sweep(m, hu, hd, nbr, w, h, gain,
                                            off, rg, co, masks[c],
                                            beta_t, u)
                        if fstep is not None:
                            m = jnp.where(
                                masks[c] & fstep(ns0, chain0), -m, m)
                    out = None
                    if accumulate or hist_w is not None:
                        accs2 = tuple(sweep_stats(m, hu, hd, xs_s[1],
                                                  accs2))
                    elif collect:
                        out = m
                    return (m, ns) + accs2, out

                xs_s = (betas_t,) if meas_t is None else (betas_t, meas_t)
                inner, outs = jax.lax.scan(sweep_body, (m, ns) + accs,
                                           xs_s)
                new_carry = (inner[0], inner[1], hu, hd) \
                    + (pend if async_ else ()) + tuple(inner[2:])
                return new_carry, outs

            chunk = L if (use_fused or seg_sweeps is None) else seg_sweeps
            body = launch if (use_fused or seg_sweeps is None) else segment
            betas_l = betas.reshape((S_total // chunk, chunk)
                                    + betas.shape[1:])
            xs = (betas_l,)
            if measured is not None:
                xs = (betas_l, measured.reshape(S_total // chunk, chunk))
            zh = jnp.zeros((m.shape[0], self.plan.halo), m.dtype)
            init = (m, ns, zh, zh)
            if async_:
                # prime the in-flight buffer with the initial boundary —
                # post-clamp, exactly what the first barrier exchange
                # would send — so the first consumption matches barrier
                m_pr = m
                if clamped and cv is not None:
                    m_pr = jnp.where(cm, cv, m)
                init = init + exchange(m_pr)
            if accumulate:
                init = init + (
                    jnp.zeros((n_loc,), jnp.float32),
                    jnp.zeros((dev["edge_e0"].shape[1],), jnp.float32))
            elif hist_w is not None:
                init = init + (jnp.zeros((2 ** hist_w,), jnp.float32),)
            final, traj = jax.lax.scan(body, init, xs)
            if collect and traj is not None:
                traj = traj.reshape((S_total,) + traj.shape[2:])
            base = 6 if async_ else 4
            return (final[0], final[1]) + final[base:], traj

        return run

    # ------------------------------------------------------------------
    # public impls (called inside the Session's jitted closures)
    # ------------------------------------------------------------------
    def sample(self, chip, m, ns, betas, cm=None, cv=None, collect=False):
        clamped = cm is not None
        has_cv = cv is not None
        run = self._local_sweeps(clamped, collect, False, None)

        def local(dev, chipp, m_p, ns_p, betas, *rest):
            kw = {}
            if clamped:
                kw["cm"] = rest[0][0]
                if has_cv:
                    kw["cv"] = rest[1][0]
            ns_l = ns_p[0] if self.noise == "lfsr" else ns_p
            (m_o, ns_o, *_), traj = run(dev, chipp, m_p[0], ns_l, betas,
                                        **kw)
            outs = [m_o[None], self._ns_out(ns_o)]
            if collect:
                outs.append(traj[None])
            return tuple(outs)

        betas = jnp.asarray(betas, jnp.float32)
        beta_spec = P() if betas.ndim == 1 else P(None, self._c)
        in_specs = [self._dev_specs(), self._chip_specs(),
                    P(self._r, self._c, None), self._ns_spec(), beta_spec]
        args = [self._dev, self._chip_parts(chip), self._m_parts(m),
                self._ns_parts(ns), betas]
        if clamped:
            in_specs.append(P(self._r, None))
            args.append(self._part_cols(cm))
            if has_cv:
                in_specs.append(P(self._r, self._c, None))
                args.append(self._m_parts(cv))
        out_specs = [P(self._r, self._c, None), self._ns_spec()]
        if collect:
            out_specs.append(P(self._r, None, self._c, None))
        out = self._shard_map(local, tuple(in_specs), tuple(out_specs))(
            *args)
        m_o = self._m_global(out[0])
        ns_o = self._ns_global(ns, out[1])
        traj = None
        if collect:
            t = jnp.moveaxis(out[2], 0, 2)          # (S, B, n_row, n_loc)
            t = t.reshape(t.shape[0], t.shape[1], -1)
            traj = jnp.take(t, self._inv_ids, axis=2)
        return m_o, ns_o, traj

    def stats(self, chip, m, ns, beta, n_sweeps, burn_in, cm=None, cv=None):
        clamped = cm is not None
        has_cv = cv is not None
        run = self._local_sweeps(clamped, False, True, None)
        betas = jnp.full((n_sweeps,), beta, jnp.float32)
        measured = (jnp.arange(n_sweeps) >= burn_in).astype(jnp.float32)
        denom = jnp.maximum(n_sweeps - burn_in, 1).astype(jnp.float32)

        def local(dev, chipp, m_p, ns_p, betas, measured, *rest):
            kw = {}
            if clamped:
                kw["cm"] = rest[0][0]
                if has_cv:
                    kw["cv"] = rest[1][0]
            ns_l = ns_p[0] if self.noise == "lfsr" else ns_p
            (m_o, ns_o, s_acc, c_acc), _ = run(dev, chipp, m_p[0], ns_l,
                                               betas, measured, **kw)
            if self.n_chain > 1:
                s_acc = jax.lax.psum(s_acc, self._chain_name)
                c_acc = jax.lax.psum(c_acc, self._chain_name)
            return m_o[None], self._ns_out(ns_o), s_acc[None], c_acc[None]

        in_specs = [self._dev_specs(), self._chip_specs(),
                    P(self._r, self._c, None), self._ns_spec(), P(), P()]
        args = [self._dev, self._chip_parts(chip), self._m_parts(m),
                self._ns_parts(ns), betas, measured]
        if clamped:
            in_specs.append(P(self._r, None))
            args.append(self._part_cols(cm))
            if has_cv:
                in_specs.append(P(self._r, self._c, None))
                args.append(self._m_parts(cv))
        out_specs = (P(self._r, self._c, None), self._ns_spec(),
                     P(self._r, None), P(self._r, None))
        m_o, ns_o, s_p, c_p = self._shard_map(
            local, tuple(in_specs), out_specs)(*args)
        scale = denom if self.n_chain == 1 else denom * self.chains
        s = jnp.take(s_p.reshape(-1), self._inv_ids) / scale
        c = jnp.take(c_p.reshape(-1), self._edge_inv) / scale
        return s, c, self._m_global(m_o), self._ns_global(ns, ns_o)

    def visible_hist(self, chip, m, ns, betas, burn_in, visible_idx,
                     cm=None, cv=None):
        clamped = cm is not None
        has_cv = cv is not None
        visible_idx = np.asarray(visible_idx)
        nv = int(visible_idx.shape[0])
        p = self.plan
        vi = np.zeros((p.n_shards, nv), np.int32)
        vw = np.zeros((p.n_shards, nv), np.int32)
        owner = np.searchsorted(p.node_starts[1:], visible_idx,
                                side="right")
        for k, (v, d) in enumerate(zip(visible_idx, owner)):
            vi[d, k] = v - p.node_starts[d]
            vw[d, k] = 2 ** k
        vi_j, vw_j = jnp.asarray(vi), jnp.asarray(vw)
        run = self._local_sweeps(clamped, False, False, nv)
        betas = jnp.asarray(betas, jnp.float32)
        n_sweeps = betas.shape[0]
        measured = (jnp.arange(n_sweeps) >= burn_in).astype(jnp.float32)

        def local(dev, chipp, m_p, ns_p, betas, measured, vi_p, vw_p,
                  *rest):
            kw = {}
            if clamped:
                kw["cm"] = rest[0][0]
                if has_cv:
                    kw["cv"] = rest[1][0]
            ns_l = ns_p[0] if self.noise == "lfsr" else ns_p
            (m_o, ns_o, hist), _ = run(dev, chipp, m_p[0], ns_l, betas,
                                       measured, vis_idx=vi_p[0],
                                       vis_w=vw_p[0], **kw)
            if self.n_chain > 1:
                hist = jax.lax.psum(hist, self._chain_name)
            return m_o[None], self._ns_out(ns_o), hist

        beta_spec = P() if betas.ndim == 1 else P(None, self._c)
        in_specs = [self._dev_specs(), self._chip_specs(),
                    P(self._r, self._c, None), self._ns_spec(), beta_spec,
                    P(), P(self._r, None), P(self._r, None)]
        args = [self._dev, self._chip_parts(chip), self._m_parts(m),
                self._ns_parts(ns), betas, measured, vi_j, vw_j]
        if clamped:
            in_specs.append(P(self._r, None))
            args.append(self._part_cols(cm))
            if has_cv:
                in_specs.append(P(self._r, self._c, None))
                args.append(self._m_parts(cv))
        out_specs = (P(self._r, self._c, None), self._ns_spec(), P())
        m_o, ns_o, hist = self._shard_map(
            local, tuple(in_specs), out_specs)(*args)
        return hist, self._m_global(m_o), self._ns_global(ns, ns_o)

    # -- small helpers ---------------------------------------------------
    def _part_cols(self, x):
        """(N,) node vector -> (n_shards, n_loc)."""
        return jnp.take(x, self._part_ids, axis=0)

    def _ns_out(self, ns_local):
        return ns_local[None] if self.noise == "lfsr" else ns_local


# ---------------------------------------------------------------------------
# Pod-scale SK lattices (SoA instance generator + Session-backed anneal)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LatticeSpec:
    cell_rows: int
    cell_cols: int
    k: int = 4
    beta: float = 1.0
    chains: int = 1   # Gibbs replicas per device tile: couplings are read
                      # from HBM once per half-sweep and serve all chains
                      # (arithmetic intensity x chains — §Perf pbit cell)

    @property
    def n_spins(self) -> int:
        return self.cell_rows * self.cell_cols * 2 * self.k


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LatticeChip:
    """SK-lattice couplings + neuron params, structure-of-arrays (O(N)).

    This is the *instance description*; `lattice_to_chip` converts it
    into the shared `EffectiveChip` slot layout the backends sample."""
    W_vh: jax.Array
    W_hv: jax.Array
    Wv_dn: jax.Array
    Wv_up: jax.Array
    Wh_rt: jax.Array
    Wh_lt: jax.Array
    h_v: jax.Array
    h_h: jax.Array
    gain_v: jax.Array
    gain_h: jax.Array
    off_v: jax.Array
    off_h: jax.Array

    def tree_flatten(self):
        f = dataclasses.fields(self)
        return tuple(getattr(self, x.name) for x in f), None

    @classmethod
    def tree_unflatten(cls, aux, ch):
        return cls(*ch)


def make_sk_lattice(spec: LatticeSpec, key: jax.Array,
                    hw: HardwareConfig | None = None,
                    dtype=jnp.float32) -> LatticeChip:
    """Random SK-style lattice instance with per-site mismatch baked in.

    Pure function of (spec, key) — under pjit each device materializes only
    its own shard (random bits are generated sharded).
    """
    hw = hw or HardwareConfig()
    R, C, k = spec.cell_rows, spec.cell_cols, spec.k
    ks = jax.random.split(key, 12)

    def g(i, shape, scale=1.0):
        return scale * jax.random.normal(ks[i], shape, dtype)

    W_cell = g(0, (R, C, k, k), 0.8)                      # shared edge DAC
    mis = lambda i, shape: 1.0 + hw.sigma_edge_gain * g(i, shape)
    Wv = g(1, (R, C, k), 0.8)
    Wh = g(2, (R, C, k), 0.8)
    row = jnp.arange(R)[:, None, None]
    col = jnp.arange(C)[None, :, None]
    # no couplers past the lattice edge
    Wv = Wv * (row < R - 1)
    Wh = Wh * (col < C - 1)
    return LatticeChip(
        W_vh=W_cell * mis(3, (R, C, k, k)),
        W_hv=jnp.swapaxes(W_cell, -1, -2) * mis(4, (R, C, k, k)),
        Wv_dn=Wv * (1.0 + hw.sigma_edge_gain * g(5, (R, C, k))),
        Wv_up=Wv * (1.0 + hw.sigma_edge_gain * g(6, (R, C, k))),
        Wh_rt=Wh * (1.0 + hw.sigma_edge_gain * g(7, (R, C, k))),
        Wh_lt=Wh * (1.0 + hw.sigma_edge_gain * g(8, (R, C, k))),
        h_v=jnp.zeros((R, C, k), dtype),
        h_h=jnp.zeros((R, C, k), dtype),
        gain_v=1.0 + hw.sigma_tanh_gain * g(9, (R, C, k)),
        gain_h=1.0 + hw.sigma_tanh_gain * g(10, (R, C, k)),
        off_v=hw.sigma_tanh_offset * 0.01 * g(11, (R, C, k)),
        off_h=jnp.zeros((R, C, k), dtype),
    )


def lattice_to_chip(spec: LatticeSpec, lat: LatticeChip,
                    graph: ChimeraGraph | None = None,
                    tables=None) -> EffectiveChip:
    """SoA lattice arrays -> the shared `EffectiveChip` slot layout.

    Directional: ``nbr_w[d, i] = W[i, nbr_idx[d, i]]`` (current INTO node
    i), so the converted chip samples the identical physics as the old
    SoA update loop — tests/test_lattice.py checks it against the dense
    reconstruction bit for bit.  O(D·N); no dense matrix anywhere.  The
    lattice's dtype carries through (dryrun's --pbit-dtype knob).
    """
    g = graph if graph is not None else make_chimera(
        spec.cell_rows, spec.cell_cols, spec.k)
    if tables is None:
        nbr_idx, _ = g.neighbor_table()
        slot_ij, slot_ji = g.edge_slots(nbr_idx)
    else:
        nbr_idx, slot_ij, slot_ji = tables
    dtype = lat.W_vh.dtype
    r_, c_, s_, k_ = g.node_r, g.node_c, g.node_side, g.node_k
    h = jnp.where(s_ == 0, lat.h_v[r_, c_, k_], lat.h_h[r_, c_, k_])
    gain = jnp.where(s_ == 0, lat.gain_v[r_, c_, k_], lat.gain_h[r_, c_, k_])
    off = jnp.where(s_ == 0, lat.off_v[r_, c_, k_], lat.off_h[r_, c_, k_])

    e0, e1 = g.edges[:, 0], g.edges[:, 1]
    r0, c0, k0 = r_[e0], c_[e0], k_[e0]
    k1 = k_[e1]
    incell = (r_[e1] == r0) & (c_[e1] == c0)
    vert = (s_[e0] == 0) & (s_[e1] == 0)
    # current INTO e0 from e1 / INTO e1 from e0 (see tests/test_lattice.py
    # for the dense index conventions these reproduce)
    w_in0 = jnp.where(
        incell, lat.W_vh[r0, c0, k0, k1],
        jnp.where(vert, lat.Wv_up[r0, c0, k0], lat.Wh_lt[r0, c0, k0]))
    w_in1 = jnp.where(
        incell, lat.W_hv[r0, c0, k1, k0],
        jnp.where(vert, lat.Wv_dn[r0, c0, k0], lat.Wh_rt[r0, c0, k0]))
    D = nbr_idx.shape[0]
    nbr_w = (jnp.zeros((D, g.n_nodes), dtype)
             .at[slot_ij, e0].set(w_in0)
             .at[slot_ji, e1].set(w_in1))
    ones = jnp.ones((g.n_nodes,), dtype)
    return EffectiveChip(
        W=None, h=h.astype(dtype), tanh_gain=gain.astype(dtype),
        tanh_offset=off.astype(dtype), rand_gain=ones,
        comp_offset=0.0 * ones, nbr_idx=jnp.asarray(nbr_idx, jnp.int32),
        nbr_w=nbr_w)


def sparse_energy(chip: EffectiveChip, m: jax.Array) -> jax.Array:
    """Symmetrized Ising energy per chain from the slot layout, O(B·N·D):
    E = -1/2 Σ_i m_i Σ_j W_ij m_j - Σ_i h_i m_i (directional W averaged
    over its two directions, exactly the old `lattice_energy`)."""
    I = sparse_neuron_input(m, chip.nbr_idx, chip.nbr_w,
                            jnp.float32(0.0))
    return -0.5 * jnp.sum(m * I, axis=1) - m @ chip.h


def make_lattice_anneal(
    spec: LatticeSpec,
    mesh: Mesh | None,
    *,
    row_axes: tuple[str, ...] = ("data",),
    col_axes: tuple[str, ...] = ("model",),
    n_sweeps: int = 100,
    record_every: int = 10,
):
    """Build the (optionally mesh-sharded) annealing step over the shared
    engine: cell rows partition over ``row_axes`` with ppermute halo
    exchange, exactly like every other sharded `api.Session` workload
    (the old private SoA update loop is retired; ``col_axes`` is accepted
    for signature compatibility — the spatial cut is 1-D over cell rows).

    Returns jitted run(lattice_chip, key, betas) ->
    (final_m (chains, N), energies (n_sweeps // record_every,)).
    """
    from repro import api

    if n_sweeps % record_every:
        raise ValueError(f"n_sweeps={n_sweeps} must be a multiple of "
                         f"record_every={record_every}")
    del col_axes
    g = make_chimera(spec.cell_rows, spec.cell_cols, spec.k)
    nbr_idx, _ = g.neighbor_table()
    tables = (nbr_idx, *g.edge_slots(nbr_idx))
    from repro.core.hardware import sample_mismatch_sparse
    sp = api.SamplerSpec(
        graph=g, hw=HardwareConfig.ideal(),
        mismatch=sample_mismatch_sparse(jax.random.PRNGKey(0), g.n_nodes,
                                        nbr_idx.shape[0],
                                        HardwareConfig.ideal()),
        noise="counter", backend="sparse", chains=spec.chains,
        beta=spec.beta, mesh=mesh,
        partition=(api.Partition(rows=row_axes) if mesh is not None
                   else None))
    session = api.Session(sp)
    n_rec = n_sweeps // record_every

    from repro.core import pbit

    def run(lat: LatticeChip, key: jax.Array, betas: jax.Array):
        chip = lattice_to_chip(spec, lat, g, tables)
        k1, k2 = jax.random.split(key)
        m = pbit.random_spins(k1, spec.chains, g.n_nodes)
        ns = session.noise_state(k2)
        segs = betas[:n_rec * record_every].reshape(n_rec, record_every)

        def seg(carry, b):
            m, ns = carry
            m, ns, _ = session.sample(chip, m, ns, b)
            return (m, ns), sparse_energy(chip, m).mean()

        (m, ns), energies = jax.lax.scan(seg, (m, ns), segs)
        return m, energies

    return jax.jit(run)


def lattice_input_sharding(mesh: Mesh, row_axes=("data",),
                           col_axes=("model",)):
    return NamedSharding(auto_axes(mesh), P(row_axes, col_axes))
