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
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import lfsr as lfsr_mod
from repro.core.chimera import ChimeraGraph
from repro.core.hardware import EffectiveChip
from repro.kernels.ref import (
    field_decision_update,
    halo_exchange_segments,
    sparse_neuron_input,
)
from repro.kernels.shard_sweep import (
    fused_shard_sweeps,
    halo_exchange,
    halo_half_sweep,
)
from repro.launch.mesh import auto_axes
from repro.runtime.spans import span


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


def _band_starts(graph: ChimeraGraph, n_shards: int):
    """(first cell row, first node id) of each row band, n_shards + 1 each.

    Cell rows split as evenly as they go, the first bands taking one more;
    nodes are numbered by (r, c, side, k), so each band owns a contiguous
    id range regardless of cell masking."""
    base, rem = divmod(graph.rows, n_shards)
    counts = [base + (d < rem) for d in range(n_shards)]
    r_start = np.concatenate([[0], np.cumsum(counts)])
    node_starts = np.searchsorted(np.asarray(graph.node_r),
                                  r_start).astype(np.int64)
    return r_start, node_starts


def partition_size(mesh, axes) -> int:
    """Devices along ``axes`` of ``mesh`` (1 for no axes)."""
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))


def band_resident(graph: ChimeraGraph, n_row: int) -> bool:
    """Are the row bands the even split of the node ids?

    True when every band owns exactly N / n_row nodes: then band d is
    the d-th equal slice of the global node axis, so a global (..., N)
    array split evenly over the rows axis already *is* the band layout —
    spins, programs, chip instances and noise live in their bands with no
    gather, and the sharded engine moves no data between layouts.  Masked
    cells or rows that do not divide leave uneven bands, which keep the
    gather path (chip-scale graphs)."""
    if n_row < 1 or n_row > graph.rows or graph.n_nodes % n_row:
        return False
    _, starts = _band_starts(graph, n_row)
    return bool(np.all(np.diff(starts) == graph.n_nodes // n_row))


def band_sharding(mesh: Mesh, partition, ndim: int, node_axis: int,
                  chain_axis: int | None = None) -> NamedSharding:
    """The sharding that splits ``node_axis`` of an ``ndim`` array over
    the partition's rows axes (and ``chain_axis`` over its chains axes):
    on a band-resident graph, each device holds exactly its band."""
    spec = [None] * ndim
    spec[node_axis] = tuple(partition.rows_axes) or None
    if chain_axis is not None:
        spec[chain_axis] = tuple(partition.chain_axes) or None
    return NamedSharding(auto_axes(mesh), P(*spec))


def mismatch_shardings(mesh: Mesh, partition):
    """`band_sharding` of every leaf of a slot-layout chip instance."""
    from repro.core.hardware import SparseMismatch

    def nodes(ndim, axis):
        return band_sharding(mesh, partition, ndim, axis)

    return SparseMismatch(
        dac_bit_j=nodes(3, 1), dac_bit_h=nodes(2, 0), edge_gain=nodes(2, 1),
        tanh_gain=nodes(1, 0), tanh_offset=nodes(1, 0),
        rand_gain=nodes(1, 0), comp_offset=nodes(1, 0), leak=nodes(2, 1))


def _plan_row_partition(graph: ChimeraGraph, n_shards: int,
                        with_lfsr: bool = False) -> RowPartition:
    if n_shards < 1 or n_shards > graph.rows:
        raise ValueError(
            f"cannot cut {graph.rows} cell rows into {n_shards} bands")
    r_start, node_starts = _band_starts(graph, n_shards)
    node_r = np.asarray(graph.node_r)
    node_side = np.asarray(graph.node_side)
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
    The public impls (`sample` / `stats` / `visible_hist`) keep the
    array contracts of the single-device engine (global (B, N) spins,
    global noise state) — the Session's closures call them unchanged, so
    every workload (CD, annealing, tempering, Max-Cut) shards without
    modification.  Each impl takes the engine's static tables (`tables`)
    as its first argument: the Session passes them into its jitted
    closures as sharded operands, so no lattice-sized table is ever
    baked into a compiled program as a constant.

    On a `band_resident` graph (every band the even slice of the node
    axis) the global arrays are the band layout: spins, programs, chip
    instances and noise go into `shard_map` as they are, split over the
    rows axis, and come out the same way — no gather, no copy.  Uneven
    bands (masked cells, rows that do not divide) are gathered into a
    padded (n_shards, ..., n_loc) layout and back.

    The `api.Sync` policy is compiled into a *launch loop*: the sweep
    schedule is cut into launches of ``sweeps_per_launch`` sweeps, the
    scan runs over launches, and the L sweeps inside a launch unroll with
    the policy's exchange points placed statically — no collective ever
    sits behind a traced conditional.  Halo buffers (and, in async mode,
    the in-flight double buffer) thread through the scan carry, so
    between exchange points every band samples against a *stale* halo —
    the deterministic, seeded emulation of the chip's clockless fabric.
    ``Sync()`` (barrier, halo_every=1) reproduces the single-device
    trajectory bit for bit.  On backend "fused_sparse" (counter noise)
    the launch runs in the sweep-resident Pallas kernel
    (`kernels/shard_sweep.py::fused_shard_sweeps`), one call per window
    between exchange points, with a ppermute between windows.
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
        self.partition = partition
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
        self.n_row = partition_size(mesh, self.rows_axes)
        self.n_chain = partition_size(mesh, self.chain_axes)
        if chains % self.n_chain:
            raise ValueError(f"chains={chains} not divisible by the "
                             f"chain-axis size {self.n_chain}")
        self.b_loc = chains // self.n_chain
        self.plan = plan_row_partition(graph, self.n_row,
                                       with_lfsr=(noise == "lfsr"))
        self.band_resident = band_resident(graph, self.n_row)
        # a band of whole cell rows sweeps by shifted views, no gathers
        self.grid = self.band_resident and not graph.masked_cells
        self._row_name = (self.rows_axes[0] if len(self.rows_axes) == 1
                          else (tuple(self.rows_axes) or None))
        self._chain_name = (self.chain_axes[0] if len(self.chain_axes) == 1
                            else (tuple(self.chain_axes) or None))
        # P-spec dimension entries (None = replicated over that dim)
        self._r = tuple(self.rows_axes) if self.rows_axes else None
        self._c = tuple(self.chain_axes) if self.chain_axes else None
        with span("dist.place"):
            self.tables = self._place_tables()

    # -- static tables (numpy plan -> sharded device arrays) -------------
    @staticmethod
    def _put(x: np.ndarray, sharding: NamedSharding) -> jax.Array:
        """Place a host table; each device receives only its part."""
        return jax.device_put(x, sharding)

    def _place_tables(self) -> dict:
        """Every static table the impls read, each placed so that a
        device holds only what it reads: per-band tables split over the
        rows axis, global index tables (the uneven gathers, edge moments)
        split where they divide and replicated where they are small."""
        p, g = self.plan, self.graph

        def bands(x):
            return self._put(x, NamedSharding(
                self.mesh, P(self._r, *([None] * (x.ndim - 1)))))

        def flat(x):
            split = self.n_row > 1 and x.shape[0] % self.n_row == 0
            return self._put(x, NamedSharding(
                self.mesh, P(self._r if split else None)))

        t = {"nbr": bands(p.nbr_idx), "send_up": bands(p.send_up),
             "send_dn": bands(p.send_dn), "upd": bands(p.upd_masks),
             "cols": bands(p.part_ids.astype(np.uint32)),
             "edge_e0": bands(p.edge_e0), "edge_e1": bands(p.edge_e1),
             "edge_inv": flat(p.edge_inv)}
        if not self.band_resident:
            t["part_ids"], t["inv_ids"] = flat(p.part_ids), flat(p.inv_ids)
        if self.noise == "lfsr":
            t["lfsr_perm"] = bands(p.lfsr_perm)
            if not self.band_resident:
                t["cell_ids"] = flat(p.cell_ids)
                t["cell_inv"] = flat(p.cell_inv)
            f = self.faults
            if f is not None and f.lfsr_stuck:
                n_cells = g.n_nodes // 8
                s0 = np.zeros((n_cells,), np.uint32)
                s1 = np.zeros((n_cells,), np.uint32)
                for cell, m0, m1 in f.lfsr_stuck:
                    s0[int(cell)] |= np.uint32(m0)
                    s1[int(cell)] |= np.uint32(m1)
                t["lfsr_s0"] = bands(s0[p.cell_ids])
                t["lfsr_s1"] = bands(s1[p.cell_ids])
        if self._fused:
            # per-edge slot row into the kernel's (D, N_ext) correlation
            # scratch: edge q of band b lives at c_slots[edge_slot[b, q],
            # edge_e0[b, q]] (endpoint 0 is always local)
            es = np.zeros((p.n_shards, p.e_loc), np.int32)
            for b in range(p.n_shards):
                hit = p.nbr_idx[b][:, p.edge_e0[b]] == p.edge_e1[b][None, :]
                es[b] = np.argmax(hit, axis=0)
            t["edge_slot"] = bands(es)
        if self.grid:
            t["grid_slots"] = bands(self._grid_slots())
        if self.band_resident:
            t.update(self._program_tables())
        return t

    def _program_tables(self) -> dict:
        """Band programming: each band's incident edges (its own, whose
        endpoint 0 it holds, then those coming up from the band above),
        and for each local slot the position of its edge in that list
        (-1 on padding slots); the global neighbor table and its mask,
        split over the node axis, which programmed chips carry."""
        p, g = self.plan, self.graph
        nbr_idx, nbr_mask, slot_ij, slot_ji = g._slot_tables()
        D, n, n_loc = nbr_idx.shape[0], g.n_nodes, p.n_loc
        e0 = g.edges[:, 0].astype(np.int64)
        e1 = g.edges[:, 1].astype(np.int64)
        slot_edge = np.full((D, n), -1, np.int32)
        slot_edge.reshape(-1)[slot_ij * n + e0] = np.arange(e0.size)
        slot_edge.reshape(-1)[slot_ji * n + e1] = np.arange(e0.size)
        e_start = np.searchsorted(e0, p.node_starts)
        lists = []
        for d in range(p.n_shards):
            s, e = int(p.node_starts[d]), int(p.node_starts[d + 1])
            up = np.nonzero((e0 < s) & (e1 >= s) & (e1 < e))[0] \
                if d else np.zeros((0,), np.int64)
            lists.append(np.concatenate(
                [np.arange(e_start[d], e_start[d + 1]), up]))
        e_band = max(1, max(x.size for x in lists))
        edge_ids = np.zeros((p.n_shards, e_band), np.int32)
        local = np.full((p.n_shards, D, n_loc), -1, np.int32)
        for d, ids in enumerate(lists):
            edge_ids[d, :ids.size] = ids
            s = int(p.node_starts[d])
            se = slot_edge[:, s:s + n_loc]
            own = (se >= e_start[d]) & (se < e_start[d + 1])
            pos = np.where(own, se - e_start[d], -1)
            n_own = int(e_start[d + 1] - e_start[d])
            far = (se >= 0) & ~own
            pos[far] = n_own + np.searchsorted(ids[n_own:], se[far])
            local[d] = pos
        self._edge_ids = edge_ids
        nodes = band_sharding(self.mesh, self.partition, 2, 1)
        band = NamedSharding(self.mesh, P(self._r, None, None))
        return {"edge_ids": self._put(edge_ids, NamedSharding(
                    self.mesh, P(self._r, None))),
                "slot_edge": self._put(local, band),
                "nbr_g": self._put(nbr_idx, nodes),
                "nbr_ok": self._put(nbr_mask, nodes)}

    # -- half-sweeps of a full band of cells, without gathers ------------
    def _grid_slots(self) -> np.ndarray:
        """(n_shards, K + 2, n_loc) int8: for each local spin, the slot of
        its neighbour table that holds, in ascending neighbour order, the
        spin before its cell (the vertical spin above, the horizontal spin
        to the left), its K in-cell partners, and the spin after its cell
        (below, to the right); -1 where that neighbour does not exist (the
        lattice's edge).  These are all of a spin's neighbours, in the
        table's own ascending order, so a term's slot counts the terms
        before it that exist."""
        g, p = self.graph, self.plan
        vert = g.node_side == 0
        first = np.where(vert, g.node_r == 0, g.node_c == 0)
        last = np.where(vert, g.node_r == g.rows - 1,
                        g.node_c == g.cols - 1)
        exists = np.ones((g.k + 2, g.n_nodes), bool)
        exists[0], exists[-1] = ~first, ~last
        out = np.where(exists, np.cumsum(exists, axis=0) - 1, -1)
        return np.ascontiguousarray(
            out.astype(np.int8).reshape(g.k + 2, p.n_shards, p.n_loc)
            .transpose(1, 0, 2))

    def _grid_weights(self, slots, w):
        """The band's slot couplings in the order of `_grid_slots`: a
        select per slot, so every value is the program's own (0 where the
        neighbour does not exist)."""
        out = []
        for t in range(slots.shape[0]):
            wt = jnp.zeros(w.shape[1:], w.dtype)
            for d in range(w.shape[0]):
                wt = jnp.where(slots[t] == d, w[d], wt)
            out.append(wt)
        return jnp.stack(out)

    def _to_grid(self, x):
        """(..., n_loc) band vector -> (..., rows, side, k, cols)."""
        g = self.graph
        R = self.plan.n_loc // (2 * g.k * g.cols)
        y = x.reshape(x.shape[:-1] + (R, g.cols, 2, g.k))
        return jnp.moveaxis(y, -3, -1)

    def _from_grid(self, y):
        x = jnp.moveaxis(y, -1, -3)
        return x.reshape(x.shape[:-4] + (self.plan.n_loc,))

    def _grid_exchange(self, m):
        """`halo_exchange` on the grid layout: a band's first and last
        rows of vertical spins, (chains, k, cols), to its row neighbours;
        zeros past the lattice's edge."""
        up_src, dn_src = m[:, -1, 0], m[:, 0, 0]
        if self.n_row <= 1:
            return jnp.zeros_like(up_src), jnp.zeros_like(dn_src)
        n = self.n_row
        return (jax.lax.ppermute(up_src, self._row_name,
                                 [(i, i + 1) for i in range(n - 1)]),
                jax.lax.ppermute(dn_src, self._row_name,
                                 [(i + 1, i) for i in range(n - 1)]))

    def _grid_half_sweep(self, m, hu, hd, ws, h, gain, off, rg, co, mask,
                         beta, u):
        """`halo_half_sweep` on the grid layout (chains, rows, side, k,
        cols): each term of the field is a shift of the band's spins —
        the vertical spin above or below (across the halo at the band's
        edge), the horizontal spin to the left or right, the in-cell
        partners — so nothing is gathered and the columns fill the
        lanes.  The terms are summed in ascending neighbour order from
        zero, as `sparse_neuron_input` sums its slots (an absent
        neighbour adds an exact zero), so the field is the same bit for
        bit; the decision is `field_decision_update`.

        The band goes in chunks of cell rows (`GRID_CHUNK_BYTES` of
        spins each), updated in place: a half-sweep reads only the other
        colour, so no chunk reads a spin another chunk writes, and the
        temporaries are chunk-sized."""
        K = self.graph.k
        B, R, _, _, C = m.shape
        step = max(1, min(R, GRID_CHUNK_BYTES // (4 * B * 2 * K * C)))
        for r0 in range(0, R, step):
            r1 = min(R, r0 + step)
            v, hz = m[:, r0:r1, 0], m[:, r0:r1, 1]     # (B, rows, K, C)
            above = hu[:, None] if r0 == 0 else m[:, r0 - 1:r0, 0]
            below = hd[:, None] if r1 == R else m[:, r1:r1 + 1, 0]
            zc = jnp.zeros(hz.shape[:-1] + (1,), m.dtype)
            before = [jnp.concatenate([above, v[:, :-1]], axis=1),
                      jnp.concatenate([zc, hz[..., :-1]], axis=-1)]
            after = [jnp.concatenate([v[:, 1:], below], axis=1),
                     jnp.concatenate([hz[..., 1:], zc], axis=-1)]
            partner = [hz, v]
            acc = []
            for sd in (0, 1):
                terms = ([before[sd]]
                         + [jnp.broadcast_to(partner[sd][:, :, j:j + 1],
                                             v.shape) for j in range(K)]
                         + [after[sd]])
                a = jnp.zeros(v.shape, jnp.float32)
                for t, x in enumerate(terms):
                    a = a + ws[t][r0:r1, sd][None] * x
                acc.append(a)
            I = jnp.stack(acc, axis=2) + h[r0:r1]
            new = field_decision_update(
                m[:, r0:r1], I, gain[r0:r1], off[r0:r1], rg[r0:r1],
                co[r0:r1], mask[r0:r1], beta, u[:, r0:r1])
            m = jax.lax.dynamic_update_slice_in_dim(m, new, r0, axis=1)
        return m

    # -- spec helpers ----------------------------------------------------
    def _dev_specs(self, dev):
        """shard_map specs of the per-band tables in ``dev``."""
        return {k: P(self._r, *([None] * (dev[k].ndim - 1)))
                for k in _BAND_TABLES if k in dev}

    def _chip_specs(self):
        if self.band_resident:
            return {"w": P(None, self._r),
                    **{k: P(self._r) for k in ("h", "gain", "off", "rg",
                                               "co")}}
        return {"w": P(self._r, None, None),
                **{k: P(self._r, None)
                   for k in ("h", "gain", "off", "rg", "co")}}

    def _m_spec(self):
        """Spins: global (B, N) split (chains, rows) on a band-resident
        graph; else the (n_shards, B, n_loc) parts."""
        if self.band_resident:
            return P(self._c, self._r)
        return P(self._r, self._c, None)

    def _node_spec(self):
        """A (N,) node vector (clamp masks, spin moments)."""
        return P(self._r) if self.band_resident else P(self._r, None)

    def _shard_map(self, fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    @property
    def spin_sharding(self) -> NamedSharding | None:
        """Where band-resident spins live: (B, N) split over (chains,
        rows).  None on uneven bands."""
        if not self.band_resident:
            return None
        return NamedSharding(self.mesh, self._m_spec())

    @property
    def noise_sharding(self) -> NamedSharding | None:
        """Where band-resident noise state lives: the counter state is
        two words, replicated; the LFSR state (B, n_cells) is split like
        the spins (a band's cells are the even slice of the cells)."""
        if not self.band_resident:
            return None
        if self.noise == "lfsr":
            return NamedSharding(self.mesh, P(self._c, self._r))
        return NamedSharding(self.mesh, P())

    def holds(self, m) -> bool:
        """Is ``m`` a concrete array already in the band layout (which
        the sharded sample consumes in place)?"""
        s = self.spin_sharding
        return (s is not None and isinstance(m, jax.Array)
                and not isinstance(m, jax.core.Tracer) and m.sharding == s)

    # -- global <-> parts layout ----------------------------------------
    def _blk(self, x):
        """A device's block inside shard_map: band-resident arrays arrive
        as the block itself, parts carry a leading shard axis of 1."""
        return x if self.band_resident else x[0]

    def _unblk(self, x):
        return x if self.band_resident else x[None]

    def _chip_parts(self, dev, chip: EffectiveChip) -> dict:
        """The chip in the engine's layout: its own arrays on a
        band-resident graph (each device reads its band of them), else
        per-device (n_shards, ...) gathers on the static index tables.

        Pure jnp, so this runs *inside* the Session's jitted closures
        with the chip as a traced operand — which is what threads runtime
        weight streaming through the sharded engine for free: a
        `Program` programmed in-jit (`Session.sample_program`) flows
        through here into the shard_map'd sweep as sharded input, and a
        swapped program is a new operand value, never a recompile.
        """
        if chip.nbr_w is None or chip.nbr_idx is None:
            raise ValueError(
                "sharded execution needs a chip carrying the slot layout "
                "(program through the Session — e.g. Session.make_program "
                "+ sample_program — or hardware.attach_sparse)")
        parts = {"w": chip.nbr_w, "h": chip.h, "gain": chip.tanh_gain,
                 "off": chip.tanh_offset, "rg": chip.rand_gain,
                 "co": chip.comp_offset}
        if self.band_resident:
            return parts
        ids = dev["part_ids"]
        return {k: (jnp.moveaxis(v[:, ids], 1, 0) if k == "w" else v[ids])
                for k, v in parts.items()}

    def _m_parts(self, dev, m: jax.Array) -> jax.Array:
        if self.band_resident:
            return m
        return jnp.moveaxis(jnp.take(m, dev["part_ids"], axis=1), 1, 0)

    def _m_global(self, dev, parts: jax.Array) -> jax.Array:
        if self.band_resident:
            return parts
        flat = jnp.moveaxis(parts, 0, 1).reshape(parts.shape[1], -1)
        return jnp.take(flat, dev["inv_ids"], axis=1)

    def _ns_parts(self, dev, ns: jax.Array):
        if self.noise == "lfsr" and not self.band_resident:
            return jnp.moveaxis(jnp.take(ns, dev["cell_ids"], axis=1), 1, 0)
        return ns  # counter: replicated uint32[2]; band-resident lfsr

    def _ns_global(self, dev, ns, parts):
        if self.noise == "lfsr" and not self.band_resident:
            flat = jnp.moveaxis(parts, 0, 1).reshape(parts.shape[1], -1)
            return jnp.take(flat, dev["cell_inv"], axis=1)
        return parts

    def _ns_spec(self):
        if self.noise != "lfsr":
            return P()
        return self._m_spec()

    def _ns_local(self, ns_p):
        return self._blk(ns_p) if self.noise == "lfsr" else ns_p

    def _ns_out(self, ns_local):
        return self._unblk(ns_local) if self.noise == "lfsr" else ns_local

    def _part_cols(self, dev, x):
        """(N,) node vector -> the engine's layout."""
        if self.band_resident:
            return x
        return jnp.take(x, dev["part_ids"], axis=0)

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
        interior compute.  Three loop shapes, picked at compile:

          * fused windows — backend "fused_sparse" (sample and stats
            paths; collect/hist fall back to the scan shapes): each
            launch splits at its exchange points
            (`halo_exchange_segments`) into half-sweep windows, each one
            `fused_shard_sweeps` Pallas call (`half_offset`/`n_half`)
            after a swap, all inside one jitted graph.  Exchange at the
            launch boundary only is the one-window case (docs/kernels.md,
            "Fused exchange windows").
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
        # plain sampling of a band of whole cell rows runs on the band's
        # grid layout (see `_grid_half_sweep`); the rest gathers
        f = self.faults
        grid = (self.grid and not (use_fused or clamped or collect
                                   or accumulate or hist_w is not None)
                and self.noise == "counter"
                and (f is None or f.flip_prob <= 0.0))
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
                if grid:
                    return self._grid_exchange(m)
                return halo_exchange(m, send_up, send_dn, self._row_name,
                                     self.n_row)

            nstep = self._noise_step(dev)
            fstep = self._flip_step(dev)
            w, h = chip["w"], chip["h"]
            gain, off = chip["gain"], chip["off"]
            rg, co = chip["rg"], chip["co"]
            chain0 = self._chain_offset()
            masks = [dev["upd"][0, c] for c in (0, 1)]
            if clamped:
                masks = [mk & ~cm for mk in masks]
            if grid:
                # the band as (chains, rows, side, k, cols): cols last, so
                # every array is lane-dense and each neighbour a shift
                ws = self._to_grid(self._grid_weights(
                    dev["grid_slots"][0], w))
                h, gain, off, rg, co = (self._to_grid(x) for x in
                                        (h, gain, off, rg, co))
                masks = [self._to_grid(mk) for mk in masks]
                cols = self._to_grid(dev["cols"][0])
                rows = (chain0 + jnp.arange(self.b_loc, dtype=jnp.uint32)
                        ).reshape(-1, 1, 1, 1, 1)
                m = self._to_grid(m)

            S_total = int(betas.shape[0])
            if S_total % L:
                raise ValueError(
                    f"this Session's sync policy fuses sweeps_per_launch="
                    f"{L} sweeps per launch, which must divide the "
                    f"schedule length (got {S_total} sweeps); pad the "
                    f"schedule or change the Sync policy")

            def half(m, hu, hd, ns, mask, beta):
                """One half-sweep of the scan paths: (m', noise state')."""
                if grid:
                    u = lfsr_mod.counter_uniform(ns[0], ns[1], rows, cols)
                    beta = jnp.asarray(beta, jnp.float32)
                    if beta.ndim == 1:
                        beta = beta.reshape(-1, 1, 1, 1, 1)
                    m = self._grid_half_sweep(m, hu, hd, ws, h, gain, off,
                                              rg, co, mask, beta, u)
                    return m, ns + jnp.array([0, 1], jnp.uint32)
                ns0 = ns
                ns, u = nstep(ns, chain0)
                m = halo_half_sweep(m, hu, hd, nbr, w, h, gain, off, rg, co,
                                    mask, beta, u)
                if fstep is not None:
                    m = jnp.where(mask & fstep(ns0, chain0), -m, m)
                return m, ns

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
                """Fused kernel windows, or L statically-unrolled sweeps
                (the odd-``halo_every`` non-fused shapes, incl. k=1)."""
                m, ns, hu, hd = carry[0], carry[1], carry[2], carry[3]
                base = 4
                pend = ()
                if async_:
                    pend, base = (carry[4], carry[5]), 6
                accs = list(carry[base:])
                betas_t = xs_t[0]
                meas_t = xs_t[1] if len(xs_t) > 1 else None
                outs = []

                if use_fused:
                    # the launch split at its exchange points into
                    # half-sweep windows of the resident kernel, a swap
                    # before each.  k=1 barrier (bit_exact) keeps the
                    # post-sweep stats refresh, so the kernel only
                    # sweeps; every other policy accumulates in-kernel.
                    kwc = {}
                    if clamped and cv is not None:
                        m = jnp.where(cm, cv, m)
                        kwc = dict(clamp_mask=cm, clamp_values=cv)
                    exact_stats = accumulate and k1_exact
                    kern_meas = meas_t \
                        if (accumulate and not exact_stats) else None
                    s_l = c_l = None
                    for h0, h1 in halo_exchange_segments(ex_pts, 2 * L):
                        hu, hd, pend = swap(m, hu, hd, pend)
                        res = fused_shard_sweeps(
                            m, hu, hd, nbr, w, h, gain, off, rg, co,
                            masks[0], masks[1], betas_t, ns, chain0,
                            dev["cols"][0][0], measured=kern_meas,
                            interpret=self.interpret, half_offset=h0,
                            n_half=h1 - h0, **kwc)
                        m, ns = res[0], res[1]
                        if kern_meas is not None:
                            # raw sums of the windows, added
                            c_w = res[3][dev["edge_slot"][0],
                                         dev["edge_e0"][0]]
                            s_l, c_l = ((res[2], c_w) if s_l is None
                                        else (s_l + res[2], c_l + c_w))
                        if exact_stats and h1 % 2 == 0:
                            # post-sweep refresh for boundary edges —
                            # part of the bit-exact contract
                            ru, rd = exchange(m)
                            accs = sweep_stats(m, ru, rd,
                                               meas_t[h1 // 2 - 1], accs)
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
                            m, ns = half(m, hu, hd, ns, masks[c], beta_t)
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
                        m, ns = half(m, hu, hd, ns, masks[c], beta_t)
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
            if grid:
                zh = jnp.zeros(m.shape[:1] + m.shape[3:], m.dtype)
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
            m_out = self._from_grid(final[0]) if grid else final[0]
            return (m_out, final[1]) + final[base:], traj

        return run

    # ------------------------------------------------------------------
    # public impls (called inside the Session's jitted closures)
    # ------------------------------------------------------------------
    def _band_dev(self, dev) -> dict:
        return {k: dev[k] for k in _BAND_TABLES if k in dev}

    def _clamp_args(self, dev, cm, cv, in_specs, args):
        if cm is not None:
            in_specs.append(self._node_spec())
            args.append(self._part_cols(dev, cm))
            if cv is not None:
                in_specs.append(self._m_spec())
                args.append(self._m_parts(dev, cv))

    def _clamp_kw(self, rest, clamped, has_cv) -> dict:
        kw = {}
        if clamped:
            kw["cm"] = self._blk(rest[0])
            if has_cv:
                kw["cv"] = self._blk(rest[1])
        return kw

    def sample(self, dev, chip, m, ns, betas, cm=None, cv=None,
               collect=False):
        clamped = cm is not None
        has_cv = cv is not None
        run = self._local_sweeps(clamped, collect, False, None)

        def local(bdev, chipp, m_p, ns_p, betas, *rest):
            (m_o, ns_o, *_), traj = run(
                bdev, {k: self._blk(v) for k, v in chipp.items()},
                self._blk(m_p), self._ns_local(ns_p), betas,
                **self._clamp_kw(rest, clamped, has_cv))
            outs = [self._unblk(m_o), self._ns_out(ns_o)]
            if collect:
                outs.append(self._unblk(traj))
            return tuple(outs)

        betas = jnp.asarray(betas, jnp.float32)
        beta_spec = P() if betas.ndim == 1 else P(None, self._c)
        bdev = self._band_dev(dev)
        in_specs = [self._dev_specs(bdev), self._chip_specs(),
                    self._m_spec(), self._ns_spec(), beta_spec]
        args = [bdev, self._chip_parts(dev, chip), self._m_parts(dev, m),
                self._ns_parts(dev, ns), betas]
        self._clamp_args(dev, cm, cv, in_specs, args)
        out_specs = [self._m_spec(), self._ns_spec()]
        if collect:
            out_specs.append(P(None, self._c, self._r) if self.band_resident
                             else P(self._r, None, self._c, None))
        out = self._shard_map(local, tuple(in_specs), tuple(out_specs))(
            *args)
        m_o = self._m_global(dev, out[0])
        ns_o = self._ns_global(dev, ns, out[1])
        traj = None
        if collect:
            traj = out[2]
            if not self.band_resident:
                t = jnp.moveaxis(traj, 0, 2)      # (S, B, n_row, n_loc)
                t = t.reshape(t.shape[0], t.shape[1], -1)
                traj = jnp.take(t, dev["inv_ids"], axis=2)
        return m_o, ns_o, traj

    def stats(self, dev, chip, m, ns, beta, n_sweeps, burn_in, cm=None,
              cv=None):
        clamped = cm is not None
        has_cv = cv is not None
        run = self._local_sweeps(clamped, False, True, None)
        betas = jnp.full((n_sweeps,), beta, jnp.float32)
        measured = (jnp.arange(n_sweeps) >= burn_in).astype(jnp.float32)
        denom = jnp.maximum(n_sweeps - burn_in, 1).astype(jnp.float32)

        def local(bdev, chipp, m_p, ns_p, betas, measured, *rest):
            (m_o, ns_o, s_acc, c_acc), _ = run(
                bdev, {k: self._blk(v) for k, v in chipp.items()},
                self._blk(m_p), self._ns_local(ns_p), betas, measured,
                **self._clamp_kw(rest, clamped, has_cv))
            if self.n_chain > 1:
                s_acc = jax.lax.psum(s_acc, self._chain_name)
                c_acc = jax.lax.psum(c_acc, self._chain_name)
            return (self._unblk(m_o), self._ns_out(ns_o),
                    self._unblk(s_acc), c_acc[None])

        bdev = self._band_dev(dev)
        in_specs = [self._dev_specs(bdev), self._chip_specs(),
                    self._m_spec(), self._ns_spec(), P(), P()]
        args = [bdev, self._chip_parts(dev, chip), self._m_parts(dev, m),
                self._ns_parts(dev, ns), betas, measured]
        self._clamp_args(dev, cm, cv, in_specs, args)
        out_specs = (self._m_spec(), self._ns_spec(), self._node_spec(),
                     P(self._r, None))
        m_o, ns_o, s_p, c_p = self._shard_map(
            local, tuple(in_specs), out_specs)(*args)
        scale = denom if self.n_chain == 1 else denom * self.chains
        s = s_p if self.band_resident else jnp.take(s_p.reshape(-1),
                                                     dev["inv_ids"])
        c = jnp.take(c_p.reshape(-1), dev["edge_inv"]) / scale
        return s / scale, c, self._m_global(dev, m_o), \
            self._ns_global(dev, ns, ns_o)

    def visible_hist(self, dev, chip, m, ns, betas, burn_in, visible_idx,
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

        def local(bdev, chipp, m_p, ns_p, betas, measured, vi_p, vw_p,
                  *rest):
            (m_o, ns_o, hist), _ = run(
                bdev, {k: self._blk(v) for k, v in chipp.items()},
                self._blk(m_p), self._ns_local(ns_p), betas, measured,
                vis_idx=vi_p[0], vis_w=vw_p[0],
                **self._clamp_kw(rest, clamped, has_cv))
            if self.n_chain > 1:
                hist = jax.lax.psum(hist, self._chain_name)
            return self._unblk(m_o), self._ns_out(ns_o), hist

        beta_spec = P() if betas.ndim == 1 else P(None, self._c)
        bdev = self._band_dev(dev)
        in_specs = [self._dev_specs(bdev), self._chip_specs(),
                    self._m_spec(), self._ns_spec(), beta_spec,
                    P(), P(self._r, None), P(self._r, None)]
        args = [bdev, self._chip_parts(dev, chip), self._m_parts(dev, m),
                self._ns_parts(dev, ns), betas, measured, vi_j, vw_j]
        self._clamp_args(dev, cm, cv, in_specs, args)
        out_specs = (self._m_spec(), self._ns_spec(), P())
        m_o, ns_o, hist = self._shard_map(
            local, tuple(in_specs), out_specs)(*args)
        return hist, self._m_global(dev, m_o), self._ns_global(dev, ns,
                                                               ns_o)

    # -- band programming (band-resident graphs) -------------------------
    def place_edge_codes(self, codes) -> jax.Array:
        """Host (E,) edge codes -> each band's incident codes on its own
        device: (n_shards, e_band), built shard by shard, so no device
        ever holds the whole edge list."""
        codes, ids = np.asarray(codes), self._edge_ids
        return jax.make_array_from_callback(
            ids.shape, self.tables["edge_ids"].sharding,
            lambda index: codes[ids[index]])

    def place_edges(self, codes) -> jax.Array:
        """Host (E,) edge codes split evenly over the rows axis (each
        band gathers its incident edges from them in-jit)."""
        codes = np.asarray(codes)
        split = self.n_row > 1 and codes.shape[0] % self.n_row == 0
        return jax.device_put(codes, NamedSharding(
            self.mesh, P(self._r if split else None)))

    def place_nodes(self, x) -> jax.Array:
        """A host (N,) node vector, split over the rows axis."""
        return jax.device_put(np.asarray(x), band_sharding(
            self.mesh, self.partition, 1, 0))

    def program(self, dev, mismatch, hw, w_scale, code_parts, h_codes):
        """Program a band-resident chip from each band's incident edge
        codes (`place_edge_codes`, or a gather of (E,) codes on
        ``dev["edge_ids"]`` in-jit) and (N,) bias codes: the slot scatter,
        DAC transfer and analog chain of `program_edges`, run by each
        device on its own band.  Returns an `EffectiveChip` whose arrays
        are split over the rows axis; ``nbr_idx`` is the global neighbor
        table, split the same way."""
        from repro.core.hardware import program_weights_sparse

        def local(slot_edge, nbr_g, nbr_ok, codes, h, mism):
            se, codes = slot_edge[0], codes[0]
            J = jnp.where(se >= 0, jnp.take(codes, jnp.maximum(se, 0)),
                          jnp.zeros((), codes.dtype))
            chip = program_weights_sparse(J, h, jnp.abs(J) > 0, mism, hw,
                                          nbr_g, nbr_ok)
            return (chip.nbr_w * w_scale, chip.h * w_scale, chip.tanh_gain,
                    chip.tanh_offset, chip.rand_gain, chip.comp_offset)

        node, slots = P(self._r), P(None, self._r)
        mm_specs = type(mismatch)(
            P(None, self._r, None), P(self._r, None), slots, node, node,
            node, node, slots)
        w, h, gain, off, rg, co = self._shard_map(
            local,
            (P(self._r, None, None), slots, slots, P(self._r, None), node,
             mm_specs),
            (slots, node, node, node, node, node))(
            dev["slot_edge"], dev["nbr_g"], dev["nbr_ok"], code_parts,
            jnp.asarray(h_codes), mismatch)
        return EffectiveChip(W=None, h=h, tanh_gain=gain, tanh_offset=off,
                             rand_gain=rg, comp_offset=co,
                             nbr_idx=dev["nbr_g"], nbr_w=w)


# a band swept on its grid layout goes in chunks of cell rows holding at
# most this many bytes of spins (`ShardedEngine._grid_half_sweep`)
GRID_CHUNK_BYTES = 1 << 28

# per-band tables the shard_map'd sweep reads (the rest of
# `ShardedEngine.tables` serves the gathers and the band programming)
_BAND_TABLES = ("nbr", "send_up", "send_dn", "upd", "cols", "edge_e0",
                "edge_e1", "lfsr_perm", "lfsr_s0", "lfsr_s1", "edge_slot",
                "grid_slots")


def sparse_energy(chip: EffectiveChip, m: jax.Array) -> jax.Array:
    """Symmetrized Ising energy per chain from the slot layout, O(B·N·D):
    E = -1/2 Σ_i m_i Σ_j W_ij m_j - Σ_i h_i m_i (directional W averaged
    over its two directions)."""
    I = sparse_neuron_input(m, chip.nbr_idx, chip.nbr_w,
                            jnp.float32(0.0))
    return -0.5 * jnp.sum(m * I, axis=1) - m @ chip.h
