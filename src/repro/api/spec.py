"""Declarative sampler specification: one frozen object describes a solver.

The chip serves every workload — Boltzmann-machine learning, SK annealing,
Max-Cut, parallel tempering — through one program/sample interface.  This
module is the software contract for that interface: a `SamplerSpec` names
*what* to sample (graph + chip programming model), *how* (noise source,
execution backend, beta `Schedule`), and `api.Session` compiles it once
into jitted closures (see session.py).

Everything that used to be re-threaded by hand through five entry points
(`backend=`, `noise=`, hand-built beta arrays, env-var lookups at call
time) is a spec field, resolved exactly once at `Session` construction:

  * ``backend`` — ``ref | pallas | fused | sparse | fused_sparse | auto``.
    ``auto`` consults ``REPRO_PBIT_BACKEND`` (the env var becomes a spec
    *default*, read at compile, never at call time) and otherwise picks
    per the docs/kernels.md VMEM model: ``fused_sparse`` when the spec
    carries the Chimera slot layout and the noise can be generated
    in-kernel, ``sparse`` when it carries the layout but noise is
    host-side, ``fused`` for a dense-only spec whose W is VMEM-resident,
    else ``ref``.  This is the single seam where the ROADMAP
    mesh-sharding follow-on will plug in (partition decisions live here).
  * ``noise`` — ``philox | counter | lfsr`` (see core/pbit.py).
  * ``schedule`` — a first-class `Schedule`: `Constant`, `Anneal`
    (geometric/linear), or `Tempered` (per-chain ladder -> (S, B) betas).
  * ``interpret`` — Pallas interpret mode; ``None`` resolves at compile
    to compiled kernels on a TPU and interpret mode elsewhere.
  * ``mesh`` + ``partition`` — multi-device execution.  A `Partition`
    names the mesh axis the Chimera *cell rows* shard over (contiguous
    row bands per device, chain-coupler boundary spins halo-exchanged by
    ``ppermute`` each half-sweep — O(√N) bytes, never a dense W or a
    global gather) and/or the axis the Gibbs *chains* shard over (CD's
    embarrassingly parallel dimension; the (E,) edge-list moments are
    psum-reduced once per phase).  ``mesh=None`` (the default) is
    bit-exact to the single-device path; a sharded Session reproduces
    the single-device spin trajectory exactly for the same noise stream
    (see docs/sharding.md).
  * ``sync`` — a `Sync` policy for sharded execution: how often row
    bands exchange halos (``halo_every``), barrier vs PASS-style async
    double-buffering (``mode``), and how many sweeps fuse into one
    device-local launch (``sweeps_per_launch``).  The default barrier
    keeps the bit-exactness contract; relaxed policies are documented,
    measured approximations (docs/sharding.md §Sync policies).
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.api.faults import Faults
from repro.core.chimera import ChimeraGraph
from repro.core.hardware import HardwareConfig, Mismatch, SparseMismatch

BACKENDS = ("ref", "pallas", "fused", "sparse", "fused_sparse")
FUSED_BACKENDS = ("fused", "fused_sparse")
SPARSE_BACKENDS = ("sparse", "fused_sparse")
NOISE_KINDS = ("philox", "counter", "lfsr")
IN_KERNEL_NOISE = ("counter", "lfsr")

# docs/kernels.md VMEM model: the resident engine needs the weights plus
# two (block_b, N) activation tiles simultaneously live in a 16 MB core.
VMEM_BYTES = 16 * 2 ** 20
_RESIDENT_BLOCK_B = 128


def band_vmem_feasible(spec: "SamplerSpec") -> bool:
    """Can one row band of a sharded spec run in the fused per-shard
    kernel (docs/kernels.md, "VMEM model of the per-shard kernel")?

    The kernel keeps about two dozen f32 tiles of (chain block, band
    plus halos) live in VMEM at once — spins in and out, double-buffered,
    noise, fields, lane rotations — so a tile may take at most 1/24 of
    the 16 MB core.  The chain block is 128 chains.  With mid-launch
    exchanges the rule assumes a tile of all of a device's chains, which
    the exchange windows (ordinary 128-chain launches) never need: a
    conservative margin until the rule is recalibrated.
    Calibrated by compiling for a v5e: 147k-element tiles compile, 263k
    do not."""
    from repro.core.distributed import partition_size, plan_row_partition
    part, sync = spec.partitioning(), spec.sync_policy()
    plan = plan_row_partition(spec.graph,
                              partition_size(spec.mesh, part.rows_axes))
    b_loc = spec.chains // partition_size(spec.mesh, part.chain_axes)
    tb = -(-b_loc // 8) * 8
    if sync.kernel_fusible:
        tb = min(_RESIDENT_BLOCK_B, tb)
    return 4 * tb * (plan.n_loc + 2 * plan.halo) * 24 <= VMEM_BYTES


def dense_vmem_feasible(n_nodes: int) -> bool:
    """Can a dense (N, N) float32 W stay VMEM-resident (kernels.md model)?"""
    return 4 * n_nodes * n_nodes + 2 * (_RESIDENT_BLOCK_B * n_nodes * 4) \
        <= VMEM_BYTES


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Schedule:
    """Base class: a declarative inverse-temperature schedule.

    ``betas(chains)`` materializes the (S,) shared — or (S, B) per-chain —
    float32 array the sampling engine scans over.  Schedules are frozen,
    hashable value objects so they can key compiled-closure caches.
    ``n_sweeps`` is keyword-only so subclasses keep natural positional
    order: ``Anneal(0.05, 3.0, n_sweeps=600)``.
    """

    n_sweeps: int = dataclasses.field(default=1, kw_only=True)

    def betas(self, chains: int | None = None) -> jax.Array:
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class Constant(Schedule):
    """Fixed beta for every sweep — the Boltzmann-sampling workloads."""

    beta: float = 1.0

    def betas(self, chains: int | None = None) -> jax.Array:
        return jnp.full((self.n_sweeps,), self.beta, jnp.float32)


@dataclasses.dataclass(frozen=True)
class Anneal(Schedule):
    """Simulated-annealing ramp (the chip's V_temp sweep, paper Fig. 9a)."""

    beta_start: float = 0.05
    beta_end: float = 3.0
    kind: str = "geometric"  # or "linear"

    def __post_init__(self):
        if self.kind not in ("geometric", "linear"):
            raise ValueError(
                f"Anneal.kind must be 'geometric' or 'linear', "
                f"got {self.kind!r}")

    def betas(self, chains: int | None = None) -> jax.Array:
        t = jnp.linspace(0.0, 1.0, self.n_sweeps)
        if self.kind == "geometric":
            return (self.beta_start
                    * (self.beta_end / self.beta_start) ** t).astype(
                        jnp.float32)
        return (self.beta_start
                + (self.beta_end - self.beta_start) * t).astype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class Tempered(Schedule):
    """Per-chain beta ladder -> (S, B) matrix (parallel-tempering replicas).

    ``ladder`` is one beta per chain; every sweep runs the whole ladder.
    The replica-exchange *controller* (core/tempering.py) permutes the
    ladder between swap rounds by passing explicit betas to
    ``Session.sample`` — the schedule fixes the shape contract.
    """

    ladder: tuple = (1.0,)

    @staticmethod
    def geometric(beta_min: float, beta_max: float, n_replicas: int,
                  n_sweeps: int = 1) -> "Tempered":
        r = jnp.arange(n_replicas) / max(n_replicas - 1, 1)
        ladder = beta_min * (beta_max / beta_min) ** r
        return Tempered(n_sweeps=n_sweeps,
                        ladder=tuple(float(b) for b in ladder))

    def betas(self, chains: int | None = None) -> jax.Array:
        ladder = jnp.asarray(self.ladder, jnp.float32)
        if chains is not None and ladder.shape[0] != chains:
            raise ValueError(
                f"Tempered ladder has {ladder.shape[0]} rungs but the spec "
                f"runs {chains} chains; one beta per chain is required")
        return jnp.broadcast_to(ladder, (self.n_sweeps, ladder.shape[0]))


# ---------------------------------------------------------------------------
# Partitioning
# ---------------------------------------------------------------------------
def _norm_axes(axes) -> tuple[str, ...]:
    """None -> (); "data" -> ("data",); tuples pass through."""
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


@dataclasses.dataclass(frozen=True)
class Partition:
    """Declarative device-partition choice, resolved at Session compile.

    ``rows`` names the mesh axis (or axes, flattened in order) the Chimera
    *cell rows* shard over: each device owns a contiguous band of cell
    rows plus the O(D·N_local) slice of the slot tables, and only the
    chain-coupler boundary spins (the vertical nodes of the band's first
    and last cell row — O(√N)) travel between row neighbors, by
    ``jax.lax.ppermute``, once per half-sweep.  This is exactly the
    chip's tiling: in-cell K44 and horizontal couplers never leave a
    device; only inter-cell vertical wires cross the cut.

    ``chains`` names the axis the Gibbs chains shard over — CD's
    embarrassingly parallel dimension.  Spins are bit-exact vs
    single-device for any chain count; the accumulated moments are
    bit-exact when ``chains`` is a power of two (the ±1 partial sums and
    their dyadic scalings are then exact in float32 — see
    docs/sharding.md) and 1-ulp-close otherwise.

    Both may be set at once (a 2-D mesh: rows x chains).  Sharded
    execution runs the slot-layout scan path ("sparse" backend
    semantics) or, with counter noise and a `Sync` whose exchange
    cadence the kernel can own (``halo_every <= sweeps_per_launch``, or
    no mid-launch exchange at all), the sweep-resident fused kernel, one
    call per window between exchange points (docs/kernels.md §Fused
    exchange windows).  Either way it needs noise that regenerates per
    (chain, node) coordinate, so ``noise`` must be "counter" or "lfsr".
    """

    rows: str | tuple[str, ...] | None = "data"
    chains: str | tuple[str, ...] | None = None

    @property
    def rows_axes(self) -> tuple[str, ...]:
        return _norm_axes(self.rows)

    @property
    def chain_axes(self) -> tuple[str, ...]:
        return _norm_axes(self.chains)


# ---------------------------------------------------------------------------
# Synchronization policy (sharded execution)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Sync:
    """How often row-band shards synchronize — a compiled sampler property.

    The chip's analog fabric has no global clock (PASS, arXiv:2409.10325,
    makes asynchrony the headline feature of a p-bit processor); how
    faithfully the sharded engine emulates a global barrier is a policy,
    not an accident of the backend:

    * ``halo_every=k`` — exchange the chain-coupler boundary spins before
      every k-th half-sweep (within-launch index; a launch boundary always
      refreshes).  ``k=1`` (the default) is today's bit-exact barrier
      path; ``k>1`` lets bands run on halos up to ``k-1`` half-sweeps
      stale; ``math.inf`` exchanges only at launch boundaries.
    * ``mode`` — ``"barrier"`` consumes each exchange immediately (the
      deterministic emulation of a synchronized swap); ``"async"``
      double-buffers it PASS-style: the values consumed at exchange point
      t are the ones *sent* at point t-1, so the transfer is in flight
      across the intervening compute (fire-and-forget staleness, still
      deterministic and seeded).
    * ``sweeps_per_launch=S`` — fuse S full sweeps into one device-local
      launch between exchange points.  With counter noise the engine
      runs the launch through the sweep-resident Pallas kernel
      (`kernels/shard_sweep.py::fused_shard_sweeps`) — spins
      VMEM-resident, in-kernel RNG.  Mid-launch exchange points keep
      the fusion: with ``halo_every <= sweeps_per_launch`` the launch
      runs as half-sweep windows of the kernel, one per exchange point,
      with a ppermute between them, in one jitted graph (docs/kernels.md
      §Fused exchange windows).

    ``halo_every=1`` keeps the sharded == single-device bit-exactness
    contract; anything looser is a *documented, measured* approximation —
    tests/test_sync_policies.py bounds the KL gap, the ``sync_policies``
    section of BENCH_kernel.json tracks the wall-clock win
    (docs/sharding.md §Sync policies).
    """

    halo_every: int | float = 1
    mode: str = "barrier"
    sweeps_per_launch: int = 1

    def __post_init__(self):
        k = self.halo_every
        if not (k == math.inf or (isinstance(k, int) and k >= 1)):
            raise ValueError(
                f"Sync.halo_every must be an int >= 1 or math.inf, got "
                f"{k!r}")
        if self.mode not in ("barrier", "async"):
            raise ValueError(
                f"Sync.mode must be 'barrier' or 'async', got {self.mode!r}")
        if not (isinstance(self.sweeps_per_launch, int)
                and self.sweeps_per_launch >= 1):
            raise ValueError(
                f"Sync.sweeps_per_launch must be an int >= 1, got "
                f"{self.sweeps_per_launch!r}")

    @property
    def bit_exact(self) -> bool:
        """Does this policy preserve the sharded == single-device spin
        trajectory exactly?  Only the per-half-sweep barrier does."""
        return self.mode == "barrier" and self.halo_every == 1

    @property
    def launch_resident(self) -> bool:
        return self.sweeps_per_launch > 1

    def exchange_points(self) -> tuple[int, ...]:
        """Within-launch half-sweep indices at which halos refresh.

        A launch spans ``2 * sweeps_per_launch`` half-sweeps; index 0 (the
        launch boundary) always refreshes."""
        n_half = 2 * self.sweeps_per_launch
        if self.halo_every == math.inf:
            return (0,)
        k = int(self.halo_every)
        return tuple(hs for hs in range(n_half) if hs % k == 0)

    @property
    def kernel_fusible(self) -> bool:
        """No mid-launch exchange -> a launch can run inside one Pallas
        kernel (the fused per-shard path also needs counter noise)."""
        return self.exchange_points() == (0,)

    @property
    def fused_compatible(self) -> bool:
        """Can a fused backend run this policy?  True when there is no
        mid-launch exchange (`kernel_fusible`) or when
        ``halo_every <= sweeps_per_launch``: the launch then runs as
        half-sweep windows of the kernel split at the exchange points
        (`kernels/ref.py::halo_exchange_segments`).  The infeasible window
        is ``sweeps_per_launch < halo_every < 2 * sweeps_per_launch``:
        exchange points too sparse for the resident segments yet not at
        launch boundaries only."""
        if self.kernel_fusible:
            return True
        return (isinstance(self.halo_every, int)
                and self.halo_every <= self.sweeps_per_launch)

    def exchanges_per_sweep(self, refresh_for_moments: bool = False
                            ) -> float:
        """Average halo exchanges per full sweep under this policy (the
        halo-bytes model's multiplier; docs/sharding.md)."""
        per = len(self.exchange_points()) / self.sweeps_per_launch
        if refresh_for_moments and self.bit_exact:
            per += 1.0  # post-sweep refresh for boundary-edge correlations
        return per


# ---------------------------------------------------------------------------
# The spec
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True, eq=False)
class SamplerSpec:
    """Frozen, pytree-registered description of one solver instance.

    The mismatch arrays are the pytree leaves (a spec can be device_put /
    donated / tree-mapped); everything else — graph, hardware sigmas,
    noise/backend/schedule choices — is static aux data fixed at trace
    time.  ``Session(spec)`` validates and compiles it; specs themselves
    hold no jax state and read no environment variables.
    """

    graph: ChimeraGraph
    hw: HardwareConfig
    mismatch: Mismatch | SparseMismatch
    noise: str = "philox"
    backend: str = "auto"
    schedule: Schedule | None = None
    chains: int = 256
    beta: float = 1.0           # base inverse temperature (stats / CD / hist)
    w_scale: float = 0.05       # weight-LSB -> coupling units
    decimation: int = 8         # LFSR clocks per half-sweep
    attach_sparse: bool = True  # carry the Chimera slot layout on dense chips
    interpret: bool | None = None  # Pallas interpret; None -> env at compile
    mesh: Any = None            # jax.sharding.Mesh; None -> single device
    partition: Partition | None = None  # how to cut over mesh; None -> default
    sync: Sync | None = None    # shard sync policy; None -> Sync() barrier
    faults: Faults | None = None  # discrete fault injection; None -> healthy

    # -- pytree ----------------------------------------------------------
    def tree_flatten(self):
        aux = tuple(
            getattr(self, f.name) for f in dataclasses.fields(self)
            if f.name != "mismatch")
        return (self.mismatch,), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        names = [f.name for f in dataclasses.fields(cls)
                 if f.name != "mismatch"]
        return cls(mismatch=children[0], **dict(zip(names, aux)))

    # -- derived properties ---------------------------------------------
    @property
    def sparse_native(self) -> bool:
        """Only the O(D·N) slot model exists (no dense W can ever be built)."""
        return isinstance(self.mismatch, SparseMismatch)

    @property
    def has_slot_layout(self) -> bool:
        """Will programmed chips carry the (D, N) neighbor-table view?"""
        return self.sparse_native or self.attach_sparse

    def replace(self, **kw) -> "SamplerSpec":
        return dataclasses.replace(self, **kw)

    def partitioning(self) -> Partition | None:
        """The effective Partition: default rows-over-"data" when a mesh
        is given without an explicit partition; None when unsharded."""
        if self.mesh is None:
            return None
        return self.partition if self.partition is not None else Partition()

    def sync_policy(self) -> Sync | None:
        """The effective Sync policy: the bit-exact per-half-sweep barrier
        when a mesh is given without an explicit sync; None unsharded."""
        if self.mesh is None:
            return None
        return self.sync if self.sync is not None else Sync()

    # -- compile-cache fingerprint ---------------------------------------
    def fingerprint(self) -> tuple:
        """Canonicalized compile-cache key for this spec (hashable tuple).

        Two specs with equal fingerprints compile to interchangeable
        Sessions: the *resolved* backend/interpret (so ``backend="auto"``
        and the explicit name it resolves to share an entry), the graph
        shape bucket (rows/cols/k/mask — node ids and edge lists are
        derived from these deterministically), the effective partition +
        sync + mesh device assignment, the schedule/chains/beta/decimation
        statics, and the mismatch *structure* (type + per-leaf
        dtype/shape — the dense/sparse programming route and every array
        extent in the trace, but never the drawn values).  This is a pure
        shape-bucket key: chips, `Program`s, and mismatch draws are
        runtime operands of the compiled closures
        (`Session.sample_program`, the CD step's `with_mismatch` entry),
        so two specs differing only in drawn values — two chip instances
        of one SKU — share one executable and stream their programs into
        it.  The analog `HardwareConfig` scalars still bake into the
        programming arithmetic as closure constants and are deliberately
        NOT keyed: a cache mixing HardwareConfigs must key on hw
        separately (the serving layer holds a single service-wide
        HardwareConfig, so its bucket key stays safe).  The serving layer
        (`repro.serve`) keys its LRU Session cache on this: a 13-spin
        adder and a 440-spin chip embedded into the same shape bucket hit
        the same compiled executable and differ only in the streamed
        program.  Env vars are consulted exactly as Session compile would
        (via `resolve_backend`/`resolve_interpret`), so the key is
        computed in the same environment the Session is built in.
        """
        g = self.graph
        graph_sig = ("chimera", int(g.rows), int(g.cols), int(g.k),
                     tuple(sorted(tuple(c) for c in (g.masked_cells or ()))),
                     int(g.n_nodes), int(g.edges.shape[0]))
        mm_sig = (type(self.mismatch).__name__,
                  tuple((jax.tree_util.keystr(path), str(leaf.dtype),
                         tuple(int(d) for d in leaf.shape))
                        for path, leaf in
                        jax.tree_util.tree_flatten_with_path(
                            self.mismatch)[0]))
        mesh_sig = None
        if self.mesh is not None:
            mesh_sig = (tuple(self.mesh.axis_names),
                        tuple(int(self.mesh.shape[a])
                              for a in self.mesh.axis_names),
                        tuple(int(d.id) for d in self.mesh.devices.flat))
        part = self.partitioning()
        part_sig = None if part is None else (part.rows_axes, part.chain_axes)
        sync = self.sync_policy()
        sync_sig = None if sync is None else (
            sync.halo_every, sync.mode, sync.sweeps_per_launch)
        sched_sig = None
        if self.schedule is not None:
            sched_sig = (type(self.schedule).__name__,
                         tuple(sorted(dataclasses.asdict(
                             self.schedule).items())))
        return (graph_sig, mm_sig, self.noise,
                resolve_backend(self), int(self.chains), float(self.beta),
                float(self.w_scale), int(self.decimation),
                bool(self.attach_sparse), resolve_interpret(self),
                mesh_sig, part_sig, sync_sig, sched_sig,
                None if self.faults is None else repr(self.faults))

    # -- validation ------------------------------------------------------
    def validate(self) -> "SamplerSpec":
        """Static sanity checks; raises ValueError naming the fix."""
        if self.noise not in NOISE_KINDS:
            raise ValueError(
                f"unknown noise {self.noise!r}; pick from {NOISE_KINDS}")
        if self.backend not in BACKENDS + ("auto",) and \
                self.backend is not None:
            raise ValueError(
                f"unknown backend {self.backend!r}; pick from "
                f"{BACKENDS + ('auto',)}")
        if self.backend in FUSED_BACKENDS and \
                self.noise not in IN_KERNEL_NOISE:
            raise ValueError(
                f"backend {self.backend!r} generates noise in-kernel and "
                f"needs noise='counter' or 'lfsr', got {self.noise!r}")
        if self.backend in SPARSE_BACKENDS and not self.has_slot_layout:
            raise ValueError(
                f"backend {self.backend!r} needs the Chimera slot layout; "
                f"use attach_sparse=True or a sparse-native mismatch")
        if self.sparse_native and self.backend in ("ref", "pallas", "fused"):
            raise ValueError(
                f"this spec is sparse-native (no dense W exists); backend "
                f"{self.backend!r} cannot run it — use 'sparse', "
                f"'fused_sparse', or 'auto'")
        if self.chains < 1:
            raise ValueError(f"chains must be >= 1, got {self.chains}")
        if self.schedule is not None:
            self.schedule.betas(self.chains)  # raises on ladder mismatch
        self._validate_partition()
        self._validate_faults()
        return self

    def _validate_partition(self) -> None:
        if self.partition is not None and self.mesh is None:
            raise ValueError(
                "partition= set but mesh=None; pass the device mesh the "
                "partition shards over (e.g. launch.mesh.make_host_mesh)")
        if self.sync is not None and self.mesh is None:
            raise ValueError(
                "sync= is a sharded-execution policy (how often row bands "
                "exchange halos) but mesh=None; pass mesh= or drop sync=")
        part = self.partitioning()
        if part is None:
            return
        mesh_axes = tuple(self.mesh.axis_names)
        rows, chains = part.rows_axes, part.chain_axes
        if not rows and not chains:
            raise ValueError(
                "mesh= set but the Partition shards nothing; set "
                "Partition(rows=...) and/or Partition(chains=...)")
        for ax in rows + chains:
            if ax not in mesh_axes:
                raise ValueError(
                    f"partition axis {ax!r} not in mesh axes {mesh_axes}")
        if set(rows) & set(chains):
            raise ValueError(
                f"partition axes must be disjoint; {set(rows) & set(chains)}"
                f" appear in both rows and chains")
        if self.noise not in IN_KERNEL_NOISE:
            raise ValueError(
                f"sharded execution regenerates noise per (chain, node) "
                f"coordinate and needs noise='counter' or 'lfsr', got "
                f"{self.noise!r}")
        if not self.has_slot_layout:
            raise ValueError(
                "sharded execution runs on the Chimera slot layout; use "
                "attach_sparse=True or a sparse-native mismatch")
        sync = self.sync_policy()
        if self.backend not in (None, "auto", "sparse", "fused_sparse"):
            raise ValueError(
                f"sharded Sessions run the slot-layout scan path or, under "
                f"a launch-resident sync policy, the fused per-shard "
                f"kernel; backend must be 'sparse', 'fused_sparse', or "
                f"'auto', got {self.backend!r}")
        if self.backend == "fused_sparse":
            if not sync.fused_compatible:
                S = sync.sweeps_per_launch
                raise ValueError(
                    f"backend 'fused_sparse' runs each launch as kernel "
                    f"windows between exchange points, which supports "
                    f"halo_every <= sweeps_per_launch, but sync={sync} has "
                    f"halo_every={sync.halo_every} with sweeps_per_launch="
                    f"{S} (exchange points {sync.exchange_points()}); "
                    f"nearest legal Sync: lower halo_every to {S} "
                    f"(exchange windows), raise it to >= {2 * S} "
                    f"or math.inf (launch-boundary exchange only), or use "
                    f"backend='sparse'")
            if self.noise != "counter":
                raise ValueError(
                    f"the fused per-shard kernel regenerates noise "
                    f"in-kernel from global (chain, node) coordinates and "
                    f"needs noise='counter', got {self.noise!r}; use "
                    f"backend='sparse' for lfsr")
        n_row = 1
        for ax in rows:
            n_row *= self.mesh.shape[ax]
        if n_row > self.graph.rows:
            raise ValueError(
                f"cannot shard {self.graph.rows} cell rows over {n_row} "
                f"devices; grow the lattice or shrink the rows axes")
        n_chain = 1
        for ax in chains:
            n_chain *= self.mesh.shape[ax]
        if self.chains % n_chain:
            raise ValueError(
                f"chains={self.chains} not divisible by the chain-axis "
                f"size {n_chain}")

    def _validate_faults(self) -> None:
        f = self.faults
        if f is None:
            return
        if not isinstance(f, Faults):
            raise ValueError(
                f"faults= must be an api.Faults instance, got "
                f"{type(f).__name__}")
        f.validate_for(self.graph, self.noise)
        if f.needs_host_hooks and self.backend in FUSED_BACKENDS:
            raise ValueError(
                f"backend {self.backend!r} runs whole sweeps inside one "
                f"kernel and cannot apply per-half-sweep fault hooks "
                f"(transient flips, stuck LFSR bits); use a scan backend "
                f"('ref'/'pallas'/'sparse') or backend='auto' (which "
                f"demotes to the scan path under these faults)")


# ---------------------------------------------------------------------------
# Compile-time resolution (the ONLY place env vars are consulted)
# ---------------------------------------------------------------------------
def resolve_backend(spec: SamplerSpec) -> str:
    """Spec backend -> concrete backend string, resolved once at compile.

    Explicit names win; ``auto``/``None`` consults REPRO_PBIT_BACKEND and
    then the kernels.md model.  The returned string is baked into the
    Session's closures — no env read ever happens at call time.

    A sharded spec (mesh=) runs the slot-layout scan per shard
    ("sparse"), or — when the sync policy is launch-resident and
    fused-compatible (``halo_every <= sweeps_per_launch`` or no
    mid-launch exchange) and the noise is counter — the fused per-shard
    kernel ("fused_sparse"), which ``auto`` picks by itself.  An env
    default naming a backend the partition cannot honor raises instead of
    being silently overridden.
    """
    if spec.mesh is not None:
        return _resolve_sharded_backend(spec)
    b = spec.backend
    if b in (None, "auto"):
        env = os.environ.get("REPRO_PBIT_BACKEND")
        b = env if env else _auto_backend(spec)
    if b not in BACKENDS:
        raise ValueError(f"unknown backend {b!r}; pick from {BACKENDS}")
    if b in FUSED_BACKENDS and spec.noise not in IN_KERNEL_NOISE:
        raise ValueError(
            f"backend {b!r} needs in-kernel noise ('counter' or 'lfsr'), "
            f"got {spec.noise!r}")
    if b in FUSED_BACKENDS and _fault_hooks(spec):
        raise ValueError(
            f"backend {b!r} cannot apply per-half-sweep fault hooks "
            f"(transient flips / stuck LFSR bits); unset "
            f"REPRO_PBIT_BACKEND or pick a scan backend")
    if b in ("ref", "pallas", "fused") and spec.sparse_native:
        raise ValueError(
            f"REPRO_PBIT_BACKEND={b!r} cannot run a sparse-native spec "
            f"(no dense W); use 'sparse' or 'fused_sparse'")
    return b


def _resolve_sharded_backend(spec: SamplerSpec) -> str:
    """Backend resolution under a mesh: 'sparse' or 'fused_sparse' only.

    The env default participates like everywhere else, but a value the
    partition cannot honor is a hard error — a sharded Session silently
    falling back to a different engine than the one the operator pinned
    is exactly the "works on my box" bug class the Session layer exists
    to kill.
    """
    sync = spec.sync_policy()
    fused_ok = (spec.noise == "counter" and sync.fused_compatible
                and not _fault_hooks(spec))
    b = spec.backend
    src = f"backend={b!r}"
    if b in (None, "auto"):
        env = os.environ.get("REPRO_PBIT_BACKEND")
        if env:
            b, src = env, f"REPRO_PBIT_BACKEND={env!r}"
        else:
            return ("fused_sparse" if fused_ok and sync.launch_resident
                    and band_vmem_feasible(spec) else "sparse")
    if b == "sparse":
        return b
    if b == "fused_sparse":
        if not fused_ok:
            S = sync.sweeps_per_launch
            raise ValueError(
                f"{src} names the fused per-shard kernel, but this sharded "
                f"spec cannot run it (needs noise='counter', a sync "
                f"policy with halo_every <= sweeps_per_launch or no "
                f"mid-launch exchange, and no fault hooks; got noise="
                f"{spec.noise!r}, sync={sync}, faults={spec.faults}); "
                f"nearest legal Sync: lower halo_every to {S}, raise it "
                f"to >= {2 * S} or math.inf, or use backend='sparse'")
        if not band_vmem_feasible(spec):
            raise ValueError(
                f"{src} runs each row band inside one kernel, but a band "
                f"of this spec does not fit the kernel's VMEM "
                f"(docs/kernels.md, \"VMEM model of the per-shard "
                f"kernel\"): {spec.graph.n_nodes} spins in row bands, "
                f"{spec.chains} chains; use "
                f"backend='sparse', more row shards, or fewer chains per "
                f"device")
        return b
    raise ValueError(
        f"{src} cannot run a mesh-sharded spec: the partitioned engine "
        f"supports 'sparse' (scan per shard) or 'fused_sparse' (launch-"
        f"resident kernel per shard), and the single-device backends "
        f"cannot halo-exchange")


def _fault_hooks(spec: SamplerSpec) -> bool:
    """Does the fault model need host-side per-half-sweep hooks?"""
    return spec.faults is not None and spec.faults.needs_host_hooks


def _auto_backend(spec: SamplerSpec) -> str:
    """kernels.md policy: prefer the slot layout; fall back by VMEM model.

    Fault hooks (transient flips, stuck LFSR bits) run between half-sweeps
    on the host side of the scan, so they demote ``auto`` from the fused
    engines to the matching scan backend.
    """
    in_kernel = spec.noise in IN_KERNEL_NOISE and not _fault_hooks(spec)
    if spec.has_slot_layout:
        return "fused_sparse" if in_kernel else "sparse"
    if in_kernel and dense_vmem_feasible(spec.graph.n_nodes):
        return "fused"
    return "ref"


def spec_fingerprint(spec: SamplerSpec) -> str:
    """Compact hex digest of `SamplerSpec.fingerprint()` — the string form
    used as the serving layer's LRU key and in health/metrics output."""
    import hashlib
    return hashlib.sha1(repr(spec.fingerprint()).encode()).hexdigest()[:16]


def resolve_interpret(spec: SamplerSpec) -> bool:
    """Pallas interpret mode, resolved once at compile.

    Delegates to the kernel layer's `default_interpret` so the rule
    exists in exactly one place.
    """
    if spec.interpret is not None:
        return bool(spec.interpret)
    from repro.kernels.ops import default_interpret
    return default_interpret()
