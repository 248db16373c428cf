"""Compiled solver sessions: `SamplerSpec` -> jitted closures.

`Session(spec)` is the one choke point between every workload (CD
learning, annealing, Max-Cut, parallel tempering, clamped inference) and
the execution backends in core/pbit.py + kernels/.  Construction does all
the one-time work:

  * validates the spec and resolves ``backend`` / ``interpret`` (the only
    place REPRO_PBIT_BACKEND is read — call time never touches the
    environment);
  * builds the noise step function once (philox / counter / lfsr,
    including the LFSR's per-node gather permutation);
  * caches the graph's color masks, edge list, and Chimera slot tables;
  * materializes the spec's `Schedule` into the default beta array.

Sampling entry points return jitted closures cached per static signature
(clamped / collect / sweep counts), so repeated calls — the CD training
loop, tempering swap rounds, evaluation — pay zero re-trace or dispatch
overhead (benchmarks/bench_kernel.py `session_dispatch` measures this
against the legacy per-call path).

State threading is explicit everywhere: chips, spins, and noise state are
arguments and return values, never hidden attributes — a Session is
immutable after construction and safe to share across workloads.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.program import Program
from repro.api.spec import (
    SamplerSpec,
    resolve_backend,
    resolve_interpret,
)
from repro.core import pbit
from repro.core.hardware import (
    EffectiveChip,
    program_weights,
    program_weights_sparse,
    quantize_codes,
)
from repro.kernels.ref import scatter_edge_slots
from repro.runtime.spans import named_jit, span

# the fleet axis vmaps whole sampling closures; the launch-resident fused
# engines demote to their bit-exact scan siblings under vmap (the Pallas
# batching path is not part of the bit-exactness contract), so a K-fleet
# result is bit-identical to K sequential single-program calls
_FLEET_BACKEND = {"fused": "ref", "fused_sparse": "sparse", "pallas": "ref"}

# the CD step's metrics, in the order `make_cd_epoch` stacks them
CD_METRICS = ("corr_err", "mean_err", "update_skipped")


def cd_data_draw(key: jax.Array, p: jax.Array, codes: jax.Array,
                 chains: int):
    """One CD epoch's key split and data batch: (key', ke, data_vis).

    ``key`` splits into (key', kd, ke); ``chains`` rows of ``codes`` are
    drawn with probabilities ``p`` under ``kd``; ``ke`` is left for the
    epoch's evaluation.
    """
    key, kd, ke = jax.random.split(key, 3)
    idx = jax.random.choice(kd, codes.shape[0], (chains,), p=p)
    return key, ke, codes[idx]


class _Bound:
    """A jitted closure with a sharded Session's static tables bound as
    its first argument; everything else (``lower``, the cache) is the
    jitted function's."""

    def __init__(self, fn, tables):
        self._fn, self._tables = fn, tables

    def __call__(self, *args):
        return self._fn(self._tables, *args)

    def lower(self, *args):
        return self._fn.lower(self._tables, *args)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class SessionState(NamedTuple):
    """Spins + noise state, the carry every closure threads explicitly."""

    m: jax.Array
    noise_state: jax.Array


# ---------------------------------------------------------------------------
# chip programming (spec-level: needs no backend/noise resolution, so it
# works on specs a Session would reject — programming only depends on the
# graph, the mismatch instance, and the analog model)
# ---------------------------------------------------------------------------
def _graph_tables(spec: SamplerSpec, tables=None):
    if tables is not None:
        return tables
    nbr_idx, nbr_mask = spec.graph.neighbor_table()
    slot_ij, slot_ji = spec.graph.edge_slots(nbr_idx)
    return nbr_idx, nbr_mask, slot_ij, slot_ji


def _scale_chip(spec: SamplerSpec, chip: EffectiveChip) -> EffectiveChip:
    # external-resistor scale: DAC LSB units -> neuron-input units
    upd = {"h": chip.h * spec.w_scale}
    if chip.W is not None:
        upd["W"] = chip.W * spec.w_scale
    if chip.nbr_w is not None:
        upd["nbr_w"] = chip.nbr_w * spec.w_scale
    return dataclasses.replace(chip, **upd)


def _saturate_edge_codes(spec: SamplerSpec, codes: jax.Array) -> jax.Array:
    """Apply stuck-at-full-scale weight DACs to (E,) edge codes.

    A saturated coupler drives ±127 regardless of the programmed code (sign
    follows the requested code; + when zero).  Idempotent, so the dense
    programming route may re-apply it at the (n, n) level harmlessly.
    """
    f = spec.faults
    if f is None or not f.saturated_edges:
        return codes
    sat = np.asarray(f.saturated_edges, np.int64)
    cur = codes[sat]
    full = jnp.where(cur < 0, -127, 127).astype(codes.dtype)
    return codes.at[sat].set(full)


def _apply_code_faults(spec: SamplerSpec, J_codes: jax.Array,
                       enable: jax.Array | None):
    """Dense-codes view of the saturation fault (+ forced enable)."""
    f = spec.faults
    if f is None or not f.saturated_edges:
        return J_codes, enable
    e = spec.graph.edges
    sat = np.asarray(f.saturated_edges, np.int64)
    i, j = e[sat, 0], e[sat, 1]
    J = jnp.asarray(J_codes)
    full = jnp.where(J[i, j] < 0, -127, 127).astype(J.dtype)
    J = J.at[i, j].set(full).at[j, i].set(full)
    if enable is not None:
        # the stuck DAC drives current whether or not the coupler was
        # meant to be enabled
        enable = jnp.asarray(enable).at[i, j].set(True).at[j, i].set(True)
    return J, enable


def _kill_dead_edges(spec: SamplerSpec, chip: EffectiveChip,
                     tables) -> EffectiveChip:
    """Open-circuit the dead couplers: zero coupling in both directions,
    including the disabled-coupler leakage (a broken bond wire carries no
    current at all).  Runs after programming/scaling so it is the last
    word on those entries."""
    f = spec.faults
    if f is None or not f.dead_edges:
        return chip
    _, _, slot_ij, slot_ji = tables
    e = spec.graph.edges
    de = np.asarray(f.dead_edges, np.int64)
    i, j = e[de, 0], e[de, 1]
    upd = {}
    if chip.W is not None:
        upd["W"] = chip.W.at[i, j].set(0.0).at[j, i].set(0.0)
    if chip.nbr_w is not None:
        s_ij = np.asarray(slot_ij)[de]
        s_ji = np.asarray(slot_ji)[de]
        upd["nbr_w"] = (chip.nbr_w.at[s_ij, i].set(0.0)
                        .at[s_ji, j].set(0.0))
    return dataclasses.replace(chip, **upd) if upd else chip


def program(spec: SamplerSpec, J_codes: jax.Array, h_codes: jax.Array,
            enable: jax.Array | None = None, *, tables=None
            ) -> EffectiveChip:
    """Program dense (n, n) symmetric 8-bit codes through the spec's
    analog model (sparse-native specs gather the codes into slots).

    The spec's `Faults` apply here: saturated couplers override their codes
    with ±127 before the DAC transfer, dead couplers are open-circuited
    after programming."""
    tables = _graph_tables(spec, tables)
    nbr_idx, nbr_mask, _, _ = tables
    J_codes, enable = _apply_code_faults(spec, J_codes, enable)
    if enable is None:
        enable = jnp.abs(jnp.asarray(J_codes)) > 0
    if spec.sparse_native:
        rows = jnp.arange(spec.graph.n_nodes)[None, :]
        idx = jnp.asarray(nbr_idx)
        chip = program_weights_sparse(
            jnp.asarray(J_codes)[rows, idx], h_codes,
            jnp.asarray(enable)[rows, idx], spec.mismatch, spec.hw,
            idx, jnp.asarray(nbr_mask))
    else:
        adj = jnp.asarray(spec.graph.adjacency())
        neighbors = jnp.asarray(nbr_idx) if spec.attach_sparse else None
        chip = program_weights(J_codes, h_codes, enable, spec.mismatch,
                               spec.hw, adjacency=adj, neighbors=neighbors)
    return _kill_dead_edges(spec, _scale_chip(spec, chip), tables)


def program_edges(spec: SamplerSpec, J_edge_codes: jax.Array,
                  h_codes: jax.Array, *, tables=None) -> EffectiveChip:
    """Program per-edge codes (E,) — the CD master-weight layout."""
    tables = _graph_tables(spec, tables)
    nbr_idx, nbr_mask, slot_ij, slot_ji = tables
    e = spec.graph.edges
    codes = _saturate_edge_codes(spec, jnp.asarray(J_edge_codes))
    if spec.sparse_native:
        J_slots = scatter_edge_slots(codes, e, slot_ij, slot_ji,
                                     nbr_idx.shape[0], spec.graph.n_nodes)
        chip = program_weights_sparse(
            J_slots, h_codes, jnp.abs(J_slots) > 0, spec.mismatch,
            spec.hw, jnp.asarray(nbr_idx), jnp.asarray(nbr_mask))
        return _kill_dead_edges(spec, _scale_chip(spec, chip), tables)
    n = spec.graph.n_nodes
    J = (jnp.zeros((n, n), codes.dtype)
         .at[e[:, 0], e[:, 1]].set(codes)
         .at[e[:, 1], e[:, 0]].set(codes))
    return program(spec, J, h_codes, tables=(nbr_idx, nbr_mask, slot_ij,
                                             slot_ji))


def program_master(spec: SamplerSpec, Jm: jax.Array, hm: jax.Array,
                   *, tables=None) -> EffectiveChip:
    """Quantize float masters — edge-list (E,) or dense (n, n) — and
    program."""
    Jm = jnp.asarray(Jm)
    if Jm.ndim == 1:
        return program_edges(spec, quantize_codes(Jm), quantize_codes(hm),
                             tables=tables)
    return program(spec, quantize_codes(Jm), quantize_codes(hm),
                   tables=tables)


def program_chip(spec: SamplerSpec, prog: Program, *, tables=None
                 ) -> EffectiveChip:
    """Program a runtime `Program` through the spec's analog model.

    This is the weight-streaming path: it runs *inside* the jitted
    sampling closures with the program's leaves as traced operands, so a
    new program never retraces — the scatter + DAC transfer + compression
    chain is part of the compiled executable and only its inputs change.
    A program-borne ``mismatch`` overrides the spec's draw (same pytree
    structure required; `Session.make_program` enforces the type).
    """
    if prog.mismatch is not None:
        spec = spec.replace(mismatch=prog.mismatch)
    return program_edges(spec, prog.J_codes, prog.h_codes, tables=tables)


class Session:
    """A compiled solver: spec-resolved programming + sampling closures."""

    def __init__(self, spec: SamplerSpec):
        self.spec = spec.validate()
        self.backend = resolve_backend(spec)
        self.interpret = resolve_interpret(spec)
        g = spec.graph
        self.graph = g
        # single-device closures only: under a mesh the engine holds its
        # own tables, split over the devices
        self._color = self._edges = None
        if spec.mesh is None:
            self._color = jnp.asarray(g.color)
            self._edges = jnp.asarray(g.edges)
        nbr_idx, nbr_mask = g.neighbor_table()
        slot_ij, slot_ji = g.edge_slots(nbr_idx)
        self._nbr = (nbr_idx, nbr_mask, slot_ij, slot_ji)
        self._fault_cm, self._fault_cv, self._alive_edges = \
            self._compile_faults()
        self._noise_init, self._noise_step = self._make_noise()
        self._flip_fn = self._make_flip_fn()
        self._engine = self._tables = None
        self._band_program = False
        if spec.mesh is not None:
            # multi-device execution: the partition plan, the sync-policy
            # launch loop, and the shard_map'd sweep live in
            # core/distributed.ShardedEngine; the closures below delegate
            # to it with identical array contracts (incl. the fault hooks:
            # stuck spins ride the clamp path below, flips and stuck LFSR
            # bits are regenerated per shard from global coordinates)
            from repro.core.distributed import ShardedEngine
            self._engine = ShardedEngine(
                g, spec.mesh, spec.partitioning(), spec.noise,
                spec.decimation, spec.chains, sync=spec.sync_policy(),
                backend=self.backend, interpret=self.interpret,
                faults=spec.faults)
            self._tables = self._engine.tables
            # a band-resident graph programs band by band on its devices
            self._band_program = (self._engine.band_resident
                                  and spec.sparse_native
                                  and spec.faults is None)
        self.default_betas = (
            None if spec.schedule is None
            else spec.schedule.betas(spec.chains))
        self._fns: dict = {}

    @property
    def partition_plan(self):
        """The compile-time `core.distributed.RowPartition` of a sharded
        Session (None when mesh=None) — the public handle for halo /
        boundary accounting (`distributed.halo_bytes_per_sweep`)."""
        return None if self._engine is None else self._engine.plan

    # ------------------------------------------------------------------
    # construction helpers
    # ------------------------------------------------------------------
    def _compile_faults(self):
        """Static fault draw -> device arrays the closures close over.

        Stuck-at-spin faults become a (N,) clamp mask + values merged into
        every entry point's clamp arguments (the same machinery the CD
        positive phase and the sharded frozen-column path use, which is
        what makes the injection bit-exact across all backends).  Dead and
        saturated couplers become the (E,) alive mask that gates the CD
        gradient — their DACs cannot take an update.
        """
        f = self.spec.faults
        n, n_edges = self.graph.n_nodes, self.graph.n_edges
        cm = cv = alive = None
        if f is not None and f.stuck_nodes:
            cm_np = np.zeros((n,), bool)
            cv_np = np.zeros((n,), np.float32)
            cm_np[list(f.stuck_nodes)] = True
            cv_np[list(f.stuck_nodes)] = np.asarray(f.stuck_values,
                                                    np.float32)
            cm, cv = jnp.asarray(cm_np), jnp.asarray(cv_np)
        if f is not None and f.faulty_edges:
            alive_np = np.ones((n_edges,), np.float32)
            alive_np[list(f.faulty_edges)] = 0.0
            alive = jnp.asarray(alive_np)
        return cm, cv, alive

    def _merge_faults(self, m, cm, cv):
        """Fold the stuck-spin fault clamp into a caller's clamp args.

        The stuck values are written into ``m`` up front, so a mask-only
        (freeze-in-place) caller clamp stays mask-only; explicit caller
        values are overridden at fault positions — a latched p-bit reads
        its latched value even when driven by data.
        """
        fm, fv = self._fault_cm, self._fault_cv
        if fm is None:
            return m, cm, cv
        m = jnp.where(fm, fv, m.astype(jnp.float32)).astype(m.dtype)
        if cm is None:
            return m, fm, None
        cm2 = jnp.asarray(cm) | fm
        if cv is None:
            return m, cm2, None
        return m, cm2, jnp.where(fm, fv, jnp.asarray(cv))

    def _make_noise(self) -> tuple[Callable, pbit.NoiseFn]:
        spec = self.spec
        if spec.noise == "lfsr":
            init, step = pbit.make_lfsr_noise(spec.graph, spec.chains,
                                              spec.decimation)
            return self._wrap_lfsr_stuck(init, step)
        if spec.noise == "counter":
            return pbit.make_counter_noise(spec.chains, spec.graph.n_nodes)
        step = pbit.make_philox_noise(spec.chains, spec.graph.n_nodes)
        return (lambda key: key), step

    def _wrap_lfsr_stuck(self, init0, step0):
        """Degraded-RNG fault: force register bits of named per-cell LFSRs
        to 0/1 after every decimated clock (and at seeding), then read the
        uniforms from the forced state."""
        f = self.spec.faults
        if f is None or not f.lfsr_stuck:
            return init0, step0
        from repro.core import lfsr as lfsr_mod
        n_cells = self.graph.n_nodes // 8
        s0 = np.zeros((n_cells,), np.uint32)
        s1 = np.zeros((n_cells,), np.uint32)
        for cell, m0, m1 in f.lfsr_stuck:
            if not 0 <= int(cell) < n_cells:
                raise ValueError(
                    f"lfsr_stuck cell {cell} out of range for "
                    f"{n_cells} unit cells")
            s0[int(cell)] |= np.uint32(m0)
            s1[int(cell)] |= np.uint32(m1)
        s0j, s1j = jnp.asarray(s0), jnp.asarray(s1)
        perm = jnp.asarray(np.asarray(step0.spec.gather_perm))
        dec = self.spec.decimation

        def fix(state):
            return (state & ~s0j) | s1j

        def init(key):
            return fix(init0(key))

        def step(state):
            st = fix(lfsr_mod.lfsr_step_n(state, dec))
            u = jnp.take(lfsr_mod.flat_cell_uniforms(st), perm, axis=-1)
            return st, u

        step.spec = step0.spec
        return init, step

    def _make_flip_fn(self):
        """Seeded transient-flip hook (api.Faults.flip_prob).

        Draws from a stream *salted away from* the sampling noise —
        counter noise XORs the seed, philox folds a constant into the key
        — addressed by the pre-half-sweep noise state, so injecting flips
        never perturbs the underlying Gibbs stream and the same fault draw
        reproduces across backends (and across shards, which regenerate
        the same hash from global (chain, node) coordinates).
        """
        from repro.api.faults import FLIP_FOLD, FLIP_SALT
        f = self.spec.faults
        if f is None or f.flip_prob <= 0.0:
            return None
        p = float(f.flip_prob)
        if self.spec.noise == "counter":
            from repro.core import lfsr as lfsr_mod
            rows = jnp.arange(self.spec.chains, dtype=jnp.uint32)[:, None]
            cols = jnp.arange(self.graph.n_nodes,
                              dtype=jnp.uint32)[None, :]
            thresh = jnp.uint32(round(p * 65536.0))
            salt = jnp.uint32((int(f.flip_seed) ^ FLIP_SALT) & 0xFFFFFFFF)

            def flip(ns0):
                bits = lfsr_mod.counter_bits(ns0[0] ^ salt, ns0[1],
                                             rows, cols)
                return ((bits >> jnp.uint32(16))
                        & jnp.uint32(0xFFFF)) < thresh

            return flip
        if self.spec.noise == "philox":
            shape = (self.spec.chains, self.graph.n_nodes)
            fold = (FLIP_FOLD ^ int(f.flip_seed)) & 0x7FFFFFFF

            def flip(ns0):
                return jax.random.bernoulli(
                    jax.random.fold_in(ns0, fold), p, shape)

            return flip
        return None  # lfsr noise + flips rejected by spec validation

    def _fn(self, key, builder, *args):
        fn = self._fns.get(key)
        if fn is None:
            fn = builder(*args)
            self._fns[key] = fn
        return fn

    def _jit(self, impl, name: str, *, donate_spins: bool = False):
        """`named_jit` of ``impl(tables, ...)``, returned with the
        tables bound.

        A single-device Session binds None, so its closures keep their
        signatures.  A sharded one binds the engine's static tables, which
        then enter the compiled program as sharded operands, never as
        constants.  ``donate_spins`` donates the spins (the argument after
        the chip or program), so a call holds one buffer of them."""
        if self._engine is None:
            return named_jit(functools.partial(impl, None), name)
        kw = {"donate_argnums": (2,)} if donate_spins else {}
        return _Bound(named_jit(impl, name, **kw), self._tables)

    def _dispatch(self, fn, *args):
        """Call a closure; a sharded Session's host dispatch is the
        ``repro.dist.sample`` span."""
        if self._engine is None:
            return fn(*args)
        with span("dist.sample"):
            return fn(*args)

    def _program_in_jit(self, tables, mismatch, J_edge_codes, h_codes):
        """`program_edges` inside a jitted closure, with the chip instance
        as an operand: band by band on a band-resident mesh (the (E,)
        codes gathered into each band's incident edges), else the global
        slot scatter."""
        if self._band_program:
            parts = jnp.take(jnp.asarray(J_edge_codes),
                             tables["edge_ids"], axis=0)
            return self._engine.program(tables, mismatch, self.spec.hw,
                                        self.spec.w_scale, parts, h_codes)
        return program_edges(self.spec.replace(mismatch=mismatch),
                             J_edge_codes, h_codes, tables=self._nbr)

    def _betas(self, betas) -> jax.Array:
        if betas is None:
            if self.default_betas is None:
                raise ValueError(
                    "this Session's spec has no schedule; pass betas "
                    "explicitly or build the spec with schedule=")
            return self.default_betas
        return jnp.asarray(betas, jnp.float32)

    # ------------------------------------------------------------------
    # state initialization (explicit key threading)
    # ------------------------------------------------------------------
    def random_spins(self, key: jax.Array) -> jax.Array:
        """(B, N) spins of +-1 drawn from ``key``; on a band-resident mesh
        each device draws only its band (the draw is partitionable, so it
        equals the single-device one)."""
        sharding = None if self._engine is None \
            else self._engine.spin_sharding
        if sharding is None:
            return pbit.random_spins(key, self.spec.chains,
                                     self.graph.n_nodes)
        fn = self._fn(("random_spins",), lambda: jax.jit(
            functools.partial(pbit.random_spins, batch=self.spec.chains,
                              n_nodes=self.graph.n_nodes),
            out_shardings=sharding))
        with span("dist.place"):
            return fn(key)

    def noise_state(self, key: jax.Array) -> jax.Array:
        sharding = None if self._engine is None \
            else self._engine.noise_sharding
        if sharding is None:
            return self._noise_init(key)
        fn = self._fn(("noise_state",), lambda: jax.jit(
            self._noise_init, out_shardings=sharding))
        with span("dist.place"):
            return fn(key)

    def init_state(self, key: jax.Array) -> SessionState:
        k1, k2 = jax.random.split(key)
        return SessionState(self.random_spins(k1), self.noise_state(k2))

    # ------------------------------------------------------------------
    # chip programming (dense or sparse-native, per the spec's mismatch)
    # ------------------------------------------------------------------
    def program(self, J_codes: jax.Array, h_codes: jax.Array,
                enable: jax.Array | None = None) -> EffectiveChip:
        """Program dense (n, n) symmetric 8-bit codes."""
        return program(self.spec, J_codes, h_codes, enable,
                       tables=self._nbr)

    def program_edges(self, J_edge_codes: jax.Array, h_codes: jax.Array
                      ) -> EffectiveChip:
        """Program per-edge codes (E,) — the CD master-weight layout.

        On a band-resident mesh the codes go from the host straight to the
        devices, each band's incident edges to its own device, and every
        device programs its band: the chip comes back split over the rows
        axis, and no device ever holds the whole edge list."""
        if not self._band_program:
            return program_edges(self.spec, J_edge_codes, h_codes,
                                 tables=self._nbr)
        with span("dist.place"):
            parts = self._engine.place_edge_codes(J_edge_codes)
            h = self._engine.place_nodes(h_codes)
        fn = self._fn(("program_bands",), self._jit,
                      self._band_program_impl, "program_edges")
        return fn(self.spec.mismatch, parts, h)

    def _band_program_impl(self, tables, mismatch, parts, h):
        return self._engine.program(tables, mismatch, self.spec.hw,
                                    self.spec.w_scale, parts, h)

    def program_master(self, Jm: jax.Array, hm: jax.Array) -> EffectiveChip:
        """Quantize float masters — edge-list (E,) or dense (n, n) — and
        program."""
        return program_master(self.spec, Jm, hm, tables=self._nbr)

    # ------------------------------------------------------------------
    # runtime weight streaming (program as operand, not constant)
    # ------------------------------------------------------------------
    def make_program(
        self,
        J_edge_codes: jax.Array,
        h_codes: jax.Array,
        *,
        mismatch=None,
        clamp_mask: jax.Array | None = None,
        clamp_values: jax.Array | None = None,
        betas: jax.Array | None = None,
    ) -> Program:
        """Package edge-list codes (E,) + bias codes (N,) as a runtime
        `Program` for `sample_program` / `sample_fleet`.

        Only shapes and the optional-field structure are compile-time;
        the values stream into an already-compiled executable.  An
        explicit ``mismatch`` must be the same type as the spec's (the
        dense/sparse programming route is a static property of the
        trace).
        """
        with span("session.make_program"):
            E, n = self.graph.n_edges, self.graph.n_nodes
            if self._band_program:
                # codes stay off any one device: h in its bands, the
                # edge list split evenly (each band gathers its edges
                # in-jit)
                J = self._engine.place_edges(J_edge_codes)
                h = self._engine.place_nodes(h_codes)
            else:
                J = jnp.asarray(J_edge_codes)
                h = jnp.asarray(h_codes)
            if J.shape != (E,):
                raise ValueError(
                    f"J_edge_codes must be edge-list shaped ({E},), got "
                    f"{J.shape}; scatter dense codes to the edge list first")
            if h.shape != (n,):
                raise ValueError(f"h_codes must be ({n},), got {h.shape}")
            if mismatch is not None and \
                    type(mismatch) is not type(self.spec.mismatch):
                raise ValueError(
                    f"program mismatch type {type(mismatch).__name__} does "
                    f"not match the spec's "
                    f"{type(self.spec.mismatch).__name__}; the dense/sparse "
                    f"programming route is baked into the trace")
            if clamp_mask is not None:
                clamp_mask = jnp.asarray(clamp_mask)
                if clamp_values is not None:
                    clamp_values = jnp.asarray(clamp_values, jnp.float32)
            elif clamp_values is not None:
                raise ValueError("clamp_values without clamp_mask")
            if betas is not None:
                betas = jnp.asarray(betas, jnp.float32)
            return Program(J_codes=J, h_codes=h, mismatch=mismatch,
                           clamp_mask=clamp_mask, clamp_values=clamp_values,
                           betas=betas)

    def sample_program(
        self,
        prog: Program,
        m: jax.Array,
        noise_state: jax.Array,
        betas: jax.Array | None = None,
        *,
        collect: bool = False,
    ) -> tuple[jax.Array, jax.Array, jax.Array | None]:
        """`sample`, with the chip programmed *inside* the jit from a
        runtime `Program`: (m', state', traj|None).

        One executable per optional-field structure serves every program
        on this Session's spec — swapping problems is an O(E) host→device
        copy, never a retrace (benchmark cell `chip440.sample` sends a
        fresh program every call).
        Beta priority: explicit ``betas`` arg > ``prog.betas`` > the
        spec's schedule.
        """
        with span("session.sample_program"):
            if betas is None and prog.betas is None:
                betas = self._betas(None)
            elif betas is not None:
                betas = jnp.asarray(betas, jnp.float32)
            fn = self._fn(("sample_program", collect),
                          self._build_sample_program, collect)
            return self._dispatch(fn, prog, m, noise_state, betas)

    def _build_sample_program(self, collect: bool):
        def impl(tables, prog, m, ns, betas):
            mm = self.spec.mismatch if prog.mismatch is None \
                else prog.mismatch
            chip = self._program_in_jit(tables, mm, prog.J_codes,
                                        prog.h_codes)
            b = betas if betas is not None else prog.betas
            m, cm, cv = self._merge_faults(m, prog.clamp_mask,
                                           prog.clamp_values)
            if self._engine is not None:
                return self._engine.sample(tables, chip, m, ns, b, cm, cv,
                                           collect)
            return pbit.gibbs_sample(
                chip, self._color, m, b, ns, self._noise_step,
                clamp_mask=cm, clamp_values=cv, collect=collect,
                backend=self.backend, interpret=self.interpret,
                flip_fn=self._flip_fn)

        # one jit: a changed optional-field structure (clamps, mismatch,
        # program-borne betas) retraces, changed values never do
        return self._jit(impl, "sample_program")

    def sample_fleet(
        self,
        progs: Program,
        m: jax.Array,
        noise_state: jax.Array,
        betas: jax.Array | None = None,
    ) -> tuple[jax.Array, jax.Array, jax.Array | None]:
        """Run a stacked K-program fleet (see `api.stack_programs`)
        through ONE executable: (m'[K, B, N], state'[K, ...], None).

        ``m`` / ``noise_state`` carry a leading K axis; ``betas`` (or the
        spec schedule) is shared across the fleet unless the programs
        carry their own.  Fused backends demote to their bit-exact scan
        siblings under vmap, so the fleet result is bit-identical to K
        sequential `sample_program` calls.  Single-device only — shard a
        fleet across a mesh by giving each device its own Session.
        """
        if self._engine is not None:
            raise ValueError(
                "sample_fleet runs on single-device Sessions; a sharded "
                "mesh already owns the device axis — run one fleet per "
                "device instead")
        if betas is not None:
            betas = jnp.asarray(betas, jnp.float32)
        elif progs.betas is None:
            betas = self._betas(None)
        fn = self._fn(("sample_fleet",), self._build_sample_fleet)
        return fn(progs, m, noise_state, betas)

    def _build_sample_fleet(self):
        backend = _FLEET_BACKEND.get(self.backend, self.backend)

        def one(prog, m, ns, betas):
            chip = program_chip(self.spec, prog, tables=self._nbr)
            b = betas if betas is not None else prog.betas
            m, cm, cv = self._merge_faults(m, prog.clamp_mask,
                                           prog.clamp_values)
            return pbit.gibbs_sample(
                chip, self._color, m, b, ns, self._noise_step,
                clamp_mask=cm, clamp_values=cv, collect=False,
                backend=backend, interpret=self.interpret,
                flip_fn=self._flip_fn)

        return named_jit(jax.vmap(one, in_axes=(0, 0, 0, None)),
                         "sample_fleet")

    # ------------------------------------------------------------------
    # sampling closures
    # ------------------------------------------------------------------
    def sample(
        self,
        chip: EffectiveChip,
        m: jax.Array,
        noise_state: jax.Array,
        betas: jax.Array | None = None,
        *,
        clamp_mask: jax.Array | None = None,
        clamp_values: jax.Array | None = None,
        collect: bool = False,
    ) -> tuple[jax.Array, jax.Array, jax.Array | None]:
        """Run the schedule (or explicit ``betas``): (m', state', traj|None).

        ``collect=True`` returns the (S, B, N) per-sweep trajectory and
        forces the scan path (the fused engines cannot emit it).
        """
        betas = self._betas(betas)
        clamped = clamp_mask is not None
        # band-resident spins are consumed in place: the output reuses
        # their buffer, and the caller's array is gone after the call
        donate = self._engine is not None and self._engine.holds(m)
        fn = self._fn(("sample", collect, clamped, donate),
                      self._build_sample, collect, clamped, donate)
        if clamped:
            return self._dispatch(fn, chip, m, noise_state, betas,
                                  clamp_mask, clamp_values)
        return self._dispatch(fn, chip, m, noise_state, betas)

    def _build_sample(self, collect: bool, clamped: bool,
                      donate: bool = False):
        def impl(tables, chip, m, ns, betas, cm=None, cv=None):
            m, cm, cv = self._merge_faults(m, cm, cv)
            if self._engine is not None:
                return self._engine.sample(tables, chip, m, ns, betas, cm,
                                           cv, collect)
            return pbit.gibbs_sample(
                chip, self._color, m, betas, ns, self._noise_step,
                clamp_mask=cm, clamp_values=cv, collect=collect,
                backend=self.backend, interpret=self.interpret,
                flip_fn=self._flip_fn)

        return self._jit(impl, "sample", donate_spins=donate)

    def stats(
        self,
        chip: EffectiveChip,
        m: jax.Array,
        noise_state: jax.Array,
        n_sweeps: int,
        burn_in: int,
        *,
        clamp_mask: jax.Array | None = None,
        clamp_values: jax.Array | None = None,
        beta: float | None = None,
    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
        """On-line first/second moments at the spec's base beta:
        (mean_spin[N], mean_edge_corr[E], m', noise_state')."""
        beta = self.spec.beta if beta is None else float(beta)
        clamped = clamp_mask is not None
        fn = self._fn(("stats", n_sweeps, burn_in, beta, clamped),
                      self._build_stats, n_sweeps, burn_in, beta, clamped)
        if clamped:
            return self._dispatch(fn, chip, m, noise_state, clamp_mask,
                                  clamp_values)
        return self._dispatch(fn, chip, m, noise_state)

    def _build_stats(self, n_sweeps, burn_in, beta, clamped):
        def impl(tables, chip, m, ns, cm=None, cv=None):
            m, cm, cv = self._merge_faults(m, cm, cv)
            if self._engine is not None:
                return self._engine.stats(tables, chip, m, ns, beta,
                                          n_sweeps, burn_in, cm, cv)
            return pbit.gibbs_stats(
                chip, self._color, m, beta, n_sweeps, burn_in, ns,
                self._noise_step, self._edges, clamp_mask=cm,
                clamp_values=cv, backend=self.backend,
                interpret=self.interpret, flip_fn=self._flip_fn)

        return self._jit(impl, "stats")

    def visible_hist(
        self,
        chip: EffectiveChip,
        m: jax.Array,
        noise_state: jax.Array,
        visible_idx: np.ndarray,
        burn_in: int,
        betas: jax.Array | None = None,
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Streaming visible-pattern histogram: (counts[2^nv], m', state')."""
        betas = self._betas(betas)
        vis_key = tuple(int(i) for i in np.asarray(visible_idx))
        fn = self._fn(("hist", vis_key, burn_in),
                      self._build_hist, np.asarray(visible_idx), burn_in)
        return self._dispatch(fn, chip, m, noise_state, betas)

    def _build_hist(self, visible_idx, burn_in):
        return self._jit(self._hist_impl(visible_idx, burn_in),
                         "visible_hist")

    def _hist_impl(self, visible_idx, burn_in):
        def impl(tables, chip, m, ns, betas):
            m, cm, cv = self._merge_faults(m, None, None)
            if self._engine is not None:
                return self._engine.visible_hist(tables, chip, m, ns, betas,
                                                 burn_in, visible_idx,
                                                 cm, cv)
            return pbit.gibbs_visible_hist(
                chip, self._color, m, betas, burn_in, ns, self._noise_step,
                visible_idx, backend=self.backend,
                interpret=self.interpret, clamp_mask=cm, clamp_values=cv,
                flip_fn=self._flip_fn)

        return impl

    def master_visible_hist(
        self,
        Jm: jax.Array,
        hm: jax.Array,
        key: jax.Array,
        visible_idx: np.ndarray,
        burn_in: int,
    ) -> jax.Array:
        """Program float masters and histogram the visible spins from
        fresh chains under the spec's schedule: counts[2^nv], in one
        dispatch.

        Equals `program_master`, then ``split(key)`` into the chains'
        spins (`random_spins`) and noise state (`noise_state`), then
        `visible_hist`, bit for bit; the mismatch draw enters as an
        operand, as in `make_cd_step`.
        """
        vis_key = tuple(int(i) for i in np.asarray(visible_idx))
        fn = self._fn(("master_hist", vis_key, burn_in),
                      self._build_master_hist, np.asarray(visible_idx),
                      burn_in)
        return self._dispatch(fn, self.spec.mismatch, Jm, hm, key,
                              self._betas(None))

    def _build_master_hist(self, visible_idx, burn_in):
        hist = self._hist_impl(visible_idx, burn_in)

        def impl(tables, mismatch, Jm, hm, key, betas):
            Jm = jnp.asarray(Jm)
            if Jm.ndim == 1:
                chip = self._program_in_jit(tables, mismatch,
                                            quantize_codes(Jm),
                                            quantize_codes(hm))
            else:
                chip = program_master(self.spec.replace(mismatch=mismatch),
                                      Jm, hm, tables=self._nbr)
            k1, k2 = jax.random.split(key)
            counts, _, _ = hist(tables, chip,
                                pbit.random_spins(k1, self.spec.chains,
                                                  self.graph.n_nodes),
                                self._noise_init(k2), betas)
            return counts

        return self._jit(impl, "cd_eval")

    # ------------------------------------------------------------------
    # contrastive divergence (the in-situ learning closure)
    # ------------------------------------------------------------------
    def make_cd_step(self, cfg, visible_idx: np.ndarray):
        """Build the jitted one-epoch CD update (paper Fig. 7a).

        ``cfg`` is a core.cd.CDConfig (duck-typed).  Returns
        step(Jm, hm, data_vis, m, noise_state, vel) ->
        (Jm, hm, m, noise_state, vel, metrics) with (E,) edge-list master
        couplings; both Gibbs phases and the weight update run inside one
        jit through this session's backend.

        The mismatch draw enters the jit as an *operand* (the returned
        step partially applies the spec's draw; ``step.with_mismatch``
        exposes the raw (mismatch, Jm, hm, ...) entry), so the compiled
        executable carries no chip-instance constants — the substrate of
        `make_cd_fleet_step` and of zero-retrace hardware-in-the-loop
        epochs.
        """
        return self._fn(self._cd_key("cd_step", cfg, visible_idx),
                        self._build_cd_step, cfg, np.asarray(visible_idx))

    def make_cd_epoch(self, cfg, visible_idx: np.ndarray):
        """Build the jitted CD epoch: the data draw and `make_cd_step`'s
        update in one dispatch.

        Returns epoch(key, p, codes, Jm, hm, m, noise_state, vel) ->
        (key', ke, Jm, hm, m, noise_state, vel, metrics).  The epoch
        splits ``key`` into (key', kd, ke), draws ``chains`` rows of
        ``codes`` (the 2^nv visible configurations) with probabilities
        ``p`` under ``kd``, and runs the step on them; ``ke`` is the
        epoch's evaluation key, and ``metrics`` the step's metrics
        stacked in `CD_METRICS` order, left on the device.
        ``epoch.with_mismatch`` is the raw (mismatch, key, ...) entry.
        """
        return self._fn(self._cd_key("cd_epoch", cfg, visible_idx),
                        self._build_cd_epoch, cfg, np.asarray(visible_idx))

    def _cd_key(self, kind, cfg, visible_idx) -> tuple:
        """The cache key of a CD builder; checks the chain count."""
        if cfg.chains != self.spec.chains:
            raise ValueError(
                f"CDConfig.chains={cfg.chains} but this Session was "
                f"compiled for chains={self.spec.chains}; build the "
                f"session with chains=cfg.chains")
        return (kind, cfg.lr, cfg.cd_k, cfg.pos_sweeps, cfg.burn_in,
                cfg.h_lr_scale, cfg.weight_decay, cfg.persistent,
                cfg.momentum,
                tuple(int(i) for i in np.asarray(visible_idx)))

    def make_cd_fleet_step(self, cfg, visible_idx: np.ndarray):
        """Build the K-replica hardware-aware CD step: one executable,
        per-chip mismatch draws streamed in as operands.

        Returns step(mismatches, Jm, hm, data_vis, m, noise_state, vel)
        -> (Jm, hm, m, noise_state, vel, metrics) where every argument
        except ``data_vis`` (the shared data batch) carries a leading K
        fleet axis: ``mismatches`` is a stacked draw (see
        `core.cd.PBitMachine.fleet_mismatch`), Jm (K, E), hm (K, N),
        m (K, B, N), vel a pair of (K, E)/(K, N) arrays; metrics come
        back stacked per chip.  Fused backends demote to their bit-exact
        scan siblings under vmap, so fleet epochs match K sequential
        per-chip epochs bit-for-bit.
        """
        if self._engine is not None:
            raise ValueError(
                "fleet CD runs on single-device Sessions; a sharded mesh "
                "already owns the device axis — run one fleet per device")
        key = self._cd_key("cd_fleet", cfg, visible_idx)

        def build():
            step_mm = functools.partial(self._build_cd_step_mm(
                cfg, np.asarray(visible_idx), fleet=True), None)
            return named_jit(jax.vmap(step_mm,
                                      in_axes=(0, 0, 0, None, 0, 0, 0)),
                             "cd_fleet_step")

        return self._fn(key, build)

    def _build_cd_step(self, cfg, visible_idx):
        step_mm = self._jit(self._build_cd_step_mm(cfg, visible_idx,
                                                   fleet=False), "cd_step")
        mm = self.spec.mismatch

        def step(Jm, hm, data_vis, m, noise_state, vel):
            return step_mm(mm, Jm, hm, data_vis, m, noise_state, vel)

        step.with_mismatch = step_mm
        return step

    def _build_cd_epoch(self, cfg, visible_idx):
        step_mm = self._build_cd_step_mm(cfg, visible_idx, fleet=False)

        def epoch_mm(tables, mismatch, key, p, codes, Jm, hm, m,
                     noise_state, vel):
            key, ke, data_vis = cd_data_draw(key, p, codes, cfg.chains)
            Jm, hm, m, noise_state, vel, metrics = step_mm(
                tables, mismatch, Jm, hm, data_vis, m, noise_state, vel)
            return (key, ke, Jm, hm, m, noise_state, vel,
                    jnp.stack([metrics[k] for k in CD_METRICS]))

        epoch_mm = self._jit(epoch_mm, "cd_epoch")
        mm = self.spec.mismatch

        def epoch(key, p, codes, Jm, hm, m, noise_state, vel):
            return epoch_mm(mm, key, p, codes, Jm, hm, m, noise_state, vel)

        epoch.with_mismatch = epoch_mm
        return epoch

    def _build_cd_step_mm(self, cfg, visible_idx, *, fleet: bool):
        from repro.core.hardware import WMAX, WMIN

        n = self.graph.n_nodes
        vis = jnp.asarray(visible_idx)
        clamp_mask = jnp.zeros((n,), bool).at[vis].set(True)
        beta = self.spec.beta
        backend = (_FLEET_BACKEND.get(self.backend, self.backend)
                   if fleet else self.backend)

        def phase(tables, chip, m0, n_sweeps, ns, cm=None, cv=None):
            if self._engine is not None:
                # sharded phases: rows partition halo-exchanges, a chains
                # partition runs the Gibbs replicas per-device and
                # psum-reduces the (E,) gradient moments once per phase
                return self._engine.stats(tables, chip, m0, ns, beta,
                                          n_sweeps, cfg.burn_in, cm, cv)
            return pbit.gibbs_stats(
                chip, self._color, m0, beta, n_sweeps, cfg.burn_in, ns,
                self._noise_step, self._edges, clamp_mask=cm,
                clamp_values=cv, backend=backend,
                interpret=self.interpret, flip_fn=self._flip_fn)

        def step(tables, mismatch, Jm, hm, data_vis, m, noise_state, vel):
            chip = self._program_in_jit(tables, mismatch, quantize_codes(Jm),
                                        quantize_codes(hm))
            clamp_values = jnp.zeros((cfg.chains, n), jnp.float32)
            clamp_values = clamp_values.at[:, vis].set(data_vis)

            # positive phase: visibles pinned to data (stuck p-bits win
            # over the data drive — the latch reads its latched value)
            m, pos_cm, pos_cv = self._merge_faults(m, clamp_mask,
                                                   clamp_values)
            pos_s, pos_c, m_pos, noise_state = phase(
                tables, chip, m, cfg.pos_sweeps, noise_state, pos_cm, pos_cv)
            # negative phase: CD-k from the positive-phase state, or from
            # the persistent chains (PCD)
            neg_init = m if cfg.persistent else m_pos
            neg_s, neg_c, m_neg, noise_state = phase(
                tables, chip, neg_init, cfg.cd_k, noise_state, self._fault_cm,
                None)

            gJ = pos_c - neg_c
            gh = pos_s - neg_s
            if self._alive_edges is not None:
                # dead/saturated couplers carry no reprogrammable DAC:
                # their gradient is noise and would only corrupt momentum
                gJ = gJ * self._alive_edges
            # skip-and-log guard: a non-finite gradient (bad data batch,
            # device fault) must never reach the master weights
            ok = jnp.isfinite(gJ).all() & jnp.isfinite(gh).all()
            vel_J, vel_h = vel
            vel_J_new = cfg.momentum * vel_J + gJ
            vel_h_new = cfg.momentum * vel_h + gh
            Jm_new = (1.0 - cfg.weight_decay) * Jm + cfg.lr * vel_J_new
            hm_new = (1.0 - cfg.weight_decay) * hm \
                + cfg.lr * cfg.h_lr_scale * vel_h_new
            Jm_new = jnp.clip(Jm_new, WMIN, WMAX)
            hm_new = jnp.clip(hm_new, WMIN, WMAX)
            Jm = jnp.where(ok, Jm_new, Jm)
            hm = jnp.where(ok, hm_new, hm)
            vel_J = jnp.where(ok, vel_J_new, vel_J)
            vel_h = jnp.where(ok, vel_h_new, vel_h)
            # the chains too: NaNs in m_neg would poison the next epoch
            m_out = jnp.where(ok, m_neg, m)
            metrics = {
                "corr_err": jnp.abs(pos_c - neg_c).mean(),
                "mean_err": jnp.abs(pos_s - neg_s).mean(),
                "update_skipped": 1.0 - ok.astype(jnp.float32),
            }
            return Jm, hm, m_out, noise_state, (vel_J, vel_h), metrics

        return step
