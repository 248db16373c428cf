"""In-situ hardware-aware learning: contrastive divergence through the chip.

Paper Fig. 7a: the training loop alternates
  positive phase  — clamp the visible nodes to data, Gibbs-sample the hidden
                    nodes *on the (mismatched) chip*, measure <m_i m_j>+.
  negative phase  — release the clamp, free-run the chip k sweeps, measure
                    <m_i m_j>-.
  update          — J_ij += lr (<mimj>+ - <mimj>-) on the physical couplers,
                    h_i  += lr (<mi>+   - <mi>-),
then re-program the 8-bit weight DACs.  Because both phases are sampled
through the same analog non-idealities, the learned weights absorb the
mismatch — the paper's central claim (we verify it in
tests/test_cd.py::test_hardware_aware_beats_transfer).

Weights are kept as float "master" values (the host accumulator) and
quantized to signed 8-bit DAC codes on every (re)program, matching the
chip's digital weight storage.  The master couplings live on the *edge
list* — one float per physical coupler, exactly the chip's weight-DAC
count — so the CD update is O(E) and never touches an (n, n) matrix.

All sampling and programming goes through `repro.api.Session`:
`PBitMachine` is the convenience wrapper that owns the chip description
(graph + mismatch + noise/backend choices) and hands out compiled
sessions; the schedule handling and backend dispatch that used to live
here are gone (see docs/api.md).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core import energy as energy_mod
from repro.runtime import fault_tolerance
from repro.runtime.spans import span
from repro.core import pbit
from repro.core.chimera import ChimeraGraph
from repro.core.hardware import (
    EffectiveChip,
    HardwareConfig,
    Mismatch,
    SparseMismatch,
    quantize_codes,  # noqa: F401  (re-export: legacy import site)
    sample_mismatch,
    sample_mismatch_sparse,
)


def _band_shardings(graph, mesh, partition):
    """Where a sparse chip instance for ``mesh`` lives: each device holds
    its band of it on a band-resident graph; None (one device) otherwise
    or without a mesh."""
    if mesh is None:
        return None
    from repro.core import distributed as dist
    part = partition if partition is not None else api.Partition()
    if not dist.band_resident(graph,
                              dist.partition_size(mesh, part.rows_axes)):
        return None
    return dist.mismatch_shardings(mesh, part)


@dataclasses.dataclass
class PBitMachine:
    """A (simulated) chip instance: graph + mismatch + programmable weights.

    With a dense `Mismatch` the machine programs the full analog model and
    attaches the Chimera-native slot view (a gather — bit-identical
    entries), so every backend runs on the same physics.  With a
    `SparseMismatch` (create(..., sparse=True)) nothing O(n²) is ever
    built: the machine only supports the sparse backends, which is the
    point — it instantiates at lattice sizes where the dense model cannot.

    The machine is sugar over `api.SamplerSpec`/`api.Session`:
    ``sampler_spec()`` builds the declarative spec, ``session()`` compiles
    (and caches) sessions per (schedule, chains).
    """

    graph: ChimeraGraph
    hw: HardwareConfig
    mismatch: Mismatch | SparseMismatch
    beta: float = 1.0
    noise: str = "philox"   # "philox" | "counter" | "lfsr"
    backend: str = "auto"   # auto | ref | pallas | fused | sparse | fused_sparse
    w_scale: float = 0.05  # weight-LSB -> coupling units (ext. resistor knob)
    mesh: object = None     # jax.sharding.Mesh -> multi-device sessions
    partition: object = None  # api.Partition; None -> rows over "data"
    sync: object = None     # api.Sync; None -> bit-exact barrier policy
    faults: object = None   # api.Faults; None -> healthy chip

    @staticmethod
    def create(graph: ChimeraGraph, key: jax.Array,
               hw: HardwareConfig | None = None, sparse: bool = False,
               **kw) -> "PBitMachine":
        hw = hw or HardwareConfig()
        if sparse:
            nbr_idx, _ = graph.neighbor_table()
            mism = sample_mismatch_sparse(
                key, graph.n_nodes, nbr_idx.shape[0], hw,
                _band_shardings(graph, kw.get("mesh"), kw.get("partition")))
            # sparse-native chips have no dense W: the dense backends
            # cannot run them, so don't let "auto" resolve to one
            kw.setdefault("backend", "sparse")
        else:
            mism = sample_mismatch(key, graph.n_nodes, hw)
        return PBitMachine(graph=graph, hw=hw, mismatch=mism, **kw)

    @property
    def sparse_native(self) -> bool:
        """True when only the O(D·n) slot model exists (no dense W ever)."""
        return isinstance(self.mismatch, SparseMismatch)

    def to_sparse(self) -> "PBitMachine":
        """Sparse-native twin reproducing THIS chip instance exactly.

        The dense machine's mismatch is gathered into the O(D·n) slot
        layout (`SparseMismatch.from_dense` — bit-identical on-graph
        entries), so programming the same codes on both machines yields
        the same effective couplings and the same spin trajectories for
        the same noise stream.  This is the bridge from a dense
        chip-scale model to lattice-scale sharded sampling: characterize
        a chip with the full (n, n) analog model, then scale out on the
        slot layout without changing the physics by a single bit.
        """
        if self.sparse_native:
            return self
        nbr_idx, _, _, _ = self.neighbor_tables()
        backend = {"ref": "sparse", "pallas": "sparse",
                   "fused": "fused_sparse"}.get(self.backend, self.backend)
        return dataclasses.replace(
            self, mismatch=SparseMismatch.from_dense(self.mismatch,
                                                     jnp.asarray(nbr_idx)),
            backend=backend)

    def neighbor_tables(self):
        """(nbr_idx, nbr_mask, slot_ij, slot_ji), cached per machine."""
        nt = getattr(self, "_nbr_tables", None)
        if nt is None:
            nbr_idx, nbr_mask = self.graph.neighbor_table()
            slot_ij, slot_ji = self.graph.edge_slots(nbr_idx)
            nt = (nbr_idx, nbr_mask, slot_ij, slot_ji)
            self._nbr_tables = nt
        return nt

    # -- the api seam ----------------------------------------------------
    def sampler_spec(self, schedule: api.Schedule | None = None,
                     chains: int = 256, **kw) -> api.SamplerSpec:
        """The declarative `api.SamplerSpec` for this chip instance."""
        kw.setdefault("mesh", self.mesh)
        kw.setdefault("partition", self.partition)
        kw.setdefault("sync", self.sync)
        kw.setdefault("faults", self.faults)
        return api.SamplerSpec(
            graph=self.graph, hw=self.hw, mismatch=self.mismatch,
            noise=self.noise, backend=self.backend, schedule=schedule,
            chains=chains, beta=self.beta, w_scale=self.w_scale, **kw)

    def session(self, schedule: api.Schedule | None = None,
                chains: int = 256) -> api.Session:
        """Compiled `api.Session`, cached per (schedule, chains)."""
        cache = getattr(self, "_sessions", None)
        if cache is None:
            cache = {}
            self._sessions = cache
        key = (schedule, chains)
        ses = cache.get(key)
        if ses is None:
            ses = api.Session(self.sampler_spec(schedule, chains))
            cache[key] = ses
        return ses

    # -- programming (the spec-level api layer: needs no backend/noise
    # resolution, so it works even where a full Session would not compile)
    def program(self, J_codes: jax.Array, h_codes: jax.Array,
                enable: jax.Array | None = None) -> EffectiveChip:
        """Program dense (n, n) symmetric codes (chip-scale convenience)."""
        return api.program(self.sampler_spec(), J_codes, h_codes, enable,
                           tables=self.neighbor_tables())

    def program_edges(self, J_edge_codes: jax.Array, h_codes: jax.Array
                      ) -> EffectiveChip:
        """Program per-edge codes (E,) — the CD master-weight layout."""
        return api.program_edges(self.sampler_spec(), J_edge_codes, h_codes,
                                 tables=self.neighbor_tables())

    def program_master(self, Jm: jax.Array, hm: jax.Array) -> EffectiveChip:
        """Quantize float master weights — edge-list (E,) or dense (n, n) —
        to 8-bit DAC codes and program."""
        return api.program_master(self.sampler_spec(), Jm, hm,
                                  tables=self.neighbor_tables())

    def fleet_mismatch(self, key: jax.Array, n_chips: int):
        """Draw a stacked (K, ...) fleet of chip-instance mismatches.

        Every leaf gains a leading ``n_chips`` axis; the result feeds the
        fleet axis directly (`make_cd_fleet_step`,
        `api.Session.make_cd_fleet_step`), running K virtual chips of
        this machine's SKU through one compiled executable.  Draw k
        equals `sample_mismatch[_sparse](split(key)[k], ...)`, so a
        fleet member is bit-identical to a standalone machine built from
        the same subkey.
        """
        keys = jax.random.split(key, n_chips)
        if self.sparse_native:
            nbr_idx, _ = self.graph.neighbor_table()
            draws = [sample_mismatch_sparse(k, self.graph.n_nodes,
                                            nbr_idx.shape[0], self.hw)
                     for k in keys]
        else:
            draws = [sample_mismatch(k, self.graph.n_nodes, self.hw)
                     for k in keys]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *draws)

    def noise_fn(self, key: jax.Array, batch: int):
        """Legacy noise constructor: (state, step).  New code should use
        ``session().noise_state(key)`` — the Session owns the step fn."""
        if self.noise == "lfsr":
            init, step = pbit.make_lfsr_noise(self.graph, batch)
            return init(key), step
        if self.noise == "counter":
            init, step = pbit.make_counter_noise(batch, self.graph.n_nodes)
            return init(key), step
        return key, pbit.make_philox_noise(batch, self.graph.n_nodes)


@dataclasses.dataclass
class CDConfig:
    lr: float = 4.0            # in DAC-LSB units per unit correlation error
    cd_k: int = 10             # sweeps per negative phase
    pos_sweeps: int = 10       # sweeps with visibles clamped
    burn_in: int = 2
    chains: int = 256          # parallel Gibbs chains (chip reprogram batches)
    epochs: int = 60
    h_lr_scale: float = 1.0
    weight_decay: float = 0.0
    # beyond-paper options (EXPERIMENTS §Perf extensions):
    persistent: bool = False   # PCD: negative chains persist across epochs
                               # instead of restarting from the data clamp
    momentum: float = 0.0      # heavy-ball on the correlation gradient


def make_cd_step(machine: PBitMachine, cfg: CDConfig,
                 visible_idx: np.ndarray):
    """Build the jitted one-epoch CD update (shim over `Session.make_cd_step`).

    Returns step(Jm, hm, data_vis, m, noise_state, vel) ->
      (Jm, hm, m, noise_state, vel, metrics) where Jm is the (n_edges,)
    float master couplings (one per physical coupler — no (n, n) matrix
    anywhere in the update), hm the (n,) master biases, and data_vis
    (chains, n_visible) ±1 data samples for the positive phase.  The CD
    gradient is already an edge-list quantity (<m_i m_j>+ - <m_i m_j>-),
    so the weight update is a pure O(E) axpy.
    """
    return machine.session(chains=cfg.chains).make_cd_step(cfg, visible_idx)


def make_cd_fleet_step(machine: PBitMachine, cfg: CDConfig,
                       visible_idx: np.ndarray):
    """Build the K-replica CD step (shim over `Session.make_cd_fleet_step`).

    Trains K virtual chip instances — K mismatch draws of the machine's
    SKU, stacked by `PBitMachine.fleet_mismatch` — through ONE compiled
    executable, each with its own master weights, chains, and noise
    stream but a shared data batch:

        step(mismatches, Jm[K,E], hm[K,N], data_vis, m[K,B,N],
             noise_state[K,...], vel) -> same, stacked

    Zero retraces across epochs *and* across chips: the mismatch is a
    streamed operand, not a baked constant, so fleet-scale
    hardware-aware learning costs one compile.
    """
    return machine.session(chains=cfg.chains).make_cd_fleet_step(
        cfg, visible_idx)


def sample_visible_dist(machine: PBitMachine, Jm, hm,
                        visible_idx: np.ndarray, key: jax.Array,
                        chains: int = 256, sweeps: int = 200,
                        burn_in: int = 20) -> np.ndarray:
    """Free-run the programmed chip and histogram the visible marginal.

    Jm may be edge-list (E,) or dense (n, n) float master weights.  The
    histogram streams (`Session.visible_hist`): on the scan backends it
    folds into the sweep loop, on the fused backends it accumulates inside
    the kernel — the (sweeps, chains, N) trajectory never materializes.
    Programming, the chains' initial state from ``key`` and the histogram
    are one compiled call (`Session.master_visible_hist`).
    """
    with span("cd.eval.hist"):
        session = machine.session(
            schedule=api.Constant(beta=machine.beta, n_sweeps=sweeps),
            chains=chains)
        counts = np.asarray(session.master_visible_hist(
            Jm, hm, key, visible_idx, burn_in), np.float64)
    return counts / max(counts.sum(), 1.0)


@dataclasses.dataclass
class CDResult:
    """Learned master weights.  ``J_edges`` is the native (E,) edge-list
    form; ``Jm`` reconstructs the symmetric dense matrix for small-n
    reporting and eval."""

    J_edges: np.ndarray
    hm: np.ndarray
    kl_history: list
    metric_history: list
    edges: np.ndarray
    n_nodes: int

    @property
    def Jm(self) -> np.ndarray:
        J = np.zeros((self.n_nodes, self.n_nodes), np.float32)
        J[self.edges[:, 0], self.edges[:, 1]] = self.J_edges
        J[self.edges[:, 1], self.edges[:, 0]] = self.J_edges
        return J


def train_cd(
    machine: PBitMachine,
    visible_idx: np.ndarray,
    target_dist: np.ndarray,
    cfg: CDConfig,
    key: jax.Array,
    eval_every: int = 10,
    verbose: bool = False,
) -> CDResult:
    """Full in-situ CD training loop against a target visible distribution.

    Each epoch is one compiled dispatch (`Session.make_cd_epoch`: the data
    draw and the update), and so is each evaluation
    (`sample_visible_dist`); the host never waits on the device between
    evaluations.  The epochs' metrics stay on the device until the next
    evaluation fetches them all at once; the last epoch always evaluates,
    so the run ends with none left.

    Profiler spans: ``repro.cd.train`` holds ``cd.setup``, one
    ``cd.epoch`` per epoch (``cd.step``), one ``cd.eval`` per evaluation
    (``cd.sync``, the one fetch of the metrics, ``cd.eval.hist`` and
    ``cd.eval.kl``) and ``cd.result``.
    """
    with span("cd.train"):
        with span("cd.setup"):
            g = machine.graph
            n, nv = g.n_nodes, len(visible_idx)
            session = machine.session(chains=cfg.chains)
            epoch_fn = session.make_cd_epoch(cfg, visible_idx)

            key, k1, k2, k3 = jax.random.split(key, 4)
            Jm = jnp.zeros((g.n_edges,), jnp.float32)
            hm = jnp.zeros((n,), jnp.float32)
            m = session.random_spins(k1)
            noise_state = session.noise_state(k2)

            # the visible configs (code order) and their target
            # probabilities, moved to the device once per run
            codes = jnp.asarray(energy_mod.all_states(nv))
            p = jnp.asarray(target_dist, jnp.float32)
            vel = (jnp.zeros((g.n_edges,), jnp.float32),
                   jnp.zeros((n,), jnp.float32))
        kl_hist, met_hist, pending = [], [], []
        for epoch in range(cfg.epochs):
            with span("cd.epoch", epoch=epoch):
                with span("cd.step"):
                    key, ke, Jm, hm, m, noise_state, vel, metrics = \
                        epoch_fn(key, p, codes, Jm, hm, m, noise_state, vel)
                pending.append(metrics)
            if (epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1:
                with span("cd.eval", epoch=epoch):
                    with span("cd.sync"):
                        met_hist += [
                            dict(zip(api.CD_METRICS, map(float, row)))
                            for row in jax.device_get(pending)]
                        pending = []
                    emp = sample_visible_dist(machine, Jm, hm, visible_idx,
                                              ke)
                    with span("cd.eval.kl"):
                        kl = energy_mod.kl_divergence(
                            np.asarray(target_dist), emp)
                kl_hist.append((epoch + 1, kl))
                if verbose:
                    print(f"epoch {epoch+1:4d}  KL={kl:.4f}  "
                          f"corr_err={met_hist[-1]['corr_err']:.4f}")
        with span("cd.result"):
            return CDResult(np.asarray(Jm), np.asarray(hm), kl_hist,
                            met_hist, edges=np.asarray(g.edges), n_nodes=n)


# -- crash-safe training ---------------------------------------------------

@dataclasses.dataclass
class CDTrainState:
    """Everything CD training needs to resume bit-exactly after a crash:
    master weights, chain spins, the noise-generator state, optimizer
    velocity and the epoch counter.  Per-epoch randomness is *derived*
    (``fold_in(base_key, epoch)``), never threaded, so restoring this
    state replays the exact uninterrupted trajectory."""

    Jm: jax.Array
    hm: jax.Array
    m: jax.Array
    noise_state: jax.Array
    vel_J: jax.Array
    vel_h: jax.Array
    epoch: int = 0

    def tree(self, base_key) -> dict:
        """Checkpointable pytree (the epoch rides as the checkpoint step)."""
        return {"Jm": self.Jm, "hm": self.hm, "m": self.m,
                "noise_state": self.noise_state, "vel_J": self.vel_J,
                "vel_h": self.vel_h, "base_key": jnp.asarray(base_key)}

    @staticmethod
    def from_tree(tree: dict, epoch: int) -> "CDTrainState":
        return CDTrainState(
            Jm=jnp.asarray(tree["Jm"]), hm=jnp.asarray(tree["hm"]),
            m=jnp.asarray(tree["m"]),
            noise_state=jnp.asarray(tree["noise_state"]),
            vel_J=jnp.asarray(tree["vel_J"]),
            vel_h=jnp.asarray(tree["vel_h"]), epoch=epoch)


def _spec_fingerprint(machine: PBitMachine, cfg: CDConfig) -> dict:
    """What must match for a resumed run to continue the same trajectory."""
    return {"noise": machine.noise, "backend": machine.backend,
            "chains": int(cfg.chains), "n_nodes": int(machine.graph.n_nodes),
            "faults": repr(machine.faults)}


def train_cd_resilient(
    machine: PBitMachine,
    visible_idx: np.ndarray,
    target_dist: np.ndarray,
    cfg: CDConfig,
    key: jax.Array,
    *,
    ckpt_dir=None,
    save_every: int = 10,
    resume: bool = True,
    eval_every: int = 10,
    max_retries: int = 3,
    backoff_s: float = 0.05,
    watchdog=None,
    on_epoch_start=None,
    sleep=time.sleep,
    verbose: bool = False,
) -> CDResult:
    """`train_cd` hardened for long unattended runs on faulty virtual chips.

    Differences from the plain loop:
      * all per-epoch randomness is ``fold_in``-derived from ``key``, so a
        run resumed from a checkpoint is bit-identical to one that never
        crashed (tests/test_resilience.py kills a training subprocess
        mid-run and asserts equal master weights);
      * every ``save_every`` epochs the full `CDTrainState` is committed
        atomically via `repro.checkpoint` — with ``resume=True`` the loop
        picks up from the latest complete checkpoint in ``ckpt_dir`` after
        validating it came from the same spec (noise/backend/chains/faults);
      * each epoch runs under `retry_step` (TransientError -> exponential
        backoff) and feeds a `StragglerWatchdog` if one is passed;
      * the jitted step's NaN/Inf guard reports via the ``update_skipped``
        metric — skipped epochs leave the master weights untouched but
        still advance the noise stream, keeping resume determinism.

    ``on_epoch_start(epoch)`` is called inside the retried region — tests
    use it to raise TransientError or to kill the process at a chosen
    epoch.
    """
    g = machine.graph
    n, nv = g.n_nodes, len(visible_idx)
    session = machine.session(chains=cfg.chains)
    step = session.make_cd_step(cfg, visible_idx)

    base_key = jnp.asarray(key)
    k1, k2 = jax.random.split(jax.random.fold_in(key, 0))
    state = CDTrainState(
        Jm=jnp.zeros((g.n_edges,), jnp.float32),
        hm=jnp.zeros((n,), jnp.float32),
        m=session.random_spins(k1),
        noise_state=session.noise_state(k2),
        vel_J=jnp.zeros((g.n_edges,), jnp.float32),
        vel_h=jnp.zeros((n,), jnp.float32))
    kl_hist, met_hist = [], []

    ckpt_mod = None
    if ckpt_dir is not None:
        from repro.checkpoint import checkpoint as ckpt_mod
        if resume and ckpt_mod.latest_step(ckpt_dir) is not None:
            step_no, tree, extra = ckpt_mod.load(
                ckpt_dir, target=state.tree(base_key))
            fp, saved = _spec_fingerprint(machine, cfg), extra.get("spec", {})
            for k_, v in fp.items():
                if k_ in saved and saved[k_] != v:
                    raise ValueError(
                        f"checkpoint {ckpt_dir} was written by a different "
                        f"run: {k_}={saved[k_]!r} != {v!r}")
            if not np.array_equal(np.asarray(tree["base_key"]),
                                  np.asarray(base_key)):
                raise ValueError(
                    f"checkpoint {ckpt_dir} was written under a different "
                    "base key; resuming would fork the trajectory")
            state = CDTrainState.from_tree(tree, step_no)
            kl_hist = [tuple(x) for x in extra.get("kl_history", [])]
            met_hist = list(extra.get("metric_history", []))
            if verbose:
                print(f"resumed from epoch {step_no}")

    codes = energy_mod.all_states(nv)
    k_data, k_eval = jax.random.fold_in(key, 1), jax.random.fold_in(key, 2)

    def _save(epoch_done: int) -> None:
        ckpt_mod.save(ckpt_dir, epoch_done, state.tree(base_key),
                      extra={"kl_history": [list(x) for x in kl_hist],
                             "metric_history": met_hist,
                             "spec": _spec_fingerprint(machine, cfg)})

    for epoch in range(state.epoch, cfg.epochs):
        t0 = time.perf_counter()

        def one_epoch():
            if on_epoch_start is not None:
                on_epoch_start(epoch)
            idx = jax.random.choice(
                jax.random.fold_in(k_data, epoch), codes.shape[0],
                (cfg.chains,), p=jnp.asarray(target_dist))
            data_vis = jnp.asarray(codes)[idx]
            return step(state.Jm, state.hm, data_vis, state.m,
                        state.noise_state, (state.vel_J, state.vel_h))

        Jm, hm, m, noise_state, vel, metrics = fault_tolerance.retry_step(
            one_epoch, max_retries=max_retries, backoff_s=backoff_s,
            sleep=sleep)
        state = CDTrainState(Jm, hm, m, noise_state, vel[0], vel[1],
                             epoch + 1)
        met_hist.append({k_: float(v) for k_, v in metrics.items()})
        if met_hist[-1].get("update_skipped", 0.0) and verbose:
            print(f"epoch {epoch+1:4d}  non-finite gradient: update skipped")
        if watchdog is not None:
            watchdog.observe(epoch, time.perf_counter() - t0)
        if (epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1:
            emp = sample_visible_dist(machine, state.Jm, state.hm,
                                      visible_idx,
                                      jax.random.fold_in(k_eval, epoch))
            kl = energy_mod.kl_divergence(np.asarray(target_dist), emp)
            kl_hist.append((epoch + 1, kl))
            if verbose:
                print(f"epoch {epoch+1:4d}  KL={kl:.4f}")
        if ckpt_mod is not None and (
                (epoch + 1) % save_every == 0 or epoch == cfg.epochs - 1):
            _save(epoch + 1)
    return CDResult(np.asarray(state.Jm), np.asarray(state.hm), kl_hist,
                    met_hist, edges=np.asarray(g.edges), n_nodes=n)
