"""`SamplerService` — the resilient multi-tenant p-bit sampling service.

One process, many tenants, one chip model: requests carry a (small)
Chimera problem; the service embeds each into a shape bucket
(`serve.cache`), multiplexes compatible requests onto the *chains* axis
of a single resident-sweep launch (the measured 3.3–6x `sync_policies`
latency lever — one launch anneals every tenant's chains at once), and
returns each tenant its slice of the spins.

Control plane
-------------
* **Admission** — a bounded FIFO; `submit` raises `AdmissionError` when
  the queue is full (backpressure, never silent drops) and
  `CircuitOpenError` for tenants whose breaker is open.  Every admitted
  request is eventually *resolved* — completed, or terminally failed
  with a reason — there is no path that loses a ticket.
* **Deadlines** — per-request; requests whose deadline passes while
  queued resolve as ``deadline_exceeded`` without burning a launch, and
  late completions are flagged and fed to the tenant's circuit breaker.
* **Batching** — the queue head defines the launch group: every queued
  request with the same `program_digest` (same bucket chip, betas, clamp
  *mask*; clamp *values* are per-chain and free to differ) packs into
  the launch until ``capacity_chains`` is reached, FIFO order preserved
  for the rest.
* **Determinism** — launch ``seq`` numbers the batched launches; all RNG
  derives from ``fold_in(base_key, seq)``.  An identical admission
  sequence therefore produces identical results regardless of retries,
  replays, or mesh degradation (barrier-sync sharding is bit-exact vs
  single device), which is how the fault-schedule tests can demand
  bit-identical output from a faulted 2-device run and a clean
  single-device run.

Data plane resilience (see `serve.degrade`, `serve.faultplan`)
--------------------------------------------------------------
`TransientError` (link flap) is absorbed by `retry_step` with jittered
backoff; `ShardLostError` walks the degradation ladder (re-plan the row
partition on survivors, else single-device) and *replays* the launch
from its recorded ``seq`` — in-flight requests survive shard loss.  A
`StragglerWatchdog` flags slow launches.  ``healthz()``/``readyz()``
are the probe surface.

The service is deliberately synchronous: callers drive it with
``pump()`` (one launch) or ``drain()`` (until the queue is empty), which
keeps every test deterministic.  A thread or asyncio wrapper is a
five-line loop around ``pump``.
"""
from __future__ import annotations

import dataclasses
import random as _random
import time
from collections import Counter, deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core import pbit
from repro.core.chimera import ChimeraGraph
from repro.core.distributed import surviving_mesh
from repro.core.hardware import HardwareConfig, sample_mismatch_sparse
from repro.runtime.fault_tolerance import StragglerWatchdog, retry_step
from repro.runtime.spans import span
from repro.serve.cache import (
    DEFAULT_BUCKETS,
    CacheEntry,
    Embedding,
    SessionCache,
    bucket_shape,
    embed_graph,
    embed_program,
    make_bucket_graph,
    program_digest,
)
from repro.serve.degrade import ShardHealthMonitor, ShardLostError


class ServiceError(RuntimeError):
    """Base class for request-rejection errors raised by `submit`."""


class AdmissionError(ServiceError):
    """Queue full — backpressure; the client should retry later."""


class CircuitOpenError(ServiceError):
    """This tenant's circuit breaker is open (repeated deadline misses)."""


@dataclasses.dataclass
class SampleRequest:
    """One tenant's problem: a Chimera graph plus edge-list programming.

    ``betas`` (an explicit (S,) float array) overrides the
    ``n_sweeps``/``beta`` pair.  ``clamp_mask`` is (N,) over the
    *request* graph; ``clamp_values`` is (chains, N) — per-chain data,
    the multiplexing axis (think: same RBM chip, each chain clamped to a
    different tenant query).
    """

    tenant: str
    graph: ChimeraGraph
    J_codes: Any
    h_codes: Any
    chains: int = 1
    n_sweeps: int = 8
    beta: float = 1.0
    betas: Any = None
    clamp_mask: Any = None
    clamp_values: Any = None
    timeout_s: Optional[float] = None


@dataclasses.dataclass
class RequestResult:
    """Terminal state of an admitted request."""

    status: str                       # ok | deadline_exceeded | failed
    tenant: str
    spins: Optional[np.ndarray]       # (chains, n_request_nodes) ±1 float32
    degraded: bool = False            # ran after a shard loss
    deadline_missed: bool = False     # completed, but past its deadline
    error: Optional[str] = None
    t_admitted: float = 0.0
    t_finished: float = 0.0
    queue_s: float = 0.0              # admission -> launch start
    exec_s: float = 0.0               # launch wall time (shared by batch)
    attempts: int = 1                 # launch attempts incl. flap retries
    launch_seq: int = -1
    chain_offset: int = -1
    bucket_shape: Optional[tuple] = None
    bucket_fingerprint: Optional[str] = None
    launch_key: Optional[np.ndarray] = None  # raw key data: full replay
                                             # recipe (tests rebuild the
                                             # launch from it)
    request_id: int = -1              # the ``request`` of its submit span


class Ticket:
    """Handle returned by `submit`; resolved by `pump`/`drain`."""

    def __init__(self, req: SampleRequest, *, request_id: int,
                 deadline: Optional[float], t_admitted: float,
                 bshape: tuple[int, int], emb: Embedding, Jb: np.ndarray,
                 hb: np.ndarray, betas: np.ndarray,
                 bucket_mask: Optional[np.ndarray], digest: str):
        self.req = req
        self.request_id = request_id
        self.deadline = deadline
        self.t_admitted = t_admitted
        self.bshape = bshape
        self.emb = emb
        self.Jb = Jb
        self.hb = hb
        self.betas = betas
        self.bucket_mask = bucket_mask
        self.digest = digest
        self._result: Optional[RequestResult] = None

    @property
    def done(self) -> bool:
        return self._result is not None

    def result(self) -> RequestResult:
        if self._result is None:
            raise ServiceError(
                "request not resolved yet — drive the service with "
                "pump() or drain()")
        return self._result

    def _resolve(self, result: RequestResult) -> None:
        self._result = result


class CircuitBreaker:
    """Per-tenant closed -> open -> half-open breaker on deadline misses.

    ``threshold`` consecutive failures open the circuit for
    ``cooldown_s``; after cooldown one probe request is admitted
    (half-open) — success closes the circuit, failure reopens it
    immediately.  Protects other tenants' latency from one tenant whose
    problems chronically blow their deadlines.
    """

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._st: dict[str, dict] = {}

    def state(self, tenant: str, now: float) -> str:
        st = self._st.get(tenant)
        if st is None or st["open_until"] is None:
            return "closed"
        return "open" if now < st["open_until"] else "half_open"

    def allow(self, tenant: str, now: float) -> bool:
        s = self.state(tenant, now)
        if s == "open":
            return False
        if s == "half_open":
            self._st[tenant]["probing"] = True
        return True

    def record(self, tenant: str, ok: bool, now: float) -> None:
        if ok:
            self._st.pop(tenant, None)
            return
        st = self._st.setdefault(
            tenant, {"fails": 0, "open_until": None, "probing": False})
        st["fails"] += 1
        if st["probing"] or st["fails"] >= self.threshold:
            st["open_until"] = now + self.cooldown_s
            st["probing"] = False
            st["fails"] = 0

    def open_tenants(self, now: float) -> list[str]:
        return sorted(t for t in self._st
                      if self.state(t, now) == "open")


class SamplerService:
    """See module docstring.  All time sources (``clock``, ``sleep``,
    ``rng``) are injectable so the fault-schedule tests run with virtual
    time and recorded backoffs; none of them influence sampled results.
    """

    def __init__(self, *,
                 hw: Optional[HardwareConfig] = None,
                 mismatch_seed: int = 0,
                 seed: int = 0,
                 mesh: Any = None,
                 capacity_chains: int = 16,
                 max_queue: int = 64,
                 default_timeout_s: float = 60.0,
                 noise: str = "counter",
                 sync: Optional[api.Sync] = None,
                 buckets=DEFAULT_BUCKETS,
                 cache_capacity: int = 8,
                 breaker: Optional[CircuitBreaker] = None,
                 monitor: Optional[ShardHealthMonitor] = None,
                 injector: Any = None,
                 watchdog: Optional[StragglerWatchdog] = None,
                 max_retries: int = 3,
                 backoff_s: float = 0.05,
                 max_backoff_s: float = 2.0,
                 rng: Optional[_random.Random] = None,
                 clock=time.monotonic,
                 sleep=time.sleep,
                 interpret: Optional[bool] = None):
        if capacity_chains < 1:
            raise ValueError(
                f"capacity_chains must be >= 1, got {capacity_chains}")
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.hw = hw if hw is not None else HardwareConfig()
        self.mismatch_seed = mismatch_seed
        self._base_key = jax.random.PRNGKey(seed)
        self.mesh = mesh
        self.capacity_chains = capacity_chains
        self.max_queue = max_queue
        self.default_timeout_s = default_timeout_s
        self.noise = noise
        self.sync = sync
        self.buckets = tuple(tuple(b) for b in buckets)
        self.cache = SessionCache(cache_capacity)
        self.breaker = breaker if breaker is not None else CircuitBreaker()
        self.monitor = monitor
        self.injector = injector
        self.watchdog = (watchdog if watchdog is not None
                         else StragglerWatchdog(threshold=3.0))
        self.max_retries = max_retries
        self.backoff_s = backoff_s
        self.max_backoff_s = max_backoff_s
        self._rng = rng
        self._clock = clock
        self._sleep = sleep
        self.interpret = interpret
        self.state = "healthy" if mesh is not None else "single"
        self.metrics: Counter = Counter()
        self._queue: deque[Ticket] = deque()
        self._dead: set[int] = set()
        self._launch_seq = 0
        self._request_seq = 0
        self._bucket_graphs: dict[tuple, ChimeraGraph] = {}
        self._bucket_mismatch: dict[tuple, Any] = {}
        self._embeddings: dict[tuple, Embedding] = {}

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------
    def submit(self, req: SampleRequest) -> Ticket:
        # every submit takes an id, refused ones too: the ids of
        # admitted requests rise but may skip
        request_id = self._request_seq
        self._request_seq += 1
        with span("serve.submit", request=request_id):
            now = self._clock()
            if not self.breaker.allow(req.tenant, now):
                self.metrics["rejected_breaker"] += 1
                raise CircuitOpenError(
                    f"tenant {req.tenant!r}: circuit open after repeated "
                    f"deadline misses; retry after cooldown")
            if len(self._queue) >= self.max_queue:
                self.metrics["rejected_backpressure"] += 1
                raise AdmissionError(
                    f"admission queue full ({self.max_queue}); apply "
                    f"backpressure upstream and retry")
            if not (1 <= req.chains <= self.capacity_chains):
                raise ValueError(
                    f"chains={req.chains} out of range [1, "
                    f"{self.capacity_chains}] (capacity_chains)")
            bshape = bucket_shape(req.graph, self.buckets)
            emb = self._embedding(req.graph, bshape)
            J = np.asarray(req.J_codes, np.int32)
            h = np.asarray(req.h_codes, np.int32)
            if J.shape != (req.graph.edges.shape[0],):
                raise ValueError(
                    f"J_codes shape {J.shape} != (E,)="
                    f"({req.graph.edges.shape[0]},)")
            if h.shape != (req.graph.n_nodes,):
                raise ValueError(
                    f"h_codes shape {h.shape} != (N,)=({req.graph.n_nodes},)")
            Jb, hb = embed_program(emb, J, h)
            betas = self._canon_betas(req)
            bucket_mask = None
            if req.clamp_mask is not None:
                cm = np.asarray(req.clamp_mask, bool)
                if cm.shape != (req.graph.n_nodes,):
                    raise ValueError(
                        f"clamp_mask shape {cm.shape} != (N,)")
                cv = np.asarray(req.clamp_values, np.float32)
                if cv.shape != (req.chains, req.graph.n_nodes):
                    raise ValueError(
                        f"clamp_values shape {cv.shape} != (chains, N)="
                        f"({req.chains}, {req.graph.n_nodes})")
                bucket_mask = np.zeros(emb.bucket.n_nodes, bool)
                bucket_mask[emb.node_map] = cm
            timeout = (req.timeout_s if req.timeout_s is not None
                       else self.default_timeout_s)
            ticket = Ticket(
                req, request_id=request_id, deadline=now + timeout,
                t_admitted=now, bshape=bshape, emb=emb, Jb=Jb, hb=hb,
                betas=betas, bucket_mask=bucket_mask,
                digest=program_digest(bshape, Jb, hb, betas, bucket_mask))
            self._queue.append(ticket)
            self.metrics["admitted"] += 1
            return ticket

    def _canon_betas(self, req: SampleRequest) -> np.ndarray:
        if req.betas is not None:
            betas = np.asarray(req.betas, np.float32)
            if betas.ndim != 1 or betas.shape[0] < 1:
                raise ValueError(
                    f"betas must be a 1-D (S,) array, got {betas.shape}")
            return betas
        if req.n_sweeps < 1:
            raise ValueError(f"n_sweeps must be >= 1, got {req.n_sweeps}")
        return np.full(req.n_sweeps, req.beta, np.float32)

    def _embedding(self, graph: ChimeraGraph,
                   bshape: tuple[int, int]) -> Embedding:
        sig = (int(graph.rows), int(graph.cols), int(graph.k),
               tuple(sorted(tuple(c) for c in (graph.masked_cells or ()))),
               bshape)
        emb = self._embeddings.get(sig)
        if emb is None:
            bg = self._bucket_graph(bshape)
            emb = embed_graph(graph, bg)
            self._embeddings[sig] = emb
        return emb

    # ------------------------------------------------------------------
    # bucket specs (the compile-cache key surface)
    # ------------------------------------------------------------------
    def _bucket_graph(self, bshape: tuple[int, int]) -> ChimeraGraph:
        bg = self._bucket_graphs.get(bshape)
        if bg is None:
            bg = make_bucket_graph(*bshape)
            self._bucket_graphs[bshape] = bg
        return bg

    def _mismatch_for(self, bshape: tuple[int, int], bg: ChimeraGraph):
        # one virtual chip instance per bucket (a bucket is a chip SKU):
        # derived from (mismatch_seed, bucket shape) so it is identical
        # across mesh states — degradation must not change the physics
        mm = self._bucket_mismatch.get(bshape)
        if mm is None:
            nbr_idx, _ = bg.neighbor_table()
            key = jax.random.fold_in(
                jax.random.PRNGKey(self.mismatch_seed),
                bshape[0] * 1009 + bshape[1])
            mm = sample_mismatch_sparse(key, bg.n_nodes, nbr_idx.shape[0],
                                        self.hw)
            self._bucket_mismatch[bshape] = mm
        return mm

    def bucket_spec(self, graph: ChimeraGraph) -> api.SamplerSpec:
        """The spec a request on ``graph`` compiles under *right now*
        (current mesh state) — public so tests and benchmarks can rebuild
        the exact Session a result came from."""
        return self._spec_for_bucket(bucket_shape(graph, self.buckets))

    def _spec_for_bucket(self, bshape: tuple[int, int]) -> api.SamplerSpec:
        bg = self._bucket_graph(bshape)
        mm = self._mismatch_for(bshape, bg)
        kw: dict = {}
        mesh = self.mesh
        if mesh is not None:
            n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
            # a bucket with fewer cell rows than devices cannot row-shard;
            # it runs single-device even while the service is healthy
            if n_dev <= bg.rows:
                kw = dict(mesh=mesh,
                          partition=api.Partition(rows=mesh.axis_names[0]))
                if self.sync is not None:
                    kw["sync"] = self.sync
        return api.SamplerSpec(
            graph=bg, hw=self.hw, mismatch=mm, noise=self.noise,
            backend="sparse", chains=self.capacity_chains,
            interpret=self.interpret, **kw)

    def _entry_for(self, bshape: tuple[int, int]
                   ) -> tuple[str, CacheEntry]:
        spec = self._spec_for_bucket(bshape)
        fp = api.spec_fingerprint(spec)

        def build() -> CacheEntry:
            with span("serve.build"):
                return CacheEntry(session=api.Session(spec), spec=spec,
                                  embeddable=spec.graph,
                                  meshed=spec.mesh is not None)

        return fp, self.cache.get_or_build(fp, build)

    # ------------------------------------------------------------------
    # the pump: one batched launch per call
    # ------------------------------------------------------------------
    def pump(self) -> int:
        """Form one launch group from the queue head, execute it, resolve
        its tickets.  Returns the number of requests resolved (including
        queue-expired ones)."""
        with span("serve.pump"):
            with span("serve.batch"):
                batch, expired = self._next_batch()
            if not batch:
                return expired
            self._execute(batch)
            return expired + len(batch)

    def drain(self) -> int:
        """Pump until the queue is empty; returns requests resolved."""
        total = 0
        while self._queue:
            total += self.pump()
        return total

    def _next_batch(self) -> tuple[list[Ticket], int]:
        now = self._clock()
        batch: list[Ticket] = []
        free = self.capacity_chains
        rest: deque[Ticket] = deque()
        expired = 0
        while self._queue:
            t = self._queue.popleft()
            if now > t.deadline:
                self._resolve_expired(t, now)
                expired += 1
                continue
            if not batch:
                batch.append(t)
                free -= t.req.chains
            elif (t.digest == batch[0].digest
                  and t.req.chains <= free):
                batch.append(t)
                free -= t.req.chains
            else:
                rest.append(t)
        self._queue = rest
        return batch, expired

    def _resolve_expired(self, t: Ticket, now: float) -> None:
        self.metrics["deadline_expired_queued"] += 1
        self.breaker.record(t.req.tenant, ok=False, now=now)
        t._resolve(RequestResult(
            status="deadline_exceeded", tenant=t.req.tenant, spins=None,
            error="deadline passed while queued",
            t_admitted=t.t_admitted, t_finished=now,
            queue_s=now - t.t_admitted, request_id=t.request_id))

    def _execute(self, batch: list[Ticket]) -> None:
        seq = self._launch_seq
        self._launch_seq += 1
        key = jax.random.fold_in(self._base_key, seq)
        t_start = self._clock()
        attempts = [0]

        def attempt():
            attempts[0] += 1
            return self._attempt(batch, seq, key)

        with span("serve.launch", seq=seq, requests=len(batch),
                  chains=sum(t.req.chains for t in batch)):
            n_dev = 0 if self.mesh is None else int(
                np.prod([self.mesh.shape[a] for a in self.mesh.axis_names]))
            replays = 0
            while True:
                try:
                    m, fp, entry = retry_step(
                        attempt, max_retries=self.max_retries,
                        backoff_s=self.backoff_s,
                        max_backoff_s=self.max_backoff_s,
                        rng=self._rng, sleep=self._sleep)
                    break
                except ShardLostError as e:
                    replays += 1
                    self._degrade(e.dead)
                    if replays > n_dev + 1:  # can't: the ladder is finite
                        now = self._clock()
                        for t in batch:
                            t._resolve(RequestResult(
                                status="failed", tenant=t.req.tenant,
                                spins=None, error=str(e),
                                t_admitted=t.t_admitted, t_finished=now,
                                request_id=t.request_id))
                        self.metrics["failed"] += len(batch)
                        return
        with span("serve.resolve"):
            now = self._clock()
            exec_s = now - t_start
            self.metrics["launches"] += 1
            self.metrics["launch_attempts_total"] += attempts[0]
            if attempts[0] > 1:
                self.metrics["transient_retries"] += attempts[0] - 1
            if replays:
                self.metrics["replays"] += replays
            if self.watchdog.observe(seq, exec_s):
                self.metrics["stragglers_flagged"] += 1
            degraded = bool(self._dead)
            off = 0
            for t in batch:
                spins = np.asarray(
                    m[off:off + t.req.chains][:, t.emb.node_map])
                missed = now > t.deadline
                self.breaker.record(t.req.tenant, ok=not missed, now=now)
                self.metrics["completed"] += 1
                if missed:
                    self.metrics["deadline_missed_exec"] += 1
                t._resolve(RequestResult(
                    status="ok", tenant=t.req.tenant, spins=spins,
                    degraded=degraded, deadline_missed=missed,
                    t_admitted=t.t_admitted, t_finished=now,
                    queue_s=t_start - t.t_admitted, exec_s=exec_s,
                    attempts=attempts[0], launch_seq=seq, chain_offset=off,
                    bucket_shape=t.bshape, bucket_fingerprint=fp,
                    launch_key=np.asarray(key), request_id=t.request_id))
                off += t.req.chains

    def _attempt(self, batch: list[Ticket], seq: int, key):
        if self.injector is not None:
            delay = self.injector.on_launch(seq, self)  # may raise Transient
            if delay:
                self.metrics["straggler_delay_injected"] += 1
                self._sleep(delay)
        self._check_shards()
        head = batch[0]
        with span("serve.entry"):
            fp, entry = self._entry_for(head.bshape)
        bg = entry.embeddable
        with span("serve.inputs"):
            km, kn = jax.random.split(key)
            m0 = pbit.random_spins(km, self.capacity_chains, bg.n_nodes)
            ns = entry.session.noise_state(kn)
            cm, cv = self._assemble_clamps(batch, bg)
            # scatter codes, call: the program (codes + clamps) is a
            # runtime operand of the bucket Session's one compiled
            # executable — no per-digest chip cache, no retrace on a new
            # tenant problem
            prog = entry.session.make_program(
                jnp.asarray(head.Jb), jnp.asarray(head.hb),
                clamp_mask=cm, clamp_values=cv)
            betas = jnp.asarray(head.betas)
        with span("serve.dispatch"):
            m, _, _ = entry.session.sample_program(prog, m0, ns, betas)
        # materialize on the host *inside* the attempt: a shard dying
        # mid-launch surfaces here, where the replay machinery can see it
        with span("serve.fetch"):
            m = np.asarray(m)
        return m, fp, entry

    def _assemble_clamps(self, batch: list[Ticket], bg: ChimeraGraph):
        head = batch[0]
        if head.bucket_mask is None:
            return None, None
        cv = np.zeros((self.capacity_chains, bg.n_nodes), np.float32)
        off = 0
        for t in batch:
            vals = np.asarray(t.req.clamp_values, np.float32)
            cv[off:off + t.req.chains, t.emb.node_map] = vals
            off += t.req.chains
        return jnp.asarray(head.bucket_mask), jnp.asarray(cv)

    # ------------------------------------------------------------------
    # degradation ladder
    # ------------------------------------------------------------------
    def _check_shards(self) -> None:
        if self.mesh is None or self.monitor is None:
            return
        mesh_ids = {int(d.id)
                    for d in np.asarray(self.mesh.devices).reshape(-1)}
        dead = set(self.monitor.dead_shards()) & mesh_ids
        if dead:
            raise ShardLostError(dead)

    def _degrade(self, dead) -> None:
        self._dead.update(int(d) for d in dead)
        self.metrics["shard_losses"] += len(set(dead))
        self.metrics["degradations"] += 1
        self.mesh = surviving_mesh(self.mesh, self._dead)
        self.state = "degraded" if self.mesh is not None else "single"
        # every Session compiled against the dead mesh is garbage now;
        # survivors recompile lazily on the re-planned mesh.  That
        # recompile rebuilds the whole engine closure — including the
        # fused exchange windows when the sync policy has mid-launch
        # exchange points — and the numpy row plan itself
        # comes from the memoized plan_row_partition cache, so a re-plan
        # onto a previously-seen shard count never recomputes it
        self.metrics["cache_invalidated"] += self.cache.invalidate(
            lambda fp, e: e.meshed)

    # ------------------------------------------------------------------
    # probes
    # ------------------------------------------------------------------
    def healthz(self) -> dict:
        now = self._clock()
        mesh_ids = ([] if self.mesh is None else
                    [int(d.id)
                     for d in np.asarray(self.mesh.devices).reshape(-1)])
        return {
            "state": self.state,
            "mesh_devices": mesh_ids,
            "dead_shards": sorted(self._dead),
            "queue_depth": len(self._queue),
            "open_breakers": self.breaker.open_tenants(now),
            "cache": self.cache.stats(),
            "stragglers": len(self.watchdog.flagged),
            "metrics": dict(self.metrics),
        }

    def readyz(self) -> bool:
        """Ready = still admitting: queue has room.  Degraded and
        single-device states stay ready — capacity shrank, correctness
        did not."""
        return len(self._queue) < self.max_queue
