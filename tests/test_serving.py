"""The resilient multi-tenant sampling service (repro.serve).

Headline contract (ISSUE acceptance): under a scripted fault schedule —
kill one of two shards mid-stream, a transient link flap, an injected
straggler — the service completes every admitted request with zero
drops, and the degraded results are bit-identical to a clean
single-device service run (the barrier sync policy makes sharded
execution bit-exact, and every launch's RNG derives from (seed, launch
seq), so degradation changes latency, never results).  That runs as a
forced 2-device subprocess; everything else — admission control,
deadlines, batching, the compile cache, the breaker, the fault plan —
is tested in-process.
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import pbit
from repro.core.chimera import make_chimera
from repro.core.distributed import surviving_mesh
from repro.runtime.fault_tolerance import TransientError
from repro.serve import (
    AdmissionError,
    CircuitBreaker,
    CircuitOpenError,
    FaultEvent,
    FaultInjector,
    FaultPlan,
    SampleRequest,
    SamplerService,
    ServiceError,
    SessionCache,
    ShardHealthMonitor,
    ShardLostError,
    bucket_shape,
    embed_graph,
    embed_program,
    make_bucket_graph,
)
from repro.serve.cache import CacheEntry

ROOT = Path(__file__).resolve().parent.parent
SUBPROC_ENV = {"PYTHONPATH": f"{ROOT}/src", "PATH": "/usr/bin:/bin",
               "HOME": "/root", "JAX_PLATFORMS": "cpu"}


def _request(g, tenant="t0", chains=2, seed=0, **kw):
    rng = np.random.default_rng(seed)
    J = rng.integers(-40, 41, size=g.edges.shape[0], dtype=np.int32)
    h = rng.integers(-10, 11, size=g.n_nodes, dtype=np.int32)
    kw.setdefault("n_sweeps", 4)
    return SampleRequest(tenant=tenant, graph=g, J_codes=J, h_codes=h,
                         chains=chains, **kw)


# ---------------------------------------------------------------------------
# spec fingerprint (the compile-cache key)
# ---------------------------------------------------------------------------
class TestFingerprint:
    def _spec(self, **kw):
        from repro.core.cd import PBitMachine
        from repro.core.hardware import HardwareConfig
        g = kw.pop("graph", make_chimera(1, 1))
        m = PBitMachine.create(g, jax.random.PRNGKey(0), HardwareConfig(),
                               sparse=True, noise="counter")
        return api.SamplerSpec(graph=g, hw=m.hw, mismatch=m.mismatch,
                               noise="counter", backend="sparse",
                               chains=4, **kw)

    def test_equal_specs_share_fingerprint(self):
        assert self._spec().fingerprint() == self._spec().fingerprint()
        assert api.spec_fingerprint(self._spec()) == \
            api.spec_fingerprint(self._spec())

    def test_fingerprint_discriminates(self):
        base = api.spec_fingerprint(self._spec())
        assert api.spec_fingerprint(
            self._spec(graph=make_chimera(2, 2))) != base
        assert api.spec_fingerprint(
            self._spec().replace(chains=8)) != base
        assert api.spec_fingerprint(
            self._spec().replace(beta=2.0)) != base
        assert api.spec_fingerprint(
            self._spec().replace(noise="lfsr")) != base

    def test_fingerprint_canonicalizes_backend_resolution(self, monkeypatch):
        """auto and the name it resolves to must share an entry."""
        monkeypatch.delenv("REPRO_PBIT_BACKEND", raising=False)
        spec = self._spec()
        resolved = api.resolve_backend(spec.replace(backend="auto"))
        assert api.spec_fingerprint(spec.replace(backend="auto")) == \
            api.spec_fingerprint(spec.replace(backend=resolved))

    def test_fingerprint_is_shape_bucket_key(self):
        """Programs and mismatch draws are runtime operands of the
        compiled closures (`Session.sample_program`), so two chip
        instances of one SKU must SHARE a cache entry; only the mismatch
        *structure* (dense vs sparse — a different programming route in
        the trace) may discriminate."""
        from repro.core.cd import PBitMachine
        from repro.core.hardware import HardwareConfig
        g = make_chimera(1, 1)
        hw = HardwareConfig()
        a = PBitMachine.create(g, jax.random.PRNGKey(0), hw, sparse=True,
                               noise="counter")
        b = PBitMachine.create(g, jax.random.PRNGKey(1), hw, sparse=True,
                               noise="counter")
        sa = api.SamplerSpec(graph=g, hw=hw, mismatch=a.mismatch,
                             noise="counter", backend="sparse", chains=4)
        sb = api.SamplerSpec(graph=g, hw=hw, mismatch=b.mismatch,
                             noise="counter", backend="sparse", chains=4)
        assert api.spec_fingerprint(sa) == api.spec_fingerprint(sb)
        # a dense-mismatch spec traces a different programming route:
        # its fingerprint must NOT alias the sparse one
        dense = PBitMachine.create(g, jax.random.PRNGKey(0), hw,
                                   noise="counter")
        sd = api.SamplerSpec(graph=g, hw=hw, mismatch=dense.mismatch,
                             noise="counter", backend="sparse", chains=4,
                             attach_sparse=True)
        assert api.spec_fingerprint(sd) != api.spec_fingerprint(sa)


# ---------------------------------------------------------------------------
# shape buckets + embedding
# ---------------------------------------------------------------------------
class TestEmbedding:
    def test_bucket_ladder(self):
        assert bucket_shape(make_chimera(1, 1)) == (1, 1)
        assert bucket_shape(make_chimera(2, 1)) == (2, 2)
        assert bucket_shape(make_chimera(3, 4)) == (4, 4)
        assert bucket_shape(make_chimera(7, 8)) == (7, 8)
        # oversize -> dedicated bucket
        assert bucket_shape(make_chimera(9, 9)) == (9, 9)

    def test_embedding_structure(self):
        g = make_chimera(1, 2)
        bucket = make_bucket_graph(2, 2)
        emb = embed_graph(g, bucket)
        assert emb.node_map.shape == (g.n_nodes,)
        assert len(np.unique(emb.node_map)) == g.n_nodes
        # every mapped edge's endpoints agree with the node map
        be = np.sort(np.asarray(bucket.edges)[emb.edge_map], axis=1)
        ge = np.sort(emb.node_map[np.asarray(g.edges)], axis=1)
        np.testing.assert_array_equal(be, ge)
        # coordinates are preserved
        np.testing.assert_array_equal(
            np.asarray(bucket.node_r)[emb.node_map], np.asarray(g.node_r))
        np.testing.assert_array_equal(
            np.asarray(bucket.node_k)[emb.node_map], np.asarray(g.node_k))

    def test_embed_program_zeroes_outside_region(self):
        g = make_chimera(1, 1)
        bucket = make_bucket_graph(2, 2)
        emb = embed_graph(g, bucket)
        J = np.arange(1, g.edges.shape[0] + 1, dtype=np.int32)
        h = np.arange(1, g.n_nodes + 1, dtype=np.int32)
        Jb, hb = embed_program(emb, J, h)
        np.testing.assert_array_equal(Jb[emb.edge_map], J)
        np.testing.assert_array_equal(hb[emb.node_map], h)
        out_e = np.setdiff1d(np.arange(Jb.shape[0]), emb.edge_map)
        out_n = np.setdiff1d(np.arange(hb.shape[0]), emb.node_map)
        assert (Jb[out_e] == 0).all() and (hb[out_n] == 0).all()

    def test_embedding_rejects_misfits(self):
        with pytest.raises(ValueError, match="does not fit"):
            embed_graph(make_chimera(3, 3), make_bucket_graph(2, 2))
        with pytest.raises(ValueError, match="k="):
            embed_graph(make_chimera(1, 1, k=2), make_bucket_graph(1, 1))

    def test_masked_graph_embeds(self):
        g = make_chimera(2, 2, masked_cells=((1, 1),))
        emb = embed_graph(g, make_bucket_graph(2, 2))
        assert emb.node_map.shape == (g.n_nodes,)


# ---------------------------------------------------------------------------
# LRU session cache
# ---------------------------------------------------------------------------
class TestSessionCache:
    def _entry(self, meshed=False):
        return CacheEntry(session=None, spec=None, embeddable=None,
                          meshed=meshed)

    def test_lru_eviction_and_counters(self):
        c = SessionCache(capacity=2)
        c.get_or_build("a", self._entry)
        c.get_or_build("b", self._entry)
        assert c.get("a") is not None          # refresh a
        c.get_or_build("c", self._entry)       # evicts b (LRU)
        assert c.get("b") is None
        assert c.get("a") is not None and c.get("c") is not None
        s = c.stats()
        assert s["evictions"] == 1 and s["misses"] == 3
        assert s["size"] == 2

    def test_invalidate_predicate(self):
        c = SessionCache(capacity=4)
        c.get_or_build("m", lambda: self._entry(meshed=True))
        c.get_or_build("s", lambda: self._entry(meshed=False))
        assert c.invalidate(lambda fp, e: e.meshed) == 1
        assert c.get("m") is None and c.get("s") is not None


# ---------------------------------------------------------------------------
# fault plans
# ---------------------------------------------------------------------------
class TestFaultPlan:
    def test_json_round_trip(self):
        plan = FaultPlan.make([
            FaultEvent(step=3, kind="kill_shard", shard=1),
            FaultEvent(step=1, kind="link_flap", flaps=2),
            FaultEvent(step=2, kind="straggler", delay_s=0.05),
        ])
        again = FaultPlan.from_json(plan.to_json())
        assert again == plan
        assert [e.step for e in again.events] == [1, 2, 3]  # sorted
        assert again.events_at(3)[0].shard == 1

    def test_validation(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultEvent(step=0, kind="meteor")
        with pytest.raises(ValueError, match="shard"):
            FaultEvent(step=0, kind="kill_shard")
        with pytest.raises(ValueError, match="list"):
            FaultPlan.from_json("{}")

    def test_injector_sequencing(self):
        class StubService:
            monitor = ShardHealthMonitor()

        svc = StubService()
        inj = FaultInjector(FaultPlan.make([
            FaultEvent(step=1, kind="link_flap", flaps=2),
            FaultEvent(step=2, kind="straggler", delay_s=0.5),
            FaultEvent(step=3, kind="kill_shard", shard=7),
        ]))
        assert inj.on_launch(0, svc) == 0.0
        # flap raises for exactly two attempts of launch 1, then clears
        with pytest.raises(TransientError):
            inj.on_launch(1, svc)
        with pytest.raises(TransientError):
            inj.on_launch(1, svc)
        assert inj.on_launch(1, svc) == 0.0
        assert inj.on_launch(2, svc) == 0.5
        assert inj.on_launch(2, svc) == 0.0     # events fire once
        inj.on_launch(3, svc)
        assert 7 in svc.monitor.dead_shards()
        assert [k for _, k in inj.log] == ["link_flap", "straggler",
                                           "kill_shard"]


# ---------------------------------------------------------------------------
# degradation planning (single-device pieces)
# ---------------------------------------------------------------------------
class TestDegradePlanning:
    def test_surviving_mesh_single_survivor_is_none(self):
        from jax.sharding import Mesh
        dev = jax.devices()
        mesh = Mesh(np.asarray(dev[:1]), ("data",))
        assert surviving_mesh(mesh, dead_ids=()) is None  # 1 survivor
        with pytest.raises(RuntimeError, match="no devices survive"):
            surviving_mesh(mesh, dead_ids=[d.id for d in dev[:1]])

    def test_monitor_unions_marks_and_heartbeats(self, tmp_path):
        from repro.runtime.fault_tolerance import Heartbeat
        mon = ShardHealthMonitor(heartbeat_dir=str(tmp_path), timeout_s=5.0,
                                 time_fn=lambda: 100.0)
        Heartbeat(tmp_path, host_id=0).path.write_text(
            json.dumps({"step": 1, "t": 99.0}))   # fresh
        Heartbeat(tmp_path, host_id=1).path.write_text(
            json.dumps({"step": 1, "t": 10.0}))   # stale
        mon.mark_dead(2)
        assert mon.dead_shards() == frozenset({1, 2})
        mon.mark_alive(2)
        assert mon.dead_shards() == frozenset({1})


# ---------------------------------------------------------------------------
# the service, single device (mesh degradation runs in the subprocess test)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def g11():
    return make_chimera(1, 1)


def _service(**kw):
    kw.setdefault("capacity_chains", 4)
    kw.setdefault("seed", 0)
    return SamplerService(**kw)


class TestServiceCore:
    def test_result_is_replayable_from_metadata(self, g11):
        """The full determinism contract in one assertion: a result's
        (launch_key, chain_offset, bucket spec) metadata is a complete
        recipe — a hand-built Session reproduces the service's spins
        bit-for-bit."""
        svc = _service()
        req = _request(g11, chains=2, seed=3)
        ticket = svc.submit(req)
        svc.drain()
        res = ticket.result()
        assert res.status == "ok"
        assert res.spins.shape == (2, g11.n_nodes)
        spec = svc.bucket_spec(g11)
        sess = api.Session(spec)
        emb = embed_graph(g11, spec.graph)
        Jb, hb = embed_program(emb, req.J_codes, req.h_codes)
        chip = sess.program_edges(jnp.asarray(Jb), jnp.asarray(hb))
        km, kn = jax.random.split(jnp.asarray(res.launch_key))
        m0 = pbit.random_spins(km, svc.capacity_chains, spec.graph.n_nodes)
        ns = sess.noise_state(kn)
        betas = jnp.full((req.n_sweeps,), req.beta, jnp.float32)
        m, _, _ = sess.sample(chip, m0, ns, betas)
        ref = np.asarray(m)[res.chain_offset:res.chain_offset + 2][
            :, emb.node_map]
        np.testing.assert_array_equal(res.spins, ref)

    def test_batching_multiplexes_one_launch(self, g11):
        svc = _service(capacity_chains=8)
        a = svc.submit(_request(g11, tenant="a", chains=2, seed=5))
        b = svc.submit(_request(g11, tenant="b", chains=3, seed=5))
        # different program -> different digest -> separate launch
        c = svc.submit(_request(g11, tenant="c", chains=2, seed=6))
        svc.drain()
        ra, rb, rc = a.result(), b.result(), c.result()
        assert ra.launch_seq == rb.launch_seq
        assert (ra.chain_offset, rb.chain_offset) == (0, 2)
        assert rc.launch_seq != ra.launch_seq
        assert svc.metrics["launches"] == 2
        # one bucket spec compiled once, reused across both launches
        assert svc.cache.stats()["misses"] == 1
        assert svc.cache.stats()["hits"] >= 1

    def test_batch_respects_capacity(self, g11):
        svc = _service(capacity_chains=4)
        t = [svc.submit(_request(g11, tenant=f"t{i}", chains=3, seed=9))
             for i in range(2)]
        svc.drain()
        # 3 + 3 > 4: second request overflows into its own launch
        assert t[0].result().launch_seq != t[1].result().launch_seq

    def test_clamp_values_are_the_tenant_axis(self, g11):
        """Two tenants share one chip + clamp mask but clamp different
        per-chain data; each gets its own data back at the clamped
        nodes — the LM-style multiplexing the chains axis exists for."""
        svc = _service(capacity_chains=8)
        mask = np.zeros(g11.n_nodes, bool)
        mask[:2] = True
        va = np.ones((2, g11.n_nodes), np.float32)
        vb = -np.ones((2, g11.n_nodes), np.float32)
        a = svc.submit(_request(g11, tenant="a", chains=2, seed=5,
                                clamp_mask=mask, clamp_values=va))
        b = svc.submit(_request(g11, tenant="b", chains=2, seed=5,
                                clamp_mask=mask, clamp_values=vb))
        svc.drain()
        ra, rb = a.result(), b.result()
        assert ra.launch_seq == rb.launch_seq      # same launch
        np.testing.assert_array_equal(ra.spins[:, :2], va[:, :2])
        np.testing.assert_array_equal(rb.spins[:, :2], vb[:, :2])

    def test_backpressure(self, g11):
        svc = _service(max_queue=2)
        svc.submit(_request(g11, seed=1))
        svc.submit(_request(g11, seed=2))
        with pytest.raises(AdmissionError, match="backpressure"):
            svc.submit(_request(g11, seed=3))
        assert not svc.readyz()                    # saturated != ready
        assert svc.healthz()["metrics"]["rejected_backpressure"] == 1
        svc.drain()
        assert svc.readyz()

    def test_submit_validates_shapes(self, g11):
        svc = _service()
        bad = _request(g11)
        bad.J_codes = np.zeros(3, np.int32)
        with pytest.raises(ValueError, match="J_codes"):
            svc.submit(bad)
        with pytest.raises(ValueError, match="chains"):
            svc.submit(_request(g11, chains=99))
        with pytest.raises(ServiceError, match="pump"):
            svc.submit(_request(g11)).result()

    def test_deadline_expires_in_queue(self, g11):
        now = [0.0]
        svc = _service(clock=lambda: now[0], sleep=lambda s: None)
        t = svc.submit(_request(g11, timeout_s=5.0))
        now[0] = 10.0
        svc.pump()
        res = t.result()
        assert res.status == "deadline_exceeded"
        assert res.spins is None
        assert svc.metrics["deadline_expired_queued"] == 1

    def test_breaker_opens_and_half_opens(self, g11):
        now = [0.0]
        svc = _service(clock=lambda: now[0], sleep=lambda s: None,
                       breaker=CircuitBreaker(threshold=2, cooldown_s=30.0))
        for _ in range(2):   # two queue-expired deadlines -> open
            svc.submit(_request(g11, tenant="bad", timeout_s=1.0))
            now[0] += 10.0
            svc.pump()
        with pytest.raises(CircuitOpenError):
            svc.submit(_request(g11, tenant="bad"))
        assert svc.healthz()["open_breakers"] == ["bad"]
        # other tenants unaffected
        ok = svc.submit(_request(g11, tenant="good", timeout_s=1e6))
        svc.drain()
        assert ok.result().status == "ok"
        # cooldown passes -> half-open probe admitted, success closes
        now[0] += 31.0
        probe = svc.submit(_request(g11, tenant="bad", timeout_s=1e6))
        svc.drain()
        assert probe.result().status == "ok"
        assert svc.breaker.state("bad", now[0]) == "closed"

    def test_link_flap_retries_and_succeeds(self, g11):
        sleeps = []
        svc = _service(
            injector=FaultInjector(FaultPlan.make(
                [FaultEvent(step=0, kind="link_flap", flaps=2)])),
            monitor=ShardHealthMonitor(),
            sleep=sleeps.append, backoff_s=0.01, max_backoff_s=0.5,
            rng=__import__("random").Random(0))
        t = svc.submit(_request(g11))
        svc.drain()
        res = t.result()
        assert res.status == "ok" and res.attempts == 3
        assert svc.metrics["transient_retries"] == 2
        assert len(sleeps) == 2 and all(0.01 <= s <= 0.5 for s in sleeps)

    def test_straggler_is_flagged(self, g11):
        now = [0.0]

        def clock():
            return now[0]

        def sleep(s):
            now[0] += s

        svc = _service(
            injector=FaultInjector(FaultPlan.make(
                [FaultEvent(step=6, kind="straggler", delay_s=50.0)])),
            monitor=ShardHealthMonitor(), clock=clock, sleep=sleep,
            default_timeout_s=1e9)
        tickets = [svc.submit(_request(g11, seed=i)) for i in range(8)]
        for t in tickets:
            now[0] += 0.1   # steady-state cadence for the EWMA
            svc.pump()
        assert all(t.result().status == "ok" for t in tickets)
        flagged = [t.result() for t in tickets
                   if t.result().launch_seq == 6]
        assert flagged and svc.metrics["stragglers_flagged"] >= 1
        assert svc.healthz()["stragglers"] >= 1

    def test_cache_eviction_under_pressure(self, g11):
        svc = _service(cache_capacity=1)
        svc.submit(_request(g11, seed=1))
        svc.submit(_request(make_chimera(2, 2), seed=1))
        svc.submit(_request(g11, seed=2))
        svc.drain()
        s = svc.cache.stats()
        assert s["evictions"] >= 1 and s["size"] == 1
        assert s["misses"] >= 3     # 1x1, 2x2, then 1x1 again


# ---------------------------------------------------------------------------
# THE acceptance test: scripted fault schedule on a forced 2-device host
# ---------------------------------------------------------------------------
_ACCEPT_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.chimera import make_chimera
    from repro.serve import (FaultEvent, FaultInjector, FaultPlan,
                             SampleRequest, SamplerService,
                             ShardHealthMonitor)

    assert len(jax.devices()) == 2

    def requests():
        g1, g2 = make_chimera(1, 1), make_chimera(2, 2)
        rng = np.random.default_rng(0)
        progs = {}
        for g in (g1, g2):
            progs[g.rows] = (
                rng.integers(-40, 41, size=g.edges.shape[0],
                             dtype=np.int32),
                rng.integers(-10, 11, size=g.n_nodes, dtype=np.int32))
        out = []
        for i in range(8):
            g = g1 if i % 2 == 0 else g2
            J, h = progs[g.rows]
            out.append(SampleRequest(
                tenant=f"tenant-{i % 3}", graph=g, J_codes=J, h_codes=h,
                chains=2, n_sweeps=6, timeout_s=600.0))
        return out

    def run(mesh, injector, monitor):
        svc = SamplerService(
            seed=0, mismatch_seed=0, capacity_chains=4, mesh=mesh,
            monitor=monitor, injector=injector, backoff_s=0.01,
            max_backoff_s=0.1)
        tickets = [svc.submit(r) for r in requests()]
        svc.drain()
        return svc, [t.result() for t in tickets]

    # clean single-device reference
    svc_b, res_b = run(None, None, None)

    # faulted 2-device run: flap at launch 1, straggler at launch 2,
    # kill shard (device 1) at launch 3 — mid-stream
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    plan = FaultPlan.make([
        FaultEvent(step=1, kind="link_flap", flaps=2),
        FaultEvent(step=2, kind="straggler", delay_s=0.05),
        FaultEvent(step=3, kind="kill_shard", shard=1),
    ])
    svc_a, res_a = run(mesh, FaultInjector(plan), ShardHealthMonitor())

    identical = all(
        a.status == b.status == "ok"
        and np.array_equal(a.spins, b.spins)
        and a.launch_seq == b.launch_seq
        and a.chain_offset == b.chain_offset
        for a, b in zip(res_a, res_b))
    hz = svc_a.healthz()
    print(json.dumps({
        "identical": bool(identical),
        "admitted": hz["metrics"]["admitted"],
        "completed": hz["metrics"]["completed"],
        "resolved": sum(r.status is not None for r in res_a),
        "state": hz["state"],
        "dead_shards": hz["dead_shards"],
        "degradations": hz["metrics"].get("degradations", 0),
        "replays": hz["metrics"].get("replays", 0),
        "transient_retries": hz["metrics"].get("transient_retries", 0),
        "straggler_injected":
            hz["metrics"].get("straggler_delay_injected", 0),
        "cache_invalidated": hz["metrics"].get("cache_invalidated", 0),
        "degraded_results": sum(r.degraded for r in res_a),
    }))
""")


def test_fault_schedule_zero_drops_bit_identical():
    """Kill one of two shards mid-stream + link flap + straggler: every
    admitted request completes (zero drops) and every spin equals the
    clean single-device run bit-for-bit."""
    out = subprocess.run([sys.executable, "-c", _ACCEPT_SCRIPT],
                         env=SUBPROC_ENV, capture_output=True, text=True,
                         timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["identical"], report
    assert report["admitted"] == report["completed"] == 8, report
    assert report["state"] == "single", report       # 2 devs - 1 = 1 left
    assert report["dead_shards"] == [1], report
    assert report["degradations"] == 1, report
    assert report["replays"] >= 1, report            # in-flight replayed
    assert report["transient_retries"] >= 2, report  # the link flap
    assert report["straggler_injected"] == 1, report
    assert report["cache_invalidated"] >= 1, report  # meshed entries
    assert report["degraded_results"] >= 1, report
