"""Serving: ``SamplerService.submit`` / ``pump``, open loop.

Poisson arrivals, timed from when each request was due to when its result
was returned, from tenants of Zipf popularity, each with a standing problem
on one graph (graphs assigned in turn).  One thread submits what is due and
pumps one launch at a time.  While the service's admission queue is full,
due requests wait upstream, in arrival order, as its back-pressure asks,
and are submitted as it drains: none is refused for load.

Traffic keys: ``rate_per_s``, ``base_seed``, ``tenants``, ``zipf_s``,
``tenant_graphs`` (cell rows and columns, or
``"config"`` for the configuration's own graph), ``chains`` and ``sweeps``
(the choices per request, sweeps with their probabilities), ``beta``,
``capacity_chains``, ``check_requests`` (answered requests the check
replays: the longest and the rest drawn from the seed), ``drain_s`` (how
long past the window's close the run waits for answers).

The sizes and arrival gaps of the mix are drawn once from ``base_seed`` and
only permuted by the run's seed, so every seed offers the same work.

Below the sustained rate the tail is what users feel; above it the
backlog grows all through the window, and the requests answered per second,
over the window and the drain of that backlog, is what the service
delivers.  ``bench/knee.py`` drives this generator over rates to find the
one it sustains.
"""
from __future__ import annotations

import collections
import math
import statistics
import time

import numpy as np

import load
import reference as ref
from load import keys, span


def rehearse(cell, mach, sds, compile) -> None:
    """Compile every bucket's launch at each sweep count the mix sends."""
    import jax.numpy as jnp
    from repro import api
    from repro.serve import SamplerService
    cfg, tr = cell.config, cell.traffic
    svc = SamplerService(capacity_chains=tr["capacity_chains"],
                         noise=cfg["noise"], interpret=False)
    shapes = sorted({(cfg["cell_rows"], cfg["cell_cols"])
                     if s == "config" else tuple(s)
                     for s in tr["tenant_graphs"]})
    cap = tr["capacity_chains"]
    for bs in shapes:
        spec = svc._spec_for_bucket(bs)
        ses = api.Session(spec)
        bg = spec.graph
        prog = api.Program(sds((bg.n_edges,), jnp.int32),
                           sds((bg.n_nodes,), jnp.int32))
        fn = ses._build_sample_program(False)
        for s, _ in tr["sweeps"]:
            compile(f"bucket {bs} {ses.backend} B={cap} S={s}", fn,
                    (prog, sds((cap, bg.n_nodes), jnp.float32),
                     sds((2,), jnp.uint32), sds((s,), jnp.float32)))


class Generator:
    """Poisson arrivals from tenants of Zipf popularity, each with a
    standing problem on its own graph; one thread submits what is due and
    pumps one launch at a time."""

    def __init__(self, cell, seed: int):
        from repro.serve import SamplerService
        from repro.core.chimera import make_chimera
        from repro.core.hardware import HardwareConfig

        self.cell, self.seed = cell, seed
        self.phases = load.Phases()
        cfg, tr = cell.config, cell.traffic
        self.cap = tr["capacity_chains"]
        self.svc_seed = int(keys(seed, "service").integers(0, 2 ** 31))
        self.mm_seed = int(keys(seed, "mismatch").integers(0, 2 ** 31))
        self.svc = SamplerService(
            hw=HardwareConfig(**load.hw_dict(cfg)), seed=self.svc_seed,
            mismatch_seed=self.mm_seed, capacity_chains=self.cap,
            noise=cfg["noise"])
        # tenants: standing problems on graphs assigned in turn
        self.tenants = []
        rng = keys(seed, "tenants")
        for t in range(tr["tenants"]):
            shape = tr["tenant_graphs"][t % len(tr["tenant_graphs"])]
            if shape == "config":
                graph = load.program_graph(cfg)
                spec = (cfg["cell_rows"], cfg["cell_cols"],
                        cfg["masked_cells"])
            else:
                graph = make_chimera(*shape)
                spec = (shape[0], shape[1], [])
            J = rng.integers(-tr["j_max"], tr["j_max"] + 1, graph.n_edges,
                             dtype=np.int32)
            h = rng.integers(-tr["h_max"], tr["h_max"] + 1, graph.n_nodes,
                             dtype=np.int32)
            self.tenants.append({"name": f"t{t}", "graph": graph,
                                 "spec": spec, "J": J, "h": h})
        # warm-up: every (graph, sweeps) shape the window sends
        warm = {}
        for t in self.tenants:
            for s, _ in tr["sweeps"]:
                warm.setdefault((t["spec"][0], t["spec"][1], s), t)
        with self.phases("warm"):
            for (_, _, s), t in sorted(warm.items(), key=lambda kv: kv[0]):
                self.svc.submit(self._request(t, 1, s))
            self.svc.drain()
        self.results = []
        self.launches0 = self.svc.metrics["launches"]
        self.info = {"backend": self._backends(),
                     "rate_per_s": tr["rate_per_s"]}

    def _backends(self) -> dict:
        out = {}
        for e in self.svc.cache._entries.values():
            g = e.spec.graph
            out[f"{g.rows}x{g.cols}"] = e.session.backend
        return out

    @staticmethod
    def _mix(tr: dict, seed: int, horizon: float) -> list:
        """Due times, tenants, chains and sweeps of every request due in a
        window of ``horizon`` seconds: the same multiset for every seed
        (drawn from ``base_seed``), in an order drawn from the seed."""
        base = np.random.default_rng(tr["base_seed"])
        rate = tr["rate_per_s"]
        gaps = []
        while sum(gaps) < horizon:
            gaps.append(base.exponential(1.0 / rate))
        gaps = gaps[:-1]
        n = len(gaps)
        w = np.array([(t + 1) ** -tr["zipf_s"]
                      for t in range(tr["tenants"])])
        tenant = base.choice(tr["tenants"], n, p=w / w.sum())
        chains = base.choice(tr["chains"], n)
        sw = [s for s, _ in tr["sweeps"]]
        sweeps = base.choice(sw, n, p=[p for _, p in tr["sweeps"]])
        rng = keys(seed, "order")
        gaps = np.asarray(gaps)[rng.permutation(n)]
        kinds = rng.permutation(n)
        due = np.cumsum(gaps)
        return [(float(due[i]), int(tenant[j]), int(chains[j]),
                 int(sweeps[j])) for i, j in enumerate(kinds)]

    def _request(self, t: dict, chains: int, sweeps: int):
        from repro.serve import SampleRequest
        return SampleRequest(tenant=t["name"], graph=t["graph"],
                             J_codes=t["J"], h_codes=t["h"], chains=chains,
                             n_sweeps=sweeps,
                             beta=float(self.cell.traffic["beta"]))

    def run(self, seconds: float) -> None:
        from repro.serve import ServiceError
        tr = self.cell.traffic
        mix = self._mix(tr, self.seed, seconds)
        self.due = mix
        pending = {}
        self.results = [None] * len(mix)
        self.late = []
        self.backlog_max = 0
        self.longest = load.Longest()
        clock = time.perf_counter
        t0 = clock()
        i = 0
        # requests due but held upstream while the admission queue is full,
        # as the service's back-pressure asks; their latency counts the wait
        backlog = collections.deque()
        give_up = seconds + tr["drain_s"]
        while i < len(mix) or backlog or pending:
            now = clock() - t0
            if now > give_up:
                break
            while i < len(mix) and mix[i][0] <= now:
                self.late.append(now - mix[i][0])
                backlog.append(i)
                i += 1
            self.backlog_max = max(self.backlog_max, len(backlog))
            while backlog and self.svc.readyz():
                j = backlog.popleft()
                due, t, c, s = mix[j]
                try:
                    with span("submit"), self.longest("submit", t, s):
                        tk = self.svc.submit(
                            self._request(self.tenants[t], c, s))
                    pending[j] = tk
                except ServiceError as e:
                    self.results[j] = ("refused", str(e), now - due)
            if pending:
                with span("pump"), self.longest("pump", len(pending),
                                                self.svc.metrics["launches"]):
                    self.svc.pump()
                done = clock() - t0
                for j in [j for j, tk in pending.items() if tk.done]:
                    r = pending.pop(j).result()
                    self.results[j] = (r.status, r, done - mix[j][0])
            elif i < len(mix):
                wait = mix[i][0] - (clock() - t0)
                if wait > 0:
                    with span("idle"):
                        time.sleep(wait)
        self.elapsed_to_drain = clock() - t0

    def _ok(self) -> list:
        return [r for r in self.results if r is not None and r[0] == "ok"]

    def counters(self) -> dict:
        return {"launches": self.svc.metrics["launches"] - self.launches0,
                "requests": sum(r is not None for r in self.results)}

    def attempted(self) -> tuple[int, int]:
        n = len(self.results)
        return n, n - len(self._ok())

    def latencies(self) -> list:
        horizon = self.cell.traffic["drain_s"]
        out = []
        for r in self.results:
            ok = r is not None and r[0] == "ok"
            out.append(r[2] if ok else math.inf)
        return [x if math.isfinite(x) else horizon for x in out]

    def p95_ms(self):
        lat = sorted(self.latencies())
        if not lat:
            return None
        return 1e3 * lat[max(0, math.ceil(0.95 * len(lat)) - 1)]

    def end_to_end(self, elapsed: float) -> dict:
        """The tail of every request due in the window, and the requests
        answered over the whole run, drain included."""
        return {"serve_p95_ms": self.p95_ms(),
                "serve_requests_per_s": len(self._ok()) / elapsed}

    def layer_counters(self) -> dict:
        ok = self._ok()
        per_launch = {}
        for _, r, _ in ok:
            per_launch[r.launch_seq] = per_launch.get(r.launch_seq, 0) + \
                r.spins.shape[0]
        launches = self.svc.metrics["launches"] - self.launches0
        return {
            "occupancy": (sum(per_launch.values())
                          / (max(launches, 1) * self.cap)) if ok else None,
            "queue_ms": (1e3 * statistics.median(r.queue_s
                                                 for _, r, _ in ok)
                         if ok else None),
            "late_ms_median": 1e3 * statistics.median(self.late)
            if self.late else None,
            "late_ms_max": 1e3 * max(self.late) if self.late else None,
            "backlog_max": self.backlog_max,
            "answered_p95_ms": (1e3 * sorted(r[2] for r in ok)[
                max(0, math.ceil(0.95 * len(ok)) - 1)] if ok else None),
            "longest_calls": self.longest.items,
            "statuses": dict(collections.Counter(
                "none" if r is None else r[0] for r in self.results)),
        }

    def free(self) -> None:
        self.svc = None

    def check(self, dtype=None) -> dict:
        """Replay a seeded sample of the answered requests, the longest
        among them, through the reference: the bucket chip, the embedding
        by cell coordinates, the launch key and the chain offset."""
        import jax
        import jax.numpy as jnp
        dtype = jnp.float32 if dtype is None else dtype
        cfg, tr = self.cell.config, self.cell.traffic
        idx = [i for i, r in enumerate(self.results)
               if r is not None and r[0] == "ok"]
        # a request the service refused or let expire has its answer (a
        # failure, counted in ``failed`` and at the drain horizon in the
        # tail); one with no result at all was lost
        unanswered = sum(r is None for r in self.results)
        rng = keys(self.seed, "check")
        pick = set()
        if idx:
            longest = max(idx, key=lambda i: self.due[i][3])
            pick.add(longest)
            rest = [i for i in idx if i != longest]
            n = min(tr["check_requests"] - 1, len(rest))
            pick.update(int(x) for x in rng.choice(rest, n, replace=False))
        hw = load.hw_dict(cfg)
        graphs, chips, programmers = {}, {}, {}
        bad = total = 0
        for i in sorted(pick):
            _, t, c, s = self.due[i]
            r = self.results[i][1]
            ten = self.tenants[t]
            bs = tuple(r.bucket_shape)
            if bs not in graphs:
                bg = ref.chimera(*bs)
                key = jax.random.fold_in(jax.random.PRNGKey(self.mm_seed),
                                         bs[0] * 1009 + bs[1])
                graphs[bs] = bg
                chips[bs] = ref.draw_chip(key, bg, hw, per_pair=False)
                programmers[bs] = load.ref_programmer(bg, cfg, per_pair=False)
            bg = graphs[bs]
            nmap, emap = embed(ref.chimera(*ten["spec"]), bg)
            Jb = np.zeros(len(bg.edges), np.int32)
            hb = np.zeros(bg.n, np.int32)
            Jb[emap], hb[nmap] = ten["J"], ten["h"]
            prog = programmers[bs](chips[bs], jnp.asarray(Jb),
                                   jnp.asarray(hb))
            key = jax.random.fold_in(jax.random.PRNGKey(self.svc_seed),
                                     r.launch_seq)
            km, kn = jax.random.split(key)
            betas = jnp.full((s,), float(tr["beta"]), jnp.float32)
            m, *_ = ref.sweeps(jnp.asarray(bg.nbr), jnp.asarray(bg.color),
                               prog, ref.spins(km, self.cap, bg.n),
                               ref.noise_seed(kn), jnp.uint32(0), betas,
                               dtype=dtype)
            want = np.asarray(m)[r.chain_offset:r.chain_offset + c][:, nmap]
            got = r.spins
            if got.shape != want.shape:
                bad += want.size
            else:
                bad += int(np.sum(want != got))
            total += want.size
        return {"spin_mismatch": bad / max(total, 1),
                "unanswered": float(unanswered)}


def embed(small: ref.Graph, bucket: ref.Graph):
    """Node and edge maps of a Chimera graph placed at the same cell
    coordinates of a larger one."""
    at = bucket.index()
    nmap = np.array([at[tuple(int(x) for x in c)] for c in small.coords])
    eidx = {(int(i), int(j)): k for k, (i, j) in enumerate(bucket.edges)}
    emap = []
    for i, j in small.edges:
        a, b = nmap[i], nmap[j]
        emap.append(eidx[(min(a, b), max(a, b))])
    return nmap, np.array(emap, np.int64)
