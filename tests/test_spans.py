"""Profiler spans of the program's layers (`repro.runtime.spans`).

Under `jax.profiler.start_trace`, each measured path writes its
``repro.*`` spans, nested on the caller's thread: `Session.sample_program`,
`SamplerService.submit`/`pump` (docs/serving.md, "Tracing") and
`core.cd.train_cd`.  A launch's span carries the ``seq`` of its results and
a submit's span the ``request_id``.  The Session's jitted functions carry
stable module names and mark each trace with a ``repro.retrace.<name>``
span.  A running profiler changes no result.
"""
from __future__ import annotations

from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import tasks
from repro.core.cd import CDConfig, PBitMachine, train_cd
from repro.core.chimera import make_chimera
from repro.serve import SampleRequest, SamplerService

Span = namedtuple("Span", "name start end meta")


def _record(tmp_path, fn):
    """Run ``fn`` under the profiler; its result and the ``repro.*``
    spans of the trace (names without the prefix), by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    s = float(ev.start_ns)
                    spans.append(Span(ev.name[len("repro."):], s,
                                      s + float(ev.duration_ns),
                                      dict(ev.stats)))
    return out, sorted(spans, key=lambda x: (x.start, -x.end))


def _named(spans, name):
    return [s for s in spans if s.name == name]


def _within(inner, outer) -> bool:
    return outer.start <= inner.start and inner.end <= outer.end


def _one_within(spans, name, outer):
    got = [s for s in _named(spans, name) if _within(s, outer)]
    assert len(got) == 1, (name, outer, got)
    return got[0]


def _machine(seed=0):
    return PBitMachine.create(make_chimera(1, 1), jax.random.PRNGKey(seed),
                              noise="counter", backend="ref")


def _codes(g, seed):
    rng = np.random.default_rng(seed)
    return (rng.integers(-60, 61, g.n_edges).astype(np.int32),
            rng.integers(-15, 16, g.n_nodes).astype(np.int32))


def _sample_run(sweeps=4):
    mach = _machine()
    ses = api.Session(mach.sampler_spec(chains=4))
    J, h = _codes(mach.graph, 1)
    prog = ses.make_program(jnp.asarray(J), jnp.asarray(h))
    m, _, _ = ses.sample_program(
        prog, ses.random_spins(jax.random.PRNGKey(2)),
        ses.noise_state(jax.random.PRNGKey(3)),
        jnp.linspace(0.2, 1.5, sweeps))
    return np.asarray(m)


def _serve_run():
    g = make_chimera(1, 1)
    svc = SamplerService(seed=5, mismatch_seed=6, capacity_chains=4)
    J, h = _codes(g, 7)
    tickets = [svc.submit(SampleRequest(tenant="t", graph=g, J_codes=J,
                                        h_codes=h, chains=c, n_sweeps=4))
               for c in (1, 2)]
    assert svc.pump() == 2
    return [t.result() for t in tickets]


def _cd_run(machine=None, epochs=2, eval_every=1):
    g = make_chimera(1, 1)
    task = tasks.and_gate_task(g)
    cfg = CDConfig(lr=6.0, cd_k=2, pos_sweeps=2, burn_in=1, chains=8,
                   epochs=epochs)
    return train_cd(machine or _machine(), task.visible_idx,
                    task.target_dist, cfg, jax.random.PRNGKey(9),
                    eval_every=eval_every)


def test_sample_program_spans(tmp_path):
    _, spans = _record(tmp_path, _sample_run)
    (make,) = _named(spans, "session.make_program")
    (call,) = _named(spans, "session.sample_program")
    assert make.end <= call.start
    _one_within(spans, "retrace.sample_program", call)


def test_serve_spans_nest_and_name_the_request_and_launch(tmp_path):
    results, spans = _record(tmp_path, _serve_run)
    submits = _named(spans, "serve.submit")
    assert [s.meta["request"] for s in submits] == \
        [r.request_id for r in results] == [0, 1]
    (pump,) = _named(spans, "serve.pump")
    _one_within(spans, "serve.batch", pump)
    launch = _one_within(spans, "serve.launch", pump)
    assert launch.meta == {"seq": results[0].launch_seq, "requests": 2,
                           "chains": 3}
    assert {r.launch_seq for r in results} == {0}
    entry = _one_within(spans, "serve.entry", launch)
    _one_within(spans, "serve.build", entry)  # the bucket's first launch
    inputs = _one_within(spans, "serve.inputs", launch)
    _one_within(spans, "session.make_program", inputs)
    dispatch = _one_within(spans, "serve.dispatch", launch)
    _one_within(spans, "session.sample_program", dispatch)
    fetch = _one_within(spans, "serve.fetch", launch)
    assert entry.end <= inputs.start <= inputs.end <= dispatch.start
    assert dispatch.end <= fetch.start
    resolve = _one_within(spans, "serve.resolve", pump)
    assert launch.end <= resolve.start


def test_cd_spans_nest_per_epoch_and_evaluation(tmp_path):
    # 4 epochs, evaluated after the 2nd and the 4th: the host fetches the
    # metrics only there, never inside an epoch
    _, spans = _record(tmp_path, lambda: _cd_run(epochs=4, eval_every=2))
    (train,) = _named(spans, "cd.train")
    assert all(_within(s, train) for s in spans
               if s.name.startswith("cd."))
    _one_within(spans, "cd.setup", train)
    _one_within(spans, "cd.result", train)
    epochs = _named(spans, "cd.epoch")
    assert [s.meta["epoch"] for s in epochs] == [0, 1, 2, 3]
    for ep in epochs:
        _one_within(spans, "cd.step", ep)
        assert not [s for s in _named(spans, "cd.sync") if _within(s, ep)]
    evals = _named(spans, "cd.eval")
    assert [s.meta["epoch"] for s in evals] == [1, 3]
    for ev in evals:
        for child in ("cd.sync", "cd.eval.hist", "cd.eval.kl"):
            _one_within(spans, child, ev)
        assert not any(_within(ev, ep) for ep in epochs)
    assert len(_named(spans, "cd.sync")) == len(evals)
    assert not _named(spans, "cd.data")


def test_cd_second_run_on_a_machine_retraces_nothing(tmp_path):
    mach = _machine()
    first = _cd_run(mach)
    second, spans = _record(tmp_path, lambda: _cd_run(mach))
    assert _named(spans, "cd.train")
    assert not [s for s in spans if s.name.startswith("retrace.")]
    np.testing.assert_equal([second.J_edges, second.hm],
                            [first.J_edges, first.hm])


def test_retrace_span_once_per_new_shape(tmp_path):
    mach = _machine()
    ses = api.Session(mach.sampler_spec(chains=4))
    J, h = _codes(mach.graph, 1)
    prog = ses.make_program(jnp.asarray(J), jnp.asarray(h))
    m0 = ses.random_spins(jax.random.PRNGKey(2))
    ns = ses.noise_state(jax.random.PRNGKey(3))
    ses.sample_program(prog, m0, ns, jnp.ones(3))  # traced before

    def calls():
        for sweeps in (5, 5, 3):  # a new shape, the same, a known one
            jax.block_until_ready(
                ses.sample_program(prog, m0, ns, jnp.ones(sweeps)))

    _, spans = _record(tmp_path, calls)
    calls_ = _named(spans, "session.sample_program")
    assert len(calls_) == 3
    retraces = _named(spans, "retrace.sample_program")
    assert len(retraces) == 1 and _within(retraces[0], calls_[0])


@pytest.fixture(scope="module")
def lowerings():
    """Each Session builder's jitted function with arguments to lower it."""
    mach = _machine()
    g = mach.graph
    n, e, b, k = g.n_nodes, g.n_edges, 4, 2
    ses = api.Session(mach.sampler_spec(chains=b))
    J, h = _codes(g, 1)
    prog = ses.make_program(jnp.asarray(J), jnp.asarray(h))
    chip = ses.program_edges(jnp.asarray(J), jnp.asarray(h))
    m = ses.random_spins(jax.random.PRNGKey(2))
    ns = ses.noise_state(jax.random.PRNGKey(3))
    betas = jnp.ones(3)
    vis = np.arange(3)
    cfg = CDConfig(cd_k=2, pos_sweeps=2, burn_in=1, chains=b)
    f32 = jnp.float32
    cd_args = (jnp.zeros(e, f32), jnp.zeros(n, f32), jnp.ones((b, 3), f32),
               m, ns, (jnp.zeros(e, f32), jnp.zeros(n, f32)))
    key = jax.random.PRNGKey(5)
    p = jnp.full((8,), 1 / 8, f32)
    codes = jnp.ones((8, 3), f32)

    def fleet(x):
        return jnp.stack([x] * k)

    return {
        "sample_program": (ses._build_sample_program(False),
                           (prog, m, ns, betas)),
        "sample": (ses._build_sample(False, False), (chip, m, ns, betas)),
        "stats": (ses._build_stats(3, 1, 1.0, False), (chip, m, ns)),
        "visible_hist": (ses._build_hist(vis, 1), (chip, m, ns, betas)),
        "cd_step": (ses.make_cd_step(cfg, vis).with_mismatch,
                    (mach.mismatch, *cd_args)),
        "cd_epoch": (ses.make_cd_epoch(cfg, vis).with_mismatch,
                     (mach.mismatch, key, p, codes, *cd_args[:2],
                      *cd_args[3:])),
        "cd_eval": (ses._build_master_hist(vis, 1),
                    (mach.mismatch, *cd_args[:2], key, betas)),
        "sample_fleet": (ses._build_sample_fleet(),
                         (api.stack_programs([prog] * k), fleet(m),
                          fleet(ns), betas)),
        "cd_fleet_step": (ses.make_cd_fleet_step(cfg, vis),
                          (mach.fleet_mismatch(jax.random.PRNGKey(4), k),
                           fleet(cd_args[0]), fleet(cd_args[1]),
                           cd_args[2], fleet(m), fleet(ns),
                           tuple(fleet(v) for v in cd_args[5]))),
    }


@pytest.mark.parametrize("name", ["sample_program", "sample", "stats",
                                  "visible_hist", "cd_step", "sample_fleet",
                                  "cd_fleet_step", "cd_epoch", "cd_eval"])
def test_session_modules_are_named(lowerings, name):
    fn, args = lowerings[name]
    assert fn.lower(*args).as_text().startswith(f"module @jit_{name} ")


@pytest.mark.parametrize("run", [_sample_run, _serve_run, _cd_run],
                         ids=["sample", "serve", "cd"])
def test_results_bit_identical_under_the_profiler(tmp_path, run):
    traced, _ = _record(tmp_path, run)
    plain = run()
    if run is _serve_run:
        traced = [r.spins for r in traced]
        plain = [r.spins for r in plain]
    elif run is _cd_run:
        traced = [traced.J_edges, traced.hm]
        plain = [plain.J_edges, plain.hm]
    np.testing.assert_equal(traced, plain)
