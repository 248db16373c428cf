"""`correct` comes out false under the control and under planted faults.

At a test size on the CPU, the harness drives a whole run with the timed
path broken underneath and compares as it always does:

* the control: the plain reference in bfloat16 put in the program's place
  (``bench/control.py``);
* a step that returns its state unchanged;
* half of the batch left out (sampling: half the chains never sampled;
  CD: the moments taken over half the chains, the rest copies of them);
* an answer altered where it is produced (one spin flipped).

The cells run on one chip, so there is no exchange between chips to leave
out.
"""
from __future__ import annotations

import pytest

import tiny
import control
from repro import api
from repro.core import cd as cd_mod
from repro.core import pbit


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _keep(monkeypatch):
    monkeypatch.setattr(api.Session, "sample_program",
                        api.Session.sample_program)
    monkeypatch.setattr(cd_mod, "train_cd", cd_mod.train_cd)
    monkeypatch.setattr(pbit, "gibbs_stats", pbit.gibbs_stats)
    monkeypatch.setattr(api.Session, "make_cd_step",
                        api.Session.make_cd_step)


@pytest.mark.parametrize("kind", ["sample", "serve", "cd"])
def test_sound_runs_are_correct(root, kind):
    assert tiny.run(root, f"tiny.{kind}")["correct"] is True


@pytest.mark.parametrize("kind", ["sample", "serve", "cd"])
def test_bf16_control_is_not_correct(root, kind, monkeypatch):
    _keep(monkeypatch)
    control.install("bf16")
    line = tiny.run(root, f"tiny.{kind}", seconds=2.0)
    assert line["correct"] is False, line["checks"]


def _wrap_sample(monkeypatch, fault):
    orig = api.Session.sample_program

    def broken(self, prog, m, ns, betas=None, **kw):
        out, ns2, traj = orig(self, prog, m, ns, betas, **kw)
        return fault(m, out), ns2, traj

    monkeypatch.setattr(api.Session, "sample_program", broken)


FAULTS = {
    "unchanged": lambda m, out: m,
    "half_batch": lambda m, out: out.at[out.shape[0] // 2:].set(
        m[out.shape[0] // 2:]),
    "altered": lambda m, out: out.at[0, 0].multiply(-1.0),
}


@pytest.mark.parametrize("kind,fault", [
    ("sample", "unchanged"), ("sample", "half_batch"),
    ("sample", "altered"), ("serve", "unchanged"), ("serve", "altered")])
def test_sampling_faults_are_caught(root, kind, fault, monkeypatch):
    _keep(monkeypatch)
    _wrap_sample(monkeypatch, FAULTS[fault])
    assert tiny.run(root, f"tiny.{kind}")["correct"] is False


def test_cd_step_that_keeps_its_state_is_caught(root, monkeypatch):
    _keep(monkeypatch)
    orig = api.Session.make_cd_step

    def make(self, cfg, visible_idx):
        step = orig(self, cfg, visible_idx)

        def stuck(Jm, hm, data, m, ns, vel):
            *_, metrics = step(Jm, hm, data, m, ns, vel)
            return Jm, hm, m, ns, vel, metrics

        return stuck

    monkeypatch.setattr(api.Session, "make_cd_step", make)
    assert tiny.run(root, "tiny.cd")["correct"] is False


def test_cd_moments_over_half_the_batch_are_caught(root, monkeypatch):
    _keep(monkeypatch)
    orig = pbit.gibbs_stats

    def half(chip, color, init_m, *a, **kw):
        h = init_m.shape[0] // 2
        return orig(chip, color, init_m.at[h:].set(init_m[:h]), *a, **kw)

    monkeypatch.setattr(pbit, "gibbs_stats", half)
    assert tiny.run(root, "tiny.cd")["correct"] is False


def test_serve_refusals_are_failures_not_wrong_answers(root, monkeypatch):
    """A request the service refuses (back-pressure) is answered with a
    refusal: counted in ``failed`` and in the tail, and ``correct`` holds."""
    from repro import serve
    orig = serve.SamplerService.submit
    n = [0]

    def every_third_refused(self, req):
        n[0] += 1
        if n[0] % 3 == 0 and n[0] > 8:  # set-up's warm-up goes through
            raise serve.service.AdmissionError("queue full")
        return orig(self, req)

    monkeypatch.setattr(serve.SamplerService, "submit", every_third_refused)
    line = tiny.run(root, "tiny.serve")
    assert line["failed"] > 0
    assert line["correct"] is True, line["checks"]


def test_serve_full_queue_holds_requests_upstream(root, monkeypatch):
    """While the admission queue is full, due requests wait in the load
    generator and go in as it drains: none is refused, none fails, and
    ``correct`` holds."""
    from repro import serve
    orig_drain, orig_ready = (serve.SamplerService.drain,
                              serve.SamplerService.readyz)
    svcs, full = [], [0]

    def small_queue(self):  # after the set-up's warm-up
        n = orig_drain(self)
        self.max_queue = 1
        svcs.append(self)
        return n

    def readyz(self):
        ok = orig_ready(self)
        full[0] += not ok
        return ok

    monkeypatch.setattr(serve.SamplerService, "drain", small_queue)
    monkeypatch.setattr(serve.SamplerService, "readyz", readyz)
    line = tiny.run(root, "tiny.serve")
    assert full[0] > 0
    assert svcs[-1].metrics["rejected_backpressure"] == 0
    assert line["failed"] == 0
    assert line["correct"] is True, line["checks"]


def test_serve_lost_request_is_caught(root, monkeypatch):
    """A request that is admitted and never answered makes ``correct``
    false."""
    from repro import serve
    orig = serve.SamplerService._next_batch
    lost = [0]

    def drop_one(self):
        batch, expired = orig(self)
        if batch and self.metrics["launches"] > 12 and not lost[0]:
            lost[0] = 1
            batch = batch[1:]
        return batch, expired

    monkeypatch.setattr(serve.SamplerService, "_next_batch", drop_one)
    line = tiny.run(root, "tiny.serve")
    assert lost[0] and line["checks"]["unanswered"]["value"] >= 1
    assert line["correct"] is False

