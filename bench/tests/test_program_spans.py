"""program_spans.py: the exact split of the device's idle time over the
program's spans, and the readers that use it.

The split is checked on synthetic spans and busy intervals; the reduction
and the readers on ``data/serve3.xplane.pb``, three ``SamplerService.pump``
calls of the chip440 serving cell traced on a TPU v5e
(``data/record_serve_trace.py``).
"""
from __future__ import annotations

import shutil
import types

import pytest

import tiny
import harness
import program_spans as ps
import trace_reduce as tr

DATA = tiny.REPO / "bench" / "tests" / "data" / "serve3.xplane.pb"


def test_split_is_exact_over_nesting_straddling_and_outside():
    # a > a.b nested; c, then d and e side by side; bench.pump over 0..20
    spans = [("a", 0, 10), ("a.b", 2, 5), ("c", 10, 20), ("d", 20, 22),
             ("e", 22, 24)]
    bench = [("bench.pump", 0, 20)]
    busy = [[1, 3], [8, 12]]
    idle, self_t, under = ps.split(spans, bench, busy, 0, 25)
    # the gap 3..8 straddles a.b and a; 20..25 straddles d, e and outside
    assert dict(idle) == {"a": 1 + 3, "a.b": 2, "c": 8, "d": 2, "e": 2,
                          "outside": 1}
    assert sum(idle.values()) + sum(e - s for s, e in busy) == 25
    assert dict(self_t) == {"a": 10 - 3, "a.b": 3, "c": 10, "d": 2, "e": 2}
    assert {k: dict(v) for k, v in under.items()} == {
        "pump": {"a": 4, "a.b": 2, "c": 8},
        "harness": {"d": 2, "e": 2, "outside": 1}}


def test_split_clips_to_the_window():
    idle, self_t, _ = ps.split([("a", -5, 5)], [], [[6, 8]], 0, 10)
    assert dict(idle) == {"a": 5, "outside": 1 + 2}
    assert dict(self_t) == {"a": 5}


@pytest.fixture(scope="module")
def red():
    return ps.reduce_trace(DATA)


def test_trace_is_small():
    assert DATA.stat().st_size < 1 << 20


def test_pump_idle_is_split_over_serve_spans(red):
    base = tr.reduce_trace(DATA)
    assert red["window_s"] == base["window_s"]
    idle = sum(red["idle_s"].values())
    assert idle + base["busy_s"] == pytest.approx(base["window_s"],
                                                  rel=1e-9)
    pump = red["idle_in_bench"]["pump"]
    assert pump.get("outside", 0.0) <= 0.1 * sum(pump.values())
    assert {k for k in pump if k != "outside"} <= {
        k for k in red["spans"] if k.startswith(("serve.", "session."))}
    assert red["spans"]["serve.pump"]["count"] == 3
    assert red["spans"]["serve.launch"]["count"] == 3
    assert red["spans"]["serve.submit"]["count"] == 6
    for name, st in red["spans"].items():
        assert 0 <= st["self_s"] <= st["total_s"] + 1e-12, name


def test_modules_are_named(red):
    assert "jit_sample_program" in red["modules"]
    assert not any("(" in m for m in red["modules"])


def _readers(monkeypatch, tmp_path, window_s):
    d = tmp_path / ".bench_traces" / "cell.1" / "plugins"
    d.mkdir(parents=True)
    shutil.copy(DATA, d / "t.xplane.pb")
    monkeypatch.setattr(ps, "ROOT", tmp_path)
    cell = types.SimpleNamespace(root=tiny.REPO)
    ctx = {"trace": {"window_s": window_s},
           "counters": {"launches": 3, "requests": 6}}
    return {m: harness.reader(cell, m)(ctx) for m in
            ("host_gap_ms.serve", "pump_host_ms.serve", "retraces.serve",
             "cd_eval_share.cd")}


def test_readers_read_the_matching_trace(monkeypatch, tmp_path, red):
    got = _readers(monkeypatch, tmp_path, red["window_s"])
    assert got["host_gap_ms.serve"] == pytest.approx(
        1e3 * ps.program_idle_s(red) / 3)
    assert 0 < got["pump_host_ms.serve"]
    assert got["retraces.serve"] == 0
    assert got["cd_eval_share.cd"] is None  # no CD spans in a serve trace


def test_readers_refuse_another_window(monkeypatch, tmp_path, red):
    got = _readers(monkeypatch, tmp_path, red["window_s"] + 1e-3)
    assert set(got.values()) == {None}
