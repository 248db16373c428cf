"""cd_syncs_per_epoch.py: the ``repro.cd.sync`` spans of the traced window
over its epochs.

It reads nothing on ``data/serve3.xplane.pb`` (three serving pumps traced
on a TPU v5e, no CD spans).  The count over epochs is checked on span
reductions made up in the form ``program_spans.reduce_trace`` gives: a CPU
trace has no device plane to reduce.
"""
from __future__ import annotations

import shutil
import types

import pytest

import tiny
import harness
import program_spans as ps

DATA = tiny.REPO / "bench" / "tests" / "data" / "serve3.xplane.pb"
CELL = types.SimpleNamespace(root=tiny.REPO)


def _read(ctx):
    return harness.reader(CELL, "cd_syncs_per_epoch.cd")(ctx)


def _reduction(window_s, **counts):
    spans = {name.replace("_", "."): {"count": n, "total_s": 1e-3 * n,
                                      "self_s": 0.0}
             for name, n in counts.items()}
    return {"window_s": window_s, "spans": spans, "idle_s": {},
            "idle_in_bench": {}, "modules": {}, "instances": []}


def test_reads_nothing_on_a_serve_trace(monkeypatch, tmp_path):
    d = tmp_path / ".bench_traces" / "cell.1" / "plugins"
    d.mkdir(parents=True)
    shutil.copy(DATA, d / "t.xplane.pb")
    monkeypatch.setattr(ps, "ROOT", tmp_path)
    red = ps.reduce_trace(DATA)
    ctx = {"trace": {"window_s": red["window_s"]},
           "counters": {"launches": 3, "requests": 6, "epochs": 3}}
    assert _read(ctx) is None


@pytest.mark.parametrize("syncs,epochs,want", [
    (180, 180, 1.0),   # a fetch of the metrics in every epoch
    (18, 180, 0.1),    # one per evaluation, every 10th epoch
    (0, 60, 0.0),      # a loop that never waits
])
def test_counts_syncs_over_epochs(monkeypatch, syncs, epochs, want):
    red = _reduction(4.1, cd_train=3, cd_epoch=epochs, cd_sync=syncs)
    if not syncs:
        del red["spans"]["cd.sync"]
    monkeypatch.setattr(ps, "load", lambda ctx: red)
    ctx = {"trace": {"window_s": 4.1}, "counters": {"epochs": epochs}}
    assert _read(ctx) == pytest.approx(want)


def test_reads_nothing_without_cd_spans_or_epochs(monkeypatch):
    red = _reduction(4.1, cd_train=3, cd_sync=18)
    monkeypatch.setattr(ps, "load", lambda ctx: red)
    assert _read({"trace": {"window_s": 4.1}, "counters": {}}) is None
    assert _read({"trace": {"window_s": 4.1},
                  "counters": {"epochs": 0}}) is None
    monkeypatch.setattr(ps, "load",
                        lambda ctx: _reduction(4.1, serve_pump=3))
    assert _read({"trace": {"window_s": 4.1},
                  "counters": {"epochs": 60}}) is None
