"""trace_reduce.py on a small trace recorded on a TPU v5e.

``data/sample3.xplane.pb`` holds three calls of the chip440 sampling
program (``Session.sample_program``, fused_sparse, 256 chains x 512 sweeps)
inside a ``bench.window`` span, each call inside ``bench.sample_program``;
``data/record_trace.py`` recorded it.
"""
from __future__ import annotations

import pytest

import tiny
import trace_reduce as tr

DATA = tiny.REPO / "bench" / "tests" / "data" / "sample3.xplane.pb"
KERNEL = {"sweep": "sweep_sparse"}


def test_union_and_labels():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    spans = [("bench.pump", 0, 10), ("bench.submit", 2, 3)]
    assert tr._label(spans, 2.5) == "submit"
    assert tr._label(spans, 6) == "pump"
    assert tr._label(spans, 11) == "harness"


@pytest.fixture(scope="module")
def red():
    return tr.reduce_trace(DATA, KERNEL)


def test_window_and_busy(red):
    assert red["devices"] == ["/device:TPU:0"]
    assert 0 < red["busy_s"] <= red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-9)


def test_kernel_found_three_times(red):
    k = red["kernels"]["sweep"]
    assert k["count"] == 3
    assert 0 < k["seconds"] <= red["busy_s"]
    top = dict(red["device_ops"])
    assert max(top, key=top.get) == max(
        (n for n in top if "sweep_sparse" in n), key=top.get)


def test_idle_gaps_are_named_by_host_spans(red):
    names = {n for n, _ in red["idle_gaps"]}
    assert names <= {"sample_program", "make_program", "harness"}
