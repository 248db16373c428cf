"""The last line of a run: its keys, and refusal anywhere but a TPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("kind", ["sample", "serve", "cd"])
def test_result_line_schema(root, kind):
    line = tiny.run(root, f"tiny.{kind}")
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {tiny.MOVES[kind], "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "chip440.sample", "--seed", "3000000000",
        "--seconds", "1", "--trace", "0"]


def test_refuses_without_a_tpu():
    p = _run(ARGS, tiny.REPO)
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.REPO / "bench", tmp_path / "bench")
    p = _run(ARGS, tmp_path)
    assert p.returncode != 0 and p.stdout == ""
