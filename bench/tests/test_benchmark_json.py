"""BENCHMARK.json against the benchmark's contract, and cell discovery."""
from __future__ import annotations

import json
import re

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
import harness
import load

BENCH = json.loads((tiny.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((tiny.REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_bounds():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_every_cell_reports_enough_and_finds_its_files():
    chips = [w["chips"] for w in BENCH["workloads"]]
    assert set(chips) <= {1, 4}
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(BENCH["workloads"])
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert callable(harness.reader(cell, m["name"]))
    for m in BENCH["per_layer"]:
        moved = next(e for e in BENCH["end_to_end"]
                     if e["name"] == m["moves"])
        for w in m["workloads"]:
            assert "workloads" not in moved or w in moved["workloads"]


def test_configs_name_their_source_and_reductions():
    for c in BENCH["configs"]:
        cfg = json.loads((tiny.REPO / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["precision"] == "float32" and cfg["guarantee"]


def test_a_cell_added_as_data_alone(tmp_path):
    """A new configuration, traffic mix and cell: files and entries only,
    found by name with the metrics whose workloads list it."""
    root = tiny.make_root(tmp_path)
    cell = harness.load_cell("tiny.sample", root)
    assert cell.config["cell_rows"] == 2 and cell.traffic["chains"] == 32
    assert {m["name"] for m in cell.end_to_end} == {"flips_per_ns",
                                                    "setup_s"}
    assert "sweep_kernel_roofline.sample" in {m["name"]
                                              for m in cell.per_layer}
    line = tiny.run(root, "tiny.sample")
    assert line["correct"] is True
    with pytest.raises(harness.Refused):
        harness.load_cell("no.such.cell", root)


def test_a_generator_added_as_a_file(tmp_path):
    """A new entry point: one file under bench/generators, named by a
    traffic file; neither the harness nor bench/load.py is edited."""
    root = tiny.make_root(tmp_path)
    (root / "bench/generators/sample_twice.py").write_text(
        "from pathlib import Path\n"
        "import load\n"
        "_sample = load.generator_module(\n"
        "    Path(__file__).resolve().parents[2], 'sample')\n\n\n"
        "class Generator(_sample.Generator):\n"
        "    def _call(self, k):\n"
        "        self.twice = getattr(self, 'twice', 0) + 1\n"
        "        return super()._call(k)\n")
    tr = json.loads((root / "bench/traffic/tiny_sample.json").read_text())
    tr["generator"] = "sample_twice"
    (root / "bench/traffic/tiny_twice.json").write_text(json.dumps(tr))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.twice", "config": "tiny",
                               "traffic": "tiny_twice", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.sample" in m.get("workloads", []):
            m["workloads"].append("tiny.twice")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell("tiny.twice", root)
    gen = load.generator(cell, 5)
    assert type(gen).__module__ == "bench_generator_sample_twice"
    assert gen.twice == 2  # the two warm-up calls went through the new file
    assert tiny.run(root, "tiny.twice")["correct"] is True
    tr["generator"] = "no_such_generator"
    (root / "bench/traffic/tiny_twice.json").write_text(json.dumps(tr))
    with pytest.raises(harness.Refused):
        tiny.run(root, "tiny.twice")
