"""A benchmark root holding small data-only cells, for tests on the CPU.

``make_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp``
and adds a 2x2-cell Chimera configuration with one cell per generator, as a
later change would: data files and entries, no code.  The cells run the
"sparse" scan so that a test needs no Pallas interpreter.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "bench"), str(REPO / "src")]

MOVES = {"sample": "flips_per_ns", "serve": "serve_requests_per_s",
         "cd": "cd_epochs_per_s"}
TRAFFIC = {
    "sample": {"chains": 32, "sweeps": 64, "check_calls": 2},
    "serve": {"rate_per_s": 20, "tenant_graphs": [[2, 2]],
              "tenants": 4, "check_requests": 12, "drain_s": 30},
    "cd": {"cd": {"lr": 6.0, "cd_k": 3, "pos_sweeps": 3, "burn_in": 1,
                  "chains": 16, "epochs": 4}, "eval_every": 2},
}


def make_root(tmp: Path) -> Path:
    root = Path(tmp)
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/chip440.json").read_text())
    cfg.update(cell_rows=2, cell_cols=2, masked_cells=[], spins=32,
               couplers=80, backend="sparse")
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    names = {m["name"] for m in bench["end_to_end"]}
    for kind, metric in MOVES.items():
        if metric not in names:
            bench["end_to_end"].append(
                {"name": metric, "unit": "ms", "better": "lower",
                 "bound": 0.25, "source": "host_clock", "workloads": []})
    for kind, extra in TRAFFIC.items():
        base = {"sample": "sample", "serve": "serve", "cd": "cd"}[kind]
        tr = json.loads((root / f"bench/traffic/{base}.json").read_text())
        tr.update(extra)
        (root / f"bench/traffic/tiny_{kind}.json").write_text(json.dumps(tr))
        name = f"tiny.{kind}"
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": f"tiny_{kind}", "chips": 1,
                                   "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m and MOVES[kind] in (m["name"],
                                                    m.get("moves")):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


def run(root: Path, name: str, seed: int = 2 ** 31 + 5, seconds=1.0):
    import jax
    import harness
    jax.config.update("jax_enable_compilation_cache", False)
    return harness.run(name, seed, seconds, False, root=root,
                       require_tpu=False, log=open("/dev/null", "w"))
