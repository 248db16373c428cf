"""The lattice cell at a test size on four host devices: sound runs are
correct, and a planted fault or the bfloat16 control makes them not.

The cell's lattice is split over four chips, so the test forces a host
platform of four devices in a subprocess.  The faults:

* a stale halo: the exchange between row bands hands back zeros, as if
  the boundary spins never arrived (the bands, whole cell rows, sweep on
  their grid layout, whose exchange is ``ShardedEngine._grid_exchange``);
* the control: the plain reference in bfloat16 in the place of the
  program's sweeps of the checked chains (``bench/control_lattice.py``).
"""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import tiny


def add_lattice_cell(root: Path) -> None:
    """``tinylat.anneal`` in a ``tiny.make_root`` root: ``pod33m.anneal``
    on 16 x 8 cells (1,024 spins, four bands of 256), 16 chains, 4-sweep
    calls of a 16-sweep anneal."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/pod33m.json").read_text())
    cfg.update(cell_rows=16, cell_cols=8, spins=1024, couplers=2976)
    (root / "bench/configs/tinylat.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "bench/traffic/lattice_anneal.json").read_text())
    tr.update(chains=16, sweeps=4,
              anneal={"beta_start": 0.1, "beta_end": 2.0, "sweeps": 16})
    (root / "bench/traffic/tinylat.json").write_text(json.dumps(tr))
    bench["configs"].append({"name": "tinylat", "source": "test",
                             "file": "bench/configs/tinylat.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tinylat.anneal",
                               "config": "tinylat", "traffic": "tinylat",
                               "chips": 4, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "pod33m.anneal" in m.get("workloads", []):
            m["workloads"].append("tinylat.anneal")
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


SCRIPT = """
import json, os, sys, tempfile
from pathlib import Path
import tiny, harness, control_lattice
import jax
from test_lattice_cell import add_lattice_cell

root = Path(tempfile.mkdtemp())
tiny.make_root(root)
add_lattice_cell(root)
jax.config.update("jax_enable_compilation_cache", False)

def run(seed):
    line = harness.run("tinylat.anneal", seed, 1.0, False, root=root,
                       require_tpu=False, log=open(os.devnull, "w"))
    return {"correct": line["correct"], "calls": line["attempted"],
            "checks": {k: v["value"] for k, v in line["checks"].items()}}

out = {"sound": run(2 ** 31 + 11)}
from repro.core import distributed
engine = distributed.ShardedEngine
good = engine._grid_exchange
engine._grid_exchange = lambda self, m: (
    jax.numpy.zeros_like(m[:, 0, 0]),) * 2
out["stale_halo"] = run(2 ** 31 + 13)
engine._grid_exchange = good
control_lattice.install("bf16", root)
out["control"] = run(2 ** 31 + 17)
print(json.dumps(out))
"""


def test_lattice_cell_sound_faulty_and_control():
    env = {"PYTHONPATH": ":".join([str(tiny.REPO / "bench" / "tests"),
                                   str(tiny.REPO / "bench"),
                                   str(tiny.REPO / "src")]),
           "PATH": "/usr/bin:/bin", "HOME": str(Path.home()), "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(SCRIPT)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=tiny.REPO)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"] is True, out
    assert out["sound"]["calls"] > 0
    for fault in ("stale_halo", "control"):
        assert out[fault]["correct"] is False, out
        assert out[fault]["checks"]["spin_mismatch"] > 0, out
        assert out[fault]["checks"]["count_mismatch"] > 0, out
