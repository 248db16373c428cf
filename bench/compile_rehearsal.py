"""Compile every cell's timed programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python bench/compile_rehearsal.py

Builds each cell's program as the harness does, at the cell's real size,
and compiles it for one chip of a described ``v5e:2x2`` topology: each
generator's ``rehearse(cell, machine, sds, compile)`` names the programs
its window runs (the sampling call on the resolved backend with compiled
kernels; every serving bucket at each sweep count the mix sends; the CD
step and its evaluation histogram).  Prints one JSON line per program
with its ``memory_analysis()``.  What the TPU compiler refuses here (tiling,
VMEM, device memory) costs no chip time.  A compile that passes is not a
chip run.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def _compile(name, fn, args, log):
    t0 = time.perf_counter()
    try:
        compiled = fn.lower(*args).compile()
    except Exception as e:  # report every refusal, go on with the rest
        log.append({"program": name, "ok": False,
                    "error": f"{type(e).__name__}: {e}"[:2000]})
        return
    text = compiled.as_text() or ""
    log.append({"program": name, "ok": True,
                "compile_s": round(time.perf_counter() - t0, 3),
                "tpu_custom_call": "tpu_custom_call" in text,
                "memory": _mem(compiled)})


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    import load

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    log: list = []
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        mod = load.generator_module(ROOT, cell.traffic["generator"])
        mach = load.machine(cell.config, jax.random.PRNGKey(0))

        def comp(what, fn, args):
            _compile(f"{w['name']}: {what}", fn, args, log)

        mod.rehearse(cell, mach, sds, comp)
    for entry in log:
        print(json.dumps(entry), flush=True)
    return 0 if all(x["ok"] for x in log) else 1


if __name__ == "__main__":
    sys.exit(main())
