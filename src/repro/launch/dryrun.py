import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (SPMD partitioner accepts it),
  * it fits: compiled.memory_analysis() per-device bytes < HBM,
  * the roofline terms: cost_analysis FLOPs/bytes + collective bytes parsed
    from the compiled HLO (benchmarks/roofline.py).

Results are cached as JSON per cell under --out (reruns skip clean cells).

Usage:
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all --mesh both        # the full sweep
"""
import argparse
import json
import time
import traceback
from pathlib import Path

from repro.configs.base import LM_SHAPES, shape_applicable
from repro.configs.registry import ARCH_IDS, get_config
from repro.launch import mesh as mesh_mod
from repro.launch.steps import make_step


def _mem_stats(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
    except Exception as e:  # backend-dependent
        return {"error": repr(e)}


def _cost_stats(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:
        return {"error": repr(e)}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path, force: bool = False,
             opt_bits: int = 32, microbatches: int = 1) -> dict:
    mesh_tag = "multipod" if multi_pod else "pod"
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_tag}.json"
    if out_path.exists() and not force:
        rec = json.loads(out_path.read_text())
        if rec.get("status") in ("ok", "skip"):
            print(f"[cached] {arch} x {shape_name} x {mesh_tag}: "
                  f"{rec['status']}")
            return rec

    cfg = get_config(arch)
    shape = LM_SHAPES[shape_name]
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_tag}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec.update(status="skip", reason=why)
        out_path.write_text(json.dumps(rec, indent=1))
        print(f"[skip]   {arch} x {shape_name}: {why}")
        return rec

    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        step = make_step(cfg, shape, mesh, opt_bits=opt_bits,
                         microbatches=microbatches)
        with mesh:
            lowered = step.fn.lower(*step.abstract_args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        hlo = compiled.as_text()
        from benchmarks.roofline import (collective_bytes_from_hlo,
                                         dot_flops_from_hlo)
        coll = collective_bytes_from_hlo(hlo)
        dflops = dot_flops_from_hlo(hlo)
        rec.update(
            dot_flops=dflops,
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            n_devices=mesh_mod.n_chips(mesh),
            memory=_mem_stats(compiled),
            cost=_cost_stats(compiled),
            collectives=coll,
            params=cfg.param_count(),
            active_params=cfg.active_param_count(),
            seq_len=shape.seq_len,
            global_batch=shape.global_batch,
            kind=shape.kind,
        )
        mem = rec["memory"]
        print(f"[ok]     {arch} x {shape_name} x {mesh_tag}: "
              f"compile={t_compile:.1f}s "
              f"args/dev={mem.get('argument_bytes', 0)/2**30:.2f}GiB "
              f"temp/dev={mem.get('temp_bytes', 0)/2**30:.2f}GiB "
              f"coll={coll.get('total_bytes', 0)/2**30:.3f}GiB")
    except Exception as e:
        rec.update(status="fail", error=repr(e),
                   trace=traceback.format_exc()[-4000:])
        print(f"[FAIL]   {arch} x {shape_name} x {mesh_tag}: {e!r}")
    out_path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(LM_SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape) cell")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--opt-bits", type=int, default=32, choices=[8, 32])
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    n_fail = 0
    if args.all:
        for arch in ARCH_IDS:
            for shape_name in LM_SHAPES:
                for mp in meshes:
                    rec = run_cell(arch, shape_name, mp, out_dir,
                                   args.force, args.opt_bits,
                                   args.microbatches)
                    n_fail += rec["status"] == "fail"
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            rec = run_cell(args.arch, args.shape, mp, out_dir, args.force,
                           args.opt_bits, args.microbatches)
            n_fail += rec["status"] == "fail"
    if n_fail:
        raise SystemExit(f"{n_fail} cells FAILED")


if __name__ == "__main__":
    main()
