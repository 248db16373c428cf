"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps.

Uses the production train step (sharded fwd+bwd+AdamW, remat, checkpoints)
on a CPU-sized mesh.  The same entry point scales to the pod meshes via
launch/train.py.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs.base import ModelCfg, ShapeCfg
from repro.data.pipeline import DataConfig, make_source
from repro.launch import mesh as mesh_mod
from repro.launch.steps import make_train_step
from repro.models.model import build_model
from repro.optim import adamw
from repro.runtime.compile_cache import use_compile_cache

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--batch", type=int, default=8)
ap.add_argument("--seq", type=int, default=256)
args = ap.parse_args()
use_compile_cache()

# ~100M params: 12L x 768, llama-style (deepseek family geometry, scaled)
cfg = ModelCfg(
    name="lm-100m", family="dense", num_layers=12, d_model=768,
    num_heads=12, num_kv_heads=4, head_dim=64, d_ff=2048,
    vocab_size=32000, dtype="float32", remat=False)
shape = ShapeCfg("train", args.seq, args.batch, "train")
mesh = mesh_mod.make_host_mesh(1, 1)

model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
print(f"model: {cfg.name} ({n/1e6:.1f}M params)")

opt_cfg = adamw.AdamWConfig(lr=6e-4, warmup_steps=30,
                            total_steps=args.steps)
step = make_train_step(cfg, shape, mesh, opt_cfg)
opt_state = adamw.init(params)
src = make_source(DataConfig(seed=0, vocab_size=cfg.vocab_size))

t0 = time.time()
for s in range(args.steps):
    batch = src.batch(s, args.batch, args.seq)
    params, opt_state, m = step.fn(params, opt_state, batch)
    if (s + 1) % 25 == 0 or s == 0:
        print(f"step {s+1:4d}  loss={float(m['loss']):.4f}  "
              f"lr={float(m['lr']):.2e}  "
              f"gnorm={float(m['grad_norm']):.2f}")
dt = time.time() - t0
print(f"\n{args.steps} steps in {dt:.0f}s "
      f"({args.steps*args.batch*args.seq/dt/1e3:.1f}k tok/s on CPU)")
