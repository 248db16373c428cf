"""Sharding rules + multi-device correctness (subprocess with 8 devices)."""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.models import sharding as shd

ROOT = Path(__file__).resolve().parent.parent


def test_spec_divisibility_fallback():
    import jax
    mesh = jax.make_mesh((1,), ("model",))  # single device, axis size 1
    s = shd.spec((40, 64), ("heads", None), mesh)
    assert s == P("model", None)  # 40 % 1 == 0

    class FakeMesh:
        shape = {"data": 16, "model": 16, "pod": 2}
    s = shd.spec((40, 64), ("heads", None), FakeMesh())
    assert s == P(None, None)    # 40 % 16 != 0 -> replicate
    s = shd.spec((64, 64), ("heads", "fsdp"), FakeMesh())
    assert s == P("model", "data")
    # batch falls back to a prefix of (pod, data) when not divisible by 32
    s = shd.spec((2, 8), ("batch", None), FakeMesh())
    assert s == P("pod", None)


def test_one_mesh_axis_shards_one_dim_only():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    s = shd.spec((32768, 16, 128), ("kv_seq", "kv_heads", None), FakeMesh())
    # kv_seq takes model first; kv_heads then must replicate
    assert s == P("model", None, None)


_MULTIDEV_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, json
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_reduced_config
    from repro.configs.base import ShapeCfg
    from repro.launch.steps import make_train_step
    from repro.launch import mesh as mesh_mod
    from repro.models.model import build_model, make_dummy_batch
    from repro.optim import adamw

    cfg = get_reduced_config("{arch}")
    shape = ShapeCfg("t", 64, 8, "train")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_dummy_batch(cfg, shape, jax.random.PRNGKey(1))
    opt = adamw.init(params)

    # single-device reference
    ref_step = make_train_step(cfg, shape,
                               mesh_mod.make_host_mesh(1, 1))
    p1, o1, m1 = ref_step.fn(params, opt, batch)

    # 2x4 sharded
    mesh = mesh_mod.make_host_mesh(2, 4)
    step = make_train_step(cfg, shape, mesh)
    params2 = model.init(jax.random.PRNGKey(0))
    opt2 = adamw.init(params2)
    p2, o2, m2 = step.fn(params2, opt2, batch)
    print(json.dumps({{
        "loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
        "gn1": float(m1["grad_norm"]), "gn2": float(m2["grad_norm"]),
    }}))
""")


@pytest.mark.parametrize("arch", ["gemma2-2b", "granite-moe-1b-a400m",
                                  "rwkv6-3b"])
def test_sharded_train_step_matches_single_device(arch):
    out = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT.format(arch=arch)],
        capture_output=True, text=True, timeout=540,
        env={"PYTHONPATH": f"{ROOT}/src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(rec["loss1"] - rec["loss2"]) < 2e-2, rec
    assert abs(rec["gn1"] - rec["gn2"]) / max(rec["gn1"], 1e-9) < 0.05, rec


def test_distributed_lattice_matches_energy_scale():
    """Sharded Chimera lattice anneals to the same energy scale (4 dev)."""
    script = textwrap.dedent("""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, "tests")
        import jax, json
        import jax.numpy as jnp
        import numpy as np
        from repro import api
        from repro.core.chimera import make_chimera
        from test_lattice import anneal, lattice_codes, lattice_machine
        g = make_chimera(8, 8)
        codes = lattice_codes(g, 0)
        betas = jnp.linspace(0.1, 2.5, 60)
        ses1 = api.Session(lattice_machine(g).sampler_spec(chains=1))
        _, e1 = anneal(ses1, ses1.program_edges(*codes),
                       jax.random.PRNGKey(1), betas, record_every=20)
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        ses2 = api.Session(lattice_machine(g).sampler_spec(
            chains=1, mesh=mesh, partition=api.Partition(rows="data")))
        _, e2 = anneal(ses2, ses2.program_edges(*codes),
                       jax.random.PRNGKey(1), betas, record_every=20)
        print(json.dumps({"e1": float(e1[e1 != 0][-1]),
                          "e2": float(e2[e2 != 0][-1])}))
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=540,
        env={"PYTHONPATH": f"{ROOT}/src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    # both anneal to low energy; same physics, different RNG streams
    assert rec["e1"] < -450 and rec["e2"] < -450, rec
    assert abs(rec["e1"] - rec["e2"]) / abs(rec["e1"]) < 0.2, rec


def test_elastic_reshard_roundtrip(tmp_path):
    """Checkpoint written under one mesh restores under another (2x4->4x2)."""
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding
        from repro.configs.registry import get_reduced_config
        from repro.models.model import build_model
        from repro.models import sharding as shd
        from repro.checkpoint import checkpoint as ckpt
        from repro.launch import mesh as mesh_mod

        cfg = get_reduced_config("gemma2-2b")
        model = build_model(cfg)
        mesh1 = mesh_mod.make_host_mesh(2, 4)
        params = jax.jit(model.init, out_shardings=shd.param_shardings(
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))),
            mesh1))(jax.random.PRNGKey(0))
        ckpt.save("{tmp_path}", 1, params)

        mesh2 = mesh_mod.make_host_mesh(4, 2)   # node-count change
        abstract = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
        shardings = shd.param_shardings(abstract, mesh2)
        target = jax.tree.map(
            lambda a, s: jax.make_array_from_callback(
                a.shape, s, lambda idx: np.zeros(a.shape, a.dtype)[idx]),
            abstract, shardings)
        step, restored, _ = ckpt.load("{tmp_path}", target=target)
        ok = all(np.allclose(np.asarray(x), np.asarray(y))
                 for x, y in zip(jax.tree.leaves(params),
                                 jax.tree.leaves(restored)))
        print(json.dumps({{"ok": bool(ok), "step": step}}))
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=540,
        env={"PYTHONPATH": f"{ROOT}/src", "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["step"] == 1
