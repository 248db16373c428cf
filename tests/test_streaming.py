"""Runtime weight streaming: the program as operand, not constant.

An `api.Program` must be invisible to the physics: sampling through
`Session.sample_program` (chip programmed *inside* the jit from runtime
codes) has to be bit-identical to programming the chip eagerly and
calling `Session.sample`, for every backend and noise kind — and swapping
programs must never retrace.  The fleet axis (`sample_fleet`,
`make_cd_fleet_step`) vmaps that operand: a stacked K-program batch
through one executable must match K sequential single-program calls bit
for bit (fused backends demote to their scan siblings under vmap).
Multi-device cases run in subprocesses with a
forced host platform (XLA_FLAGS must be set before jax initializes).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core.cd import CDConfig, PBitMachine
from repro.core.chimera import make_chimera
from repro.core.hardware import sample_mismatch_sparse

ROOT = Path(__file__).resolve().parent.parent
SUBPROC_ENV = {"PYTHONPATH": f"{ROOT}/src", "PATH": "/usr/bin:/bin",
               "HOME": "/root", "JAX_PLATFORMS": "cpu"}


def _codes(g, seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32),
            jnp.asarray(rng.integers(-15, 15, g.n_nodes), jnp.int32))


def _machine(backend, noise, seed=0, rows=2, cols=2):
    g = make_chimera(rows, cols)
    sparse = backend in ("sparse", "fused_sparse")
    return g, PBitMachine.create(g, jax.random.PRNGKey(seed), sparse=sparse,
                                 noise=noise, backend=backend)


def _session(mach, chains=4):
    return api.Session(mach.sampler_spec(chains=chains, interpret=True))


# ---------------------------------------------------------------------------
# operand == constant, per backend x noise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,noise", [
    ("ref", "philox"), ("ref", "counter"), ("ref", "lfsr"),
    ("sparse", "counter"), ("fused", "counter"),
    ("fused_sparse", "counter"),
])
def test_program_operand_matches_constant(backend, noise):
    """sample_program == program_edges + sample, bit for bit; a second
    program reuses the same executable (zero retraces on a value swap)."""
    g, mach = _machine(backend, noise)
    ses = _session(mach)
    m0 = ses.random_spins(jax.random.PRNGKey(2))
    ns = ses.noise_state(jax.random.PRNGKey(3))
    betas = jnp.linspace(0.3, 1.5, 5)
    for seed in (1, 2):  # two programs, one executable
        J, h = _codes(g, seed)
        m_c, ns_c, _ = ses.sample(ses.program_edges(J, h), m0, ns, betas)
        m_o, ns_o, _ = ses.sample_program(ses.make_program(J, h), m0, ns,
                                          betas)
        np.testing.assert_array_equal(np.asarray(m_o), np.asarray(m_c))
        np.testing.assert_array_equal(np.asarray(ns_o), np.asarray(ns_c))
    fn = ses._fn(("sample_program", False), ses._build_sample_program, False)
    assert fn._cache_size() == 1, "program value swap must not retrace"


def test_program_collect_and_program_borne_betas():
    """collect=True trajectories match, and a program-borne schedule is
    honored (explicit betas arg still wins)."""
    g, mach = _machine("ref", "counter")
    ses = _session(mach)
    J, h = _codes(g, 4)
    chip = ses.program_edges(J, h)
    m0 = ses.random_spins(jax.random.PRNGKey(2))
    ns = ses.noise_state(jax.random.PRNGKey(3))
    betas = jnp.linspace(0.2, 1.2, 4)
    a = ses.sample(chip, m0, ns, betas, collect=True)
    prog = ses.make_program(J, h, betas=betas)
    b = ses.sample_program(prog, m0, ns, collect=True)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    override = jnp.linspace(0.5, 0.9, 4)
    m_ov, _, _ = ses.sample_program(prog, m0, ns, override)
    m_ex, _, _ = ses.sample(chip, m0, ns, override)
    np.testing.assert_array_equal(np.asarray(m_ov), np.asarray(m_ex))


def test_program_clamps_match_sample_clamps():
    """Clamps riding in the Program == clamps passed to sample."""
    g, mach = _machine("sparse", "counter")
    ses = _session(mach)
    J, h = _codes(g, 5)
    chip = ses.program_edges(J, h)
    B = 4
    m0 = ses.random_spins(jax.random.PRNGKey(2))
    ns = ses.noise_state(jax.random.PRNGKey(3))
    betas = jnp.linspace(0.3, 1.5, 5)
    cm = jnp.zeros((g.n_nodes,), bool).at[jnp.array([0, 7, 13])].set(True)
    cv = jnp.tile(jnp.asarray([[-1.0]]), (B, g.n_nodes))
    m_c, ns_c, _ = ses.sample(chip, m0, ns, betas, clamp_mask=cm,
                              clamp_values=cv)
    prog = ses.make_program(J, h, clamp_mask=cm, clamp_values=cv)
    m_o, ns_o, _ = ses.sample_program(prog, m0, ns, betas)
    np.testing.assert_array_equal(np.asarray(m_o), np.asarray(m_c))
    np.testing.assert_array_equal(np.asarray(ns_o), np.asarray(ns_c))
    assert bool(jnp.all(m_o[:, jnp.array([0, 7, 13])] == -1.0))


def test_program_mismatch_operand_matches_baked():
    """A mismatch draw streamed through the Program equals a machine with
    that draw baked into its spec — and both specs share a fingerprint
    (one executable serves every chip instance of the SKU)."""
    g = make_chimera(2, 2)
    mach_a = PBitMachine.create(g, jax.random.PRNGKey(0), sparse=True,
                                noise="counter")
    mach_b = PBitMachine.create(g, jax.random.PRNGKey(1), sparse=True,
                                noise="counter")
    ses_a, ses_b = _session(mach_a), _session(mach_b)
    assert ses_a.spec.fingerprint() == ses_b.spec.fingerprint()
    J, h = _codes(g, 6)
    m0 = ses_a.random_spins(jax.random.PRNGKey(2))
    ns = ses_a.noise_state(jax.random.PRNGKey(3))
    betas = jnp.linspace(0.3, 1.5, 5)
    m_baked, ns_baked, _ = ses_b.sample(ses_b.program_edges(J, h), m0, ns,
                                        betas)
    prog = ses_a.make_program(J, h, mismatch=mach_b.mismatch)
    m_op, ns_op, _ = ses_a.sample_program(prog, m0, ns, betas)
    np.testing.assert_array_equal(np.asarray(m_op), np.asarray(m_baked))
    np.testing.assert_array_equal(np.asarray(ns_op), np.asarray(ns_baked))


# ---------------------------------------------------------------------------
# the fleet axis (acceptance: vmapped K == K sequential, bit-identical)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend,noise", [
    ("sparse", "counter"), ("ref", "philox"), ("fused_sparse", "counter"),
])
def test_fleet_k8_matches_sequential(backend, noise):
    g, mach = _machine(backend, noise)
    ses = _session(mach)
    K, betas = 8, jnp.linspace(0.3, 1.5, 4)
    progs = [ses.make_program(*_codes(g, 10 + k)) for k in range(K)]
    m0 = jnp.stack([ses.random_spins(jax.random.PRNGKey(20 + k))
                    for k in range(K)])
    ns = jnp.stack([ses.noise_state(jax.random.PRNGKey(40 + k))
                    for k in range(K)])
    m_f, ns_f, _ = ses.sample_fleet(api.stack_programs(progs), m0, ns,
                                    betas)
    for k in range(K):
        m_k, ns_k, _ = ses.sample_program(progs[k], m0[k], ns[k], betas)
        np.testing.assert_array_equal(np.asarray(m_f[k]), np.asarray(m_k))
        np.testing.assert_array_equal(np.asarray(ns_f[k]), np.asarray(ns_k))


def test_fleet_mismatch_axis_matches_standalone_machines():
    """fleet_mismatch draw k == a standalone machine built from subkey k;
    the K-chip fleet equals per-machine sampling of one shared program."""
    g = make_chimera(2, 2)
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), sparse=True,
                              noise="counter")
    ses = _session(mach)
    K = 3
    draws = mach.fleet_mismatch(jax.random.PRNGKey(7), K)
    J, h = _codes(g, 8)
    betas = jnp.linspace(0.3, 1.5, 4)
    progs = api.stack_programs([
        ses.make_program(J, h,
                         mismatch=jax.tree_util.tree_map(lambda x: x[k],
                                                         draws))
        for k in range(K)])
    m0 = jnp.stack([ses.random_spins(jax.random.PRNGKey(2))] * K)
    ns = jnp.stack([ses.noise_state(jax.random.PRNGKey(3))] * K)
    m_f, _, _ = ses.sample_fleet(progs, m0, ns, betas)
    subkeys = jax.random.split(jax.random.PRNGKey(7), K)
    for k in range(K):
        mk = PBitMachine.create(g, subkeys[k], sparse=True, noise="counter")
        sk = _session(mk)
        m_k, _, _ = sk.sample(sk.program_edges(J, h), m0[k], ns[k], betas)
        np.testing.assert_array_equal(np.asarray(m_f[k]), np.asarray(m_k))


def test_fleet_cd_matches_sequential():
    """K=2 hardware-aware CD fleet == two sequential per-chip epochs."""
    g = make_chimera(1, 2)
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), sparse=True,
                              noise="counter")
    cfg = CDConfig(chains=4, cd_k=2, pos_sweeps=2, burn_in=1, momentum=0.5)
    ses = mach.session(chains=cfg.chains)
    vis = np.arange(6)
    K = 2
    mms = mach.fleet_mismatch(jax.random.PRNGKey(5), K)
    rng = np.random.default_rng(0)
    Jm = jnp.asarray(rng.normal(size=(K, g.n_edges)) * 8, jnp.float32)
    hm = jnp.asarray(rng.normal(size=(K, g.n_nodes)) * 2, jnp.float32)
    data = jnp.asarray(rng.integers(0, 2, (cfg.chains, len(vis))) * 2 - 1,
                       jnp.float32)
    m0 = jnp.stack([ses.random_spins(jax.random.PRNGKey(30 + k))
                    for k in range(K)])
    ns = jnp.stack([ses.noise_state(jax.random.PRNGKey(50 + k))
                    for k in range(K)])
    vel = (jnp.zeros((K, g.n_edges)), jnp.zeros((K, g.n_nodes)))
    fleet = ses.make_cd_fleet_step(cfg, vis)
    out_f = fleet(mms, Jm, hm, data, m0, ns, vel)
    step = ses.make_cd_step(cfg, vis).with_mismatch
    for k in range(K):
        mm_k = jax.tree_util.tree_map(lambda x: x[k], mms)
        out_k = step(mm_k, Jm[k], hm[k], data, m0[k], ns[k],
                     (vel[0][k], vel[1][k]))
        for f, s in zip(out_f[:5], out_k[:5]):
            for x, y in zip(jax.tree_util.tree_leaves(f),
                            jax.tree_util.tree_leaves(s)):
                np.testing.assert_array_equal(np.asarray(x[k]),
                                              np.asarray(y))
        for name in out_k[5]:
            np.testing.assert_array_equal(np.asarray(out_f[5][name][k]),
                                          np.asarray(out_k[5][name]))


# ---------------------------------------------------------------------------
# construction / fingerprint contracts
# ---------------------------------------------------------------------------
def test_make_program_validation():
    g, mach = _machine("sparse", "counter")
    ses = _session(mach)
    J, h = _codes(g, 9)
    with pytest.raises(ValueError, match="edge-list"):
        ses.make_program(jnp.zeros((g.n_nodes,), jnp.int32), h)
    with pytest.raises(ValueError, match="h_codes"):
        ses.make_program(J, jnp.zeros((g.n_edges,), jnp.int32))
    with pytest.raises(ValueError, match="clamp_values"):
        ses.make_program(J, h, clamp_values=jnp.zeros((4, g.n_nodes)))
    dense = PBitMachine.create(g, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="mismatch type"):
        ses.make_program(J, h, mismatch=dense.mismatch)


def test_stack_programs_requires_same_structure():
    g, mach = _machine("sparse", "counter")
    ses = _session(mach)
    J, h = _codes(g, 9)
    a = ses.make_program(J, h)
    b = ses.make_program(J, h, betas=jnp.linspace(0.3, 1.5, 4))
    with pytest.raises(ValueError, match="structure"):
        api.stack_programs([a, b])
    with pytest.raises(ValueError, match="at least one"):
        api.stack_programs([])


def test_fingerprint_is_shape_bucket_key():
    """Fingerprint ignores mismatch *values* (two chip instances share an
    executable) but still keys on mismatch structure and graph shape."""
    g = make_chimera(2, 2)
    a = PBitMachine.create(g, jax.random.PRNGKey(0), sparse=True,
                           noise="counter").sampler_spec(chains=4)
    b = PBitMachine.create(g, jax.random.PRNGKey(1), sparse=True,
                           noise="counter").sampler_spec(chains=4)
    assert a.fingerprint() == b.fingerprint()
    other = PBitMachine.create(make_chimera(1, 2), jax.random.PRNGKey(0),
                               sparse=True,
                               noise="counter").sampler_spec(chains=4)
    assert a.fingerprint() != other.fingerprint()


# ---------------------------------------------------------------------------
# forced multi-device host platform (subprocess)
# ---------------------------------------------------------------------------
def _run_forced(script: str, n_dev: int, timeout: int = 540) -> dict:
    head = (f"import os\nos.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={n_dev}'\n")
    out = subprocess.run(
        [sys.executable, "-c", head + textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout, env=SUBPROC_ENV,
        cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_two_device_sharded_program_operand():
    """Program-as-operand through the sharded engine: a 2-device rows
    partition's sample_program == its own sample(chip) == the
    single-device sample_program, for two programs on one executable."""
    rec = _run_forced("""
    import jax, json
    import jax.numpy as jnp
    import numpy as np
    from repro import api
    from repro.core.cd import PBitMachine
    from repro.core.chimera import make_chimera

    g = make_chimera(2, 2)
    mach = PBitMachine.create(g, jax.random.PRNGKey(0), sparse=True,
                              noise="counter")
    mesh = jax.make_mesh((2,), ("data",))
    ses0 = api.Session(mach.sampler_spec(chains=4, interpret=True))
    ses1 = api.Session(mach.sampler_spec(
        chains=4, interpret=True, mesh=mesh,
        partition=api.Partition(rows="data")))
    m0 = ses0.random_spins(jax.random.PRNGKey(2))
    ns = ses0.noise_state(jax.random.PRNGKey(3))
    betas = jnp.linspace(0.3, 1.5, 5)
    checks = 0
    rng = np.random.default_rng(1)
    for seed in (1, 2):
        rng = np.random.default_rng(seed)
        J = jnp.asarray(rng.integers(-60, 60, g.n_edges), jnp.int32)
        h = jnp.asarray(rng.integers(-15, 15, g.n_nodes), jnp.int32)
        prog = ses1.make_program(J, h)
        m_sh, ns_sh, _ = ses1.sample_program(prog, m0, ns, betas)
        m_c, ns_c, _ = ses1.sample(ses1.program_edges(J, h), m0, ns, betas)
        np.testing.assert_array_equal(np.asarray(m_sh), np.asarray(m_c))
        np.testing.assert_array_equal(np.asarray(ns_sh), np.asarray(ns_c))
        m_1d, ns_1d, _ = ses0.sample_program(
            ses0.make_program(J, h), m0, ns, betas)
        np.testing.assert_array_equal(np.asarray(m_sh), np.asarray(m_1d))
        np.testing.assert_array_equal(np.asarray(ns_sh), np.asarray(ns_1d))
        checks += 1
    fn = ses1._fn(("sample_program", False),
                  ses1._build_sample_program, False)
    print(json.dumps({"checks": checks,
                      "cache_size": fn._cache_size()}))
    """, 2)
    assert rec["checks"] == 2
    assert rec["cache_size"] == 1
