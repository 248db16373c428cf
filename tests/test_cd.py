"""Hardware-aware contrastive divergence — the paper's central claims."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import energy, tasks
from repro.core.cd import (
    CDConfig,
    PBitMachine,
    quantize_codes,
    sample_visible_dist,
    train_cd,
)
from repro.core.chimera import make_chimera
from repro.core.hardware import HardwareConfig

CFG = CDConfig(lr=6.0, cd_k=15, pos_sweeps=15, burn_in=3, chains=256,
               epochs=50)


def _train(hw, seed=7, task_fn=tasks.and_gate_task, cfg=CFG):
    g = make_chimera(1, 1)
    machine = PBitMachine.create(g, jax.random.PRNGKey(42), hw, beta=1.0,
                                 w_scale=0.05)
    task = task_fn(g)
    res = train_cd(machine, task.visible_idx, task.target_dist, cfg,
                   jax.random.PRNGKey(seed), eval_every=cfg.epochs)
    return g, machine, task, res


def test_cd_learns_and_gate_ideal_hardware():
    _, _, task, res = _train(HardwareConfig.ideal())
    assert res.kl_history[-1][1] < 0.25, res.kl_history


def test_cd_learns_and_gate_with_mismatch():
    """Paper Fig 7b: learning succeeds ON the mismatched chip."""
    _, _, task, res = _train(HardwareConfig())
    assert res.kl_history[-1][1] < 0.3, res.kl_history


def test_correlation_error_decreases():
    """Paper Fig 7c: positive/negative phase correlations converge."""
    _, _, _, res = _train(HardwareConfig())
    first = np.mean([m["corr_err"] for m in res.metric_history[:5]])
    last = np.mean([m["corr_err"] for m in res.metric_history[-5:]])
    assert last < first


def test_hardware_aware_beats_transfer():
    """The paper's thesis: weights learned in-situ on the mismatched chip
    beat ideal-chip weights transferred onto the same mismatched chip."""
    g = make_chimera(1, 1)
    task = tasks.and_gate_task(g)
    key_chip = jax.random.PRNGKey(42)

    # 1) train on ideal hardware
    ideal_machine = PBitMachine.create(g, key_chip, HardwareConfig.ideal(),
                                       beta=1.0, w_scale=0.05)
    res_ideal = train_cd(ideal_machine, task.visible_idx, task.target_dist,
                         CFG, jax.random.PRNGKey(7), eval_every=CFG.epochs)
    # 2) train in-situ on the mismatched chip (same chip instance key)
    real_machine = PBitMachine.create(g, key_chip, HardwareConfig(),
                                      beta=1.0, w_scale=0.05)
    res_real = train_cd(real_machine, task.visible_idx, task.target_dist,
                        CFG, jax.random.PRNGKey(7), eval_every=CFG.epochs)

    # evaluate BOTH weight sets on the mismatched chip
    kl_transfer = energy.kl_divergence(
        task.target_dist,
        sample_visible_dist(real_machine, jnp.asarray(res_ideal.Jm),
                            jnp.asarray(res_ideal.hm), task.visible_idx,
                            jax.random.PRNGKey(3)))
    kl_insitu = energy.kl_divergence(
        task.target_dist,
        sample_visible_dist(real_machine, jnp.asarray(res_real.Jm),
                            jnp.asarray(res_real.hm), task.visible_idx,
                            jax.random.PRNGKey(3)))
    # in-situ learning absorbs the mismatch
    assert kl_insitu < kl_transfer + 0.05, (kl_insitu, kl_transfer)
    assert kl_insitu < 0.3


def test_learned_weights_are_8bit_codes():
    g, machine, task, res = _train(HardwareConfig(), seed=3)
    codes = np.asarray(quantize_codes(jnp.asarray(res.Jm)))
    assert codes.min() >= -128 and codes.max() <= 127
    assert codes.dtype == np.int32
    # one master weight per physical coupler, clipped to the DAC range
    assert res.J_edges.shape == (g.n_edges,)
    assert np.isfinite(res.J_edges).all()
    assert res.J_edges.min() >= -128 and res.J_edges.max() <= 127
    # the dense reconstruction is supported on the graph edges only
    off_graph = ~g.adjacency()
    assert (res.Jm[off_graph] == 0).all()


def _eager_train_cd(machine, visible_idx, target_dist, cfg, key,
                    eval_every):
    """`train_cd` as an eager host loop, the oracle of the compiled one: per
    epoch the key split, `choice` and gather on the host, one
    `make_cd_step` call and a read of its metrics; per evaluation eager
    `program_master`, `random_spins`, `noise_state` and `visible_hist`
    with `sample_visible_dist`'s defaults (256 chains, 200 sweeps, burn-in
    20)."""
    g = machine.graph
    session = machine.session(chains=cfg.chains)
    step = session.make_cd_step(cfg, visible_idx)
    key, k1, k2, _ = jax.random.split(key, 4)
    Jm = jnp.zeros((g.n_edges,), jnp.float32)
    hm = jnp.zeros((g.n_nodes,), jnp.float32)
    m = session.random_spins(k1)
    noise_state = session.noise_state(k2)
    codes = energy.all_states(len(visible_idx))
    vel = (jnp.zeros((g.n_edges,), jnp.float32),
           jnp.zeros((g.n_nodes,), jnp.float32))
    evs = machine.session(
        schedule=api.Constant(beta=machine.beta, n_sweeps=200), chains=256)
    kl_hist, met_hist = [], []
    for epoch in range(cfg.epochs):
        key, kd, ke = jax.random.split(key, 3)
        idx = jax.random.choice(kd, codes.shape[0], (cfg.chains,),
                                p=jnp.asarray(target_dist))
        Jm, hm, m, noise_state, vel, metrics = step(
            Jm, hm, jnp.asarray(codes)[idx], m, noise_state, vel)
        met_hist.append({k: float(v) for k, v in metrics.items()})
        if (epoch + 1) % eval_every == 0 or epoch == cfg.epochs - 1:
            chip = evs.program_master(Jm, hm)
            ka, kb = jax.random.split(ke)
            counts, _, _ = evs.visible_hist(
                chip, evs.random_spins(ka), evs.noise_state(kb),
                visible_idx, 20)
            counts = np.asarray(counts, np.float64)
            kl_hist.append((epoch + 1, energy.kl_divergence(
                np.asarray(target_dist), counts / max(counts.sum(), 1.0))))
    return np.asarray(Jm), np.asarray(hm), kl_hist, met_hist


@pytest.mark.parametrize("backend,noise", [("auto", "counter"),
                                           ("ref", "philox")])
def test_train_cd_bit_identical_to_eager_loop(backend, noise):
    """One compiled dispatch per epoch and one per evaluation change no
    bit: the weights, every epoch's metrics and the KL history equal the
    eager loop's."""
    g = make_chimera(1, 2)
    machine = PBitMachine.create(g, jax.random.PRNGKey(5), noise=noise,
                                 backend=backend)
    task = tasks.full_adder_task(g)
    cfg = CDConfig(lr=6.0, cd_k=3, pos_sweeps=3, burn_in=1, chains=16,
                   epochs=5)
    res = train_cd(machine, task.visible_idx, task.target_dist, cfg,
                   jax.random.PRNGKey(11), eval_every=2)
    J, h, kl_hist, met_hist = _eager_train_cd(
        machine, task.visible_idx, task.target_dist, cfg,
        jax.random.PRNGKey(11), eval_every=2)
    np.testing.assert_array_equal(res.J_edges, J)
    np.testing.assert_array_equal(res.hm, h)
    assert [e for e, _ in res.kl_history] == [2, 4, 5]
    assert res.kl_history == kl_hist
    assert res.metric_history == met_hist
    assert len(met_hist) == cfg.epochs


@pytest.mark.parametrize("target", ["full_adder", "random"])
def test_compiled_data_draw_equals_eager_choice(target):
    """The epoch's draw (`api.session.cd_data_draw` under jit) equals the
    eager split, `choice` and gather, on the dyadic full-adder target and
    on a non-dyadic one, for 32 keys."""
    from repro.api.session import cd_data_draw
    codes = energy.all_states(5)
    if target == "full_adder":
        dist = tasks.full_adder_task(make_chimera(1, 2)).target_dist
    else:
        dist = np.random.default_rng(3).dirichlet(np.ones(32))
    draw = jax.jit(cd_data_draw, static_argnums=3)
    p, codes_d = jnp.asarray(dist, jnp.float32), jnp.asarray(codes)
    for seed in range(32):
        key = jax.random.PRNGKey(seed)
        got = draw(key, p, codes_d, 256)
        key2, kd, ke = jax.random.split(key, 3)
        idx = jax.random.choice(kd, codes.shape[0], (256,),
                                p=jnp.asarray(dist))
        for a, b in zip(got, (key2, ke, jnp.asarray(codes)[idx])):
            np.testing.assert_array_equal(a, b)
