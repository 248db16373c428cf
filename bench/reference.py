"""Plain reference of the p-bit Chimera machine, in straightforward jax.numpy.

This is the benchmark's yardstick for `correct`.  It imports nothing of the
program under test and takes nothing the program has made: it builds its own
Chimera graph and neighbour table, draws its own chip instance (process
variation) from the same key with the same recipe, programs the 8-bit DAC
codes through its own copy of the analog model, and runs chromatic Gibbs
sweeps with the counter-hash noise.  The semantics follow the paper
(arXiv:2504.14070, eqns 1 and 2) and the hardware model the configuration
files state:

    I_i  = sum_j W_ij m_j + h_i                         (current summation)
    m_i  = sgn(tanh(beta g_i (I_i + o_i)) + r_i u + c_i)  (stochastic neuron)

with W the programmed couplings after DAC mismatch, per-direction multiplier
gain and soft compression, and u an 8-bit uniform from a stateless hash of
(seed, counter, chain, node).  The field is summed over a node's neighbours in
ascending node order, one multiply-add at a time; that is the order the
configuration's guarantee ("the same program and seed give the same spins")
fixes, so a run of the reference at float32 reproduces the sampler bit for
bit.  ``dtype=jnp.bfloat16`` computes the couplings, fields and activation in
bfloat16: the control that the comparison must fail.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

K = 4  # spins per side of a K4,4 unit cell


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Graph:
    rows: int
    cols: int
    coords: np.ndarray   # (N, 4) int: (row, col, side, k) of each spin
    edges: np.ndarray    # (E, 2) int, i < j, sorted
    color: np.ndarray    # (N,) 0/1 chromatic class
    nbr: np.ndarray      # (D, N) ascending neighbours, self-padded
    nbr_ok: np.ndarray   # (D, N) bool, real coupler

    @property
    def n(self) -> int:
        return int(self.coords.shape[0])

    def index(self) -> dict:
        return {tuple(int(x) for x in c): i
                for i, c in enumerate(self.coords)}


def chimera(rows: int, cols: int, masked=()) -> Graph:
    """Chimera C(rows, cols, 4): spins numbered cell by cell in row-major
    order, vertical side (0) before horizontal (1), k ascending; masked
    cells are left out.  In-cell K4,4 couplers join the two sides;
    vertical spins couple to the same k in the cell below, horizontal
    spins to the same k in the cell to the right."""
    masked = {tuple(int(v) for v in c) for c in masked}
    coords = [(r, c, s, k) for r in range(rows) for c in range(cols)
              if (r, c) not in masked for s in (0, 1) for k in range(K)]
    idx = {c: i for i, c in enumerate(coords)}
    edges = set()
    for (r, c, s, k), i in idx.items():
        if s == 0:
            for k2 in range(K):
                edges.add((i, idx[(r, c, 1, k2)]))
            j = idx.get((r + 1, c, 0, k))
        else:
            j = idx.get((r, c + 1, 1, k))
        if j is not None:
            edges.add((min(i, j), max(i, j)))
    edges = np.array(sorted(edges), np.int64).reshape(-1, 2)
    n = len(coords)
    adj = [[] for _ in range(n)]
    for i, j in edges:
        adj[i].append(j)
        adj[j].append(i)
    D = max(len(a) for a in adj)
    nbr = np.tile(np.arange(n), (D, 1))
    ok = np.zeros((D, n), bool)
    for i, a in enumerate(adj):
        for d, j in enumerate(sorted(a)):
            nbr[d, i], ok[d, i] = j, True
    co = np.array(coords, np.int64).reshape(-1, 4)
    color = (co[:, 0] + co[:, 1] + co[:, 2]) % 2
    return Graph(rows, cols, co, edges, color, nbr, ok)


def slot_of(g: Graph, i: np.ndarray, j: np.ndarray) -> np.ndarray:
    """Row d of the neighbour table with nbr[d, i] == j."""
    return np.argmax(g.nbr[:, i] == j[None, :], axis=0)


# ---------------------------------------------------------------------------
# chip instance and programming (the analog model)
# ---------------------------------------------------------------------------
def _normals(key, shapes, sigmas):
    ks = jax.random.split(key, 8)
    out = [s * jax.random.normal(k, sh, jnp.float32) if s else
           jnp.zeros(sh, jnp.float32)
           for k, sh, s in zip(ks, shapes, sigmas)]
    out[7] = jnp.abs(out[7])
    return out


def draw_chip(key, g: Graph, hw: dict, *, per_pair: bool):
    """Process variation of one chip instance, in draw order: DAC bit
    errors of the couplers and of the biases, multiplier gain, tanh gain,
    tanh offset, RNG gain, comparator offset, disabled-coupler leakage.
    ``per_pair`` draws the coupler terms over every (i, j) pair, as a
    chip characterised as a whole is; otherwise over the (D, N) coupler
    slots only, as the sampling service's bucket chips are."""
    n, D = g.n, g.nbr.shape[0]
    pair = (n, n) if per_pair else (D, n)
    return _normals(
        key,
        [pair + (8,), (n, 8), pair, (n,), (n,), (n,), (n,), pair],
        [hw["sigma_dac_bit"], hw["sigma_dac_bit"], hw["sigma_edge_gain"],
         hw["sigma_tanh_gain"], hw["sigma_tanh_offset"],
         hw["sigma_rand_gain"], hw["sigma_comp_offset"], hw["leak_frac"]])


def dac(code, bit_err):
    """Sign-magnitude R-2R DAC: sign(c) * sum_b bit_b(|c|) 2^b (1 + e_b)."""
    sign = jnp.sign(code.astype(jnp.float32))
    mag = jnp.abs(code.astype(jnp.int32))
    bits = ((mag[..., None] >> jnp.arange(8, dtype=jnp.int32)) & 1
            ).astype(jnp.float32)
    return sign * jnp.sum(
        bits * ((2.0 ** jnp.arange(8, dtype=jnp.float32)) * (1.0 + bit_err)),
        axis=-1)


def _analog(W, enable, gain_err, leak, ok, compression):
    W = W * (1.0 + gain_err)
    W = jnp.where(enable, W, jnp.sign(W) * leak * 128.0)
    W = jnp.where(ok, W, 0.0)
    if compression > 0.0:
        W = W / (1.0 + compression * jnp.abs(W))
    return W


def program(g: Graph, chip, hw: dict, w_scale: float, J_codes, h_codes,
            *, per_pair: bool):
    """Edge codes (E,) and bias codes (N,) -> (w[D, N], h, gain, off,
    rand_gain, comp_off), the couplings in neighbour-slot layout."""
    n = g.n
    e0, e1 = g.edges[:, 0], g.edges[:, 1]
    bj, bh, eg, tg, to, rg, co, lk = chip
    nbr = jnp.asarray(g.nbr)
    if per_pair:
        J = (jnp.zeros((n, n), jnp.int32).at[e0, e1].set(J_codes)
             .at[e1, e0].set(J_codes))
        adj = np.zeros((n, n), bool)
        adj[e0, e1] = adj[e1, e0] = True
        W = _analog(dac(J, bj), jnp.abs(J) > 0, eg, lk, adj,
                    hw["compression"])
        W = W * (1.0 - jnp.eye(n, dtype=jnp.float32))
        w = W[jnp.arange(n)[None, :], nbr]
    else:
        s_ij, s_ji = slot_of(g, e0, e1), slot_of(g, e1, e0)
        J = (jnp.zeros(g.nbr.shape, jnp.int32).at[s_ij, e0].set(J_codes)
             .at[s_ji, e1].set(J_codes))
        w = _analog(dac(J, bj), jnp.abs(J) > 0, eg, lk, g.nbr_ok,
                    hw["compression"])
    h = dac(jnp.asarray(h_codes), bh)
    return (w * w_scale, h * w_scale, 1.0 + tg, to, 1.0 + rg, co)


# ---------------------------------------------------------------------------
# noise
# ---------------------------------------------------------------------------
def _mix(x):
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> jnp.uint32(16))


def uniform(seed, ctr, B: int, n: int):
    """8-bit mid-tread uniform in (-1, 1) for every (chain, spin)."""
    rows = jnp.arange(B, dtype=jnp.uint32)[:, None]
    cols = jnp.arange(n, dtype=jnp.uint32)[None, :]
    x = _mix(seed ^ (ctr * jnp.uint32(0x9E3779B9)))
    x = _mix(x ^ (rows * jnp.uint32(0x85EBCA77))
             ^ (cols * jnp.uint32(0xC2B2AE3D)))
    b = (x & jnp.uint32(0xFF)).astype(jnp.int32).astype(jnp.float32)
    return (b - 127.5) / 128.0


def noise_seed(key):
    return jax.random.bits(key, (1,), jnp.uint32)[0]


def spins(key, B: int, n: int):
    return jnp.where(jax.random.bernoulli(key, 0.5, (B, n)), 1.0,
                     -1.0).astype(jnp.float32)


# ---------------------------------------------------------------------------
# chromatic Gibbs sweeps
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("dtype", "stats", "hist_w"))
def sweeps(nbr, color, prog, m, seed, ctr, betas, clamp_mask=None,
           clamp_values=None, measured=None, *, dtype=jnp.float32,
           stats=None, hist_w=None):
    """Run len(betas) sweeps from spins ``m`` (B, N).

    Returns (m', counter') and, on request, moment sums over the measured
    sweeps (``stats`` = (e0, e1) edge tuple: (sum of spins (N,), sum of
    edge products (E,))) or a visible-pattern histogram (``hist_w``: the
    2^k weight of each visible spin, 0 elsewhere).
    """
    w, h, gain, off, rgain, coff = (x.astype(dtype) for x in prog)
    B, n = m.shape
    D = nbr.shape[0]
    masks = [color == c for c in (0, 1)]
    if clamp_mask is not None:
        masks = [mk & ~clamp_mask for mk in masks]
    if measured is None:
        measured = jnp.zeros(betas.shape, jnp.float32)
    e0 = e1 = None
    if stats is not None:
        e0, e1 = (jnp.asarray(np.asarray(x)) for x in stats)
    hw_ = None if hist_w is None else jnp.asarray(np.asarray(hist_w))

    def half(m, ctr, beta, mk):
        u = uniform(seed, ctr, B, n).astype(dtype)
        md = m.astype(dtype)
        acc = jnp.zeros((B, n), dtype)
        for d in range(D):
            acc = acc + w[d][None, :] * md[:, nbr[d]]
        I = acc + h
        act = jnp.tanh(beta.astype(dtype) * gain * (I + off))
        dec = act + rgain * u + coff
        new = jnp.where(dec >= 0, 1.0, -1.0).astype(jnp.float32)
        return jnp.where(mk, new, m), ctr + jnp.uint32(1)

    def body(carry, inp):
        m, ctr, s_sum, c_sum, hist = carry
        beta, wt = inp
        if clamp_mask is not None:
            m = jnp.where(clamp_mask, clamp_values, m)
        for mk in masks:
            m, ctr = half(m, ctr, beta, mk)
        if stats is not None:
            s_sum = s_sum + wt * jnp.sum(m, axis=0)
            c_sum = c_sum + wt * jnp.sum(m[:, e0] * m[:, e1], axis=0)
        if hw_ is not None:
            codes = jnp.sum(jnp.where(m > 0, hw_[None, :], 0), axis=1)
            hist = hist.at[codes].add(wt)
        return (m, ctr, s_sum, c_sum, hist), None

    n_e = 1 if e0 is None else e0.shape[0]
    n_h = 1 if hw_ is None else int(np.asarray(hist_w).sum()) + 1
    init = (m, jnp.asarray(ctr, jnp.uint32), jnp.zeros((n,), jnp.float32),
            jnp.zeros((n_e,), jnp.float32), jnp.zeros((n_h,), jnp.float32))
    (m, ctr, s_sum, c_sum, hist), _ = jax.lax.scan(body, init,
                                                   (betas, measured))
    return m, ctr, s_sum, c_sum, hist


# ---------------------------------------------------------------------------
# hardware-aware contrastive divergence (paper Fig. 7a)
# ---------------------------------------------------------------------------
def all_patterns(nv: int) -> np.ndarray:
    bits = (np.arange(2 ** nv)[:, None] >> np.arange(nv)[None, :]) & 1
    return (2.0 * bits - 1.0).astype(np.float32)


def kl(p: np.ndarray, q: np.ndarray, eps: float = 1e-9) -> float:
    q = (q + eps) / (q + eps).sum()
    mask = p > 0
    return float(np.sum(p[mask] * np.log(p[mask] / q[mask])))


def quantize(x):
    return jnp.clip(jnp.round(x), -128, 127).astype(jnp.int32)


def train_cd(g: Graph, chip, hw: dict, w_scale: float, beta: float,
             vis: np.ndarray, target: np.ndarray, cd: dict, key, *,
             epochs: int, eval_every: int, eval_chains: int,
             eval_sweeps: int, eval_burn_in: int,
             dtype=jnp.float32) -> dict:
    """In-situ CD on the mismatched chip: positive phase with the visible
    spins clamped to data, negative phase free-running from it, update
    J += lr (<m_i m_j>+ - <m_i m_j>-), h += lr (<m_i>+ - <m_i>-) on float
    master weights re-quantized to 8-bit codes every epoch.  Every few
    epochs the free-running chip's visible histogram gives KL(target||q).

    Returns {"losses", "kl", "J", "h", "J1", "h1", "J3", "h3"}: the per-epoch
    mean |correlation error|, the KL history, the final master weights and
    those after one and after three epochs."""
    B, n = cd["chains"], g.n
    e0, e1 = g.edges[:, 0], g.edges[:, 1]
    nbr, color = jnp.asarray(g.nbr), jnp.asarray(g.color)
    vis_j = jnp.asarray(vis)
    clamp_mask = jnp.zeros((n,), bool).at[vis_j].set(True)
    patterns = jnp.asarray(all_patterns(len(vis)))
    p_target = jnp.asarray(target)
    hist_w = np.zeros((n,), np.int32)
    hist_w[np.asarray(vis)] = 2 ** np.arange(len(vis))
    denom = float(max(cd["pos_sweeps"] - cd["burn_in"], 1))

    def phase(prog, m, seed, ctr, n_sw, cm=None, cv=None):
        betas = jnp.full((n_sw,), beta, jnp.float32)
        meas = (jnp.arange(n_sw) >= cd["burn_in"]).astype(jnp.float32)
        m, ctr, s, c, _ = sweeps(nbr, color, prog, m, seed, ctr, betas,
                                 cm, cv, meas, dtype=dtype,
                                 stats=(tuple(e0), tuple(e1)))
        scale = max(n_sw - cd["burn_in"], 1) * B
        return s / scale, c / scale, m, ctr

    programmer = jax.jit(lambda chip, J, h: program(
        g, chip, hw, w_scale, quantize(J), quantize(h), per_pair=True))
    key, k1, k2, _ = jax.random.split(key, 4)
    Jm = jnp.zeros((len(e0),), jnp.float32)
    hm = jnp.zeros((n,), jnp.float32)
    m = spins(k1, B, n)
    seed, ctr = noise_seed(k2), jnp.uint32(0)
    out = {"losses": [], "kl": []}
    assert denom > 0
    for epoch in range(epochs):
        key, kd, ke = jax.random.split(key, 3)
        idx = jax.random.choice(kd, patterns.shape[0], (B,), p=p_target)
        cv = jnp.zeros((B, n), jnp.float32).at[:, vis_j].set(patterns[idx])
        prog = programmer(chip, Jm, hm)
        pos_s, pos_c, m_pos, ctr = phase(prog, m, seed, ctr,
                                         cd["pos_sweeps"], clamp_mask, cv)
        neg_s, neg_c, m_neg, ctr = phase(prog, m_pos, seed, ctr, cd["cd_k"])
        gJ, gh = pos_c - neg_c, pos_s - neg_s
        Jm = jnp.clip(Jm + cd["lr"] * gJ, -128, 127)
        hm = jnp.clip(hm + cd["lr"] * gh, -128, 127)
        m = m_neg
        out["losses"].append(float(jnp.abs(gJ).mean()))
        if epoch == 0:
            out["J1"], out["h1"] = np.asarray(Jm), np.asarray(hm)
        if epoch == 2:
            out["J3"], out["h3"] = np.asarray(Jm), np.asarray(hm)
        if (epoch + 1) % eval_every == 0 or epoch == epochs - 1:
            ka, kb = jax.random.split(ke)
            prog = programmer(chip, Jm, hm)
            betas = jnp.full((eval_sweeps,), beta, jnp.float32)
            meas = (jnp.arange(eval_sweeps) >= eval_burn_in
                    ).astype(jnp.float32)
            *_, hist = sweeps(nbr, color, prog, spins(ka, eval_chains, n),
                              noise_seed(kb), jnp.uint32(0), betas,
                              measured=meas, dtype=dtype,
                              hist_w=tuple(int(x) for x in hist_w))
            counts = np.asarray(hist, np.float64)
            out["kl"].append(kl(np.asarray(target),
                                counts / max(counts.sum(), 1.0)))
    out["J"], out["h"] = np.asarray(Jm), np.asarray(hm)
    return out
