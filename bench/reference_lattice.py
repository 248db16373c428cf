"""Plain reference of a Chimera lattice too large for one chip.

The yardstick for `correct` in cells whose state is split over several
chips.  Like ``bench/reference.py``, whose helpers it uses (the DAC, the
analog chain, the counter hash, the spins), it imports nothing of the
program under test and takes nothing the program has made:

* the graph is built with numpy, vectorized: the node numbering, edge
  order and ascending neighbour table of ``reference.chimera``, which loops
  in Python and would take hours at 33.5M spins;
* the chip instance is drawn in the coupler-slot layout (D, N) from the
  same key with the same recipe as ``reference.draw_chip(per_pair=False)``,
  and the program is computed with the reference's analog model; both run
  under ``jax.jit`` with every array split over the node axis of the
  devices given (a plain jit: the compiler partitions it), since the
  instance alone is 9.66 GB at 33.5M spins;
* the chromatic Gibbs sweeps of a few chains run the same way, each term
  of a spin's field a shifted view of the lattice laid out as (chain,
  row, side, k, col), split over the devices by rows (the compiler moves
  the rows a shift crosses): a gather over the whole lattice for four
  chains took minutes per anneal, and the whole lattice's temporaries do
  not fit one chip.

Chains are addressed by their global index, in the noise hash too, so a
replay of chains 3, 17, ... reads the noise those chains read in the
64-chain run.  ``dtype=jnp.bfloat16`` computes couplings, fields and the
activation in bfloat16: the control the comparison must fail.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import reference as ref

K = ref.K
AXIS = "nodes"


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------
def chimera(rows: int, cols: int, masked=()) -> ref.Graph:
    """``reference.chimera(rows, cols, masked)``, vectorized."""
    alive = np.ones((rows, cols), bool)
    for r, c in masked:
        alive[int(r), int(c)] = False
    cr, cc = np.nonzero(alive)
    n_cells, per = cr.size, 2 * K
    cid = np.full((rows + 1, cols + 1), -1, np.int64)
    cid[:rows, :cols][alive] = np.arange(n_cells)
    first = cid[cr, cc] * per
    side = np.tile(np.repeat(np.arange(2), K), n_cells)
    kk = np.tile(np.arange(K), 2 * n_cells)
    coords = np.stack([np.repeat(cr, per), np.repeat(cc, per), side, kk],
                      axis=1)
    # couplers of each cell, ascending (i, j): vertical spin k with the
    # K horizontal spins and the vertical spin k below, then each
    # horizontal spin with the horizontal spin to its right
    k = np.arange(K)
    below, right = cid[cr + 1, cc], cid[cr, cc + 1]
    vi = np.broadcast_to((first[:, None] + k)[:, :, None],
                         (n_cells, K, K + 1))
    vj = np.concatenate(
        [np.broadcast_to((first[:, None] + K + k)[:, None, :],
                         (n_cells, K, K)),
         (below[:, None] * per + k)[:, :, None]], axis=2)
    vok = np.concatenate([np.ones((n_cells, K, K), bool),
                          np.broadcast_to((below >= 0)[:, None, None],
                                          (n_cells, K, 1))], axis=2)
    hi = first[:, None] + K + k
    hj = right[:, None] * per + K + k
    hok = np.broadcast_to((right >= 0)[:, None], (n_cells, K))
    i = np.concatenate([vi.reshape(n_cells, -1), hi], axis=1)
    j = np.concatenate([vj.reshape(n_cells, -1), hj], axis=1)
    ok = np.concatenate([vok.reshape(n_cells, -1), hok], axis=1)
    edges = np.stack([i[ok], j[ok]], axis=1).astype(np.int64).reshape(-1, 2)
    n = n_cells * per
    # neighbour table: each spin's lower neighbours (edges ending at it,
    # in edge order) then its higher ones (edges starting at it)
    lo_n = np.bincount(edges[:, 1], minlength=n)
    hi_n = np.bincount(edges[:, 0], minlength=n)
    D = max(int((lo_n + hi_n).max()) if n else 1, 1)
    nbr = np.tile(np.arange(n), (D, 1))
    nbr_ok = np.zeros((D, n), bool)
    e_hi = np.arange(edges.shape[0]) - np.concatenate(
        [[0], np.cumsum(hi_n)[:-1]])[edges[:, 0]]
    d_hi = lo_n[edges[:, 0]] + e_hi
    order = np.argsort(edges[:, 1], kind="stable")
    d_lo = np.empty(edges.shape[0], np.int64)
    d_lo[order] = np.arange(edges.shape[0]) - np.concatenate(
        [[0], np.cumsum(lo_n)[:-1]])[edges[order, 1]]
    nbr[d_hi, edges[:, 0]], nbr_ok[d_hi, edges[:, 0]] = edges[:, 1], True
    nbr[d_lo, edges[:, 1]], nbr_ok[d_lo, edges[:, 1]] = edges[:, 0], True
    color = (coords[:, 0] + coords[:, 1] + coords[:, 2]) % 2
    return ref.Graph(rows, cols, coords, edges, color, nbr, nbr_ok)


def slot_edges(g: ref.Graph) -> np.ndarray:
    """(D, N): the edge each coupler slot programs, -1 on padding."""
    D, n = g.nbr.shape
    e = np.arange(g.edges.shape[0])
    out = np.full((D, n), -1, np.int64)
    for end, other in ((0, 1), (1, 0)):
        i, j = g.edges[:, end], g.edges[:, other]
        d = np.argmax(g.nbr[:, i] == j[None, :], axis=0)
        out[d, i] = e
    return out


# ---------------------------------------------------------------------------
# chip instance and programming, split over the devices' node axis
# ---------------------------------------------------------------------------
def node_mesh(devices) -> Mesh:
    return Mesh(np.asarray(devices), (AXIS,))


def _split(mesh: Mesh, ndim: int, axis: int) -> NamedSharding:
    spec = [None] * ndim
    spec[axis] = AXIS
    return NamedSharding(mesh, P(*spec))


def draw_chip(mesh: Mesh, key, g: ref.Graph, hw: dict) -> list:
    """``reference.draw_chip(key, g, hw, per_pair=False)``, each device
    drawing its part of the node axis.  As there, each normal is drawn
    and then scaled by its sigma as an op of its own."""
    D, n = g.nbr.shape
    shapes = [(D, n, 8), (n, 8), (D, n), (n,), (n,), (n,), (n,), (D, n)]
    axes = [1, 0, 1, 0, 0, 0, 0, 1]
    sigmas = [hw["sigma_dac_bit"], hw["sigma_dac_bit"],
              hw["sigma_edge_gain"], hw["sigma_tanh_gain"],
              hw["sigma_tanh_offset"], hw["sigma_rand_gain"],
              hw["sigma_comp_offset"], hw["leak_frac"]]
    out = []
    for k, sh, ax, s in zip(jax.random.split(key, 8), shapes, axes, sigmas):
        split = _split(mesh, len(sh), ax)
        if s:
            out.append(s * jax.jit(
                lambda k, sh=sh: jax.random.normal(k, sh, jnp.float32),
                out_shardings=split)(k))
        else:
            out.append(jax.jit(lambda sh=sh: jnp.zeros(sh, jnp.float32),
                               out_shardings=split)())
    out[7] = jnp.abs(out[7])
    return out


def program(mesh: Mesh, g: ref.Graph, chip, hw: dict, w_scale: float,
            J_codes, h_codes):
    """Edge codes (E,) and bias codes (N,) -> (w[D, N], h, gain, off,
    rand_gain, comp_off), as ``reference.program(per_pair=False)`` gives
    them, computed over the node axis."""
    bj, bh, eg, tg, to, rg, co, lk = chip
    slots = jax.device_put(slot_edges(g), _split(mesh, 2, 1))
    ok = jax.device_put(g.nbr_ok, _split(mesh, 2, 1))
    J_codes = jax.device_put(np.asarray(J_codes), NamedSharding(mesh, P()))
    h_codes = jax.device_put(np.asarray(h_codes), _split(mesh, 1, 0))

    @jax.jit
    def run(slots, ok, J_codes, h_codes, bj, bh, eg, tg, to, rg, co, lk):
        J = jnp.where(slots >= 0, J_codes[jnp.maximum(slots, 0)], 0)
        w = ref._analog(ref.dac(J, bj), jnp.abs(J) > 0, eg, lk, ok,
                        hw["compression"])
        h = ref.dac(h_codes, bh)
        return (w * w_scale, h * w_scale, 1.0 + tg, to, 1.0 + rg, co)

    return run(slots, ok, J_codes, h_codes, bj, bh, eg, tg, to, rg, co, lk)


def spin_rows(mesh: Mesh, key, B: int, n: int, chains):
    """Rows ``chains`` of ``reference.spins(key, B, n)``, split over the
    node axis."""
    m = jax.jit(lambda k: ref.spins(k, B, n),
                out_shardings=_split(mesh, 2, 1))(key)
    return jax.device_put(m[np.asarray(chains)], _split(mesh, 2, 1))


def place(mesh: Mesh, x, axis: int | None):
    """A host array on the devices: split over the node axis ``axis``,
    or whole on each (None)."""
    x = np.asarray(x)
    return jax.device_put(x, NamedSharding(mesh, P()) if axis is None
                          else _split(mesh, x.ndim, axis))


# ---------------------------------------------------------------------------
# chromatic Gibbs sweeps of a few chains
# ---------------------------------------------------------------------------
def term_slots(g: ref.Graph) -> np.ndarray:
    """(K + 2, N): for each spin, the slot of its neighbour table holding
    each term of its field in ascending neighbour order — the spin before
    its cell (vertical: the one above; horizontal: the one to the left),
    its K in-cell partners, the spin after its cell (below; to the right)
    — or -1 where the lattice ends.  A lattice without masked cells, whose
    table lists exactly these, ascending: a term's slot counts the
    present terms before it."""
    r, c, side = g.coords[:, 0], g.coords[:, 1], g.coords[:, 2]
    have = np.ones((K + 2, g.n), bool)
    have[0] = np.where(side == 0, r > 0, c > 0)
    have[K + 1] = np.where(side == 0, r < g.rows - 1, c < g.cols - 1)
    slot = np.cumsum(have, axis=0) - 1
    return np.where(have, slot, -1).astype(np.int8)


@partial(jax.jit, static_argnames=("rows", "cols", "dtype"))
def sweeps(slots, color, prog, m, seed, ctr, betas, chains, *, rows: int,
           cols: int, dtype=jnp.float32):
    """Run len(betas) sweeps of chains ``chains`` (global indices) from
    their spins ``m`` (C, N) on a rows x cols lattice without masked
    cells: (m', counter', count of +1 spins per chain).

    Each spin's field sums its neighbours' couplings times their spins in
    ascending neighbour order from zero (``slots``, `term_slots`), as
    ``reference.sweeps`` does through its neighbour table; here each
    term is a shifted view of the lattice laid out as (chain, row, side,
    k, col), so a whole lattice of a few chains runs without gathers."""
    C, n = m.shape

    def grid(x):  # (..., N) -> (..., rows, side, k, cols)
        y = x.reshape(x.shape[:-1] + (rows, cols, 2, K))
        return jnp.moveaxis(y, -3, -1)

    w, h, gain, off, rgain, coff = (x.astype(dtype) for x in prog)
    wt = []
    for t in range(slots.shape[0]):
        x = jnp.zeros(w.shape[1:], dtype)
        for d in range(w.shape[0]):
            x = jnp.where(slots[t] == d, w[d], x)
        wt.append(grid(x))
    h, gain, off, rgain, coff = (grid(x) for x in (h, gain, off, rgain,
                                                   coff))
    masks = [grid(color == q) for q in (0, 1)]
    node = grid(jnp.arange(n, dtype=jnp.uint32))
    chain = jnp.asarray(chains, jnp.uint32).reshape(-1, 1, 1, 1, 1)

    def half(m, ctr, beta, mk):
        x = ref._mix(seed ^ (ctr * jnp.uint32(0x9E3779B9)))
        x = ref._mix(x ^ (chain * jnp.uint32(0x85EBCA77))
                     ^ (node * jnp.uint32(0xC2B2AE3D)))
        u = ((x & jnp.uint32(0xFF)).astype(jnp.int32).astype(jnp.float32)
             - 127.5) / 128.0
        md = m.astype(dtype)
        v, hz = md[:, :, 0], md[:, :, 1]
        zr = jnp.zeros_like(v[:, :1])
        zc = jnp.zeros_like(hz[..., :1])
        before = (jnp.concatenate([zr, v[:, :-1]], axis=1),
                  jnp.concatenate([zc, hz[..., :-1]], axis=-1))
        after = (jnp.concatenate([v[:, 1:], zr], axis=1),
                 jnp.concatenate([hz[..., 1:], zc], axis=-1))
        own = (hz, v)
        fields = []
        for sd in (0, 1):
            terms = ([before[sd]] + [jnp.broadcast_to(own[sd][:, :, j:j + 1],
                                                      v.shape)
                                     for j in range(K)] + [after[sd]])
            acc = jnp.zeros(v.shape, dtype)
            for t, x in enumerate(terms):
                acc = acc + wt[t][:, sd][None] * x
            fields.append(acc)
        I = jnp.stack(fields, axis=2) + h
        act = jnp.tanh(beta.astype(dtype) * gain * (I + off))
        new = jnp.where(act + rgain * u.astype(dtype) + coff >= 0, 1.0, -1.0)
        return jnp.where(mk, new.astype(jnp.float32), m), ctr + jnp.uint32(1)

    def body(carry, beta):
        m, ctr = carry
        for q in (0, 1):
            m, ctr = half(m, ctr, beta, masks[q])
        return (m, ctr), None

    (m, ctr), _ = jax.lax.scan(body, (grid(m), jnp.asarray(ctr, jnp.uint32)),
                               betas)
    m = jnp.moveaxis(m, -1, -3).reshape(C, n)
    return m, ctr, jnp.sum(m > 0, axis=1, dtype=jnp.int32)
