"""Chimera graph topology (D-Wave style), as used by the paper's chip.

The chip arranges 440 spins as a 7x8 array of Chimera unit cells with one
cell replaced by bias circuits / SPI (=> 55 cells x 8 spins = 440).

Each unit cell is a K_{4,4} bipartite "restricted Boltzmann machine":
4 *vertical* nodes (side=0) fully connected to 4 *horizontal* nodes (side=1).
Inter-cell couplers connect vertical node i of cell (r, c) to vertical node i
of cells (r±1, c), and horizontal node j of (r, c) to horizontal node j of
(r, c±1).  Maximum degree is therefore 4 (in-cell) + 2 (inter-cell) = 6,
matching the paper's "each node has 6 current inputs".

Chimera is 2-colorable: color(r, c, side=0) = (r + c) % 2 and
color(r, c, side=1) = (r + c + 1) % 2 is a proper coloring (in-cell edges
cross sides; vertical inter-cell edges change r; horizontal change c).
Chromatic Gibbs therefore needs exactly two parallel half-sweeps per sweep —
the TPU analogue of the chip's fully parallel analog update.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

K_CELL = 4  # nodes per side of a unit cell


@dataclasses.dataclass(frozen=True)
class ChimeraGraph:
    """Static description of a (possibly cell-masked) Chimera graph.

    Nodes of masked cells are removed entirely; all index arrays refer to the
    *compacted* node numbering [0, n_nodes).
    """

    rows: int
    cols: int
    k: int
    masked_cells: tuple[tuple[int, int], ...]
    n_nodes: int
    # per-node coordinates, shape (n_nodes,)
    node_r: np.ndarray
    node_c: np.ndarray
    node_side: np.ndarray  # 0 = vertical, 1 = horizontal
    node_k: np.ndarray     # 0..k-1 within side
    color: np.ndarray      # chromatic class in {0, 1}
    edges: np.ndarray      # (n_edges, 2) int32, i < j, compacted ids

    # ------------------------------------------------------------------
    @property
    def n_edges(self) -> int:
        return int(self.edges.shape[0])

    @property
    def n_cells(self) -> int:
        return self.rows * self.cols - len(self.masked_cells)

    def adjacency(self) -> np.ndarray:
        """Dense boolean adjacency (n_nodes, n_nodes)."""
        a = np.zeros((self.n_nodes, self.n_nodes), dtype=bool)
        a[self.edges[:, 0], self.edges[:, 1]] = True
        a[self.edges[:, 1], self.edges[:, 0]] = True
        return a

    def degree(self) -> np.ndarray:
        a = self.adjacency()
        return a.sum(axis=1).astype(np.int32)

    def color_mask(self, color: int) -> np.ndarray:
        return self.color == color

    def cell_nodes(self, r: int, c: int, side: int | None = None) -> np.ndarray:
        """Compacted node ids of cell (r, c), optionally one side only."""
        sel = (self.node_r == r) & (self.node_c == c)
        if side is not None:
            sel &= self.node_side == side
        return np.nonzero(sel)[0].astype(np.int32)

    def validate_two_coloring(self) -> bool:
        e = self.edges
        return bool(np.all(self.color[e[:, 0]] != self.color[e[:, 1]]))

    def coord_lut(self) -> np.ndarray:
        """Coordinate -> compacted-node-id lookup table.

        ``lut[r, c, side, k]`` is the compacted node id at that Chimera
        coordinate, or -1 where the cell is masked.  This is the inverse
        of the (node_r, node_c, node_side, node_k) arrays and the basis
        of every coordinate-addressed embedding (the serving layer's
        shape buckets, the PSL chain embedder).
        """
        lut = -np.ones((self.rows, self.cols, 2, self.k), np.int64)
        lut[self.node_r, self.node_c, self.node_side,
            self.node_k] = np.arange(self.n_nodes)
        return lut

    def edge_index(self) -> dict[tuple[int, int], int]:
        """Map (i, j) with i < j -> row index into ``edges``."""
        return {(int(i), int(j)): e
                for e, (i, j) in enumerate(np.asarray(self.edges))}

    # -- fixed-degree sparse layout -------------------------------------
    def _slot_tables(self) -> tuple[np.ndarray, ...]:
        """(nbr_idx, nbr_mask, slot_ij, slot_ji), built once per graph.

        Edges are sorted by (i, j), so node i's higher neighbors are the
        contiguous run of edges with endpoint 0 == i, already ascending;
        its lower neighbors are the edges with endpoint 1 == i, ascending
        after a stable sort on endpoint 1.  Every lower neighbor precedes
        every higher one, so lower slots first, then higher, is ascending
        order.  O(E) plus one stable sort; the tables are read-only and
        shared by every caller.
        """
        cached = self.__dict__.get("_slots")
        if cached is not None:
            return cached
        n = self.n_nodes
        e0 = self.edges[:, 0].astype(np.int64)
        e1 = self.edges[:, 1].astype(np.int64)
        deg_hi = np.bincount(e0, minlength=n)
        deg_lo = np.bincount(e1, minlength=n)
        deg = deg_lo + deg_hi
        D = max(int(deg.max()) if deg.size else 0, 1)
        start_hi = np.concatenate([[0], np.cumsum(deg_hi)[:-1]])
        slot_ij = deg_lo[e0] + np.arange(e0.size) - start_hi[e0]
        order = np.argsort(e1, kind="stable")
        start_lo = np.concatenate([[0], np.cumsum(deg_lo)[:-1]])
        slot_ji = np.empty(e1.size, np.int64)
        slot_ji[order] = np.arange(e1.size) - start_lo[e1[order]]
        nbr_idx = np.tile(np.arange(n, dtype=np.int32), (D, 1))
        nbr_mask = np.zeros((D, n), dtype=bool)
        f_ij, f_ji = slot_ij * n + e0, slot_ji * n + e1
        nbr_idx.reshape(-1)[f_ij] = e1
        nbr_idx.reshape(-1)[f_ji] = e0
        nbr_mask.reshape(-1)[f_ij] = True
        nbr_mask.reshape(-1)[f_ji] = True
        tables = (nbr_idx, nbr_mask, slot_ij.astype(np.int32),
                  slot_ji.astype(np.int32))
        for t in tables:
            t.setflags(write=False)
        object.__setattr__(self, "_slots", tables)
        return tables

    def neighbor_table(self) -> tuple[np.ndarray, np.ndarray]:
        """Fixed-degree neighbor table (ELL layout) of the coupler set.

        Returns ``(nbr_idx, nbr_mask)``, both ``(D, n_nodes)`` with
        D = max degree (k + 2 on an unmasked Chimera: k in-cell K_{k,k}
        partners + 2 chain couplers).  ``nbr_idx[d, i]`` is node i's d-th
        neighbor in ascending node order; unused slots point at i itself
        (mask False) so gathers stay in bounds and gathered weights are 0.
        Ascending order matters: it makes the slot-major sparse sum visit
        nonzeros in the same order as a sequential dense row reduction,
        which is what keeps the sparse backends bit-exact vs the dense ref
        (zeros are additive identities).

        Built from the edge list in O(E) — never materializes the dense
        adjacency, so it scales to lattices where (N, N) does not fit.
        The arrays are read-only and shared between calls.
        """
        nbr_idx, nbr_mask, _, _ = self._slot_tables()
        return nbr_idx, nbr_mask

    def edge_slots(self, nbr_idx: np.ndarray | None = None
                   ) -> tuple[np.ndarray, np.ndarray]:
        """Per-edge slot coordinates in the neighbor table.

        For edge e = (i, j): ``slot_ij[e]`` is the row d with
        ``nbr_idx[d, i] == j`` and ``slot_ji[e]`` the row with
        ``nbr_idx[d, j] == i`` — the two directed entries every undirected
        coupler owns in the (D, N) slot layout.  ``nbr_idx`` defaults to
        (and is normally) this graph's own `neighbor_table`.
        """
        own, _, slot_ij, slot_ji = self._slot_tables()
        if nbr_idx is None or nbr_idx is own:
            return slot_ij, slot_ji
        e0, e1 = self.edges[:, 0], self.edges[:, 1]
        slot_ij = np.argmax(nbr_idx[:, e0] == e1[None, :], axis=0)
        slot_ji = np.argmax(nbr_idx[:, e1] == e0[None, :], axis=0)
        return slot_ij.astype(np.int32), slot_ji.astype(np.int32)


def make_chimera(
    rows: int,
    cols: int,
    k: int = K_CELL,
    masked_cells: Sequence[tuple[int, int]] = (),
) -> ChimeraGraph:
    """Build a Chimera graph C(rows, cols, k) with optional masked cells.

    Nodes are numbered cell by cell in row-major order, vertical side (0)
    before horizontal (1), k ascending, skipping masked cells.  Edges come
    out sorted by (i, j) without a sort: within a cell, vertical node i
    lists its k in-cell partners and then the vertical node below; the
    horizontal nodes follow, each with the horizontal node to its right.
    Vectorized numpy, O(N): a 2048 x 2048 lattice builds in seconds.
    """
    masked = set((int(r), int(c)) for r, c in masked_cells)
    for (r, c) in masked:
        if not (0 <= r < rows and 0 <= c < cols):
            raise ValueError(f"masked cell {(r, c)} out of range")
    alive = np.ones((rows, cols), bool)
    for r, c in masked:
        alive[r, c] = False
    cell_r, cell_c = np.nonzero(alive)
    n_cells = cell_r.size
    per = 2 * k
    cell_id = np.full((rows + 1, cols + 1), -1, np.int64)
    cell_id[:rows, :cols][alive] = np.arange(n_cells)
    base = cell_id[cell_r, cell_c] * per                      # (n_cells,)

    node_side = np.repeat(np.arange(2, dtype=np.int32), k)
    node_k = np.tile(np.arange(k, dtype=np.int32), 2)
    node_r = np.repeat(cell_r.astype(np.int32), per)
    node_c = np.repeat(cell_c.astype(np.int32), per)

    # per cell: k vertical nodes x (k in-cell + 1 down), then k horizontal
    # nodes x 1 right, in (i, j) order; invalid slots are dropped
    kk = np.arange(k)
    down = cell_id[cell_r + 1, cell_c]
    right = cell_id[cell_r, cell_c + 1]
    e0 = np.empty((n_cells, k, k + 1), np.int64)
    e1 = np.empty((n_cells, k, k + 1), np.int64)
    e0[:] = (base[:, None] + kk[None, :])[:, :, None]
    e1[:, :, :k] = (base[:, None] + k + kk[None, :])[:, None, :]
    e1[:, :, k] = down[:, None] * per + kk[None, :]
    ok = np.ones((n_cells, k, k + 1), bool)
    ok[:, :, k] = (down >= 0)[:, None]
    h0 = base[:, None] + k + kk[None, :]
    h1 = right[:, None] * per + k + kk[None, :]
    hok = np.broadcast_to((right >= 0)[:, None], (n_cells, k))
    e0 = np.concatenate([e0.reshape(n_cells, k * (k + 1)), h0], axis=1)
    e1 = np.concatenate([e1.reshape(n_cells, k * (k + 1)), h1], axis=1)
    ok = np.concatenate([ok.reshape(n_cells, k * (k + 1)), hok], axis=1)
    edges_arr = np.stack([e0[ok], e1[ok]], axis=1).astype(np.int32)
    edges_arr = edges_arr.reshape(-1, 2)
    g = ChimeraGraph(
        rows=rows,
        cols=cols,
        k=k,
        masked_cells=tuple(sorted(masked)),
        n_nodes=n_cells * per,
        node_r=node_r,
        node_c=node_c,
        node_side=np.tile(node_side, n_cells),
        node_k=np.tile(node_k, n_cells),
        color=((node_r + node_c + np.tile(node_side, n_cells)) % 2
               ).astype(np.int32),
        edges=edges_arr,
    )
    assert g.validate_two_coloring(), "Chimera 2-coloring broken"
    return g


def make_chip_graph() -> ChimeraGraph:
    """The paper's chip: 7x8 Chimera with one cell replaced by bias/SPI.

    440 spins = (7*8 - 1) cells * 8 spins.
    """
    return make_chimera(7, 8, K_CELL, masked_cells=[(6, 7)])
