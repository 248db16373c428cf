"""Operation and byte counts against a hand count, and the reference's
graph against the program's."""
from __future__ import annotations

import numpy as np
import pytest

import tiny  # noqa: F401
import reference
import roofline


@pytest.mark.parametrize("rows,cols,masked,n,e", [
    (7, 8, [(6, 7)], 440, 1260),   # 55 cells x 16 + 188 + 192
    (16, 16, [], 2048, 6016),      # 256 x 16 + 2 x 15 x 16 x 4
])
def test_counts_by_hand(rows, cols, masked, n, e):
    g = reference.chimera(rows, cols, masked)
    assert (g.n, len(g.edges)) == (n, e)
    B, S = 256, 512
    # per spin update: one multiply and one add per coupler end (2E ends
    # per sweep), then 8 operations of eqn 2
    assert roofline.sweep_ops(n, e, B, S) == B * S * (2 * 2 * e + 8 * n)
    if n == 440:
        assert roofline.sweep_ops(n, e, B, S) == 1_121_976_320
        # 2 x 1260 f32 couplings + 5 x 440 f32 constants + spins in and
        # out (2 x 256 x 440 f32) + 512 betas + the (seed, counter) pair
        assert roofline.launch_bytes(n, e, B, S) == (
            10_080 + 8_800 + 901_120 + 2_048 + 8)
    else:
        assert roofline.sweep_ops(n, e, B, S) == 5_301_600_256
    assert roofline.moment_ops(n, e, B, 12) == B * 12 * (n + 2 * e)


def test_roofline_names_its_bound():
    peak = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert roofline.roofline_share(1e12, 1e6, 2.0, peak) == (50.0,
                                                             "compute")
    assert roofline.roofline_share(1e6, 1e9, 4.0, peak) == (25.0, "memory")


def test_reference_graph_is_the_programs():
    from repro.core.chimera import make_chimera
    for rows, cols, masked in ((7, 8, [(6, 7)]), (2, 3, []), (1, 1, [])):
        g = make_chimera(rows, cols, masked_cells=masked)
        r = reference.chimera(rows, cols, masked)
        assert np.array_equal(g.edges, r.edges)
        assert np.array_equal(g.color, r.color)
        idx, ok = g.neighbor_table()
        assert np.array_equal(idx, r.nbr) and np.array_equal(ok, r.nbr_ok)
