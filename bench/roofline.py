"""Operations and HBM bytes the p-bit sweep needs, from shapes alone.

The counts are of the algorithm, not of any implementation: a later kernel
that gathers, rolls or multiplies differently does the same work here.  Per
spin update the neuron sums its couplers (one multiply and one add per
coupler it has) and then applies eqn 2:

    + h, + offset, x gain, x beta, tanh, x rng gain, + noise, + comparator
    offset                                                    = 8 operations

so one sweep of one chain costs 2 x 2E + 8N operations (E undirected
couplers, each read from both ends).  The counter-hash noise is integer
work and is not counted.  The bytes are what one launch must move through
HBM at least: the programmed couplers (two f32 directions per coupler), the
five per-spin f32 constants, the spins in and out, the schedule and the
noise state; a moment launch also writes its sums, a histogram launch its
bins.  Moments add one add per spin and one multiply-add per coupler for
each measured sweep of each chain.
"""
from __future__ import annotations

NEURON_OPS = 8
F32 = 4


def sweep_ops(n: int, e: int, chains: int, sweeps: int) -> int:
    return chains * sweeps * (4 * e + NEURON_OPS * n)


def moment_ops(n: int, e: int, chains: int, measured: int) -> int:
    return chains * measured * (n + 2 * e)


def launch_bytes(n: int, e: int, chains: int, sweeps: int, *,
                 moments: bool = False, hist_bins: int = 0) -> int:
    program = 2 * e * F32 + 5 * n * F32
    state = 2 * chains * n * F32 + sweeps * F32 + 2 * F32
    out = (n + e) * F32 if moments else 0
    return program + state + out + hist_bins * F32


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: dict) -> tuple[float, str]:
    """Share (%) of the least time the chip could take for this work, and
    which bound sets that least time ("compute" or "memory")."""
    t_c = ops / peak["flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound
