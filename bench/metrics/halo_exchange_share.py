"""Share of the device's busy time spent exchanging halos (%).

The summed device time of the ops matching the traffic's
``kernels.exchange`` pattern (the collective permutes that carry each row
band's boundary spins to its neighbours) over the union of the device's
op intervals, both averaged over the chips used (bench/trace_reduce.py).
Under the per-half-sweep barrier the exchange sits between the half
sweeps, so this share is device time the sweep does not hide.  No number
where the traffic names no exchange or none ran (one chip, no halo).
"""


def read(ctx):
    t = ctx["trace"]
    k = t["kernels"].get("exchange")
    if not k or not k["count"] or t["busy_s"] <= 0:
        return None
    return 100.0 * k["seconds"] / t["busy_s"]
