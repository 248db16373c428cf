"""Share of the sweep kernel's roofline (%).

The least time the chip could take for the sweeps the traced window ran
(operations and HBM bytes of the algorithm, from shapes, as the generator's
``work()`` gives them, over the peaks of ``bench/peaks.json``), over the
summed device time of the sweep kernel's ops in the trace (matched by the
traffic's ``kernels.sweep`` pattern).  Nothing to read, and no number,
where no sweep kernel ran.  The bound that binds goes to standard error.
"""
import sys


def read(ctx):
    k = ctx["trace"]["kernels"].get("sweep")
    if not k or not k["count"] or not ctx["work"] or ctx["peak"] is None:
        return None
    import load
    ops, nbytes = ctx["work"]
    share, bound = load.roofline().roofline_share(ops, nbytes, k["seconds"],
                                                  ctx["peak"])
    print(f"sweep_kernel_roofline: {bound}-bound, {ops:.6g} ops, "
          f"{nbytes:.6g} B in {k['seconds']!r} s of kernel time over "
          f"{k['count']} launches", file=sys.stderr)
    return share
