"""The whole window's share of the chip's peak FLOP/s (%).

The operations of every sweep the traced window completed (the algorithm's
count from shapes, ``bench/roofline.py``, as the generator's ``work()``
gives them) over the traced window's length times the peak of
``bench/peaks.json``.  The window is at least as long as any kernel's
device time, so this share is at most any sweep kernel's compute share and
bounds it from below.  It reads whichever kernel does the work, or none:
where a later change renames or removes the sweep kernel, the roofline
reader finds nothing and this one still reads.
"""


def read(ctx):
    work, win = ctx["work"], ctx["trace"]["window_s"]
    if not work or ctx["peak"] is None or win <= 0 or work[0] <= 0:
        return None
    return 100.0 * work[0] / (win * ctx["peak"]["flops_per_s"])
