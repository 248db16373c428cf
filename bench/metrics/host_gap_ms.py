"""Device-idle time inside the program's own spans, per unit of work (ms).

The idle time of the traced window that lies under a ``repro.*`` span
(``bench/program_spans.py``: each idle piece goes to the innermost program
span that covers it), over the window's calls (``.sample``), launches
(``.serve``) or epochs (``.cd``).  It is the host work of the program that
the device waits for; a pipelined loop or less host work lowers it.  The
split by span, and the device time per module, go to standard error.
"""
import program_spans as ps

# the generator's counter of the cell's unit of work
UNITS = {"launches": "launch", "epochs": "epoch", "calls": "call"}


def read(ctx):
    red = ps.load(ctx)
    unit = next((u for u in UNITS if u in ctx["counters"]), None)
    if red is None or unit is None or not ctx["counters"][unit]:
        return None
    value = 1e3 * ps.program_idle_s(red) / ctx["counters"][unit]
    ps.report(f"host_gap_ms per {UNITS[unit]}", value, red)
    return value
