"""Host time of a serving launch (ms): the median, over the window's pumps
that launched, of the ``repro.serve.pump`` span less its
``repro.serve.fetch`` child.

What a launch costs the host, whether the device waits for it or not: a
pipelined pump lowers ``host_gap_ms.serve`` but not this number; cutting
host work lowers both.
"""
import statistics

import program_spans as ps


def read(ctx):
    red = ps.load(ctx)
    if red is None:
        return None
    fetch = ps.nested(red, "serve.pump", "serve.fetch")
    launch = ps.nested(red, "serve.pump", "serve.launch")
    host = [d - f for (d, f), (_, n) in zip(fetch, launch) if n]
    if not host:
        return None
    value = 1e3 * statistics.median(host)
    ps.report(f"pump_host_ms over {len(host)} launches", value, red)
    return value
