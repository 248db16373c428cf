"""Traces of the Session's jitted functions inside the traced window.

The ``repro.retrace.<name>`` spans that ``repro.runtime.spans.named_jit``
opens while JAX traces a function: every shape is warmed up in set-up, so
any here is a retrace in the measured window (and most likely a compile).
0 where there are none; no number from a program without spans.
"""
import program_spans as ps


def read(ctx):
    red = ps.load(ctx)
    if red is None:
        return None
    value = sum(s["count"] for name, s in red["spans"].items()
                if name.startswith("retrace."))
    ps.report("retraces", value, red)
    return value
