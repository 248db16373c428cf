"""95th percentile latency of the requests answered in the traced window
(ms), from when each was due to when its answer came back.

Above the rate the service sustains, due requests wait upstream in a
backlog that grows all through the window, and the tail grows with it.  It
swings with the smallest change in the service's rate, so it is a
per-layer reading and not a bounded metric.
"""


def read(ctx):
    return ctx["layer"].get("answered_p95_ms")
