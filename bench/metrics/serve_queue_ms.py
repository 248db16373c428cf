"""Median time an answered request waited in the service's queue (ms).

RequestResult.queue_s: admission to the start of its launch, over the whole
window.
"""


def read(ctx):
    return ctx["layer"].get("queue_ms")
