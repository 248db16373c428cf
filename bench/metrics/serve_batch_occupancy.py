"""Chains packed per launch over the service's capacity_chains (%).

From the answered requests' launch_seq and chain counts and the service's
own launch counter, over the whole window.
"""


def read(ctx):
    v = ctx["layer"].get("occupancy")
    return None if v is None else 100.0 * v
