"""Blocking reads in ``train_cd``'s loop per epoch (syncs).

The ``repro.cd.sync`` spans of the traced window
(``bench/program_spans.py``) over the window's epochs.  Each such span is
one wait of the host on the device for the epochs' metrics: 1 where every
epoch reads its metrics back, about 1/``eval_every`` where only the
evaluations do, which keeps the device fed between them.  No number where
the window holds no ``repro.cd.train`` span (no CD, or a program without
spans).
"""
import program_spans as ps


def read(ctx):
    red = ps.load(ctx)
    epochs = ctx["counters"].get("epochs")
    if red is None or not epochs or "cd.train" not in red["spans"]:
        return None
    value = red["spans"].get("cd.sync", {}).get("count", 0) / epochs
    ps.report("cd_syncs_per_epoch", value, red)
    return value
