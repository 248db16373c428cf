"""Share of ``train_cd``'s time spent evaluating (%): the time in
``repro.cd.eval`` spans over the time in ``repro.cd.train`` spans, inside
the traced window.

The evaluation (program the master weights, histogram the visible spins,
KL on the host) every ``eval_every`` epochs is work beside the epochs that
``cd_epochs_per_s`` counts.
"""
import program_spans as ps


def read(ctx):
    red = ps.load(ctx)
    spans = {} if red is None else red["spans"]
    if "cd.eval" not in spans or not spans.get("cd.train", {}).get(
            "total_s"):
        return None
    value = 100.0 * spans["cd.eval"]["total_s"] / spans["cd.train"]["total_s"]
    ps.report("cd_eval_share", value, red)
    return value
