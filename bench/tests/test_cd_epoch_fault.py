"""`correct` comes out false when ``train_cd``'s compiled epoch keeps its
state: the epoch still draws its data and returns the step's metrics, but
hands back the weights, chains, noise state and velocity it was given.

``train_cd`` runs each epoch through ``Session.make_cd_epoch``, which holds
the update, so the fault is planted there; the tiny CD cell runs on the
CPU as in ``test_correctness.py``.
"""
from __future__ import annotations

import tiny
from repro import api


def test_cd_epoch_that_keeps_its_state_is_caught(tmp_path, monkeypatch):
    root = tiny.make_root(tmp_path)
    orig = api.Session.make_cd_epoch

    def make(self, cfg, visible_idx):
        epoch = orig(self, cfg, visible_idx)

        def stuck(key, p, codes, Jm, hm, m, ns, vel):
            key, ke, *_, metrics = epoch(key, p, codes, Jm, hm, m, ns, vel)
            return key, ke, Jm, hm, m, ns, vel, metrics

        return stuck

    monkeypatch.setattr(api.Session, "make_cd_epoch", make)
    assert tiny.run(root, "tiny.cd")["correct"] is False
