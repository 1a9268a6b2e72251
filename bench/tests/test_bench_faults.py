"""The comparison catches a broken timed path: each run here skips the
look for a chip, drives set-up, window and comparison at a tiny size with
the program's dispatch broken underneath, and must come out not correct.
Faults: half of each batch left out (its rows get no answer), one answer
altered where it is produced, and the answers handed back in reverse
order. (The cells run on one chip and carry
no state from step to step, so there is no exchange and no stale state to
break.)"""

import numpy as np
import pytest

import tiny
from repro.serving.session import SearchSession


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _half_left_out(orig):
    def execute(self, queries, *, n_images=None):
        h = len(queries) // 2
        ids, dists, leaves, dt = orig(self, queries[:h], n_images=n_images)
        pad = len(queries) - h
        ids = np.concatenate([ids, np.full((pad, ids.shape[1]), -1,
                                           ids.dtype)])
        dists = np.concatenate([dists, np.full((pad, dists.shape[1]),
                                               np.inf, dists.dtype)])
        return ids, dists, leaves, dt
    return execute


def _answer_altered(orig):
    def execute(self, queries, *, n_images=None):
        ids, dists, leaves, dt = orig(self, queries, n_images=n_images)
        ids = ids.copy()
        ids[:, 0] = np.where(ids[:, 0] >= 0, ids[:, 0] ^ 1, ids[:, 0])
        return ids, dists, leaves, dt
    return execute


def _order_reversed(orig):
    def execute(self, queries, *, n_images=None):
        ids, dists, leaves, dt = orig(self, queries, n_images=n_images)
        return ids[:, ::-1].copy(), dists[:, ::-1].copy(), leaves, dt
    return execute


@pytest.mark.parametrize("fault", [_half_left_out, _answer_altered,
                                   _order_reversed])
@pytest.mark.parametrize("cell", tiny.cells())
def test_fault_is_not_correct(root, cell, fault, monkeypatch):
    monkeypatch.setattr(SearchSession, "_execute",
                        fault(SearchSession._execute))
    result, lines = tiny.measure(root, cell)
    assert not result["correct"], lines
    assert any(c["value"] > c["limit"] for c in result["checks"].values())
