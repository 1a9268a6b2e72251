"""The plain reference and the comparison that decides ``correct``.

Semantics of a search with ``probes=1``: route the query row down the tree
greedily (nearest centre per level, first index on ties) to one leaf, and
return the ``k`` rows of that leaf nearest to it by squared L2, ascending,
with ``-1``/``inf`` where the leaf holds fewer than ``k`` rows. Row ``i`` of
the corpus has descriptor id ``i``.

Nothing here imports the program. The leaf of each corpus row comes from
the reference's own descent over the tree this benchmark made
(``corpus.make_tree``), run on the accelerator once the program's state is
freed (``corpus.device_descent``: exact for byte values); distances are
float64 on the host.

The comparison is tie-tolerant and exact: an answer is right when each
returned id is a distinct row of the query's leaf whose exact distance is
the distance returned, the returned distances ascend, and they equal the
reference's ``k`` smallest, position by position.
"""

from __future__ import annotations

import numpy as np

import corpus as corpus_lib


def _bf16(x):
    import ml_dtypes

    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


class Reference:
    """Exact k-NN within each query's leaf over ``corpus`` (rows = ids)."""

    def __init__(self, corpus: np.ndarray, levels, k: int):
        self.corpus = corpus
        self.levels = levels
        self.k = int(k)
        self.n_leaves = levels[1].shape[0] * levels[1].shape[1]
        self._descend = corpus_lib.device_descent(levels)
        self.row_leaf = self._descend(corpus)
        self.order = np.argsort(self.row_leaf, kind="stable")
        self.starts = np.searchsorted(self.row_leaf[self.order],
                                      np.arange(self.n_leaves + 1))

    @property
    def leaf_sizes(self) -> np.ndarray:
        return np.diff(self.starts)

    def leaves(self, queries: np.ndarray) -> np.ndarray:
        return self._descend(queries)

    def knn(self, queries: np.ndarray, q_leaf: np.ndarray | None = None):
        """``(q_leaf, ids (n, k) int64, dists (n, k) float64)``."""
        q_leaf = self.leaves(queries) if q_leaf is None else q_leaf
        return (q_leaf,) + self._knn(queries, q_leaf, self._dists)

    def _knn(self, queries, q_leaf, dist):
        n, k = len(queries), self.k
        ids = np.full((n, k), -1, np.int64)
        dists = np.full((n, k), np.inf)
        qs = np.argsort(q_leaf, kind="stable")
        bounds = np.flatnonzero(np.diff(q_leaf[qs])) + 1
        for grp in np.split(qs, bounds):
            if grp.size == 0:
                continue
            leaf = q_leaf[grp[0]]
            cand = self.order[self.starts[leaf]:self.starts[leaf + 1]]
            if cand.size == 0:
                continue
            d = dist(queries[grp], cand)
            take = min(k, cand.size)
            for gi, row in enumerate(grp):
                o = np.lexsort((cand, d[gi]))[:take]
                ids[row, :take] = cand[o]
                dists[row, :take] = d[gi, o]
        return ids, dists

    def _dists(self, q: np.ndarray, cand: np.ndarray) -> np.ndarray:
        x = self.corpus[cand].astype(np.float64)
        q = q.astype(np.float64)
        return ((q * q).sum(1)[:, None] - 2.0 * q @ x.T
                + (x * x).sum(1)[None, :])

    def exact(self, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """Exact squared distance of each ``(query row, id)``; ``inf`` at
        ``-1`` and at ids outside the corpus."""
        ok = (ids >= 0) & (ids < len(self.corpus))
        x = self.corpus[np.where(ok, ids, 0)].astype(np.float64)
        d = ((x - queries.astype(np.float64)[:, None, :]) ** 2).sum(-1)
        return np.where(ok, d, np.inf)

    def control_knn(self, queries: np.ndarray):
        """The reference in bfloat16: every norm, dot product and distance
        rounded to bfloat16 (the output of a bf16 contraction), in the
        descent and in the scan. Ids are ordered by the rounded distance,
        and that distance is returned."""
        roots, children = self.levels
        f1 = children.shape[1]

        def nearest(x, c):
            c = c.astype(np.float64)
            part = _bf16(_bf16((c * c).sum(1))[None, :]
                         - 2.0 * _bf16(x.astype(np.float64) @ c.T))
            return part.argmin(1)

        def dist(q, cand):
            x = self.corpus[cand].astype(np.float64)
            q = q.astype(np.float64)
            return _bf16(_bf16(_bf16((x * x).sum(1))[None, :]
                               - 2.0 * _bf16(q @ x.T))
                         + _bf16((q * q).sum(1))[:, None])

        b0 = nearest(queries, roots)
        q_leaf = np.empty(len(queries), np.int64)
        for node in np.unique(b0):
            sel = np.flatnonzero(b0 == node)
            q_leaf[sel] = node * f1 + nearest(queries[sel], children[node])
        return self._knn(queries, q_leaf, dist)

    def compare(self, queries, ids, dists):
        """Numbers of the comparison of one answer table with the
        reference: ``wrong_answers`` (returned entries that are not a
        distinct row of the query's leaf at its exact distance, or that
        should be empty and are not), ``unordered_answers`` (positions
        whose distance is below the one before it), ``missed_neighbours``
        (positions whose distance is not the reference's) and
        ``positions`` (the non-empty positions of the reference)."""
        ids = np.asarray(ids, np.int64)
        dists = np.asarray(dists, np.float64)
        q_leaf, _, ref_d = self.knn(queries)
        n_valid = np.minimum(self.leaf_sizes[q_leaf], self.k)
        due = np.arange(self.k)[None, :] < n_valid[:, None]
        exact = self.exact(queries, ids)
        in_leaf = np.zeros(ids.shape, bool)
        ok = ids >= 0
        in_leaf[ok] = self.row_leaf[np.clip(ids[ok], 0,
                                            len(self.corpus) - 1)] == \
            np.broadcast_to(q_leaf[:, None], ids.shape)[ok]
        srt = np.sort(ids, axis=1)
        dup_sorted = np.zeros(ids.shape, bool)
        dup_sorted[:, 1:] = (srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)
        dup_rows = dup_sorted.sum(1)
        right = np.where(due, in_leaf & (dists == exact),
                         (ids == -1) & np.isinf(dists))
        wrong = int((~right).sum() + dup_rows.sum())
        unordered = int((dists[:, 1:] < dists[:, :-1]).sum())
        missed = int((due & (dists != ref_d)).sum())
        return {"wrong_answers": wrong, "unordered_answers": unordered,
                "missed_neighbours": missed, "positions": int(due.sum())}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the configuration's
    ``checks`` (each a number that may not exceed its limit)."""
    table = {name: {"value": numbers[name], "limit": limit}
             for name, limit in limits.items()}
    return all(v["value"] <= v["limit"] for v in table.values()), table
