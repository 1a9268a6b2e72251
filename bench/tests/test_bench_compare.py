"""The comparison with the reference on hand-made answer tables: a
table that names only real leaf rows at their exact distances still fails
when those rows are not the leaf's nearest or are out of order."""

import numpy as np
import pytest

import corpus
from reference import Reference

K = 5


@pytest.fixture(scope="module")
def case():
    rng = np.random.default_rng(11)
    x = np.rint(rng.gamma(2.0, 24.0, (2000, 128)).clip(0, 255))
    x = x.astype(np.float32)
    levels = corpus.make_tree(x, (4, 4), 1000, seed=3)
    ref = Reference(x, levels, K)
    # query rows whose leaf holds more than K + 1 rows
    queries = x[::7][:200]
    q_leaf = ref.leaves(queries)
    keep = ref.leaf_sizes[q_leaf] > K + 1
    return ref, queries[keep], q_leaf[keep]


def test_the_reference_passes(case):
    ref, q, _ = case
    nums = ref.compare(q, *ref.knn(q)[1:])
    assert nums == {"wrong_answers": 0, "unordered_answers": 0,
                    "missed_neighbours": 0, "positions": len(q) * K}


def test_a_leaf_row_beyond_the_k_nearest_is_missed(case):
    ref, q, q_leaf = case
    wide = Reference(ref.corpus, ref.levels, K + 1)
    _, ids, dists = wide.knn(q, q_leaf)
    # the k-th answer replaced by the (k+1)-th: a real, distinct leaf row at
    # its exact distance, still ascending, but not one of the k nearest
    ids = np.concatenate([ids[:, :K - 1], ids[:, K:]], axis=1)
    dists = np.concatenate([dists[:, :K - 1], dists[:, K:]], axis=1)
    nums = ref.compare(q, ids, dists)
    assert nums["wrong_answers"] == nums["unordered_answers"] == 0
    assert nums["missed_neighbours"] > 0


def test_the_first_rows_of_the_leaf_are_missed(case):
    ref, q, q_leaf = case
    ids = np.stack([ref.order[ref.starts[leaf]:ref.starts[leaf] + K]
                    for leaf in q_leaf])
    dists = ref.exact(q, ids)
    o = np.argsort(dists, axis=1, kind="stable")
    ids, dists = np.take_along_axis(ids, o, 1), np.take_along_axis(dists, o, 1)
    nums = ref.compare(q, ids, dists)
    assert nums["wrong_answers"] == nums["unordered_answers"] == 0
    assert nums["missed_neighbours"] > 0


def test_answers_out_of_order_are_unordered(case):
    ref, q, _ = case
    _, ids, dists = ref.knn(q)
    nums = ref.compare(q, ids[:, ::-1], dists[:, ::-1])
    assert nums["wrong_answers"] == 0
    assert nums["unordered_answers"] > 0
