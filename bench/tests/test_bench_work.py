"""The roofline work functions against hand counts on a tiny index, and
the peaks table."""

import numpy as np
import pytest

import work


def test_dense_work_matches_hand_count():
    # leaves 0..3 hold 5, 0, 2, 7 rows; four query rows route to 0, 0, 2, 3
    sizes = np.array([5, 0, 2, 7])
    q_leaf = np.array([0, 0, 2, 3])
    ops, nbytes = work.dispatch_work(sizes, q_leaf, dim=4, k=3)
    pairs = 5 + 5 + 2 + 7
    assert ops == 2 * 4 * pairs
    # distinct candidate rows 5 + 2 + 7 at one byte per dimension, the
    # query rows, and k ids + distances of 4 bytes each per query row
    assert nbytes == (5 + 2 + 7) * 4 + 4 * 4 + 4 * 3 * 8


def test_roofline_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = work.roofline(ops=100.0, nbytes=50.0, device_s=10.0,
                                 peaks=peaks)
    assert (share, bound) == (50.0, "hbm")
    share, bound = work.roofline(ops=1000.0, nbytes=5.0, device_s=20.0,
                                 peaks=peaks)
    assert (share, bound) == (50.0, "compute")
    assert work.roofline(1.0, 1.0, 0.0, peaks) == (None, None)


def test_peaks_table_is_keyed_by_device_kind():
    v5e = work.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks_for("cpu")


def test_device_descent_equals_the_host_descent():
    import corpus

    rng = np.random.default_rng(3)
    x = np.rint(rng.gamma(2.0, 24.0, (3000, 128)).clip(0, 255))
    x = x.astype(np.float32)
    x[:40] = x[40:80]  # exact ties between rows
    levels = corpus.make_tree(x, (8, 16), 1000, seed=5)
    want = corpus.leaves_of(levels, x)
    descend = corpus.device_descent(levels, chunk=512)
    assert (descend(x) == want).all()
    with pytest.raises(ValueError):
        descend(x + 0.5)
