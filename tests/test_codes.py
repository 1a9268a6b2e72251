"""Compressed-codes tier: PQ encoder determinism, codebook manifest
round-trips, ADC kernel-vs-reference, exact-rerank bit-identity, the
batched ``read_rows`` gather, and the recall floor at shards 1-3
(docs/compressed_codes.md)."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codes import ProductQuantizer, rerank_exact
from repro.core.engine import plan as make_plan
from repro.core.tree import build_tree
from repro.data import synth
from repro.distributed.meshutil import local_mesh
from repro.index import Index
from repro.index.sharding import ShardedIndex
from repro.kernels.adcscan import adc_topk, adc_topk_ref

DIM = 32
N = 6000
SPLIT = 2600
K = 10
PROBES = 4


@pytest.fixture(scope="module")
def corpus():
    vecs_np, _ = synth.sample_descriptors(N, DIM, seed=0, n_centers=64)
    tree = build_tree(jnp.asarray(vecs_np), (8, 8),
                      key=jax.random.PRNGKey(1))
    mesh = local_mesh()
    q_np = vecs_np[:64] + np.random.default_rng(2).standard_normal(
        (64, DIM)
    ).astype(np.float32)
    return vecs_np, tree, mesh, q_np


@pytest.fixture(scope="module")
def coded_index(corpus, tmp_path_factory):
    """create -> append x2 -> enable_codes -> commit: the canonical
    codes-enabled grown index, durable so reopen tests can share it."""
    vecs_np, tree, mesh, _ = corpus
    d = str(tmp_path_factory.mktemp("codes") / "idx")
    idx = Index.create(tree, d, mesh=mesh)
    idx.append(vecs_np[:SPLIT])
    idx.append(vecs_np[SPLIT:])
    idx.enable_codes(m=8, bits=8, seed=0)
    idx.commit()
    return idx


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------


def test_pq_train_deterministic(corpus):
    vecs_np = corpus[0]
    a = ProductQuantizer.train(vecs_np, m=8, bits=8, seed=0)
    b = ProductQuantizer.train(vecs_np, m=8, bits=8, seed=0)
    assert a.codebooks.tobytes() == b.codebooks.tobytes()
    assert a.encode(vecs_np[:500]).tobytes() == \
        b.encode(vecs_np[:500]).tobytes()
    # a different seed trains different centroids (the sample moved)
    c = ProductQuantizer.train(vecs_np, m=8, bits=8, seed=1)
    assert a.codebooks.tobytes() != c.codebooks.tobytes()


def test_pq_json_roundtrip_bytes(corpus):
    vecs_np = corpus[0]
    pq = ProductQuantizer.train(vecs_np, m=8, bits=8, seed=0)
    back = ProductQuantizer.from_json(json.loads(json.dumps(pq.to_json())))
    assert back.codebooks.tobytes() == pq.codebooks.tobytes()
    assert back.m == pq.m and back.bits == pq.bits
    assert back.encode(vecs_np[:200]).tobytes() == \
        pq.encode(vecs_np[:200]).tobytes()


def test_pq_decode_reduces_error_and_lut_is_exact(corpus):
    vecs_np = corpus[0]
    pq = ProductQuantizer.train(vecs_np, m=8, bits=8, seed=0)
    codes = pq.encode(vecs_np)
    assert codes.dtype == np.uint8 and codes.shape == (N, 8)
    recon = pq.decode(codes)
    err = float(((recon - vecs_np) ** 2).sum(1).mean())
    baseline = float(((vecs_np - vecs_np.mean(0)) ** 2).sum(1).mean())
    assert err < 0.25 * baseline, (err, baseline)
    # lut[q, j, c] == ||q_j - codebook[j, c]||^2, and summing the coded
    # entries reproduces the decoded distance exactly
    q = vecs_np[:5]
    lut = pq.lut(q)
    dsub = DIM // 8
    for j in (0, 7):
        want = ((q[:, None, j * dsub:(j + 1) * dsub]
                 - pq.codebooks[None, j]) ** 2).sum(-1)
        np.testing.assert_allclose(lut[:, j], want, rtol=1e-5, atol=1e-3)
    adc = lut[np.arange(5)[:, None, None],
              np.arange(8)[None, None, :],
              codes[None, :50].astype(np.int64)].sum(-1)
    want = ((pq.decode(codes[:50])[None] - q[:, None]) ** 2).sum(-1)
    np.testing.assert_allclose(adc, want, rtol=1e-4, atol=1e-2)


# ---------------------------------------------------------------------------
# ADC kernel vs reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "shape", [(300, 40, 4, 16), (513, 129, 8, 256), (700, 64, 2, 4)]
)
def test_adcscan_kernel_matches_ref(shape):
    P, Q, m, C = shape
    rng = np.random.default_rng(3)
    codes = rng.integers(0, C, (P, m)).astype(np.uint8)
    lut = rng.random((Q, m, C), dtype=np.float32)
    plf = rng.integers(0, 5, P).astype(np.int32)
    qlf = rng.integers(0, 5, Q).astype(np.int32)
    rd, ri = adc_topk_ref(jnp.asarray(codes), jnp.asarray(plf),
                          jnp.asarray(lut), jnp.asarray(qlf), 8)
    kd, ki = adc_topk(jnp.asarray(codes), jnp.asarray(plf),
                      jnp.asarray(lut), jnp.asarray(qlf),
                      k=8, impl="pallas")
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd),
                               rtol=1e-5, atol=1e-4)
    # ids agree everywhere: ties keep the reference's (distance, row)
    # order (the 2-subvector x 4-centroid case is almost all ties)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))


# ---------------------------------------------------------------------------
# exact rerank
# ---------------------------------------------------------------------------


def test_rerank_exact_bit_identical_to_bruteforce(corpus):
    vecs_np, _, _, q_np = corpus

    def read_rows(ids):
        return vecs_np[np.asarray(ids)]

    rng = np.random.default_rng(4)
    cand = rng.integers(0, N, (len(q_np), 24)).astype(np.int64)
    cand[:, 5] = cand[:, 3]   # duplicates must not double-count
    cand[:, -1] = -1          # empty slots must be ignored
    ids, dists = rerank_exact(read_rows, q_np, cand, K)
    for i in range(len(q_np)):
        u = np.unique(cand[i][cand[i] >= 0])
        d = ((vecs_np[u] - q_np[i]) ** 2).sum(1).astype(np.float32)
        order = np.lexsort((u, d))[:K]
        np.testing.assert_array_equal(ids[i], u[order])
        np.testing.assert_array_equal(dists[i], d[order])
    # fewer valid candidates than k: -1/inf padding, no crash
    ids, dists = rerank_exact(read_rows, q_np[:2],
                              np.array([[7, -1, -1], [-1, -1, -1]]), K)
    assert ids[0][0] == 7 and (ids[0][1:] == -1).all()
    assert (ids[1] == -1).all() and np.isinf(dists[1]).all()


def test_index_codes_search_matches_manual_rerank(coded_index, corpus):
    """The facade's codes path == ADC candidates + rerank_exact by hand:
    rerank ordering is exact (bit-identical) over the same candidates."""
    q_np = corpus[3]
    res = coded_index.search(q_np, k=K, probes=PROBES, layout="scan_codes")
    again = coded_index.search(q_np, k=K, probes=PROBES,
                               layout="scan_codes")
    np.testing.assert_array_equal(np.asarray(res.ids),
                                  np.asarray(again.ids))
    # rerank distances must be *exact* L2 against raw rows, not ADC
    ids = np.asarray(res.ids)
    dists = np.asarray(res.dists)
    live = ids >= 0
    rows = coded_index.read_rows(ids[live].astype(np.int64))
    qexp = np.repeat(q_np, K, axis=0).reshape(len(q_np), K, DIM)[live]
    np.testing.assert_allclose(((rows - qexp) ** 2).sum(1), dists[live],
                               rtol=1e-5, atol=1e-3)


# ---------------------------------------------------------------------------
# lifecycle round-trips
# ---------------------------------------------------------------------------


def test_codebook_roundtrip_commit_open(coded_index, corpus):
    _, _, mesh, q_np = corpus
    reopened = Index.open(coded_index.directory, mesh=mesh)
    assert reopened.quantizer is not None
    assert reopened.quantizer.codebooks.tobytes() == \
        coded_index.quantizer.codebooks.tobytes()
    assert reopened.codes_stats() == coded_index.codes_stats()
    a = coded_index.search(q_np, k=K, probes=PROBES, layout="scan_codes")
    b = reopened.search(q_np, k=K, probes=PROBES, layout="scan_codes")
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.dists), np.asarray(b.dists))


def test_codes_survive_compact_and_delete(corpus, tmp_path):
    vecs_np, tree, mesh, q_np = corpus
    idx = Index.create(tree, str(tmp_path / "idx"), mesh=mesh)
    idx.append(vecs_np[:SPLIT])
    idx.append(vecs_np[SPLIT:])
    idx.enable_codes(m=8, bits=8, seed=0)
    idx.commit()
    before = idx.quantizer.codebooks.tobytes()
    idx.delete(np.arange(40))
    idx.compact()
    # same codebooks, survivors re-encoded, deleted ids gone
    assert idx.quantizer.codebooks.tobytes() == before
    assert idx.n_segments == 1
    res = idx.search(q_np, k=K, probes=PROBES, layout="scan_codes",
                     rerank=64)
    ids = np.asarray(res.ids)
    assert not np.isin(ids, np.arange(40)).any()
    reopened = Index.open(idx.directory, mesh=mesh)
    res2 = reopened.search(q_np, k=K, probes=PROBES, layout="scan_codes",
                           rerank=64)
    np.testing.assert_array_equal(ids, np.asarray(res2.ids))


def test_append_to_coded_index_encodes_new_segment(corpus, tmp_path):
    vecs_np, tree, mesh, q_np = corpus
    idx = Index.create(tree, str(tmp_path / "idx"), mesh=mesh)
    idx.append(vecs_np[:SPLIT])
    idx.enable_codes(m=8, bits=8, seed=0)
    idx.commit()
    idx.append(vecs_np[SPLIT:])
    idx.commit()
    reopened = Index.open(idx.directory, mesh=mesh)
    assert len(reopened._codes) == reopened.n_segments == 2
    a = idx.search(q_np, k=K, probes=PROBES, layout="scan_codes")
    b = reopened.search(q_np, k=K, probes=PROBES, layout="scan_codes")
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


# ---------------------------------------------------------------------------
# batched read_rows
# ---------------------------------------------------------------------------


def test_read_rows_out_of_order_dup_cross_segment(coded_index, corpus):
    vecs_np = corpus[0]
    # out-of-order + duplicates + ids straddling both segments, one call
    ids = np.array([N - 1, 3, SPLIT - 1, 3, SPLIT, 0, N - 1, SPLIT + 7])
    got = coded_index.read_rows(ids)
    np.testing.assert_array_equal(got, vecs_np[ids])
    with pytest.raises(IndexError):
        coded_index.read_rows(np.array([0, N + 100]))
    with pytest.raises(IndexError):
        coded_index.read_rows(np.array([-2]))


# ---------------------------------------------------------------------------
# planning + recall floor
# ---------------------------------------------------------------------------


def test_auto_plan_prices_codes_per_shape():
    kw = dict(n_leaves=64, n_queries=64, n_shards=1, k=K, probes=PROBES,
              layout="auto", model="heuristic", dim=DIM,
              code_m=8, code_bits=8)
    assert make_plan(rows=40_000, **kw).layout == "scan_codes"
    assert make_plan(rows=1_000, **kw).layout == "point_major"
    # without a codes artifact the layout never enters the candidates
    dense = make_plan(rows=40_000, n_leaves=64, n_queries=64, n_shards=1,
                      k=K, probes=PROBES, layout="auto", model="heuristic")
    assert dense.layout != "scan_codes"


def test_scan_codes_without_quantizer_raises(corpus, tmp_path):
    vecs_np, tree, mesh, q_np = corpus
    idx = Index.create(tree, str(tmp_path / "idx"), mesh=mesh)
    idx.append(vecs_np[:SPLIT])
    idx.commit()
    with pytest.raises(ValueError, match="codes"):
        idx.search(q_np, k=K, layout="scan_codes")


@pytest.mark.parametrize("shards", [1, 2, 3])
def test_codes_recall_floor_and_shard_identity(coded_index, corpus, shards):
    """recall@k(scan_codes) >= 0.9 vs scan-exact at the same probes, and
    the sharded codes path is bit-identical to unsharded."""
    q_np = corpus[3]
    ref = coded_index.search(q_np, k=K, probes=PROBES,
                             layout="point_major")
    ref_ids = np.asarray(ref.ids)
    base = coded_index.search(q_np, k=K, probes=PROBES,
                              layout="scan_codes")
    sharded = ShardedIndex(coded_index, n_shards=shards)
    res = sharded.search(q_np, k=K, probes=PROBES, layout="scan_codes")
    np.testing.assert_array_equal(np.asarray(res.ids),
                                  np.asarray(base.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(base.dists))
    ids = np.asarray(res.ids)
    recall = np.mean([
        len(set(ids[i][ids[i] >= 0]) & set(ref_ids[i][ref_ids[i] >= 0]))
        / K
        for i in range(len(q_np))
    ])
    assert recall >= 0.9, f"recall@{K} {recall:.3f} (shards={shards})"


def test_serving_session_codes_matches_facade(coded_index, corpus):
    from repro.serving import SearchSession

    _, _, mesh, q_np = corpus
    s = SearchSession(coded_index, mesh=mesh, k=K, probes=PROBES,
                      buckets=(64,))
    assert s.serving_layout == "scan_codes"
    s.warmup()
    ids, dists = s.search(q_np)
    assert s.steady_state_recompiles() == 0
    res = coded_index.search(q_np, k=K, probes=PROBES, layout="scan_codes")
    np.testing.assert_array_equal(ids, np.asarray(res.ids))
    np.testing.assert_array_equal(dists, np.asarray(res.dists))
