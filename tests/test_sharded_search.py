"""Sharded scatter-gather search: ShardPlan derivation/persistence, and the
acceptance invariant — sharded results bit-identical to unsharded (ids AND
distances) at shard counts 1-4, both layouts, probes >= 1, with deletes."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.tree import build_tree
from repro.data import synth
from repro.distributed.meshutil import local_mesh, shard_submeshes
from repro.index import Index, ShardedIndex, ShardPlan

DIM = 16
N = 2000


@pytest.fixture(scope="module")
def corpus():
    vecs_np, _ = synth.sample_descriptors(N, DIM, seed=0, n_centers=40)
    tree = build_tree(jnp.asarray(vecs_np), (8, 4), key=jax.random.PRNGKey(1))
    mesh = local_mesh()
    q_np = vecs_np[:48] + np.random.default_rng(2).standard_normal(
        (48, DIM)
    ).astype(np.float32)
    return vecs_np, tree, mesh, q_np


def _grow(corpus, bounds, directory=None):
    vecs_np, tree, mesh, _ = corpus
    idx = Index.create(tree, directory, mesh=mesh)
    for lo, hi in zip((0,) + bounds, bounds + (N,)):
        if hi > lo:
            idx.append(vecs_np[lo:hi])
    idx.commit()
    return idx


# ---------------------------------------------------------------------------
# ShardPlan: derivation, validation, serialization
# ---------------------------------------------------------------------------


def test_round_robin_covers_and_keeps_global_order():
    names = [f"seg_{i:06d}" for i in range(1, 8)]
    p = ShardPlan.round_robin(names, 3)
    assert p.covers(names)
    assert p.assignment[0] == (names[0], names[3], names[6])
    for shard in p.assignment:  # global append order within every shard
        assert list(shard) == sorted(shard)


def test_balanced_spreads_sizes_and_keeps_global_order():
    names = [f"seg_{i:06d}" for i in range(1, 6)]
    sizes = [100, 100, 100, 100, 400]  # one giant segment
    p = ShardPlan.balanced(names, sizes, 2)
    assert p.covers(names)
    by_name = dict(zip(names, sizes))
    loads = [sum(by_name[n] for n in shard) for shard in p.assignment]
    assert max(loads) == 400 and min(loads) == 400  # LPT: 400 vs 4x100
    for shard in p.assignment:
        assert list(shard) == sorted(shard)


def test_shardplan_validation():
    with pytest.raises(ValueError, match="must be >= 1"):
        ShardPlan.round_robin(["a"], 0)
    with pytest.raises(ValueError, match="unknown shard strategy"):
        ShardPlan(n_shards=1, strategy="hash", assignment=(("a",),))
    with pytest.raises(ValueError, match="twice"):
        ShardPlan.explicit([["a", "b"], ["b"]])
    with pytest.raises(ValueError, match="sizes"):
        ShardPlan.balanced(["a", "b"], [1], 2)
    p = ShardPlan.explicit([["a"], ["b"]])
    assert p.shard_of("b") == 1
    with pytest.raises(KeyError):
        p.shard_of("c")
    assert not p.covers(["a", "b", "c"])


def test_shardplan_json_roundtrip():
    p = ShardPlan.round_robin([f"seg_{i:06d}" for i in range(1, 5)], 3)
    assert ShardPlan.from_json(p.to_json()) == p


def test_explicit_plan_cannot_rederive(corpus):
    idx = _grow(corpus, (1000,))
    p = ShardPlan.explicit([[s.name] for s in idx.segments])
    with pytest.raises(ValueError, match="cannot derive"):
        p.rederived(idx)


# ---------------------------------------------------------------------------
# the acceptance invariant: sharded == unsharded, bit for bit
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(
    n_segments=st.integers(1, 4),
    n_shards=st.integers(1, 4),
    layout=st.sampled_from(["point_major", "query_routed"]),
    strategy=st.sampled_from(["round_robin", "balanced"]),
    probes=st.integers(1, 2),
    with_deletes=st.booleans(),
    seed=st.integers(0, 10_000),
)
def test_sharded_search_bit_identical_property(
    corpus, n_segments, n_shards, layout, strategy, probes, with_deletes, seed
):
    vecs_np, tree, mesh, q_np = corpus
    rng = np.random.default_rng(seed)
    # segment boundaries on a 500-row grid: bounded compile diversity
    cuts = rng.choice([500, 1000, 1500], size=n_segments - 1, replace=False)
    idx = _grow(corpus, tuple(sorted(int(c) for c in cuts)))
    if with_deletes:
        idx.delete(rng.choice(N, size=25, replace=False))
    ref = idx.search(q_np, k=5, layout=layout, probes=probes, q_cap=512)
    sharded = ShardedIndex(idx, n_shards=n_shards, strategy=strategy)
    res = sharded.search(q_np, k=5, layout=layout, probes=probes, q_cap=512)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))
    np.testing.assert_array_equal(np.asarray(res.dists),
                                  np.asarray(ref.dists))
    assert float(res.pairs) == float(ref.pairs)
    assert int(res.q_cap_overflow) == int(ref.q_cap_overflow)


def test_sharded_search_empty_index_and_empty_shards(corpus):
    vecs_np, tree, mesh, q_np = corpus
    empty = Index.create(tree, None, mesh=mesh)
    res = ShardedIndex(empty, n_shards=2).search(q_np[:4], k=3)
    assert (np.asarray(res.ids) == -1).all()
    assert np.isinf(np.asarray(res.dists)).all()
    # more shards than segments: the empty scatter legs contribute nothing
    idx = _grow(corpus, (1000,))
    ref = idx.search(q_np, k=5, q_cap=512)
    res = ShardedIndex(idx, n_shards=4).search(q_np, k=5, q_cap=512)
    np.testing.assert_array_equal(np.asarray(res.ids), np.asarray(ref.ids))


def test_sharded_index_rejects_stale_plan(corpus):
    idx = _grow(corpus, (1000,))
    plan = ShardPlan.for_index(idx, 2)
    idx.append(corpus[0][:500], ids=np.arange(9000, 9500))
    with pytest.raises(ValueError, match="does not cover"):
        ShardedIndex(idx, plan=plan)
    assert ShardedIndex(idx, plan=plan.rederived(idx)).n_shards == 2


# ---------------------------------------------------------------------------
# manifest persistence
# ---------------------------------------------------------------------------


def test_shard_plan_persists_and_follows_lifecycle(corpus, tmp_path):
    vecs_np, tree, mesh, _ = corpus
    d = str(tmp_path / "idx")
    idx = _grow(corpus, (1000,), directory=d)
    sharded = ShardedIndex(idx, n_shards=2, strategy="balanced")
    sharded.persist_plan()
    idx.commit()
    reopened = Index.open(d, mesh=mesh)
    assert reopened.shard_plan == sharded.plan
    # an append + commit re-derives the same strategy over the new set
    reopened.append(vecs_np[:500], ids=np.arange(7000, 7500))
    reopened.commit()
    assert reopened.shard_plan.strategy == "balanced"
    assert reopened.shard_plan.covers([s.name for s in reopened.segments])
    # compaction folds to one segment; the plan follows
    reopened.compact()
    assert reopened.shard_plan.covers([s.name for s in reopened.segments])
    again = Index.open(d, mesh=mesh)
    assert again.shard_plan == reopened.shard_plan
    # explicit plans cannot follow a changed segment set: dropped
    again.set_shard_plan(
        ShardPlan.explicit([[s.name] for s in again.segments])
    )
    again.commit()
    again.append(vecs_np[:500], ids=np.arange(8000, 8500))
    again.commit()
    assert again.shard_plan is None


def test_set_shard_plan_rejects_non_covering(corpus):
    idx = _grow(corpus, (1000,))
    with pytest.raises(ValueError, match="does not cover"):
        idx.set_shard_plan(ShardPlan.explicit([["seg_999999"]]))


# ---------------------------------------------------------------------------
# serving: ShardedSearchSession above the scatter
# ---------------------------------------------------------------------------


def test_sharded_session_matches_unsharded_session(corpus):
    from repro.serving import SearchSession, ShardedSearchSession

    vecs_np, tree, mesh, q_np = corpus
    idx = _grow(corpus, (500, 1500))
    ref = SearchSession(idx, k=5, layout="point_major", probes=2,
                        buckets=(32, 96))
    ref.warmup()
    for n_shards in (1, 2, 3):
        s = ShardedSearchSession(idx, shards=n_shards, k=5,
                                 layout="point_major", probes=2,
                                 buckets=(32, 96))
        s.warmup()
        assert s.recompiles() == len(s.buckets) * min(n_shards, 3)
        for n in (1, 31, 48):
            ids, dists = s.search(q_np[:n])
            ref_ids, ref_dists = ref.search(q_np[:n])
            np.testing.assert_array_equal(ids, ref_ids)
            np.testing.assert_array_equal(dists, ref_dists)
        assert s.steady_state_recompiles() == 0


def test_sharded_session_refresh_after_delete(corpus):
    from repro.serving import ShardedSearchSession

    vecs_np, tree, mesh, q_np = corpus
    idx = _grow(corpus, (1000,))
    s = ShardedSearchSession(idx, shards=2, k=3, buckets=(32,),
                             cache_leaves=tree.n_leaves, cache_admit_after=1)
    s.warmup()
    q = q_np[:8]
    s.search(q)  # admit + memoise (pre-scatter cache)
    hit = s.cache.try_serve(q, 3)
    assert hit is not None
    victim = int(hit[0][0, 0])
    idx.delete([victim])
    s.refresh()
    s.warmup()
    assert s.cache.try_serve(q, 3) is None  # stale slabs dropped
    ids, _ = s.search(q)
    assert victim not in ids
    assert s.steady_state_recompiles() == 0


def test_sharded_session_micro_batcher_and_cache(corpus):
    from repro.serving import MicroBatcher, ShardedSearchSession, \
        TraceLoadGenerator

    vecs_np, tree, mesh, q_np = corpus
    idx = _grow(corpus, (1000,))
    s = ShardedSearchSession(idx, shards=2, k=5, buckets=(64, 128),
                             cache_leaves=64, cache_admit_after=1)
    s.warmup()
    gen = TraceLoadGenerator(vecs_np, 20, seed=3)
    reqs = gen.from_trace(60, N // 20, skew="zipf", rate=400.0)
    done = MicroBatcher(s, max_wait_ms=4.0, max_queue=1024).run(reqs)
    assert s.metrics.requests == 60
    assert s.steady_state_recompiles() == 0
    # a cache-served repeat agrees with the engine's scatter-gather answer
    served = next(c for c in done if c.source == "engine")
    q = gen.requests([served.image_id], [0.0])[0].queries
    if s.cache.try_serve(q, s.k) is not None:
        c_ids, c_d = s.cache.try_serve(q, s.k)
        e_ids, e_d = s.search(q)
        np.testing.assert_array_equal(c_ids, e_ids)
        # ids agree exactly; distances to f32 rounding (the cache contract,
        # same tolerance as tests/test_serving.py)
        np.testing.assert_allclose(c_d, e_d, rtol=1e-3, atol=0.5)


def test_sharded_session_from_persisted_plan(corpus, tmp_path):
    from repro.serving import ShardedSearchSession

    vecs_np, tree, mesh, q_np = corpus
    d = str(tmp_path / "idx")
    idx = _grow(corpus, (1000,), directory=d)
    idx.set_shard_plan(ShardPlan.for_index(idx, 2))
    idx.commit()
    s = ShardedSearchSession(Index.open(d, mesh=mesh), k=3, buckets=(32,))
    assert s.n_shards == 2
    with pytest.raises(ValueError, match="needs shards"):
        ShardedSearchSession(_grow(corpus, (1000,)), k=3, buckets=(32,))


def test_shard_submeshes_fallback_is_shared_mesh():
    mesh = local_mesh()
    subs = shard_submeshes(mesh, 3)
    assert len(subs) == 3
    if len(jax.devices()) == 1:  # sequential-but-isolated fallback
        assert all(m is mesh for m in subs)
    with pytest.raises(ValueError):
        shard_submeshes(mesh, 0)
