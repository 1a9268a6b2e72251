"""Live-wave sweeps: the point-major and codes sweeps visit only the tiles
whose leaf span meets a lookup row (``tilescan.live_waves``), and return
exactly what a sweep over every tile returns — ids, distances, pairs and
slab overflow, bit for bit — on one shard and on two."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.codes import ProductQuantizer
from repro.core.engine import SearchPlan, make_executor, tilescan
from repro.core.engine.executors import pad_lookup
from repro.core.index_build import build_index
from repro.core.lookup import build_lookup
from repro.core.sentinels import LEAF_SENTINEL
from repro.core.tree import build_tree, tree_assign
from repro.data import synth
from repro.distributed.meshutil import data_axis_size, local_mesh

DIM = 16
N = 2400
K = 4
HERE = os.path.dirname(os.path.abspath(__file__))


def _every_wave(leaves, offsets, *, block_rows, n_entries):
    """The sweep before skipping: every tile, in order."""
    n = leaves.shape[0] // block_rows
    return jnp.arange(n, dtype=jnp.int32), jnp.int32(n)


def _corpus(seed=0, capacity_factor=2.0, mesh=None):
    vecs_np, _ = synth.sample_descriptors(N, DIM, seed=seed, n_centers=32)
    vecs = jnp.asarray(vecs_np)
    tree = build_tree(vecs, (4, 4), key=jax.random.PRNGKey(seed + 1))
    mesh = mesh if mesh is not None else local_mesh()
    index = build_index(vecs, tree, mesh, wire_dtype=jnp.float32,
                        capacity_factor=capacity_factor)
    return vecs_np, tree, mesh, index


def _run(index, lookup, plan, mesh, *, q_total, codes=None, codebooks=None):
    """One fresh trace of ``plan``'s executor (no cached program)."""
    n_shards = data_axis_size(mesh)
    fn = make_executor(mesh, plan, n_leaves=index.n_leaves,
                       shard_rows=index.rows // n_shards, q_total=q_total)
    args = (index, pad_lookup(lookup, q_total))
    if codes is not None:
        args += (jnp.asarray(codes), jnp.asarray(codebooks))
    res = jax.jit(fn)(*args)
    return jax.device_get((res.ids, res.dists, res.pairs,
                           res.q_cap_overflow, res.waves))


def _skip_and_full(monkeypatch, *args, **kw):
    live = _run(*args, **kw)
    with monkeypatch.context() as m:
        m.setattr(tilescan, "live_waves", _every_wave)
        full = _run(*args, **kw)
    return live, full


def _assert_identical(live, full):
    for a, b in zip(live[:4], full[:4]):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def _numpy_live(leaves, lookup_leaves, block_rows):
    """Tiles whose span [first leaf, last real leaf] holds a lookup leaf."""
    ql = np.asarray(lookup_leaves)
    ql = ql[ql >= 0]
    n = 0
    for tile in np.asarray(leaves).reshape(-1, block_rows):
        real = tile[tile != LEAF_SENTINEL]
        if real.size and ((ql >= tile[0]) & (ql <= real.max())).any():
            n += 1
    return n


def _tombstone(index, every):
    """Mask every ``every``-th real row as a deleted one is masked."""
    ids = np.asarray(index.ids)
    hit = (ids >= 0) & (ids % every == 0)
    return index.__class__(
        vecs=jnp.where(jnp.asarray(hit)[:, None], 1e15, index.vecs),
        ids=jnp.where(jnp.asarray(hit), -1, index.ids),
        leaves=index.leaves, offsets=index.offsets, n_valid=index.n_valid,
        overflow=index.overflow, n_leaves=index.n_leaves,
    )


CASES = {
    # name: (n queries, probes, block_rows, capacity factor, tombstones)
    "padding_tail_and_gaps": (6, 1, 100, 2.0, 0),
    "dense_every_tile_live": (600, 1, 200, 1.0, 0),
    "probes_2": (10, 2, 100, 2.0, 0),
    "tombstoned_rows": (20, 1, 100, 2.0, 3),
    "leaf_over_many_tiles": (8, 1, 20, 2.0, 0),
}


@pytest.mark.parametrize("layout", ["point_major", "scan_codes"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_live_sweep_is_bit_identical_to_full_sweep(monkeypatch, case,
                                                   layout):
    n_q, probes, block_rows, cf, every = CASES[case]
    vecs_np, tree, mesh, index = _corpus(capacity_factor=cf)
    if every:
        index = _tombstone(index, every)
    rng = np.random.default_rng(7)
    q = vecs_np[rng.choice(N, n_q, replace=False)] + 0.05
    lookup = build_lookup(tree, jnp.asarray(q), probes=probes)
    q_total = max(64, n_q * probes)
    plan = SearchPlan(layout=layout, k=K, probes=probes, impl="xla",
                      block_rows=block_rows, q_cap=64)
    kw = {}
    if layout == "scan_codes":
        pq = ProductQuantizer.train(vecs_np, m=4, bits=4, seed=0)
        kw = dict(codes=pq.encode(np.asarray(index.vecs)),
                  codebooks=pq.codebooks)
        plan = SearchPlan(layout=layout, k=K, probes=probes, impl="xla",
                          block_rows=block_rows, q_cap=64, rerank=8,
                          code_m=4, code_bits=4)
    live, full = _skip_and_full(monkeypatch, index, lookup, plan, mesh,
                                q_total=q_total, **kw)
    _assert_identical(live, full)
    n_waves = index.rows // block_rows
    assert int(full[4]) == n_waves
    want = _numpy_live(index.leaves, lookup.leaves, block_rows)
    assert int(live[4]) == want
    leaves = np.asarray(index.leaves)
    real = leaves[leaves != LEAF_SENTINEL]
    if case == "dense_every_tile_live":
        assert want == n_waves
    else:
        assert 0 < want < n_waves
    if case == "padding_tail_and_gaps":
        # all-padding tiles sit at the tail, query-free ones between live
        assert real.size < leaves.size - block_rows
        live_tiles = np.asarray(tilescan.live_waves(
            index.leaves, lookup.offsets, block_rows=block_rows,
            n_entries=index.n_leaves)[0])[:want]
        assert np.diff(live_tiles).max() > 1
    if case == "leaf_over_many_tiles":
        assert np.bincount(real).max() > 2 * block_rows
    if case == "tombstoned_rows":
        assert (np.asarray(index.ids) == -1).sum() > leaves.size - real.size


@pytest.mark.parametrize("probes", [1, 2])
def test_live_waves_count_matches_numpy(probes):
    vecs_np, tree, _, index = _corpus(seed=3)
    q = vecs_np[::97][:12] + 0.1
    lookup = build_lookup(tree, jnp.asarray(q), probes=probes)
    waves, n_live = jax.jit(
        tilescan.live_waves, static_argnames=("block_rows", "n_entries")
    )(index.leaves, lookup.offsets, block_rows=50, n_entries=index.n_leaves)
    n_live = int(n_live)
    assert n_live == _numpy_live(index.leaves, lookup.leaves, 50)
    waves = np.asarray(waves)
    assert (np.diff(waves[:n_live]) > 0).all()
    assert (waves[n_live:] == 0).all()


def two_shard_check():
    """Run under two forced CPU devices: each shard skips its own tiles."""
    mesh = local_mesh()
    assert data_axis_size(mesh) == 2
    vecs_np, tree, _, index = _corpus(seed=5, mesh=mesh)
    leaves = np.asarray(index.leaves)
    half = index.n_leaves // 2
    # many queries from shard 0's leaves, two from shard 1's
    own = np.asarray(tree_assign(tree, jnp.asarray(vecs_np)))
    rows = np.concatenate([np.flatnonzero(own < half)[::40],
                           np.flatnonzero(own >= half)[:2]])
    lookup = build_lookup(tree, jnp.asarray(vecs_np[rows] + 0.05))
    block_rows = 100
    per_shard = [
        _numpy_live(s, lookup.leaves, block_rows)
        for s in leaves.reshape(2, -1)
    ]
    assert per_shard[0] != per_shard[1] and min(per_shard) > 0
    plan = SearchPlan(layout="point_major", k=K, impl="xla",
                      block_rows=block_rows, q_cap=64)
    q_total = max(64, len(rows))
    live = _run(index, lookup, plan, mesh, q_total=q_total)
    orig = tilescan.live_waves
    tilescan.live_waves = _every_wave
    try:
        full = _run(index, lookup, plan, mesh, q_total=q_total)
    finally:
        tilescan.live_waves = orig
    _assert_identical(live, full)
    assert int(live[4]) == sum(per_shard)
    assert int(full[4]) == index.rows // block_rows
    print("two shards ok", per_shard)


def test_two_shards_skip_their_own_waves():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(HERE), "src"), HERE,
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-c",
         "import test_live_waves as t; t.two_shard_check()"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "two shards ok" in out.stdout
