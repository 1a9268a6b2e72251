"""Device-phase scopes: every executor names its phases with the
``repro.*`` vocabulary of ``repro.core.phases``, and the names survive
into the optimized HLO that a profile is read against
(``SearchSession.compiled_hlo``). A refactor that drops a scope fails here,
on the CPU."""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import phases
from repro.core.tree import build_tree
from repro.data import synth
from repro.distributed.meshutil import local_mesh
from repro.index import Index
from repro.serving import SearchSession, ShardedSearchSession

DIM = 16
N = 4000
BUCKET = 512  # > 256 query rows, so point-major slabs are narrower (q_cap)

ALL = set(phases.PHASES)
NO_CARRY = ALL - {phases.CARRY}  # one slab per query tile, no running table
# whole-shard kernel: slicing, the running table and the fold live inside
# the kernel, which the distance scope covers
KERNEL = ALL - {phases.SLICE, phases.CARRY}


@pytest.fixture(scope="module")
def index():
    vecs, _ = synth.sample_descriptors(N, DIM, seed=0, n_centers=40)
    tree = build_tree(jnp.asarray(vecs), (16, 16), key=jax.random.PRNGKey(1))
    idx = Index.create(tree, None, mesh=local_mesh())
    idx.append(vecs[:1500])
    idx.append(vecs[1500:])
    idx.enable_codes(m=4, bits=8, seed=0)
    idx.commit()
    return idx


def _scopes(text):
    return set(re.findall(r"repro\.[a-z.]+", text))


@pytest.mark.parametrize("layout,impl,want", [
    ("point_major", "xla", ALL),
    ("query_routed", "xla", NO_CARRY),
    ("scan_codes", "xla", ALL),
    ("point_major", "fused", ALL),
    ("scan_codes", "fused", ALL),
])
def test_every_phase_scope_reaches_the_compiled_program(index, layout, impl,
                                                        want):
    s = SearchSession(index, k=5, layout=layout, impl=impl, probes=1,
                      buckets=(BUCKET,))
    s.warmup()
    if layout != "query_routed":
        assert s.plan_summary()[0]["q_cap"] < BUCKET
    texts = s.compiled_hlo()
    assert len(texts) == 1
    assert _scopes(texts[0]) == want


def test_kernel_paths_name_their_phases(index, monkeypatch):
    monkeypatch.setenv("REPRO_FUSED_FORCE_KERNEL", "1")
    s = SearchSession(index, k=5, layout="point_major", impl="fused",
                      probes=1, buckets=(BUCKET,))
    assert _scopes(s.compiled_hlo()[0]) == KERNEL


def test_sharded_session_programs_name_their_phases(index):
    s = ShardedSearchSession(index, shards=2, k=5, layout="point_major",
                             probes=2, buckets=(BUCKET // 2,))
    texts = s.compiled_hlo()
    assert len(texts) == 2  # one program per shard
    for text in texts:
        assert phases.PHASES[0] in _scopes(text)
        assert _scopes(text) <= ALL


def test_compiled_hlo_is_what_serves(index):
    """The text is of the program the rung runs: compiling it again adds
    no program to the rung's cache, and its buckets filter."""
    s = SearchSession(index, k=5, layout="point_major", probes=1,
                      buckets=(64, BUCKET))
    s.warmup()
    before = s.recompiles()
    texts = s.compiled_hlo(buckets=[BUCKET])
    assert len(texts) == 1 and texts[0].startswith("HloModule ")
    assert s.recompiles() == before
    q = np.asarray(index.read_rows(np.arange(40)), np.float32)
    ids, _ = s.search(q)
    np.testing.assert_array_equal(ids[:, 0], np.arange(40))


@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compilation cache in ``tmp_path`` for one test."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_compiled_hlo_names_this_source_past_a_stale_cache(index,
                                                          persistent_cache):
    """The caches key a program without its metadata, so an executable
    compiled from an earlier source of the same program serves with that
    source's op names; compiled_hlo() compiles afresh and reads this
    one's."""
    s = SearchSession(index, k=5, layout="point_major", probes=1,
                      buckets=(BUCKET,))
    (_, fn, args), = s._programs()

    @functools.wraps(fn.__wrapped__)  # same name: same module, same key
    def earlier(*a):
        with jax.named_scope("earlier_source"):
            return fn.__wrapped__(*a)

    jax.jit(earlier).lower(*args).compile()  # fills the persistent cache
    s.warmup()  # same program: served from that entry
    served = fn.lower(*args).compile().as_text()
    assert "earlier_source" in served
    before = s.recompiles()
    text = s.compiled_hlo()[0]
    assert "earlier_source" not in text
    assert _scopes(text) == ALL

    def strip(t):  # the same program, op for op
        return re.sub(r"metadata=\{[^}]*\}", "", t[t.index("\n%"):])

    assert strip(text) == strip(served)
    assert s.recompiles() == before
    assert jax.config.jax_enable_compilation_cache
