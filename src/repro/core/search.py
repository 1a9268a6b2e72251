"""Distributed batch search (paper §2.4) — compatibility shim.

The actual machinery lives in :mod:`repro.core.engine`: a declarative
:class:`~repro.core.engine.SearchPlan`, a ``plan()`` heuristic, and two
executors (point-major and query-routed) rewritten on one shared tile-scan
core. This module keeps the historical entry points stable:

  * ``batch_search_fn`` / ``routed_search_fn`` — jittable pipeline builders
    with their original signatures (configs and hillclimb cells call these);
  * ``pad_lookup`` — lookup padding (now sentinel-named);
  * ``batch_search`` — the eager convenience wrapper, which gained
    ``layout="auto"`` (plan-heuristic pick) and multi-probe ``probes=T``;
  * ``search_with_lookup`` — one executor run over a *pre-built* lookup
    table. The segment-based :class:`repro.index.Index` shares a single
    lookup build across all its segments and calls this per segment.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.engine import SearchPlan, make_executor, plan as make_plan
from repro.core.engine.executors import SearchResult, pad_lookup  # noqa: F401
from repro.core.index_build import DistributedIndex
from repro.core.lookup import LookupTable, build_lookup
from repro.core.tree import VocabTree
from repro.distributed.meshutil import data_axis_size, round_up

# one shared jitted lookup build: repeated eager searches (and the
# per-segment Index.search path) reuse the compiled program instead of
# re-lowering per call
jit_build_lookup = jax.jit(build_lookup, static_argnames=("probes",))


@lru_cache(maxsize=128)
def _cached_executor(mesh, plan: SearchPlan, n_leaves: int, shard_rows: int,
                     q_total: int):
    """Jitted executor cache keyed by everything that shapes the program.

    Segment searches hit the same (plan, shapes) repeatedly — once per
    search call per segment — and must not recompile each time.
    """
    return jax.jit(make_executor(
        mesh, plan, n_leaves=n_leaves, shard_rows=shard_rows, q_total=q_total
    ))


def lookup_q_total(p: SearchPlan, n_queries: int, n_shards: int) -> int:
    """Padded lookup-row count an executor for ``p`` needs.

    Query-routed rows must land on the ``(q_tile * n_shards)`` routing grid
    *and* stay a multiple of ``probes`` for the probe-group merge;
    point-major only needs the slab budget covered.
    """
    q_rows = n_queries * p.probes
    if p.layout == "query_routed":
        return round_up(q_rows, p.q_tile * n_shards * p.probes)
    return round_up(max(q_rows, p.q_cap), p.probes)


def search_with_lookup(
    index: DistributedIndex,
    lookup: LookupTable,
    plan: SearchPlan,
    mesh: Mesh,
    *,
    n_queries: int,
    codes=None,
    codebooks=None,
) -> SearchResult:
    """Run one resolved plan's executor over a pre-built lookup table.

    ``lookup`` is the unpadded ``n_queries * probes``-row table from
    :func:`~repro.core.lookup.build_lookup`; it is padded here to the
    executor's row count. Results are trimmed back to ``n_queries`` rows.

    For a ``scan_codes`` plan, ``codes`` (the segment's ``(rows, m)``
    uint8 PQ codes, row-aligned with ``index``) and ``codebooks`` (the
    quantizer's ``(m, C, dsub)`` table) are required, and the returned
    tables hold ``plan.rerank`` approximate ADC candidates per query —
    the caller reranks exactly (docs/compressed_codes.md).
    """
    n_shards = data_axis_size(mesh)
    shard_rows = index.rows // n_shards
    q_total = lookup_q_total(plan, n_queries, n_shards)
    fn = _cached_executor(mesh, plan, index.n_leaves, shard_rows, q_total)
    padded = pad_lookup(lookup, q_total)
    if plan.layout == "scan_codes":
        if codes is None or codebooks is None:
            raise ValueError("scan_codes plan needs codes + codebooks")
        res = fn(index, padded, jnp.asarray(codes), jnp.asarray(codebooks))
    else:
        res = fn(index, padded)
    return SearchResult(
        ids=res.ids[:n_queries],
        dists=res.dists[:n_queries],
        pairs=res.pairs,
        q_cap_overflow=res.q_cap_overflow,
        waves=res.waves,
    )


def batch_search_fn(
    mesh: Mesh,
    *,
    n_leaves: int,
    shard_rows: int,
    q_total: int,
    block_rows: int,
    q_cap: int,
    k: int,
    probes: int = 1,
    impl: str = "xla",
    axes=None,
):
    """Build the point-major (index, lookup) -> SearchResult pipeline."""
    p = SearchPlan(
        layout="point_major", k=k, probes=probes, impl=impl,
        block_rows=block_rows, q_cap=q_cap,
    )
    return make_executor(
        mesh, p, n_leaves=n_leaves, shard_rows=shard_rows, q_total=q_total,
        axes=axes,
    )


def routed_search_fn(
    mesh: Mesh,
    *,
    n_leaves: int,
    shard_rows: int,
    q_total: int,
    q_tile: int,
    p_cap: int,
    k: int,
    probes: int = 1,
    query_capacity_factor: float = 4.0,
    impl: str = "xla",
    wire_dtype=jnp.float32,
    axes=None,
):
    """Build the query-routed (index, lookup) -> SearchResult pipeline."""
    p = SearchPlan(
        layout="query_routed", k=k, probes=probes, impl=impl,
        wire_dtype=wire_dtype, q_tile=q_tile, p_cap=p_cap,
        query_capacity_factor=query_capacity_factor,
    )
    return make_executor(
        mesh, p, n_leaves=n_leaves, shard_rows=shard_rows, q_total=q_total,
        axes=axes,
    )


def batch_search(
    index: DistributedIndex,
    tree: VocabTree,
    queries: jax.Array,
    k: int,
    mesh: Mesh,
    *,
    layout: str = "point_major",
    probes: int = 1,
    block_rows: int | None = None,
    q_cap: int | None = None,
    impl: str = "xla",
    p_cap: int | None = None,
    q_tile: int | None = None,
    cost_model="auto",
    calibration=None,
) -> SearchResult:
    """Eager convenience wrapper: plan, build lookup, pad, jit, run, trim.

    ``layout`` is one of ``point_major`` (paper-faithful wave scan),
    ``query_routed`` (beyond-paper shuffle), or ``auto`` (the ``plan()``
    cost model picks — ``cost_model``/``calibration`` select which model
    and which calibration store, see
    :mod:`repro.core.engine.costmodel`). ``impl`` selects the executor
    implementation (``"fused"`` = the fast path, ``"auto"`` = the cost
    model prices it; docs/kernels.md). ``probes=T`` visits each query's
    T nearest leaves — the multi-probe recall lever (docs/engine.md).
    """
    n_shards = data_axis_size(mesh)
    q = queries.shape[0]
    p = make_plan(
        rows=index.rows,
        n_leaves=index.n_leaves,
        n_queries=q,
        n_shards=n_shards,
        k=k,
        probes=probes,
        layout=layout,
        impl=impl,
        block_rows=block_rows,
        q_cap=q_cap,
        q_tile=q_tile,
        p_cap=p_cap,
        model=cost_model,
        calibration=calibration,
    )
    lookup = jit_build_lookup(tree, queries, probes=probes)
    return search_with_lookup(index, lookup, p, mesh, n_queries=q)
