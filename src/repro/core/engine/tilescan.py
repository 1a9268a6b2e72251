"""Shared tile-scan core for both search layouts.

Both executors reduce to the same inner shape: an *anchor* tile (sliced by
wave index) meets a *slab* (a contiguous run of the opposite, cluster-sorted
table, located through CSR offsets), one fused distance+top-k produces
per-query candidates, and pairs/overflow are accounted exactly. Point-major
anchors on index rows and slabs the lookup table; query-routed anchors on
query tiles and slabs the local point rows. The arithmetic is identical and
lives here once.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import phases
from repro.core.sentinels import INVALID_ID, LEAF_SENTINEL
from repro.kernels.l2topk import ops as l2topk_ops


class Slab(NamedTuple):
    """A contiguous slab start for one tile, plus its budget."""

    start: jax.Array  # () int32 row offset into the sorted table
    cap: int  # static slab row budget


@jax.named_scope(phases.SLICE)
def leaf_slab(
    offsets: jax.Array, first_leaf: jax.Array, *, n_entries: int,
    total_rows: int, cap: int
) -> Slab:
    """Locate the slab covering ``first_leaf`` in a CSR-sorted table.

    ``offsets`` has ``n_entries + 1`` entries; the returned start is clamped
    so a full ``cap``-row dynamic_slice stays in bounds (padding rows at the
    tail never match any real leaf).
    """
    l0 = jnp.clip(first_leaf, 0, n_entries - 1)
    start = jnp.clip(offsets[l0], 0, max(0, total_rows - cap))
    return Slab(start=start, cap=cap)


@jax.named_scope(phases.COUNT)
def slab_overflow(
    offsets: jax.Array, last_leaf: jax.Array, slab: Slab, *, n_entries: int
) -> jax.Array:
    """Rows of the tile's leaf span that did not fit in the slab budget.

    ``last_leaf`` is the highest *valid local* leaf id of the anchor tile
    (``-1`` when the tile is all padding). Exact, never silently wrong: the
    pipelines report the psum of this and tests assert 0 on healthy runs.
    """
    need_end = jnp.where(
        last_leaf >= 0,
        offsets[jnp.clip(last_leaf, 0, n_entries - 1) + 1],
        slab.start,
    )
    return jnp.maximum(0, need_end - slab.start - slab.cap).astype(jnp.int32)


@jax.named_scope(phases.COUNT)
def last_valid_leaf(leaves: jax.Array, *, base=0) -> jax.Array:
    """Highest real leaf id in a tile, shifted by ``base``; -1 if none."""
    valid = leaves != LEAF_SENTINEL
    return jnp.max(jnp.where(valid, leaves - base, -1))


@jax.named_scope(phases.SLICE)
def live_waves(
    leaves: jax.Array, offsets: jax.Array, *, block_rows: int,
    n_entries: int
) -> tuple[jax.Array, jax.Array]:
    """The point tiles a point-major sweep has to visit, in sweep order.

    ``leaves`` is a shard's cluster-sorted leaf column, cut into
    ``block_rows``-row tiles; ``offsets`` the lookup table's CSR offsets
    (``n_entries + 1``). A tile is *live* when some lookup row falls in its
    leaf span ``[leaves[t * block_rows], last valid leaf]``; any other tile
    (all padding, or leaves no query routed to) has no same-leaf pair, no
    slab overflow and only ``inf`` candidates, so skipping it leaves every
    result bit-identical.

    Returns ``(waves, n_live)``: the live tile indices, ascending, at the
    front of an ``(n_tiles,)`` int32 array, and how many there are.
    """
    tiles = leaves.reshape(-1, block_rows)
    first = jnp.clip(tiles[:, 0], 0, n_entries - 1)
    # the highest real leaf of each tile, -1 if none (last_valid_leaf)
    last = jnp.max(jnp.where(tiles != LEAF_SENTINEL, tiles, -1), axis=1)
    hits = offsets[jnp.clip(last, 0, n_entries - 1) + 1] - offsets[first]
    live = (last >= 0) & (hits > 0)
    (waves,) = jnp.nonzero(live, size=tiles.shape[0], fill_value=0)
    return waves.astype(jnp.int32), jnp.sum(live, dtype=jnp.int32)


def scan_tile(
    pv: jax.Array,
    plf: jax.Array,
    pid: jax.Array,
    qv: jax.Array,
    qlf: jax.Array,
    *,
    k: int,
    impl: str,
) -> tuple[jax.Array, jax.Array]:
    """Fused distance + per-query top-k over one (points, queries) tile.

    Returns ``(cand_d, cand_i)`` of shape ``(Q, k)``: partial squared
    distances (no ``||q||^2`` term) with ``inf``/``INVALID_ID`` where fewer
    than ``k`` same-leaf points exist. ``cand_i`` holds *global* descriptor
    ids (mapped through ``pid``), not tile-row indices.
    """
    with jax.named_scope(phases.DISTANCE):  # the ref names its own select
        cand_d, cand_sel = l2topk_ops.l2_topk(pv, plf, qv, qlf, k=k,
                                              impl=impl)
    with jax.named_scope(phases.SELECT):
        cand_i = jnp.where(cand_sel >= 0, pid[jnp.clip(cand_sel, 0)],
                           INVALID_ID)
        cand_d = jnp.where(cand_i >= 0, cand_d, jnp.inf)
    return cand_d, cand_i


@jax.named_scope(phases.COUNT)
def count_pairs(plf: jax.Array, qlf: jax.Array) -> jax.Array:
    """Exact number of same-leaf (point, query) distance pairs in a tile.

    Sentinel/padding leaves on either side never match a real leaf (see
    ``repro.core.sentinels``), but two padded rows of the *same* kind would
    match each other — mask both sides explicitly.
    """
    p_ok = (plf >= 0) & (plf != LEAF_SENTINEL)
    q_ok = (qlf >= 0) & (qlf != LEAF_SENTINEL)
    match = (plf[:, None] == qlf[None, :]) & p_ok[:, None] & q_ok[None, :]
    return jnp.sum(match, dtype=jnp.float32)


@jax.named_scope(phases.SELECT)
def fold_topk(
    cur_d: jax.Array, cur_i: jax.Array, cand_d: jax.Array, cand_i: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Merge a candidate table into a running best-k table (row-wise)."""
    k = cur_d.shape[-1]
    all_d = jnp.concatenate([cur_d, cand_d], axis=-1)
    all_i = jnp.concatenate([cur_i, cand_i], axis=-1)
    neg, sel = jax.lax.top_k(-all_d, k)
    return -neg, jnp.take_along_axis(all_i, sel, axis=-1)


def fold_rows(
    best_d: jax.Array, best_i: jax.Array, cand_d: jax.Array,
    cand_i: jax.Array, start: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Fold a ``(rows, w)`` candidate table into rows ``[start, start +
    rows)`` of the running best table: read them, merge, write back."""
    rows, w = cand_d.shape
    with jax.named_scope(phases.SLICE):
        cur_d = jax.lax.dynamic_slice(best_d, (start, 0), (rows, w))
        cur_i = jax.lax.dynamic_slice(best_i, (start, 0), (rows, w))
    new_d, new_i = fold_topk(cur_d, cur_i, cand_d, cand_i)
    with jax.named_scope(phases.CARRY):
        return (jax.lax.dynamic_update_slice(best_d, new_d, (start, 0)),
                jax.lax.dynamic_update_slice(best_i, new_i, (start, 0)))


@jax.named_scope(phases.SELECT)
def merge_probe_groups(
    d: jax.Array, i: jax.Array, probes: int
) -> tuple[jax.Array, jax.Array]:
    """Dedupe/merge the ``probes`` candidate rows of each original query.

    ``d``/``i`` are ``(rows, k)`` tables indexed by flat lookup-row slot
    (``query_id * probes + probe_rank``). Each query's probe rows target
    *distinct* leaves and every point lives in exactly one leaf, so the id
    sets are disjoint and merging is a plain per-group top-k.
    """
    if probes == 1:
        return d, i
    rows, k = d.shape
    if rows % probes:
        raise ValueError(f"{rows=} not a multiple of {probes=}")
    gd = d.reshape(rows // probes, probes * k)
    gi = i.reshape(rows // probes, probes * k)
    neg, sel = jax.lax.top_k(-gd, k)
    return -neg, jnp.take_along_axis(gi, sel, axis=-1)
