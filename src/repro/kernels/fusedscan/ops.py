"""Jit'd wrappers for the whole-shard fused scan with impl selection.

``impl`` (shared contract with l2topk/adcscan):
  * ``"xla"``    — the pure-jnp oracle (efficient XLA; default off-TPU)
  * ``"pallas"`` — the Pallas kernel (``interpret=True`` off-TPU; the
    interpreter is an eval loop, so off-TPU this is for parity tests —
    the fused *executor* uses a ``jax.lax``-pipelined XLA path instead,
    see docs/kernels.md)
  * ``"auto"``   — pallas on TPU, xla elsewhere

Unlike the per-tile kernels these return *global descriptor ids* (mapped
through ``point_ids``, -1 where no match or tombstoned), because the
whole shard is scanned in one call — there is no per-wave id mapping
left for the executor to do.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import phases
from repro.core.sentinels import (
    INVALID_ID,
    PAD_TILE_POINT_LEAF,
    PAD_TILE_QUERY_LEAF,
)
from repro.kernels import interpret_mode
from repro.kernels.fusedscan.kernel import fusedadc_pallas, fusedscan_pallas
from repro.kernels.fusedscan.ref import fused_adc_topk_ref, fused_topk_ref
from repro.kernels.l2topk.ops import resolve_impl
from repro.kernels.tiles import adc_tiles, dense_tiles

_PAD_P_LEAF = PAD_TILE_POINT_LEAF
_PAD_Q_LEAF = PAD_TILE_QUERY_LEAF


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_leaves(leaves, n: int, pad_leaf: int):
    out = jnp.full((n,), pad_leaf, jnp.int32)
    return out.at[: leaves.shape[0]].set(leaves.astype(jnp.int32))


@jax.named_scope(phases.SELECT)
def _map_ids(out_d, sel, point_ids, Q: int):
    ids = jnp.where(
        sel >= 0, point_ids[jnp.clip(sel, 0)], jnp.int32(INVALID_ID)
    ).astype(jnp.int32)
    return jnp.where(ids >= 0, out_d, jnp.inf)[:Q], ids[:Q]


@partial(jax.jit, static_argnames=("k", "impl", "tile_p", "tile_q"))
def fused_topk(
    points: jax.Array,  # (P, d) whole cluster-sorted shard
    point_leaves: jax.Array,  # (P,) int32
    point_ids: jax.Array,  # (P,) int32 global descriptor ids (-1 dead)
    queries: jax.Array,  # (Q, d) whole probe-expanded lookup table
    query_leaves: jax.Array,  # (Q,) int32
    *,
    k: int,
    impl: str = "auto",
    tile_p: int | None = None,
    tile_q: int | None = None,
):
    """(dists (Q,k), ids (Q,k)) whole-shard fused k-NN; see ref.py."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return fused_topk_ref(points, point_leaves, point_ids, queries,
                              query_leaves, k)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    P, d = points.shape
    Q = queries.shape[0]
    tp, tq = dense_tiles(P, Q, k=k, d=d, itemsize=max(
        points.dtype.itemsize, queries.dtype.itemsize))
    tp, tq = tile_p or tp, tile_q or tq
    Pp, Qp = _round_up(P, tp), _round_up(Q, tq)
    pts = jnp.zeros((Pp, d), points.dtype).at[:P].set(points)
    qrs = jnp.zeros((Qp, d), queries.dtype).at[:Q].set(queries)
    plf = _pad_leaves(point_leaves, Pp, _PAD_P_LEAF)
    qlf = _pad_leaves(query_leaves, Qp, _PAD_Q_LEAF)
    out_d, sel = fusedscan_pallas(
        pts, plf[None, :], qrs, qlf[:, None], k=k, tile_p=tp, tile_q=tq,
        interpret=interpret_mode(),
    )
    return _map_ids(out_d, sel, point_ids, Q)


@partial(jax.jit, static_argnames=("k", "impl", "tile_p", "tile_q"))
def fused_adc_topk(
    codes: jax.Array,  # (P, m) uint8/int32 code rows (whole shard)
    point_leaves: jax.Array,  # (P,) int32 (tombstones pre-masked)
    point_ids: jax.Array,  # (P,) int32 global descriptor ids (-1 dead)
    lut: jax.Array,  # (Q, m, C) f32 per-query distance tables
    query_leaves: jax.Array,  # (Q,) int32
    *,
    k: int,
    impl: str = "auto",
    tile_p: int | None = None,
    tile_q: int | None = None,
):
    """(dists (Q,k), ids (Q,k)) whole-shard fused ADC k-NN; see ref.py."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return fused_adc_topk_ref(codes, point_leaves, point_ids, lut,
                                  query_leaves, k)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    P, m = codes.shape
    Q, _, n_centers = lut.shape
    tp, tq = adc_tiles(P, Q, k=k, m=m, n_centers=n_centers)
    tp, tq = tile_p or tp, tile_q or tq
    Pp, Qp = _round_up(P, tp), _round_up(Q, tq)
    cds = jnp.zeros((Pp, m), jnp.int32).at[:P].set(codes.astype(jnp.int32))
    lt = jnp.zeros((Qp, m * n_centers), jnp.float32).at[:Q].set(
        lut.astype(jnp.float32).reshape(Q, m * n_centers)
    )
    plf = _pad_leaves(point_leaves, Pp, _PAD_P_LEAF)
    qlf = _pad_leaves(query_leaves, Qp, _PAD_Q_LEAF)
    out_d, sel = fusedadc_pallas(
        cds, plf[None, :], lt, qlf[:, None], k=k, n_centers=n_centers,
        tile_p=tp, tile_q=tq,
        interpret=interpret_mode(),
    )
    return _map_ids(out_d, sel, point_ids, Q)
