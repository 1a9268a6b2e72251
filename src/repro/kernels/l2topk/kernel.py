"""Pallas TPU kernel: fused L2-distance GEMM + per-query running top-k.

Search hot path (paper §2.4 map task): one tile of cluster-sorted index
points against one contiguous query slab. The kernel keeps the running
(k-best distance, index) table in VMEM scratch across point tiles, so the
full (P, Q) distance matrix never exists in HBM — the MXU produces a
(TQ, TP) tile, the VPU folds it into the running table, and only (Q, k)
leaves the kernel.

TPU mapping notes:
  * the distance GEMM uses the augmentation trick
        d2[q, p] = [-2q | 1] . [p | ||p||^2]
    so the whole partial distance is a single ``dot_general`` on the MXU —
    no transposes, no separate norm broadcast (d+1 contraction pads to the
    next lane multiple inside the MXU).
  * reductions run along the lane (last) axis of a (TQ, TP) layout.
  * top-k is k rounds of min-extraction + replace-current-max insertion;
    k <= 64 keeps this VPU-cheap relative to the MXU tile.
  * grid = (q_tiles, p_tiles), p innermost ("arbitrary") so scratch carries
    across point tiles; q tiles are parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.distance import PRECISION
from repro.distributed.meshutil import match_varying


def _augment(q_tile, p_tile):
    """Build the (TQ, TP) partial squared-distance tile with one dot."""
    pf = p_tile.astype(jnp.float32)
    qf = q_tile.astype(jnp.float32)
    pn = jnp.sum(pf * pf, axis=1, keepdims=True)  # (TP, 1)
    pa = jnp.concatenate([pf, pn], axis=1)  # (TP, d+1)
    qa = jnp.concatenate([-2.0 * qf, jnp.ones_like(qf[:, :1])], axis=1)
    return jax.lax.dot_general(
        qa, pa, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=PRECISION,
    )  # (TQ, TP)


def _extract_min(d2, iota, bound):
    """(value, first-index) min along the last axis, keepdims, inf-safe."""
    m = jnp.min(d2, axis=1, keepdims=True)
    is_min = d2 == m
    a = jnp.min(jnp.where(is_min, iota, bound), axis=1, keepdims=True)
    return m, a


def l2topk_kernel(
    q_ref, qlf_ref, p_ref, plf_ref, out_d_ref, out_i_ref, run_d, run_i, *, k: int
):
    j = pl.program_id(1)
    np_tiles = pl.num_programs(1)
    tq = q_ref.shape[0]
    tp = p_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full((tq, k), jnp.inf, jnp.float32)
        run_i[...] = jnp.full((tq, k), jnp.int32(-1), jnp.int32)

    d2 = _augment(q_ref[...], p_ref[...])  # (TQ, TP)
    match = qlf_ref[...] == plf_ref[...]  # (TQ,1) == (1,TP) -> (TQ, TP)
    d2 = jnp.where(match, d2, jnp.inf)

    p_iota = jax.lax.broadcasted_iota(jnp.int32, (tq, tp), 1)
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (tq, k), 1)
    rd = run_d[...]
    ri = run_i[...]
    for _ in range(k):
        m, a = _extract_min(d2, p_iota, tp)  # (TQ,1) tile-best
        d2 = jnp.where(p_iota == a, jnp.inf, d2)  # remove from tile
        cur_max = jnp.max(rd, axis=1, keepdims=True)
        is_max = rd == cur_max
        amax = jnp.min(jnp.where(is_max, k_iota, k), axis=1, keepdims=True)
        repl = (k_iota == amax) & (m < cur_max)
        rd = jnp.where(repl, m, rd)
        ri = jnp.where(repl, a + j * tp, ri)
    run_d[...] = rd
    run_i[...] = ri

    @pl.when(j == np_tiles - 1)
    def _emit():
        rd2 = run_d[...]
        ri2 = run_i[...]
        cols_d, cols_i = [], []
        for _ in range(k):
            m, am = _extract_min(rd2, k_iota, k)
            sel = k_iota == am
            ci = jnp.sum(jnp.where(sel, ri2, 0), axis=1, keepdims=True)
            rd2 = jnp.where(sel, jnp.inf, rd2)
            cols_d.append(m)
            cols_i.append(jnp.where(jnp.isfinite(m), ci, jnp.int32(-1)))
        out_d_ref[...] = jnp.concatenate(cols_d, axis=1)
        out_i_ref[...] = jnp.concatenate(cols_i, axis=1)


def l2topk_pallas(
    points: jax.Array,  # (P, d)
    point_leaves: jax.Array,  # (1, P) int32
    queries: jax.Array,  # (Q, d)
    query_leaves: jax.Array,  # (Q, 1) int32
    *,
    k: int,
    tile_p: int = 512,
    tile_q: int = 256,
    interpret: bool = False,
):
    P, d = points.shape
    Q = queries.shape[0]
    if P % tile_p or Q % tile_q:
        raise ValueError(f"{P=} % {tile_p=} or {Q=} % {tile_q=} nonzero")
    grid = (Q // tile_q, P // tile_p)
    (queries, query_leaves, points, point_leaves), vma = match_varying(
        queries, query_leaves, points, point_leaves
    )
    kernel = functools.partial(l2topk_kernel, k=k)
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_p, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tile_p), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_q, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((Q, k), jnp.int32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_q, k), jnp.float32),
            pltpu.VMEM((tile_q, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(queries, query_leaves, points, point_leaves)
    return out_d, out_i
