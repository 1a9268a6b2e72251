"""Pallas TPU kernel: fused ADC code-scan + per-query running top-k.

Compressed-tier hot path (docs/compressed_codes.md): one tile of
cluster-sorted uint8 code rows against one contiguous query-LUT slab. As
in l2topk, the running (k-best distance, index) table lives in VMEM
scratch across point tiles so the full (Q, P) ADC matrix never exists in
HBM; only (Q, k) leaves the kernel.

TPU mapping notes:
  * the ADC gather ``sum_j lut[q, j, codes[p, j]]`` is re-expressed as
    ``m`` small one-hot GEMMs on the MXU:
        d2 += lut[:, j*C:(j+1)*C] @ onehot(codes[:, j], C).T
    — a (TQ, C) x (C, TP) dot per subspace, which beats a per-element
    VPU gather on TPU and needs no scatter/gather addressing.
  * reductions run along the lane (last) axis of a (TQ, TP) layout.
  * top-k is k rounds of min-extraction + replace-current-max insertion
    (k here is the *rerank depth*, kept <= 128); unlike l2topk, ties keep
    the reference's (distance, row) order, since PQ codes repeat.
  * grid = (q_tiles, p_tiles), p innermost ("arbitrary") so scratch
    carries across code tiles; q tiles are parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.distance import PRECISION
from repro.distributed.meshutil import match_varying


_NO_ROW = 2**31 - 1  # above every row index


def _extract_min(d2, iota, bound):
    """(value, first-index) min along the last axis, keepdims, inf-safe."""
    m = jnp.min(d2, axis=1, keepdims=True)
    is_min = d2 == m
    a = jnp.min(jnp.where(is_min, iota, bound), axis=1, keepdims=True)
    return m, a


def adcscan_kernel(
    lut_ref, qlf_ref, codes_ref, plf_ref, out_d_ref, out_i_ref, run_d, run_i,
    *, k: int, m: int, n_centers: int
):
    j = pl.program_id(1)
    np_tiles = pl.num_programs(1)
    tq = lut_ref.shape[0]
    tp = codes_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full((tq, k), jnp.inf, jnp.float32)
        run_i[...] = jnp.full((tq, k), jnp.int32(-1), jnp.int32)

    lut = lut_ref[...]  # (TQ, m * C)
    codes = codes_ref[...]  # (TP, m) int32
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (tp, n_centers), 1)
    d2 = jnp.zeros((tq, tp), jnp.float32)
    for s in range(m):
        onehot = (c_iota == codes[:, s][:, None]).astype(jnp.float32)
        d2 = d2 + jax.lax.dot_general(
            lut[:, s * n_centers:(s + 1) * n_centers], onehot,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=PRECISION,
        )  # (TQ, TP)
    match = qlf_ref[...] == plf_ref[...]  # (TQ,1) == (1,TP) -> (TQ, TP)
    d2 = jnp.where(match, d2, jnp.inf)

    p_iota = jax.lax.broadcasted_iota(jnp.int32, (tq, tp), 1)
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (tq, k), 1)
    rd = run_d[...]
    ri = run_i[...]
    for _ in range(k):
        mv, a = _extract_min(d2, p_iota, tp)  # (TQ,1) tile-best
        d2 = jnp.where(p_iota == a, jnp.inf, d2)  # remove from tile
        # evict the latest row among the tied maxima, and admit only a
        # strictly smaller distance (a tied candidate is a later row): the
        # table holds the k smallest by (distance, row), the reference's
        # top_k order — PQ codes repeat, so ADC ties are common
        cur_max = jnp.max(rd, axis=1, keepdims=True)
        is_max = rd == cur_max
        max_row = jnp.max(jnp.where(is_max, ri, -1), axis=1, keepdims=True)
        amax = jnp.min(jnp.where(is_max & (ri == max_row), k_iota, k),
                       axis=1, keepdims=True)
        repl = (k_iota == amax) & (mv < cur_max)
        rd = jnp.where(repl, mv, rd)
        ri = jnp.where(repl, a + j * tp, ri)
    run_d[...] = rd
    run_i[...] = ri

    @pl.when(j == np_tiles - 1)
    def _emit():
        rd2 = run_d[...]
        ri2 = run_i[...]
        cols_d, cols_i = [], []
        for _ in range(k):
            # ascending (distance, row); rows are unique among finite
            # entries, and inf entries all emit -1
            mv = jnp.min(rd2, axis=1, keepdims=True)
            row = jnp.min(jnp.where(rd2 == mv, ri2, _NO_ROW), axis=1,
                          keepdims=True)
            rd2 = jnp.where((rd2 == mv) & (ri2 == row), jnp.inf, rd2)
            cols_d.append(mv)
            cols_i.append(jnp.where(jnp.isfinite(mv), row, jnp.int32(-1)))
        out_d_ref[...] = jnp.concatenate(cols_d, axis=1)
        out_i_ref[...] = jnp.concatenate(cols_i, axis=1)


def adcscan_pallas(
    codes: jax.Array,  # (P, m) int32 code rows
    point_leaves: jax.Array,  # (1, P) int32
    lut: jax.Array,  # (Q, m * C) f32 per-query distance tables
    query_leaves: jax.Array,  # (Q, 1) int32
    *,
    k: int,
    n_centers: int,
    tile_p: int = 512,
    tile_q: int = 256,
    interpret: bool = False,
):
    P, m = codes.shape
    Q = lut.shape[0]
    if lut.shape[1] != m * n_centers:
        raise ValueError(f"lut width {lut.shape[1]} != {m=} * {n_centers=}")
    if P % tile_p or Q % tile_q:
        raise ValueError(f"{P=} % {tile_p=} or {Q=} % {tile_q=} nonzero")
    grid = (Q // tile_q, P // tile_p)
    (lut, query_leaves, codes, point_leaves), vma = match_varying(
        lut, query_leaves, codes, point_leaves
    )
    kernel = functools.partial(adcscan_kernel, k=k, m=m, n_centers=n_centers)
    out_d, out_i = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, m * n_centers), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_p, m), lambda i, j: (j, 0)),
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
    )(lut, query_leaves, codes, point_leaves)
    return out_d, out_i
