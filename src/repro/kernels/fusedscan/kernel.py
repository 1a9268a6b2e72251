"""Pallas TPU kernel: whole-shard fused multi-probe scan + k-selection.

Search fast path (``SearchPlan.impl="fused"``): the full cluster-sorted
shard meets the full probe-expanded lookup table in one kernel launch.
The grid walks (query tiles x point tiles); a per-query-tile running
top-k table lives in VMEM scratch across point tiles, so neither the
(P, Q) distance slab nor any per-tile candidate list ever round-trips to
HBM/host between scan and select — only (Q, k) leaves the kernel.

Where l2topk/adcscan keep an *unordered* running table (insertion into
the current-max slot), this kernel must be bit-identical to the
wave-folded ``impl="xla"`` executor, whose selection contract is the k
smallest by ``(distance, shard row)`` lexicographic (``top_k`` breaks
ties toward the earlier row; ``fold_topk`` keeps earlier waves ahead).
So the running table is kept *sorted*: each point tile's top-k is
extracted in ascending ``(distance, row)`` order, then merged with the
run table via k rounds of positional min-extraction over the
concatenated 2k-list — run entries (earlier tiles = lower shard rows)
sit at lower positions and win distance ties, reproducing the fold
exactly.

TPU mapping notes:
  * the distance tile is computed exactly as the XLA reference does —
        d2[q, p] = ||p||^2 - 2 q.p
    (norm broadcast + one MXU ``dot_general`` over d) so the float
    results match the reference bit for bit; the l2topk augmentation
    trick contracts over d+1 and may round differently.
  * tiles whose leaf ranges cannot overlap (both sides cluster-sorted)
    skip the GEMM + selection entirely under ``pl.when`` — the fused
    analogue of the executor's CSR slab slicing.
  * grid = (q_tiles, p_tiles), p innermost ("arbitrary") so scratch
    carries across point tiles; q tiles are parallel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.distance import PRECISION
from repro.distributed.meshutil import match_varying


def _extract_min(d2, iota, bound):
    """(value, first-index) min along the last axis, keepdims, inf-safe."""
    m = jnp.min(d2, axis=1, keepdims=True)
    is_min = d2 == m
    a = jnp.min(jnp.where(is_min, iota, bound), axis=1, keepdims=True)
    return m, a


def _tile_topk_sorted(d2, *, k: int, row_base):
    """Tile top-k in ascending ``(distance, row)`` order.

    Returns ``(tile_d, tile_i)`` of shape (TQ, k); ``tile_i`` carries
    *global* shard row indices (``row_base`` + tile row). Rows backing
    ``inf`` distances are garbage — the emit step maps them to -1.
    """
    tq, tp = d2.shape
    p_iota = jax.lax.broadcasted_iota(jnp.int32, (tq, tp), 1)
    cols_d, cols_i = [], []
    for _ in range(k):
        m, a = _extract_min(d2, p_iota, tp)
        d2 = jnp.where(p_iota == a, jnp.inf, d2)
        cols_d.append(m)
        cols_i.append(a + row_base)
    return jnp.concatenate(cols_d, axis=1), jnp.concatenate(cols_i, axis=1)


def _merge_sorted(run_d, run_i, cand_d, cand_i, *, k: int):
    """Merge two ascending k-lists into one, run entries winning ties.

    k rounds of positional min-extraction over the concatenated 2k-list:
    the run table occupies positions 0..k-1, so on a distance tie the
    run entry (an earlier tile = lower shard row) is selected first —
    the same order ``tilescan.fold_topk`` produces.
    """
    tq = run_d.shape[0]
    md = jnp.concatenate([run_d, cand_d], axis=1)  # (TQ, 2k)
    mi = jnp.concatenate([run_i, cand_i], axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, (tq, 2 * k), 1)
    cols_d, cols_i = [], []
    for _ in range(k):
        m, a = _extract_min(md, pos, 2 * k)
        sel = pos == a
        ci = jnp.sum(jnp.where(sel, mi, 0), axis=1, keepdims=True)
        md = jnp.where(sel, jnp.inf, md)
        cols_d.append(m)
        cols_i.append(ci)
    return jnp.concatenate(cols_d, axis=1), jnp.concatenate(cols_i, axis=1)


def _select_and_carry(distances, qlf, plf, out_d_ref, out_i_ref, run_d,
                      run_i, *, k: int):
    """The shared tail of both fused kernels: leaf-mask the distance
    tile ``distances()``, fold its sorted top-k into the VMEM run table,
    emit at the last point tile (leaf-disjoint tiles skip the distance
    GEMM and the fold entirely)."""
    j = pl.program_id(1)
    np_tiles = pl.num_programs(1)
    tq = run_d.shape[0]

    @pl.when(j == 0)
    def _init():
        run_d[...] = jnp.full((tq, k), jnp.inf, jnp.float32)
        run_i[...] = jnp.full((tq, k), jnp.int32(-1), jnp.int32)

    # cluster-sorted on both sides: a tile pair whose [min, max] leaf
    # ranges are disjoint contributes nothing — skip GEMM fold + merge
    q_lo = jnp.min(qlf)
    q_hi = jnp.max(qlf)
    p_lo = jnp.min(plf)
    p_hi = jnp.max(plf)
    overlap = (p_lo <= q_hi) & (q_lo <= p_hi)

    @pl.when(overlap)
    def _fold():
        match = qlf[:, None] == plf[None, :]  # (TQ, TP)
        masked = jnp.where(match, distances(), jnp.inf)
        tile_d, tile_i = _tile_topk_sorted(
            masked, k=k, row_base=j * plf.shape[0]
        )
        new_d, new_i = _merge_sorted(run_d[...], run_i[...], tile_d, tile_i,
                                     k=k)
        run_d[...] = new_d
        run_i[...] = new_i

    @pl.when(j == np_tiles - 1)
    def _emit():
        rd = run_d[...]
        out_d_ref[...] = rd
        out_i_ref[...] = jnp.where(jnp.isfinite(rd), run_i[...],
                                   jnp.int32(-1))


def fusedscan_kernel(q_ref, qlf_ref, p_ref, plf_ref, pn_ref, out_d_ref,
                     out_i_ref, run_d, run_i, *, k: int):
    def distances():
        pf = p_ref[...].astype(jnp.float32)
        qf = q_ref[...].astype(jnp.float32)
        # reference-identical partial distance: ||p||^2 - 2 q.p,
        # contraction over d (NOT the augmented d+1 trick — it can round
        # differently). The point norms arrive precomputed as a
        # lane-major (1, TP) row: a norm reduced here comes out
        # sublane-major, and turning it into a row costs the compiler
        # ~512 B of VMEM per (query, point) tile pair.
        return pn_ref[...] - 2.0 * jax.lax.dot_general(
            qf, pf, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=PRECISION,
        )  # (TQ, TP)

    _select_and_carry(distances, qlf_ref[...][:, 0], plf_ref[...][0, :],
                      out_d_ref, out_i_ref, run_d, run_i, k=k)


def fusedadc_kernel(lut_ref, qlf_ref, codes_ref, plf_ref, out_d_ref,
                    out_i_ref, run_d, run_i, *, k: int, m: int,
                    n_centers: int):
    def distances():
        lut = lut_ref[...]  # (TQ, m * C)
        codes = codes_ref[...]  # (TP, m) int32
        tq = lut.shape[0]
        tp = codes.shape[0]
        c_iota = jax.lax.broadcasted_iota(jnp.int32, (tp, n_centers), 1)
        d2 = jnp.zeros((tq, tp), jnp.float32)
        for s in range(m):
            onehot = (c_iota == codes[:, s][:, None]).astype(jnp.float32)
            d2 = d2 + jax.lax.dot_general(
                lut[:, s * n_centers:(s + 1) * n_centers], onehot,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32, precision=PRECISION,
            )  # (TQ, TP)
        return d2

    _select_and_carry(distances, qlf_ref[...][:, 0], plf_ref[...][0, :],
                      out_d_ref, out_i_ref, run_d, run_i, k=k)


def _pallas_scan(kernel, q_side, qlf, p_side, p_rows, *, k, tile_p, tile_q,
                 interpret):
    """Launch ``kernel`` over the (query tile, point tile) grid.

    ``p_rows`` are the per-point ``(1, P)`` rows the kernel reads beside
    ``p_side`` (the leaves first), each blocked ``(1, tile_p)``.
    """
    P = p_side.shape[0]
    Q = q_side.shape[0]
    if P % tile_p or Q % tile_q:
        raise ValueError(f"{P=} % {tile_p=} or {Q=} % {tile_q=} nonzero")
    grid = (Q // tile_q, P // tile_p)
    (q_side, qlf, p_side, *p_rows), vma = match_varying(
        q_side, qlf, p_side, *p_rows
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, q_side.shape[1]), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_p, p_side.shape[1]), lambda i, j: (j, 0)),
        ] + [pl.BlockSpec((1, tile_p), lambda i, j: (0, j))] * len(p_rows),
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
    )(q_side, qlf, p_side, *p_rows)


def fusedscan_pallas(
    points: jax.Array,  # (P, d)
    point_leaves: jax.Array,  # (1, P) int32
    queries: jax.Array,  # (Q, d)
    query_leaves: jax.Array,  # (Q, 1) int32
    *,
    k: int,
    tile_p: int,
    tile_q: int,
    interpret=False,
):
    pf = points.astype(jnp.float32)
    norms = jnp.sum(pf * pf, axis=1)[None, :]  # (1, P), as the reference
    kernel = functools.partial(fusedscan_kernel, k=k)
    return _pallas_scan(kernel, queries, query_leaves, points,
                        (point_leaves, norms), k=k, tile_p=tile_p,
                        tile_q=tile_q, interpret=interpret)


def fusedadc_pallas(
    codes: jax.Array,  # (P, m) int32 code rows
    point_leaves: jax.Array,  # (1, P) int32
    lut: jax.Array,  # (Q, m * C) f32 per-query distance tables
    query_leaves: jax.Array,  # (Q, 1) int32
    *,
    k: int,
    n_centers: int,
    tile_p: int,
    tile_q: int,
    interpret=False,
):
    m = codes.shape[1]
    if lut.shape[1] != m * n_centers:
        raise ValueError(f"lut width {lut.shape[1]} != {m=} * {n_centers=}")
    kernel = functools.partial(fusedadc_kernel, k=k, m=m, n_centers=n_centers)
    return _pallas_scan(kernel, lut, query_leaves, codes, (point_leaves,),
                        k=k, tile_p=tile_p, tile_q=tile_q,
                        interpret=interpret)
