"""Pallas TPU kernel: fused causal/windowed GQA attention (FlashAttention
dataflow, arXiv:2205.14135 adapted to the MXU/VMEM hierarchy).

EXPERIMENTS.md §Perf Cell 3 measured that the flash *dataflow* in pure XLA
(lax.scan over KV chunks) is counterproductive — the running
(max, denom, accumulator) carry churns HBM every chunk. This kernel is the
correct home for that state: it lives in VMEM scratch across the KV-tile
grid dimension, the (Sq, Skv) score matrix never reaches HBM, and HBM
traffic collapses to reading q/k/v once and writing o once.

Mapping notes:
  * grid = (B*Hq, q_tiles, kv_tiles), kv innermost ("arbitrary") so scratch
    carries; batch*head and q tiles are parallel.
  * GQA without materialising repeated KV: the k/v BlockSpec index_map
    divides the fused (b*Hq + h) grid index by the group size, so each
    query head streams its shared KV head's tiles straight from HBM.
  * causal + sliding-window masking from absolute positions (q offset =
    Skv - Sq supports prefill-with-history shapes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def flashattn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                     scale: float, window: int, q_offset: int):
    i = pl.program_id(1)  # q tile
    j = pl.program_id(2)  # kv tile
    nj = pl.num_programs(2)
    tq = q_ref.shape[1]
    tk = k_ref.shape[1]
    hd = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full((tq, 1), -jnp.inf, jnp.float32)
        l_scr[...] = jnp.zeros((tq, 1), jnp.float32)
        acc_scr[...] = jnp.zeros((tq, hd), jnp.float32)

    q = q_ref[0].astype(jnp.float32)  # (tq, hd)
    k = k_ref[0].astype(jnp.float32)  # (tk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (tq, tk)

    q_pos = q_offset + i * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
    k_pos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
    dist = q_pos - k_pos
    mask = dist >= 0
    if window > 0:
        mask &= dist < window
    s = jnp.where(mask, s, -1e30)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _emit():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flashattn_pallas(
    q: jax.Array,  # (BH, Sq, hd)   BH = B * Hq
    k: jax.Array,  # (BHkv, Skv, hd)
    v: jax.Array,
    *,
    group: int,  # Hq // Hkv
    window: int = -1,
    tile_q: int = 128,
    tile_kv: int = 128,
    interpret: bool = False,
):
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    if Sq % tile_q or Skv % tile_kv:
        raise ValueError(f"{Sq=}%{tile_q=} or {Skv=}%{tile_kv=} nonzero")
    grid = (BH, Sq // tile_q, Skv // tile_kv)
    kernel = functools.partial(
        flashattn_kernel,
        scale=1.0 / math.sqrt(hd),
        window=window,
        q_offset=Skv - Sq,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tile_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, tile_kv, hd), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, tile_kv, hd), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, 1), jnp.float32),
            pltpu.VMEM((tile_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
