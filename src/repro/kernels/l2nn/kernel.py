"""Pallas TPU kernel: fused L2 distance + argmin (nearest centroid).

Index-build hot path (paper §2.3 map task): assign a tile of descriptors to
their nearest representative. Centroid tiles stream through VMEM while the
(best-distance, best-index) pair per descriptor rides in scratch — the
(N, C) distance matrix never reaches HBM. Same augmented-GEMM trick as
``l2topk``: d2[n, c] = [-2x | 1] . [c | ||c||^2] in a single MXU dot.

Grid = (n_tiles, c_tiles), centroid axis innermost so scratch accumulates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def l2nn_kernel(x_ref, c_ref, out_i_ref, out_d_ref, best_d, best_i, *, n_valid_c: int):
    j = pl.program_id(1)
    nc_tiles = pl.num_programs(1)
    tn = x_ref.shape[0]
    tc = c_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        best_d[...] = jnp.full((tn, 1), jnp.inf, jnp.float32)
        best_i[...] = jnp.full((tn, 1), -1, jnp.int32)

    xf = x_ref[...].astype(jnp.float32)
    cf = c_ref[...].astype(jnp.float32)
    cn = jnp.sum(cf * cf, axis=1, keepdims=True)  # (TC, 1)
    ca = jnp.concatenate([cf, cn], axis=1)  # (TC, d+1)
    xa = jnp.concatenate([-2.0 * xf, jnp.ones_like(xf[:, :1])], axis=1)
    d2 = jax.lax.dot_general(
        xa, ca, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (TN, TC) partial: ||c||^2 - 2 x.c

    c_iota = jax.lax.broadcasted_iota(jnp.int32, (tn, tc), 1)
    # mask out zero-padded centroid columns
    d2 = jnp.where(c_iota + j * tc < n_valid_c, d2, jnp.inf)
    m = jnp.min(d2, axis=1, keepdims=True)
    a = jnp.min(jnp.where(d2 == m, c_iota, tc), axis=1, keepdims=True) + j * tc
    upd = m < best_d[...]
    best_d[...] = jnp.where(upd, m, best_d[...])
    best_i[...] = jnp.where(upd, a, best_i[...])

    @pl.when(j == nc_tiles - 1)
    def _emit():
        xn = jnp.sum(xf * xf, axis=1, keepdims=True)
        out_d_ref[...] = best_d[...] + xn  # back to true squared distance
        out_i_ref[...] = best_i[...]


def l2nn_pallas(
    x: jax.Array,  # (N, d)
    centroids: jax.Array,  # (C, d)
    *,
    tile_n: int = 1024,
    tile_c: int = 512,
    interpret: bool = False,
    n_valid_c: int = 0,
):
    N, d = x.shape
    C = centroids.shape[0]
    if N % tile_n or C % tile_c:
        raise ValueError(f"{N=} % {tile_n=} or {C=} % {tile_c=} nonzero")
    grid = (N // tile_n, C // tile_c)
    out_i, out_d = pl.pallas_call(
        functools.partial(l2nn_kernel, n_valid_c=n_valid_c if n_valid_c else C),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_c, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_n, 1), jnp.float32),
            pltpu.VMEM((tile_n, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, centroids)
    return out_i, out_d
