"""L2-distance algebra used everywhere (index build, search, k-means refine).

All entry points use the expansion  ||x - c||^2 = ||x||^2 - 2 x.c + ||c||^2
so the inner loop is a GEMM (MXU work on TPU). The ``x`` norm term is dropped
where only an argmin/top-k over ``c`` is needed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Precision of every f32 distance product on the search and build path.
#: A TPU runs a default-precision f32 matmul as one bf16 pass, which rounds
#: the operands to 8 bits of mantissa; the norm expansion below then
#: reorders near neighbours against an exact brute-force oracle. HIGHEST
#: keeps f32 accuracy on the TPU and changes nothing on the CPU.
PRECISION = jax.lax.Precision.HIGHEST


def sq_norms(x: jax.Array) -> jax.Array:
    """Row squared norms, accumulated in fp32."""
    xf = x.astype(jnp.float32)
    return jnp.sum(xf * xf, axis=-1)


def sq_dists(x: jax.Array, c: jax.Array, c_norms: jax.Array | None = None) -> jax.Array:
    """Full (n, m) squared distances between rows of x (n,d) and c (m,d)."""
    if c_norms is None:
        c_norms = sq_norms(c)
    dots = jnp.einsum(
        "nd,md->nm", x, c, preferred_element_type=jnp.float32,
        precision=PRECISION,
    )
    return sq_norms(x)[:, None] - 2.0 * dots + c_norms[None, :]


def nearest(x: jax.Array, c: jax.Array, c_norms: jax.Array | None = None):
    """(argmin, min_sqdist) of each row of x over centroid rows c.

    The ||x||^2 term is omitted from the argmin and added back to the
    returned distance, saving one reduction.
    """
    if c_norms is None:
        c_norms = sq_norms(c)
    dots = jnp.einsum("nd,md->nm", x, c, preferred_element_type=jnp.float32,
                      precision=PRECISION)
    partial = c_norms[None, :] - 2.0 * dots  # (n, m)
    idx = jnp.argmin(partial, axis=1)
    best = jnp.min(partial, axis=1) + sq_norms(x)
    return idx.astype(jnp.int32), best


def topk_neighbors(x: jax.Array, c: jax.Array, k: int,
                   c_norms: jax.Array | None = None):
    """(indices, sq_dists) of the k nearest rows of c for each row of x."""
    d2 = sq_dists(x, c, c_norms)
    neg, idx = jax.lax.top_k(-d2, k)
    return idx.astype(jnp.int32), -neg
