"""Pure-jnp oracle for the fused distance + top-k search tile.

Semantics (shared by kernel and XLA fallback):

  given points (P, d) with leaf ids (P,), queries (Q, d) with leaf ids (Q,),
  return for every query the k nearest points *within the same leaf*:
    dists (Q, k) fp32  — partial squared distance ||p||^2 - 2 p.q
                         (the ||q||^2 term is a per-query constant and is
                         added back by the caller), +inf where no match
    idx   (Q, k) int32 — row index into the point tile, -1 where no match

Ordering contract: ascending by distance (the Pallas kernel also emits
ascending order via iterative min-extraction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import phases
from repro.core.distance import PRECISION


def l2_topk_ref(points, point_leaves, queries, query_leaves, k: int):
    with jax.named_scope(phases.DISTANCE):
        pf = points.astype(jnp.float32)
        qf = queries.astype(jnp.float32)
        pn = jnp.sum(pf * pf, axis=-1)
        d2 = pn[:, None] - 2.0 * jnp.einsum(
            "pd,qd->pq", pf, qf, preferred_element_type=jnp.float32,
            precision=PRECISION,
        )
        match = point_leaves[:, None] == query_leaves[None, :]
        d2 = jnp.where(match, d2, jnp.inf)
    with jax.named_scope(phases.SELECT):
        neg, sel = jax.lax.top_k(-d2.T, k)  # (Q, k) over point rows
        dists = -neg
        idx = jnp.where(jnp.isfinite(dists), sel, -1).astype(jnp.int32)
    return dists, idx
