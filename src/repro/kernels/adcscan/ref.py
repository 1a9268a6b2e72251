"""Pure-jnp oracle for the fused ADC (asymmetric-distance) code scan.

Semantics (shared by kernel and XLA fallback):

  given uint8 codes (P, m) with leaf ids (P,), and per-query distance
  lookup tables lut (Q, m, C) f32 with query leaf ids (Q,), return for
  every query the k approximately-nearest code rows *within the same
  leaf* under the asymmetric distance

      d2[q, p] = sum_j lut[q, j, codes[p, j]]

  (``lut[q, j, c] = ||q_j - codebook[j, c]||^2``, so d2 is a full squared
  distance estimate — unlike l2topk there is no deferred ``||q||^2``
  term):
    dists (Q, k) fp32  — ascending ADC squared distance, +inf no match
    idx   (Q, k) int32 — row index into the code tile, -1 where no match

Ordering contract: ascending by distance (the Pallas kernel also emits
ascending order via iterative min-extraction).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import phases


def adc_topk_ref(codes, point_leaves, lut, query_leaves, k: int):
    with jax.named_scope(phases.DISTANCE):
        c = codes.astype(jnp.int32)
        m = c.shape[1]
        d2 = jnp.zeros((lut.shape[0], c.shape[0]), jnp.float32)
        for j in range(m):  # m is static and small (bytes per row)
            d2 = d2 + jnp.take(lut[:, j, :], c[:, j], axis=1)  # (Q, P)
        match = query_leaves[:, None] == point_leaves[None, :]
        d2 = jnp.where(match, d2, jnp.inf)
    with jax.named_scope(phases.SELECT):
        neg, sel = jax.lax.top_k(-d2, k)  # (Q, k) over code rows
        dists = -neg
        idx = jnp.where(jnp.isfinite(dists), sel, -1).astype(jnp.int32)
    return dists, idx
