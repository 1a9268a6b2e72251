"""Jit'd wrapper for the fused distance+top-k tile with impl selection.

``impl``:
  * ``"xla"``    — the pure-jnp oracle (efficient XLA; default off-TPU)
  * ``"pallas"`` — the Pallas kernel (``interpret=True`` off-TPU)
  * ``"auto"``   — pallas on TPU, xla elsewhere
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.sentinels import PAD_TILE_POINT_LEAF, PAD_TILE_QUERY_LEAF
from repro.kernels import interpret_mode
from repro.kernels.l2topk.kernel import l2topk_pallas
from repro.kernels.l2topk.ref import l2_topk_ref
from repro.kernels.tiles import l2topk_tiles

# Probe-aware padding: point-side and query-side tile padding use distinct
# negative sentinels so padded rows never match anything — not real leaves,
# not each other, and not padded multi-probe lookup rows (PAD_QUERY_LEAF).
_PAD_P_LEAF = PAD_TILE_POINT_LEAF
_PAD_Q_LEAF = PAD_TILE_QUERY_LEAF


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


@partial(jax.jit, static_argnames=("k", "impl", "tile_p", "tile_q"))
def l2_topk(
    points: jax.Array,
    point_leaves: jax.Array,
    queries: jax.Array,
    query_leaves: jax.Array,
    *,
    k: int,
    impl: str = "auto",
    tile_p: int | None = None,
    tile_q: int | None = None,
):
    """(dists (Q,k), idx (Q,k)) of same-leaf k-NN; see ref.py for semantics."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return l2_topk_ref(points, point_leaves, queries, query_leaves, k)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    P, d = points.shape
    Q = queries.shape[0]
    tp, tq = l2topk_tiles(P, Q)
    tp, tq = tile_p or tp, tile_q or tq
    Pp, Qp = _round_up(P, tp), _round_up(Q, tq)
    pts = jnp.zeros((Pp, d), points.dtype).at[:P].set(points)
    qrs = jnp.zeros((Qp, d), queries.dtype).at[:Q].set(queries)
    plf = jnp.full((Pp,), _PAD_P_LEAF, jnp.int32).at[:P].set(
        point_leaves.astype(jnp.int32)
    )
    qlf = jnp.full((Qp,), _PAD_Q_LEAF, jnp.int32).at[:Q].set(
        query_leaves.astype(jnp.int32)
    )
    out_d, out_i = l2topk_pallas(
        pts,
        plf[None, :],
        qrs,
        qlf[:, None],
        k=k,
        tile_p=tp,
        tile_q=tq,
        interpret=interpret_mode(),
    )
    return out_d[:Q], out_i[:Q]
