"""Jit'd wrapper for the fused ADC code-scan + top-k tile with impl
selection.

``impl`` (shared contract with l2topk):
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
from repro.kernels.adcscan.kernel import adcscan_pallas
from repro.kernels.adcscan.ref import adc_topk_ref
from repro.kernels.l2topk.ops import resolve_impl
from repro.kernels.tiles import adc_tiles

# Probe-aware padding, same scheme as l2topk: point-side and query-side
# tile padding use distinct negative sentinels so padded rows never match
# anything.
_PAD_P_LEAF = PAD_TILE_POINT_LEAF
_PAD_Q_LEAF = PAD_TILE_QUERY_LEAF


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@partial(jax.jit, static_argnames=("k", "impl", "tile_p", "tile_q"))
def adc_topk(
    codes: jax.Array,  # (P, m) uint8/int32 code rows
    point_leaves: jax.Array,  # (P,) int32
    lut: jax.Array,  # (Q, m, C) f32 per-query distance tables
    query_leaves: jax.Array,  # (Q,) int32
    *,
    k: int,
    impl: str = "auto",
    tile_p: int | None = None,
    tile_q: int | None = None,
):
    """(dists (Q,k), idx (Q,k)) of same-leaf ADC k-NN; see ref.py."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return adc_topk_ref(codes, point_leaves, lut, query_leaves, k)
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
    plf = jnp.full((Pp,), _PAD_P_LEAF, jnp.int32).at[:P].set(
        point_leaves.astype(jnp.int32)
    )
    qlf = jnp.full((Qp,), _PAD_Q_LEAF, jnp.int32).at[:Q].set(
        query_leaves.astype(jnp.int32)
    )
    out_d, out_i = adcscan_pallas(
        cds,
        plf[None, :],
        lt,
        qlf[:, None],
        k=k,
        n_centers=n_centers,
        tile_p=tp,
        tile_q=tq,
        interpret=interpret_mode(),
    )
    return out_d[:Q], out_i[:Q]
