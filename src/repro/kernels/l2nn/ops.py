"""Jit'd wrapper for fused nearest-centroid with impl selection."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.l2nn.kernel import l2nn_pallas
from repro.kernels.l2nn.ref import l2_nearest_ref


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


@partial(jax.jit, static_argnames=("impl", "tile_n", "tile_c"))
def l2_nearest(
    x: jax.Array,
    centroids: jax.Array,
    *,
    impl: str = "auto",
    tile_n: int | None = None,
    tile_c: int | None = None,
):
    """(idx (N,), dist (N,)) nearest centroid per row; see ref.py."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return l2_nearest_ref(x, centroids)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")

    N, d = x.shape
    C = centroids.shape[0]
    tn = tile_n or min(1024, _round_up(N, 128))
    tc = tile_c or min(512, _round_up(C, 128))
    Np, Cp = _round_up(N, tn), _round_up(C, tc)
    xp = jnp.zeros((Np, d), x.dtype).at[:N].set(x)
    # zero-padded centroids are masked out inside the kernel (n_valid_c)
    cp = jnp.zeros((Cp, d), centroids.dtype).at[:C].set(centroids)
    out_i, out_d = l2nn_pallas(
        xp,
        cp,
        tile_n=tn,
        tile_c=tc,
        interpret=interpret_mode(),
        n_valid_c=C,
    )
    return out_i[:N, 0], out_d[:N, 0]
