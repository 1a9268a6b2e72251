"""Jit'd wrapper for fused GQA flash attention with impl selection."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flashattn.kernel import flashattn_pallas
from repro.kernels.flashattn.ref import flash_attention_ref


def resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


@partial(jax.jit, static_argnames=("window", "impl", "tile_q", "tile_kv"))
def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, hd)
    k: jax.Array,  # (B, Skv, Hkv, hd)
    v: jax.Array,
    *,
    window: int = -1,
    impl: str = "auto",
    tile_q: int = 128,
    tile_kv: int = 128,
):
    """Causal (optionally sliding-window) GQA attention; see ref.py."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return flash_attention_ref(q, k, v, window=window)
    if impl != "pallas":
        raise ValueError(f"unknown impl {impl!r}")
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    tq = min(tile_q, Sq)
    tkv = min(tile_kv, Skv)
    if Sq % tq or Skv % tkv:
        raise ValueError(
            f"flash kernel needs Sq%{tq}==0 and Skv%{tkv}==0 (got {Sq},{Skv})"
        )
    # (B, S, H, hd) -> (B*H, S, hd) with head-major fusion for the BlockSpec
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, hd)
    out = flashattn_pallas(
        qf, kf, vf, group=group, window=window, tile_q=tq, tile_kv=tkv,
        interpret=interpret_mode(),
    )
    return out.reshape(B, Hq, Sq, hd).transpose(0, 2, 1, 3)
