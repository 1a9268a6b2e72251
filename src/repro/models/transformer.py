"""Decoder-only transformer family: dense / GQA / sliding-window / MoE.

Covers the five assigned LM architectures (llama3.2-3b, gemma3-4b,
internlm2-1.8b, moonshot-v1-16b-a3b, phi3.5-moe) from one config. Design
points for pod scale:

  * layers are scanned (stacked params), so HLO size is O(1) in depth —
    essential for the 512-device dry-run compiles;
  * MoE routing reuses ``repro.core.dispatch`` — the paper's lookup-table
    grouping applied to experts (DESIGN.md §4); dropped-token counts are the
    failed-map-task analog and are surfaced in metrics;
  * sliding-window vs global attention is a per-layer *traced* window size
    folded into the mask, so gemma3's 5:1 local:global pattern runs in one
    scanned layer body (no unrolled branches);
  * logical-axis sharding: qkv/ffn/experts/vocab shard over ``model``,
    batch over (``pod``, ``data``), decode KV caches over the free axes of
    (pod, data, model) via the ``kv_seq`` rule (context parallelism).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.dispatch import combine_rows, dispatch_rows, make_dispatch
from repro.models.module import ParamSpec, shard


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int  # per-expert hidden dim
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    window: int = 0  # 0 = all layers global attention
    global_every: int = 0  # >0: layer i is global iff (i+1) % global_every == 0
    moe: Optional[MoEConfig] = None
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-6
    scale_embed: bool = False  # gemma-style sqrt(d_model) input scaling
    qk_norm: bool = False
    dtype: str = "bfloat16"
    remat: str = "dots"  # none | full | dots
    # "global": pjit sort-based dispatch (baseline); "routed": shard_map
    # all_to_all routing over the expert axis — the paper's shuffle applied
    # to experts (EXPERIMENTS.md §Perf hillclimb #1)
    moe_impl: str = "global"
    # "full": one (Sq, Skv) logits tensor; "chunked": lax.scan over KV
    # chunks with running max/denominator (flash-attention dataflow in pure
    # XLA — bounds the materialised score tile to (Sq, chunk))
    attn_impl: str = "full"
    attn_chunk: int = 1024

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def window_sizes(self) -> jnp.ndarray:
        """(L,) per-layer attention window; -1 = unbounded (global)."""
        if self.window <= 0:
            return jnp.full((self.n_layers,), -1, jnp.int32)
        idx = jnp.arange(self.n_layers)
        if self.global_every > 0:
            is_global = (idx + 1) % self.global_every == 0
        else:
            is_global = jnp.zeros((self.n_layers,), bool)
        return jnp.where(is_global, -1, self.window).astype(jnp.int32)

    def param_specs(self):
        L, D, V = self.n_layers, self.d_model, self.vocab_size
        qd, kvd, hd = self.q_dim, self.kv_dim, self.head_dim
        layer = {
            "attn_norm": ParamSpec((L, D), ("layers", "embed"), init="ones"),
            "wq": ParamSpec((L, D, qd), ("layers", "embed", "qkv")),
            "wk": ParamSpec((L, D, kvd), ("layers", "embed", "qkv")),
            "wv": ParamSpec((L, D, kvd), ("layers", "embed", "qkv")),
            "wo": ParamSpec((L, qd, D), ("layers", "qkv", "embed")),
            "mlp_norm": ParamSpec((L, D), ("layers", "embed"), init="ones"),
        }
        if self.qk_norm:
            layer["q_norm"] = ParamSpec((L, hd), ("layers", "head_dim"), init="ones")
            layer["k_norm"] = ParamSpec((L, hd), ("layers", "head_dim"), init="ones")
        if self.moe is None:
            F = self.d_ff
            layer["w_gate"] = ParamSpec((L, D, F), ("layers", "embed", "ffn"))
            layer["w_up"] = ParamSpec((L, D, F), ("layers", "embed", "ffn"))
            layer["w_down"] = ParamSpec((L, F, D), ("layers", "ffn", "embed"))
        else:
            E, Fe = self.moe.n_experts, self.moe.d_ff
            layer["router"] = ParamSpec((L, D, E), ("layers", "embed", "experts"))
            layer["w_gate"] = ParamSpec(
                (L, E, D, Fe), ("layers", "experts", "embed", "ffn")
            )
            layer["w_up"] = ParamSpec(
                (L, E, D, Fe), ("layers", "experts", "embed", "ffn")
            )
            layer["w_down"] = ParamSpec(
                (L, E, Fe, D), ("layers", "experts", "ffn", "embed")
            )
        return {
            "embed": ParamSpec((V, D), ("vocab", "embed"), scale=1.0),
            "layers": layer,
            "final_norm": ParamSpec((D,), ("embed",), init="ones"),
        }

    def param_count(self) -> int:
        from repro.models.module import param_count

        return param_count(self.param_specs())

    def active_param_count(self) -> int:
        """6*N*D bookkeeping for MoE rooflines: only routed experts count."""
        total = self.param_count()
        if self.moe is None:
            return total
        E, k, Fe, L, D = (
            self.moe.n_experts,
            self.moe.top_k,
            self.moe.d_ff,
            self.n_layers,
            self.d_model,
        )
        expert_params = L * E * 3 * D * Fe
        return total - expert_params + L * k * 3 * D * Fe


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


def rope(x, positions, theta):
    """x: (..., S, H, hd); positions broadcastable to (..., S)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def attend(q, k, v, *, q_pos, kv_pos, window, kv_valid_len=None):
    """Grouped-query attention with causal + sliding-window mask.

    q: (B, Sq, Hq, hd); k, v: (B, Skv, Hkv, hd); window: traced int32
    (-1 = unbounded). kv_valid_len: () — mask kv positions >= it (decode).
    """
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, hd)
    logits = jnp.einsum(
        "bqkgh,bskh->bkgqs", qg, k, preferred_element_type=jnp.float32
    ) * (1.0 / math.sqrt(hd))
    dist = q_pos[:, None] - kv_pos[None, :]  # (Sq, Skv)
    mask = dist >= 0
    win = jnp.where(window > 0, window, jnp.int32(2**30))
    mask &= dist < win
    if kv_valid_len is not None:
        mask &= (kv_pos < kv_valid_len)[None, :]
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, Sq, Hq * hd)


def attend_chunked(q, k, v, *, q_pos, kv_pos, window, kv_valid_len=None,
                   chunk=1024):
    """Flash-attention dataflow: scan KV chunks with a running
    (max, denominator, accumulator) — the (Sq, Skv) score matrix never
    exists; only (Sq, chunk) tiles do. Same signature/semantics as
    ``attend``."""
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    Skv = k.shape[1]
    if Skv % chunk:
        chunk = Skv  # degenerate fallback
    n_chunks = Skv // chunk
    qg = q.reshape(B, Sq, Hkv, G, hd)
    scale = 1.0 / math.sqrt(hd)

    kc = k.reshape(B, n_chunks, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(n_chunks, chunk)

    def body(carry, inp):
        m, l, acc = carry
        k_i, v_i, p_i = inp
        s = jnp.einsum(
            "bqkgh,bskh->bkgqs", qg, k_i, preferred_element_type=jnp.float32
        ) * scale  # (B, Hkv, G, Sq, chunk)
        dist = q_pos[:, None] - p_i[None, :]
        mask = dist >= 0
        win = jnp.where(window > 0, window, jnp.int32(2**30))
        mask &= dist < win
        if kv_valid_len is not None:
            mask &= (p_i < kv_valid_len)[None, :]
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p.astype(v_i.dtype), v_i,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((B, Hkv, G, Sq), -jnp.inf, jnp.float32),
        jnp.zeros((B, Hkv, G, Sq), jnp.float32),
        jnp.zeros((B, Hkv, G, Sq, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    # (B, Hkv, G, Sq, hd) -> (B, Sq, Hq*hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, Hq * hd)
    return out.astype(q.dtype)


def _moe_ffn(x2d, layer, cfg: TransformerConfig, capacity: int):
    """Expert FFN via the shared dispatch substrate. x2d: (T, D)."""
    moe = cfg.moe
    T = x2d.shape[0]
    router_logits = jnp.einsum(
        "td,de->te", x2d, layer["router"], preferred_element_type=jnp.float32
    )
    top_vals, top_idx = jax.lax.top_k(router_logits, moe.top_k)  # (T, k)
    gates = jax.nn.softmax(top_vals, axis=-1)  # (T, k)
    flat_assign = top_idx.reshape(T * moe.top_k)
    disp = make_dispatch(flat_assign, moe.n_experts, capacity)
    # gather tokens (row r of the flattened (T*k) space is token r // k)
    xd = x2d[disp.gather_idx // moe.top_k]
    xd = xd * disp.slot_valid[..., None].astype(xd.dtype)
    # 2D shard: experts over model, capacity rows over the data axes —
    # without the capacity sharding every data replica would redundantly
    # compute the full expert GEMM (16x waste on the production mesh).
    xd = shard(xd, "experts", "batch", None)
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xd, layer["w_gate"].astype(xd.dtype))
    ) * jnp.einsum("ecd,edf->ecf", xd, layer["w_up"].astype(xd.dtype))
    h = shard(h, "experts", "batch", None)
    y = jnp.einsum("ecf,efd->ecd", h, layer["w_down"].astype(xd.dtype))
    y = shard(y, "experts", "batch", None)
    flat = combine_rows(disp, y)
    per_k = flat.reshape(T, moe.top_k, -1)
    out = jnp.einsum("tkd,tk->td", per_k, gates.astype(per_k.dtype))
    return out, disp.overflow


def _moe_ffn_routed(x2d, layer, cfg: TransformerConfig, capacity: int):
    """Expert FFN with explicit shard_map routing (paper's shuffle).

    Tokens are sharded over every mesh axis; each shard routes its rows to
    the model-axis shard owning the chosen expert via capacity-padded
    counting sort + ``all_to_all`` (exactly ``repro.core.route``), computes
    locally, and routes back through the same slots. Versus the pjit global
    dispatch this removes the all-gather of the full token array and the
    backward scatter-add all-reduces — wire drops from O(T*D) broadcast to
    O(T_local*k*D) point-to-point. Falls back to the global impl when the
    token count does not divide the mesh (tiny decode batches).
    """
    import math as _math

    from jax.sharding import PartitionSpec as P

    from repro.core.route import counting_layout, scatter_to_slots
    from repro.models.module import _CTX

    moe = cfg.moe
    mesh, _rules = _CTX[-1]
    axes_all = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    n_total = _math.prod(mesh.shape[a] for a in axes_all)
    n_model = mesh.shape.get("model", 1)
    T, D = x2d.shape
    if T % n_total or moe.n_experts % n_model:
        return _moe_ffn(x2d, layer, cfg, capacity)
    e_loc = moe.n_experts // n_model
    t_loc = T // n_total
    k = moe.top_k
    cap = max(8, -(-t_loc * k // n_model))
    cap = ((int(cap * moe.capacity_factor) + 7) // 8) * 8
    cap2 = ((int(n_model * cap / e_loc * 1.25) + 7) // 8) * 8 if e_loc > 1 else 0

    def inner(x_loc, router, wg, wu, wd):
        x_loc = x_loc  # (t_loc, D)
        logits = jnp.einsum(
            "td,de->te", x_loc, router, preferred_element_type=jnp.float32
        )
        top_vals, top_idx = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(top_vals, axis=-1)
        flat_e = top_idx.reshape(t_loc * k).astype(jnp.int32)
        dest = flat_e // e_loc  # destination model shard
        lay = counting_layout(dest, n_model, cap)
        rows = x_loc[jnp.arange(t_loc * k, dtype=jnp.int32) // k]
        send_x = scatter_to_slots(lay, rows, n_model, cap)
        send_e = scatter_to_slots(lay, flat_e, n_model, cap, fill=-1)
        used = scatter_to_slots(
            lay, jnp.ones((t_loc * k,), jnp.int8), n_model, cap
        )
        send_e = jnp.where(used > 0, send_e, -1)
        recv_x = jax.lax.all_to_all(send_x, "model", 0, 0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, "model", 0, 0, tiled=True)
        m_id = jax.lax.axis_index("model")
        local_e = recv_e - m_id * e_loc
        valid = (recv_e >= 0) & (local_e >= 0) & (local_e < e_loc)
        drops2 = jnp.zeros((), jnp.int32)
        if e_loc == 1:
            xr = recv_x * valid[:, None].astype(recv_x.dtype)
            h = jax.nn.silu(xr @ wg[0]) * (xr @ wu[0])
            y = (h @ wd[0]) * valid[:, None].astype(recv_x.dtype)
        else:
            disp2 = make_dispatch(
                jnp.where(valid, local_e, e_loc), e_loc, cap2
            )
            xd = dispatch_rows(disp2, recv_x)
            h = jax.nn.silu(
                jnp.einsum("ecd,edf->ecf", xd, wg)
            ) * jnp.einsum("ecd,edf->ecf", xd, wu)
            y2 = jnp.einsum("ecf,efd->ecd", h, wd)
            y = combine_rows(disp2, y2)
            drops2 = disp2.overflow - jnp.sum(~valid).astype(jnp.int32)
        back = jax.lax.all_to_all(y, "model", 0, 0, tiled=True)
        safe = jnp.clip(lay.slot_of_row, 0, n_model * cap - 1)
        out_rows = back[safe] * lay.fits[:, None].astype(back.dtype)
        per_k = out_rows.reshape(t_loc, k, D)
        out = jnp.einsum("tkd,tk->td", per_k, gates.astype(per_k.dtype))
        drops = jax.lax.psum(lay.overflow + jnp.maximum(drops2, 0), axes_all)
        return out, drops

    dt = x2d.dtype
    out, drops = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(
            P(axes_all, None),
            P(None, None),
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(axes_all, None), P()),
    )(
        x2d,
        layer["router"].astype(dt),
        layer["w_gate"].astype(dt),
        layer["w_up"].astype(dt),
        layer["w_down"].astype(dt),
    )
    return out, drops


def _dense_ffn(x, layer):
    h = jax.nn.silu(
        jnp.einsum("bsd,df->bsf", x, layer["w_gate"].astype(x.dtype))
    ) * jnp.einsum("bsd,df->bsf", x, layer["w_up"].astype(x.dtype))
    h = shard(h, "batch", None, "ffn")
    return jnp.einsum("bsf,fd->bsd", h, layer["w_down"].astype(x.dtype))


def _layer_body(
    x,
    layer,
    cfg: TransformerConfig,
    *,
    q_pos,
    kv_pos,
    cache_kv=None,
    cache_pos=None,
    moe_capacity: int = 0,
):
    """One transformer block. Returns (x, new_cache_kv, moe_drops, kv)."""
    B, Sq, D = x.shape
    h = rms_norm(x, layer["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dq->bsq", h, layer["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dq->bsq", h, layer["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dq->bsq", h, layer["wv"].astype(h.dtype))
    q = shard(q, "batch", None, "qkv")
    q = q.reshape(B, Sq, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, Sq, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, Sq, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"], cfg.norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.norm_eps)
    q = rope(q, q_pos, cfg.rope_theta)
    k = rope(k, q_pos, cfg.rope_theta)
    fresh_kv = (k, v)

    kv_valid_len = None
    if cache_kv is not None:
        ck, cv = cache_kv
        ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype), (0, cache_pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype), (0, cache_pos, 0, 0))
        ck = shard(ck, "batch", "kv_seq", None, None)
        cv = shard(cv, "batch", "kv_seq", None, None)
        k, v = ck, cv
        new_cache = (ck, cv)
        kv_valid_len = cache_pos + Sq
    else:
        new_cache = None

    if cfg.attn_impl == "chunked" and Sq > 1:
        attn = attend_chunked(
            q,
            k.astype(q.dtype),
            v.astype(q.dtype),
            q_pos=q_pos,
            kv_pos=kv_pos,
            window=layer["window"],
            kv_valid_len=kv_valid_len,
            chunk=cfg.attn_chunk,
        )
    else:
        attn = attend(
            q,
            k.astype(q.dtype),
            v.astype(q.dtype),
            q_pos=q_pos,
            kv_pos=kv_pos,
            window=layer["window"],
            kv_valid_len=kv_valid_len,
        )
    attn = shard(attn, "batch", None, "qkv")
    x = x + jnp.einsum("bsq,qd->bsd", attn, layer["wo"].astype(attn.dtype))

    h = rms_norm(x, layer["mlp_norm"], cfg.norm_eps)
    if cfg.moe is None:
        ffn = _dense_ffn(h, layer)
        drops = jnp.zeros((), jnp.int32)
    else:
        from repro.models.module import _CTX

        moe_fn = (
            _moe_ffn_routed if cfg.moe_impl == "routed" and _CTX else _moe_ffn
        )
        ffn2d, drops = moe_fn(h.reshape(B * Sq, D), layer, cfg, moe_capacity)
        ffn = ffn2d.reshape(B, Sq, D)
    x = x + ffn
    x = shard(x, "batch", None, None)
    return x, new_cache, drops, fresh_kv


def moe_capacity_for(cfg: TransformerConfig, n_tokens: int,
                     capacity_factor: float | None = None) -> int:
    if cfg.moe is None:
        return 0
    cf = capacity_factor or cfg.moe.capacity_factor
    cap = int(math.ceil(n_tokens * cfg.moe.top_k / cfg.moe.n_experts * cf))
    # round to 32 so the capacity dim divides the (pod, data) axes
    cap = ((max(cap, 32) + 31) // 32) * 32
    return min(n_tokens, cap)


def _remat_wrap(fn, mode: str):
    if mode == "none":
        return fn
    if mode == "full":
        return jax.checkpoint(fn)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    )


def _scan_layers(params, cfg: TransformerConfig, x, body):
    """Scan ``body`` over stacked layer params (+ per-layer window size)."""
    xs = dict(params["layers"])
    xs["window"] = cfg.window_sizes()

    def step(carry, layer):
        return body(carry, layer)

    step = _remat_wrap(step, cfg.remat)
    return jax.lax.scan(step, x, xs)


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def forward(params, cfg: TransformerConfig, tokens, *, capacity_factor=None):
    """Training/scoring forward: tokens (B, S) -> logits (B, S, V) fp32.

    Returns (logits, aux) with aux = {"moe_drops": total dropped rows}.
    """
    B, S = tokens.shape
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    x = shard(x, "batch", None, None)
    pos = jnp.arange(S, dtype=jnp.int32)
    cap = moe_capacity_for(cfg, B * S, capacity_factor)

    def body(carry, layer):
        y, _, drops, _kv = _layer_body(
            carry, layer, cfg, q_pos=pos, kv_pos=pos, moe_capacity=cap
        )
        return y, drops

    x, drops = _scan_layers(params, cfg, x, body)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,vd->bsv", x, params["embed"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    logits = shard(logits, "batch", None, "vocab")
    return logits, {"moe_drops": jnp.sum(drops)}


def loss_fn(params, cfg: TransformerConfig, batch, *, capacity_factor=None):
    """Next-token cross entropy. batch = {tokens (B,S), labels (B,S)}."""
    logits, aux = forward(params, cfg, batch["tokens"], capacity_factor=capacity_factor)
    labels = batch["labels"]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = jnp.mean(logz - label_logit)
    aux["loss"] = loss
    return loss, aux


def init_cache(cfg: TransformerConfig, batch: int, max_seq: int, dtype=None):
    """Stacked (L, B, S, Hkv, hd) KV cache pytree (zeros)."""
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_specs(cfg: TransformerConfig, batch: int, max_seq: int, dtype=None):
    dtype = dtype or cfg.compute_dtype
    shape = (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    sds = jax.ShapeDtypeStruct(shape, dtype)
    return {"k": sds, "v": sds}


CACHE_AXES = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")


def decode_step(params, cfg: TransformerConfig, tokens, cache, pos,
                *, capacity_factor=None):
    """One decode step. tokens (B, 1); pos () int32 current length.

    Returns (logits (B, 1, V), new_cache). The KV cache rides through the
    layer scan as stacked xs/ys so HLO stays depth-independent.
    """
    B, Sq = tokens.shape
    S_max = cache["k"].shape[2]
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    q_pos = (pos + jnp.arange(Sq, dtype=jnp.int32))[None, :].astype(jnp.int32)
    kv_pos = jnp.arange(S_max, dtype=jnp.int32)
    cap = moe_capacity_for(cfg, B * Sq, capacity_factor or 4.0)

    xs = dict(params["layers"])
    xs["window"] = cfg.window_sizes()
    xs["cache_k"] = cache["k"]
    xs["cache_v"] = cache["v"]

    def step(carry, layer_and_cache):
        layer = {
            k2: v2
            for k2, v2 in layer_and_cache.items()
            if k2 not in ("cache_k", "cache_v")
        }
        ck, cv = layer_and_cache["cache_k"], layer_and_cache["cache_v"]
        y, new_cache, _, _kv = _layer_body(
            carry,
            layer,
            cfg,
            q_pos=q_pos[0],
            kv_pos=kv_pos,
            cache_kv=(ck, cv),
            cache_pos=pos,
            moe_capacity=cap,
        )
        return y, {"cache_k": new_cache[0], "cache_v": new_cache[1]}

    x, new_caches = jax.lax.scan(step, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,vd->bsv", x, params["embed"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    return logits, {"k": new_caches["cache_k"], "v": new_caches["cache_v"]}


def prefill(params, cfg: TransformerConfig, tokens, max_seq: int,
            *, capacity_factor=None):
    """Prefill: run the full prompt, materialising the KV cache.

    tokens (B, S); returns (logits (B, S, V), cache with S_max=max_seq).
    """
    B, S = tokens.shape
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    x = shard(x, "batch", None, None)
    pos = jnp.arange(S, dtype=jnp.int32)
    cap = moe_capacity_for(cfg, B * S, capacity_factor)
    pad = max_seq - S

    def body(carry, layer):
        y, _, drops, (k, v) = _layer_body(
            carry, layer, cfg, q_pos=pos, kv_pos=pos, moe_capacity=cap
        )
        ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ck = shard(ck, "batch", "kv_seq", None, None)
        cv = shard(cv, "batch", "kv_seq", None, None)
        return y, {"cache_k": ck, "cache_v": cv}

    x, caches = _scan_layers(params, cfg, x, body)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,vd->bsv", x, params["embed"].astype(x.dtype),
        preferred_element_type=jnp.float32,
    )
    return logits, {"k": caches["cache_k"], "v": caches["cache_v"]}
