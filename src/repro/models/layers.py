"""Shared neural-net layers (pure functions + param pytrees; no flax).

Conventions:
* every ``init_*`` returns a dict pytree; params stored in ``cfg.jdtype``
  (bf16 by default) except norm scales (fp32);
* forward functions take ``(params, inputs, ...)`` and compute softmax/norm
  statistics in fp32;
* per-layer params are STACKED on axis 0 by the model builders and consumed
  via ``jax.lax.scan`` so the HLO (and compile time) is depth-independent.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in or shape[0]
    scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    return y.astype(x.dtype)


def init_layernorm(d: int):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, dh); positions: broadcastable to (..., S)."""
    if theta <= 0.0:  # arch without rope (whisper: learned abs pos added elsewhere)
        return x
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, dh/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, dh/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig):
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * dh), cfg.jdtype),
        "wk": dense_init(ks[1], (d, KV * dh), cfg.jdtype),
        "wv": dense_init(ks[2], (d, KV * dh), cfg.jdtype),
        "wo": dense_init(ks[3], (H * dh, d), cfg.jdtype, fan_in=H * dh),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), cfg.jdtype)
        p["bk"] = jnp.zeros((KV * dh,), cfg.jdtype)
        p["bv"] = jnp.zeros((KV * dh,), cfg.jdtype)
    return p


def _project_qkv(params, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    B, S, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,de->bse", x, params["wq"])
    k = jnp.einsum("bsd,de->bse", x, params["wk"])
    v = jnp.einsum("bsd,de->bse", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, KV, dh)
    v = v.reshape(B, S, KV, dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _attn_chunk(q, k, v, scale: float, mask: Optional[jax.Array]):
    """One (q-chunk, kv-chunk) block. q: (B,Cq,H,dh) k/v: (B,Ck,KV,dh).

    KV heads are expanded to H *per chunk* (bytes ∝ chunk, cheap) so the
    score/accumulate einsums carry a flat H axis — H is TP-divisible for the
    assigned archs while KV (1–8) generally is not; without this the model
    axis idles through the whole attention. Returns unnormalized
    (acc, m, l) online-softmax statistics, each (B,Cq,H,…) fp32.
    """
    from repro.distributed.hints import BATCH, constrain

    B, Cq, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bchd->bqhc", q, k, preferred_element_type=jnp.float32) * scale
    s = constrain(s, BATCH, None, "model", None)
    if mask is not None:
        s = jnp.where(mask[:, :, None, :], s, -1e9)
    m = jnp.max(s, axis=-1)  # (B,Cq,H)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bqhc,bchd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return acc, m, l


def _merge_online(stats_a, stats_b):
    acc_a, m_a, l_a = stats_a
    acc_b, m_b, l_b = stats_b
    m = jnp.maximum(m_a, m_b)
    ca = jnp.exp(m_a - m)
    cb = jnp.exp(m_b - m)
    return acc_a * ca[..., None] + acc_b * cb[..., None], m, l_a * ca + l_b * cb


def chunked_causal_attention(cfg: ModelConfig, q, k, v) -> jax.Array:
    """Flash-style causal attention with exact-causal FLOPs.

    Python loop over query chunks (static); for q-chunk i an inner
    ``lax.scan`` visits only kv chunks 0..i (static trip count), so the HLO
    contains no wasted fully-masked blocks. q,k,v: (B,S,H|KV,dh) → (B,S,H,dh).
    """
    B, S, H, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    Cq = min(cfg.attn_q_chunk, S)
    Ck = min(cfg.attn_kv_chunk, S)
    if S % Cq or S % Ck:  # small/odd sizes: single full block
        Cq = Ck = S
    nq, nk_total = S // Cq, S // Ck
    KV = k.shape[2]
    outs = []
    for i in range(nq):
        qi = jax.lax.dynamic_slice_in_dim(q, i * Cq, Cq, axis=1)
        q_pos = i * Cq + jnp.arange(Cq)
        # diagonal block (causal-masked)
        j_diag = (i * Cq) // Ck  # kv chunk index containing the diagonal start
        kd = jax.lax.dynamic_slice_in_dim(k, j_diag * Ck, Ck, axis=1)
        vd = jax.lax.dynamic_slice_in_dim(v, j_diag * Ck, Ck, axis=1)
        kv_pos = j_diag * Ck + jnp.arange(Ck)
        mask = q_pos[None, :, None] >= kv_pos[None, None, :]
        stats = _attn_chunk(qi, kd, vd, scale, mask)
        if j_diag > 0:
            # strictly-below-diagonal kv chunks: no mask needed
            k_hist = k[:, : j_diag * Ck].reshape(B, j_diag, Ck, KV, dh)
            v_hist = v[:, : j_diag * Ck].reshape(B, j_diag, Ck, KV, dh)

            def body(carry, kv_j):
                kj, vj = kv_j
                blk = _attn_chunk(qi, kj, vj, scale, None)
                return _merge_online(carry, blk), ()

            stats, _ = jax.lax.scan(
                body, stats, (jnp.moveaxis(k_hist, 1, 0), jnp.moveaxis(v_hist, 1, 0))
            )
        acc, m, l = stats
        outs.append((acc / l[..., None]).reshape(B, Cq, H, dh))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def full_attention(q, k, v, causal: bool) -> jax.Array:
    """Plain attention for short sequences / encoders. Shapes as above."""
    from repro.distributed.hints import BATCH, constrain

    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    Skv = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bchd->bqhc", q, k, preferred_element_type=jnp.float32) * scale
    s = constrain(s, BATCH, None, "model", None)
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(Skv)[None, :]
        s = jnp.where(mask[None, :, None, :], s, -1e9)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhc,bchd->bqhd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def attention_block(params, cfg: ModelConfig, x: jax.Array, positions, *, causal=True):
    """Self-attention over full sequences (train / prefill). Returns output
    projection AND the (k, v) tensors for cache construction."""
    q, k, v = _project_qkv(params, cfg, x, positions)
    S = x.shape[1]
    if causal and S > cfg.attn_q_chunk:
        o = chunked_causal_attention(cfg, q, k, v)
    else:
        o = full_attention(q, k, v, causal=causal)
    B = x.shape[0]
    out = jnp.einsum("bse,ed->bsd", o.reshape(B, S, -1), params["wo"])
    return out, (k, v)


def _kv_write(cache, new, layer, *idx):
    """Scatter ``new`` into one layer's K/V at the integer-array index
    ``idx`` (its trailing dims follow). ``layer`` None: ``cache`` is that
    layer's slab; else the stacked ``(L, ...)`` cache, written in place
    at ``[layer, *idx]``."""
    if layer is not None:
        idx = (layer,) + idx
    return cache.at[idx].set(new.astype(cache.dtype))


def _kv_write_block(cache, new, layer, pos):
    """Write the ``(B, C, KV, dh)`` block ``new`` at sequence position
    ``pos`` of one layer's contiguous K/V (``layer`` as in
    :func:`_kv_write`)."""
    starts = (0, pos, 0, 0)
    if layer is not None:
        new, starts = new[None], (layer,) + starts
    return jax.lax.dynamic_update_slice(cache, new.astype(cache.dtype), starts)


def _kv_view(cache, layer, pages):
    """One layer's logical ``(B, S, KV, dh)`` K/V: the slab itself, its
    row of the stacked cache, or (paged) its pages gathered per slot."""
    if pages is None:
        return cache if layer is None else cache[layer]
    n_pg, ps = pages.shape[1], cache.shape[-3]
    view = cache[pages] if layer is None else cache[layer, pages]
    return view.reshape(pages.shape[0], n_pg * ps, *cache.shape[-2:])


def attention_decode(params, cfg: ModelConfig, x, cache_k, cache_v, pos,
                     pages=None, layer=None):
    """One-token decode against a (B, S_cache, KV, dh) cache.

    x: (B, 1, d); pos: scalar int (one shared position; cache rows > pos
    are masked out) or a (B,) int32 vector of **per-row** positions — the
    continuous-batching case where every slot decodes at its own sequence
    length. Returns (out (B,1,d), new_k, new_v) with the caches updated in
    place at ``pos`` (row b at ``pos[b]`` for the vector form).

    ``layer`` (a traced layer index) makes ``cache_k``/``cache_v`` the
    STACKED ``(L, ...)`` caches: only the new rows of layer ``layer`` are
    written, and attention reads that layer's slab straight from the
    stack — a decode scan that carries the stack never rebuilds it.

    ``pages`` switches to the PAGED cache layout: ``cache_k``/``cache_v``
    are then ``(n_pages, page_size, KV, dh)`` arenas and ``pages`` is the
    ``(B, n_pg)`` int32 per-slot page table (threaded the way ``pos``
    is). Row *b*'s K/V at ``pos[b]`` is scattered into page
    ``pages[b, pos//page_size]`` at offset ``pos % page_size``, the
    logical ``(B, n_pg*page_size, KV, dh)`` view is gathered by page id,
    and the attention math below runs UNCHANGED on that view — outputs
    are bit-identical to the contiguous cache (stale/unmapped rows are
    masked to the same exact -1e9 scores either way; unmapped table
    entries must point at an all-zero page so their V rows contribute
    exact zeros, never NaN).
    """
    B = x.shape[0]
    pos = jnp.asarray(pos)
    per_row = pos.ndim == 1
    pos_b = pos if per_row else jnp.full((B,), pos)  # (B,)
    q, k_new, v_new = _project_qkv(params, cfg, x, pos_b[:, None])
    with jax.named_scope("kv_write"):
        if pages is not None:
            ps = cache_k.shape[-3]
            rows = jnp.arange(B)
            pid = pages[rows, pos_b // ps]  # (B,) write page per row
            off = pos_b % ps
            cache_k = _kv_write(cache_k, k_new[:, 0], layer, pid, off)
            cache_v = _kv_write(cache_v, v_new[:, 0], layer, pid, off)
        elif per_row:
            rows = jnp.arange(B)
            cache_k = _kv_write(cache_k, k_new[:, 0], layer, rows, pos_b)
            cache_v = _kv_write(cache_v, v_new[:, 0], layer, rows, pos_b)
        else:
            cache_k = _kv_write_block(cache_k, k_new, layer, pos)
            cache_v = _kv_write_block(cache_v, v_new, layer, pos)
    view_k = _kv_view(cache_k, layer, pages)
    view_v = _kv_view(cache_v, layer, pages)
    from repro.distributed.hints import BATCH, constrain

    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    S = view_k.shape[1]
    qg = q.reshape(B, KV, G, dh)
    # Split-KV (flash-decode): scores carry the cache's model-sharded S axis;
    # softmax over the sharded axis lowers to local partials + all-reduce.
    s = jnp.einsum(
        "bkgd,bckd->bkgc", qg, view_k, preferred_element_type=jnp.float32
    ) / math.sqrt(dh)
    s = constrain(s, BATCH, None, None, "model")
    valid = jnp.arange(S)[None, None, None, :] <= pos_b[:, None, None, None]
    s = jnp.where(valid, s, -1e9)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgc,bckd->bkgd", p.astype(view_v.dtype), view_v,
                   preferred_element_type=jnp.float32)
    out = jnp.einsum("be,ed->bd", o.reshape(B, H * dh).astype(x.dtype), params["wo"])
    return out[:, None, :], cache_k, cache_v


def attention_prefill_chunk(params, cfg: ModelConfig, x, cache_k, cache_v, pos0,
                            pages=None, layer=None):
    """Cache-context chunked prefill: C new tokens against a partially
    filled (B, S_max, KV, dh) cache.

    x: (B, C, d) chunk embeddings at positions ``pos0 .. pos0+C-1``
    (scalar ``pos0`` shared across B — one slot is prefilled at a time —
    or a (B,) int32 vector of per-row start positions, the speculative
    verify case where every resident slot checks its own γ-block at its
    own sequence length). K/V are written into the cache and each query
    attends causally to every cache position ≤ its own, so running a
    prompt through consecutive chunks is mathematically identical to one
    full-prompt prefill (masked positions contribute exact zeros to the
    softmax). Padding rows at the chunk tail write K/V at positions that
    stay masked until a later real token overwrites them.

    ``layer`` makes the caches the stacked ``(L, ...)`` ones, written and
    read at that layer in place (see :func:`attention_decode`).

    ``pages`` switches to the PAGED layout (see
    :func:`attention_decode`): ``cache_k``/``cache_v`` are
    ``(n_pages, page_size, KV, dh)`` arenas, the chunk's K/V rows are
    scattered into ``pages[b, position//page_size]``, and the identical
    masked attention runs on the gathered logical view — this is how a
    shared-prefix tail chunk attends to pages prefilled by ANOTHER
    request.
    """
    from repro.distributed.hints import BATCH, constrain

    B, C, _ = x.shape
    pos0 = jnp.asarray(pos0)
    per_row = pos0.ndim == 1
    positions = jnp.broadcast_to(
        (pos0[:, None] if per_row else pos0) + jnp.arange(C)[None, :], (B, C))
    q, k_new, v_new = _project_qkv(params, cfg, x, positions)
    with jax.named_scope("kv_write"):
        if pages is not None:
            ps = cache_k.shape[-3]
            pid = jnp.take_along_axis(pages, positions // ps, axis=1)  # (B, C)
            off = positions % ps
            cache_k = _kv_write(cache_k, k_new, layer, pid, off)
            cache_v = _kv_write(cache_v, v_new, layer, pid, off)
        elif per_row:
            rows = jnp.arange(B)[:, None]
            cache_k = _kv_write(cache_k, k_new, layer, rows, positions)
            cache_v = _kv_write(cache_v, v_new, layer, rows, positions)
        else:
            cache_k = _kv_write_block(cache_k, k_new, layer, pos0)
            cache_v = _kv_write_block(cache_v, v_new, layer, pos0)
    view_k = _kv_view(cache_k, layer, pages)
    view_v = _kv_view(cache_v, layer, pages)
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    G = H // KV
    S = view_k.shape[1]
    k = jnp.repeat(view_k, G, axis=2) if G > 1 else view_k
    v = jnp.repeat(view_v, G, axis=2) if G > 1 else view_v
    # Same einsum/dtype conventions as full_attention so chunked prefill is
    # bit-identical to the whole-prompt path row-for-row.
    s = jnp.einsum("bqhd,bchd->bqhc", q, k,
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(dh))
    s = constrain(s, BATCH, None, "model", None)
    valid = positions[:, :, None] >= jnp.arange(S)[None, None, :]  # (B, C, S)
    s = jnp.where(valid[:, :, None, :], s, -1e9)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhc,bchd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(q.dtype)
    out = jnp.einsum("bse,ed->bsd", o.reshape(B, C, -1), params["wo"])
    return out, cache_k, cache_v


def cross_attention_block(params, cfg: ModelConfig, x, memory):
    """Decoder cross-attention to encoder output (whisper). Non-causal."""
    B, S, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(B, S, H, dh)
    k = jnp.einsum("bsd,de->bse", memory, params["wk"]).reshape(B, memory.shape[1], KV, dh)
    v = jnp.einsum("bsd,de->bse", memory, params["wv"]).reshape(B, memory.shape[1], KV, dh)
    o = full_attention(q, k, v, causal=False)
    return jnp.einsum("bse,ed->bsd", o.reshape(B, S, -1), params["wo"])


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.act == "swiglu":
        return {
            "w_gate": dense_init(ks[0], (d, ff), cfg.jdtype),
            "w_up": dense_init(ks[1], (d, ff), cfg.jdtype),
            "w_down": dense_init(ks[2], (ff, d), cfg.jdtype, fan_in=ff),
        }
    return {
        "w_up": dense_init(ks[1], (d, ff), cfg.jdtype),
        "w_down": dense_init(ks[2], (ff, d), cfg.jdtype, fan_in=ff),
    }


def mlp(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if "w_gate" in params:
        g = jax.nn.silu(jnp.einsum("...d,df->...f", x, params["w_gate"]).astype(jnp.float32))
        u = jnp.einsum("...d,df->...f", x, params["w_up"]).astype(jnp.float32)
        return jnp.einsum("...f,fd->...d", (g * u).astype(x.dtype), params["w_down"])
    u = jax.nn.gelu(jnp.einsum("...d,df->...f", x, params["w_up"]).astype(jnp.float32))
    return jnp.einsum("...f,fd->...d", u.astype(x.dtype), params["w_down"])


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype):
    return {"table": dense_init(key, (vocab, d), dtype, fan_in=d)}


def embed(params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)
