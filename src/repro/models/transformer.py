"""Decoder-only transformer LM (dense / moe / vlm families).

Per-layer parameters are stacked on axis 0 and consumed by ``jax.lax.scan``
so HLO size and compile time are depth-independent (mandatory for the
95-layer archs on the 512-device dry-run). ``cfg.remat='layer'`` wraps the
scan body in ``jax.checkpoint`` for train memory.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import heads, layers, moe
from repro.models.layers import (
    attention_block,
    attention_decode,
    attention_prefill_chunk,
    embed,
    init_attention,
    init_embedding,
    init_mlp,
    init_rmsnorm,
    mlp,
    rmsnorm,
)


class DecodeCache(NamedTuple):
    """The stacked per-layer K/V cache. Every step that extends it
    (:func:`decode_step`, :func:`prefill_chunk`, :func:`verify_step`)
    carries it through the layer scan and writes only the new rows, so a
    caller that donates it (``ServeSession`` does, to every program whose
    result replaces its cache) has it updated in place, never rebuilt or
    copied."""
    k: jax.Array  # (L, B, S_max, KV, dh); paged: (L, n_pages, page_size, KV, dh)
    v: jax.Array


def init_layer(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    p = {
        "ln1": init_rmsnorm(cfg.d_model),
        "attn": init_attention(ks[0], cfg),
        "ln2": init_rmsnorm(cfg.d_model),
    }
    if cfg.moe is not None:
        p["moe"] = moe.init_moe(ks[1], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg)
    return p


def init_params(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg))(layer_keys)
    params = {
        "embed": init_embedding(ks[1], cfg.padded_vocab, cfg.d_model, cfg.jdtype),
        "layers": stacked,
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    head_params, ds_state = heads.init_head(ks[2], cfg)
    params["head"] = head_params
    return params, ds_state


def _layer_body(cfg: ModelConfig, x, layer_params, positions):
    h, _ = attention_block(layer_params["attn"], cfg, rmsnorm(layer_params["ln1"], x), positions)
    x = x + h
    xn = rmsnorm(layer_params["ln2"], x)
    if cfg.moe is not None:
        y, aux = moe.moe_block(layer_params["moe"], cfg, xn)
        return x + y, aux.load_loss
    return x + mlp(layer_params["mlp"], cfg, xn), jnp.zeros((), jnp.float32)


def forward_hidden(params, cfg: ModelConfig, x: jax.Array, positions) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) embeddings → (hidden (B, S, d), moe_aux_sum)."""
    from repro.distributed.hints import constrain_residual

    def body(carry, layer_params):
        y, aux = _layer_body(cfg, carry, layer_params, positions)
        return constrain_residual(y), aux

    if cfg.remat == "layer":
        body = jax.checkpoint(body)
    elif cfg.remat == "dots":
        # save weight-matmul outputs: the backward recompute skips the
        # TP partial-sum all-reduces (~1/3 of train collective traffic)
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    x, auxs = jax.lax.scan(body, constrain_residual(x), params["layers"])
    return rmsnorm(params["final_norm"], x), jnp.sum(auxs)


def embed_inputs(params, cfg: ModelConfig, batch, gather=None):
    """Token (+ optional vision-prefix) embedding. Returns (x, positions,
    label_mask) where label_mask marks CE-able positions (text only).
    ``gather`` (FSDP-stored serving weights) swaps the lookup for a
    sharded take + O(B·S·d) activation gather — the full table stays
    sharded."""
    tokens = batch["tokens"]
    if gather is not None:
        tok_emb = gather.rows("embed/table", params["embed"]["table"], tokens)
    else:
        tok_emb = embed(params["embed"], tokens)
    if cfg.vision is not None and "patches" in batch:
        patches = batch["patches"].astype(tok_emb.dtype)  # (B, P, d) stub frontend
        x = jnp.concatenate([patches, tok_emb], axis=1)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        label_mask = jnp.concatenate(
            [jnp.zeros(patches.shape[:2], bool), jnp.ones(tokens.shape, bool)], axis=1
        )
        return x, positions, label_mask
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return tok_emb, positions, None


def train_loss(params, ds_state, cfg: ModelConfig, batch):
    """batch: tokens (B, S+1) [+ patches]. → (total_loss, metrics dict)."""
    inp = dict(batch)
    tokens = batch["tokens"]
    inp["tokens"] = tokens[:, :-1]
    labels = tokens[:, 1:]
    x, positions, label_mask = embed_inputs(params, cfg, inp)
    h, moe_aux = forward_hidden(params, cfg, x, positions)
    if label_mask is not None:
        # CE only over text positions; labels aligned to text suffix
        n_pre = x.shape[1] - labels.shape[1]
        h_text = h[:, n_pre:]
    else:
        h_text = h
    ce, aux = heads.head_loss(
        params["head"], ds_state, cfg, h_text, labels,
        embed_table=params["embed"]["table"], label_mask=None,
    )
    moe_w = cfg.moe.router_aux_weight if cfg.moe is not None else 0.0
    total = ce + aux["head_aux_total"] + moe_w * moe_aux
    metrics = {"ce": ce, "moe_aux": moe_aux, **aux}
    return total, metrics


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def prefill(params, ds_state_or_table, cfg: ModelConfig, batch, k: int = 8,
            kernel=None, mesh=None, gather=None):
    """Run the full prompt; returns (topk_vals, topk_ids, DecodeCache).

    The cache is built to ``S_max = prompt length`` (the dry-run decode cells
    size it to seq_len per the assignment). ``kernel`` overrides the DS
    head's serve path (name or KernelPolicy; None => cfg.ds.serve_kernel).
    ``gather`` serves from FSDP-stored weights: each scanned layer's slice
    is all-gathered inside the loop body, just in time, so the full stack
    is never resident at once.
    """
    x, positions, _ = embed_inputs(params, cfg, batch, gather=gather)

    def body(carry, layer_params):
        xc = carry
        if gather is not None:
            layer_params = gather.layer("layers", layer_params)
        h, (kv_k, kv_v) = attention_block(
            layer_params["attn"], cfg, rmsnorm(layer_params["ln1"], xc), positions
        )
        xc = xc + h
        xn = rmsnorm(layer_params["ln2"], xc)
        if cfg.moe is not None:
            y, _ = moe.moe_block(layer_params["moe"], cfg, xn)
        else:
            y = mlp(layer_params["mlp"], cfg, xn)
        return xc + y, (kv_k, kv_v)

    xf, (ck, cv) = jax.lax.scan(body, x, params["layers"])
    h = rmsnorm(params["final_norm"], xf)[:, -1]  # last position
    vals, ids = heads.head_topk(
        params["head"], ds_state_or_table, cfg, h, k,
        embed_table=params["embed"]["table"], kernel=kernel, mesh=mesh,
        gather=gather,
    )
    return vals, ids, DecodeCache(k=ck, v=cv)


def prefill_chunk(params, serve_table, cfg: ModelConfig, cache: DecodeCache,
                  tokens, pos0, n_valid, k: int = 8, kernel=None, mesh=None,
                  gather=None, pages=None, state_pages=None):
    """Prefill one chunk of a prompt into an existing decode cache.

    tokens: (B, C) int32 at positions ``pos0 .. pos0+C-1`` (B=1 in the
    serving scheduler — one slot is prefilled at a time); rows ≥ ``n_valid``
    are right-padding (their K/V writes land at positions that stay masked
    until later real tokens overwrite them). Returns (vals, ids, cache)
    with the head applied to the hidden state of token ``n_valid-1`` —
    only the final chunk's top-k is meaningful.

    Every chunk call has the same static shapes, so chunked
    prefill-into-slots compiles ONCE for all prompt lengths (vs one
    whole-prompt compile per distinct length). Exactness: identical math
    to :func:`prefill` for dense/vlm-text models; MoE backbones drop
    tokens per expert-capacity computed over the chunk rather than the
    full prompt, so chunked and whole-prompt prefill can differ there.

    ``pages`` ((B, n_pg) int32 page table) switches the cache to the
    paged-arena layout (``cache.k``/``cache.v``:
    ``(L, n_pages, page_size, KV, dh)``) — see
    ``layers.attention_prefill_chunk``. ``state_pages`` is accepted for
    bundle-level API uniformity with the state families and ignored.
    """
    del state_pages  # KV-only family
    h, nk, nv = _chunk_hidden(params, cfg, cache, tokens, pos0,
                              gather=gather, pages=pages)
    B = h.shape[0]
    h_last = h[jnp.arange(B), n_valid - 1]  # (B, d)
    vals, ids = heads.head_topk(
        params["head"], serve_table, cfg, h_last, k,
        embed_table=params["embed"]["table"], kernel=kernel, mesh=mesh,
        gather=gather,
    )
    return vals, ids, DecodeCache(k=nk, v=nv)


def _chunk_hidden(params, cfg: ModelConfig, cache: DecodeCache, tokens, pos0,
                  gather=None, pages=None):
    """Shared chunk backbone for :func:`prefill_chunk` / :func:`verify_step`:
    run a (B, C) token block at positions ``pos0 .. pos0+C-1`` (scalar or
    per-row ``pos0``) against the cache. Returns (hidden (B, C, d), new
    cache_k, new cache_v)."""
    with jax.named_scope("embed"):
        if gather is not None:
            x = gather.rows("embed/table", params["embed"]["table"], tokens)
        else:
            x = embed(params["embed"], tokens)  # (B, C, d)
    xf, nk, nv = _cache_scan(
        params, cfg, cache, x, gather,
        lambda ap, xn, ck, cv, layer: attention_prefill_chunk(
            ap, cfg, xn, ck, cv, pos0, pages=pages, layer=layer))
    with jax.named_scope("final_norm"):
        return rmsnorm(params["final_norm"], xf), nk, nv


def _cache_scan(params, cfg: ModelConfig, cache: DecodeCache, x, gather,
                attend):
    """The layer scan of every cache-updating step: the stacked cache rides
    in the carry beside the residual, and ``attend(attn_params, x_normed,
    cache_k, cache_v, layer)`` writes only its layer's new rows into it
    and reads that layer's slab in place. Scanning the cache as xs/ys
    instead would rebuild the whole stack every call. Returns (x,
    cache_k, cache_v)."""

    def body(carry, scanned):
        xc, ck, cv = carry
        layer_params, layer = scanned
        if gather is not None:
            layer_params = gather.layer("layers", layer_params)
        with jax.named_scope("attn"):
            h, ck, cv = attend(layer_params["attn"],
                               rmsnorm(layer_params["ln1"], xc), ck, cv, layer)
            xc = xc + h
        with jax.named_scope("mlp"):
            xc = xc + _mlp_sublayer(layer_params, cfg, xc)
        return (xc, ck, cv), None

    with jax.named_scope("layers"):
        (xf, nk, nv), _ = jax.lax.scan(
            body, (x, cache.k, cache.v),
            (params["layers"], jnp.arange(cache.k.shape[0], dtype=jnp.int32)))
    return xf, nk, nv


def _mlp_sublayer(layer_params, cfg: ModelConfig, x):
    """ln2 then the layer's MLP (or MoE block), before the residual add."""
    xn = rmsnorm(layer_params["ln2"], x)
    if cfg.moe is not None:
        return moe.moe_block(layer_params["moe"], cfg, xn)[0]
    return mlp(layer_params["mlp"], cfg, xn)


def verify_step(params, serve_table, cfg: ModelConfig, cache: DecodeCache,
                tokens, pos0, k: int = 8, kernel=None, mesh=None, gather=None,
                capacity_factor=None, with_stats=False, pages=None,
                state_pages=None):
    """Speculative draft–verify: score a (B, W) block of candidate tokens.

    tokens: (B, W) int32 — row b holds ``[t_b, d_1 .. d_{W-1}]`` (the
    slot's last committed token followed by the draft proposals) at
    positions ``pos0[b] .. pos0[b]+W-1`` where ``pos0`` is the per-slot
    (B,) position vector. Reuses the chunked-prefill backbone (per-row
    ``pos0``), so every decoder family verifies through the same
    one-compile path; the head runs on ALL W positions at once — a
    (B·W, d) batch that lands in the grouped kernel regime under
    AutoPolicy — returning (vals, ids) of shape (B, W, k): position w
    scores the target's candidates for the token AFTER the w-th input.

    KV for all W positions is committed as written; candidate positions
    beyond the accepted prefix need no rollback — attention masks
    positions > the slot's ``pos`` to exact zeros and later real tokens
    overwrite them.
    """
    del state_pages  # KV-only family
    h, nk, nv = _chunk_hidden(params, cfg, cache, tokens, pos0,
                              gather=gather, pages=pages)
    B, W, d = h.shape
    out = heads.head_topk(
        params["head"], serve_table, cfg, h.reshape(B * W, d), k,
        embed_table=params["embed"]["table"], kernel=kernel, mesh=mesh,
        gather=gather, capacity_factor=capacity_factor, with_stats=with_stats,
    )
    vals = out[0].reshape(B, W, k)
    ids = out[1].reshape(B, W, k)
    if with_stats:
        return vals, ids, DecodeCache(k=nk, v=nv), out[2]
    return vals, ids, DecodeCache(k=nk, v=nv)


def decode_step(params, serve_table, cfg: ModelConfig, cache: DecodeCache, token, pos, k: int = 8,
                kernel=None, mesh=None, gather=None, capacity_factor=None,
                with_stats=False, pages=None, state_pages=None):
    """One-token decode. token: (B,) int32; pos: scalar position shared by
    the batch, or (B,) int32 per-slot positions (continuous batching).
    Returns (vals, ids, new_cache) — plus the head's per-expert
    ``{'dispatched', 'overflow'}`` telemetry when ``with_stats=True``.
    ``capacity_factor`` overrides the DS head's config value (serving
    circuit-breaker). ``gather`` serves from FSDP-stored weights
    (per-layer just-in-time all-gather inside the scan body). ``pages``
    ((B, n_pg) int32) switches the cache to the paged-arena layout (see
    ``layers.attention_decode``); ``state_pages`` is ignored (KV-only
    family). ``serve_table`` accepts a raw packed ServeTable or a
    versioned ``TableResource`` (unwrapped once in
    ``heads.head_topk``) — the backbone never reads it, which is why a
    hot-swap leaves resident requests' tokens identical from the swap
    point.

    ``new_cache`` is ``cache`` with one row per slot and layer written:
    the stacked cache rides in the layer scan's carry (:func:`_cache_scan`)
    and each layer attends over its own slab in place. Donated, as the
    serving session donates it, the step writes those rows into the
    buffer it was given and moves no other cache byte but the reads."""
    del state_pages
    with jax.named_scope("embed"):
        if gather is not None:
            x = gather.rows("embed/table", params["embed"]["table"], token)[:, None, :]
        else:
            x = embed(params["embed"], token)[:, None, :]  # (B,1,d)

    xf, nk, nv = _cache_scan(
        params, cfg, cache, x, gather,
        lambda ap, xn, ck, cv, layer: attention_decode(
            ap, cfg, xn, ck, cv, pos, pages=pages, layer=layer))
    with jax.named_scope("final_norm"):
        h = rmsnorm(params["final_norm"], xf)[:, 0]
    out = heads.head_topk(
        params["head"], serve_table, cfg, h, k,
        embed_table=params["embed"]["table"], kernel=kernel, mesh=mesh,
        gather=gather, capacity_factor=capacity_factor, with_stats=with_stats,
    )
    if with_stats:
        return out[0], out[1], DecodeCache(k=nk, v=nv), out[2]
    return out[0], out[1], DecodeCache(k=nk, v=nv)
