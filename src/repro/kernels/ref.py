"""Pure-jnp oracles for every Pallas kernel (tests assert allclose)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e9


def dss_topk_ref(weights, ids, h, expert_idx, g, k):
    """Oracle for the fused DS-Softmax serve kernel.

    weights: (K, V_pad, d); ids: (K, V_pad) int32 (-1 pad); h: (B, d);
    expert_idx: (B,) int32; g: (B,) gate value, applied to the logits.
    → (vals (B,k) f32, ids (B,k) int32).
    """
    w_sel = weights[expert_idx]  # (B, V_pad, d)
    ids_sel = ids[expert_idx]
    z = jnp.einsum("bvd,bd->bv", w_sel.astype(jnp.float32), h.astype(jnp.float32))
    z = z * g.astype(jnp.float32)[:, None]
    z = jnp.where(ids_sel >= 0, z, NEG_INF)
    vals, pos = jax.lax.top_k(z, k)
    return vals, jnp.take_along_axis(ids_sel, pos, axis=1)


def gate_top1_ref(gate_w, h):
    """Oracle for the fused top-1 gate: → (idx (B,), g (B,) f32)."""
    z = jnp.einsum("bd,kd->bk", h.astype(jnp.float32), gate_w.astype(jnp.float32))
    p = jax.nn.softmax(z, axis=-1)
    return jnp.argmax(p, axis=-1).astype(jnp.int32), jnp.max(p, axis=-1)


def lasso_prune_ref(weights, mask, gamma):
    """Oracle for row-norm pruning: → (norms (K,N) f32, new_mask (K,N) bool)."""
    w = weights.astype(jnp.float32) * mask[..., None].astype(jnp.float32)
    norms = jnp.sqrt(jnp.sum(jnp.square(w), axis=-1))
    return norms, jnp.logical_and(mask, norms > gamma)


def flash_attention_ref(q, k, v, causal=True):
    """Oracle attention. q,k,v: (B, H, S, dh) → (B, H, S, dh)."""
    S = q.shape[2]
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    if causal:
        m = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(m[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
