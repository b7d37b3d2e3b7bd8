"""Qwen2 (arXiv:2407.10671; huggingface.co/Qwen/Qwen2-1.5B config.json):
a dense decoder with grouped-query attention, bias on the q/k/v
projections, rotary positions (rotate-half form), RMSNorm before each
block, and a SwiGLU MLP. Plain float32, one sequence at a time.

``init`` makes the random weights from a key, in bfloat16 (norm scales in
float32), stacked over layers; the harness hands the same arrays to the
program. The reference never reads anything the program made: it calls
``init`` again from the same seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.common import einsum, mm, normal, rmsnorm


def sizes(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, L=cfg["num_hidden_layers"], H=H,
                KV=cfg["num_key_value_heads"], hd=d // H,
                ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def init(key, s: dict) -> dict:
    """Random weights. Unit-variance embeddings keep the first norm's
    input far above its epsilon; projections are scaled by 1/sqrt(fan-in)."""
    L, d, H, KV, hd, ff = s["L"], s["d"], s["H"], s["KV"], s["hd"], s["ff"]
    k = jax.random.split(key, 14)
    f32 = jnp.float32
    return {
        "embed": normal(k[0], (s["vocab"], d), 1.0),
        "ln1": 1.0 + normal(k[1], (L, d), 0.1, f32),
        "wq": normal(k[2], (L, d, H * hd), d ** -0.5),
        "wk": normal(k[3], (L, d, KV * hd), d ** -0.5),
        "wv": normal(k[4], (L, d, KV * hd), d ** -0.5),
        "bq": normal(k[5], (L, H * hd), 0.1),
        "bk": normal(k[6], (L, KV * hd), 0.1),
        "bv": normal(k[7], (L, KV * hd), 0.1),
        "wo": normal(k[8], (L, H * hd, d), (H * hd) ** -0.5),
        "ln2": 1.0 + normal(k[9], (L, d), 0.1, f32),
        "w_gate": normal(k[10], (L, d, ff), d ** -0.5),
        "w_up": normal(k[11], (L, d, ff), d ** -0.5),
        "w_down": normal(k[12], (L, ff, d), ff ** -0.5),
        "final_norm": 1.0 + normal(k[13], (d,), 0.1, f32),
    }


def _rope(x, pos, theta):
    """x: (T, heads, hd); rotate-half rotary embedding at positions pos."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv           # (T, hd/2)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def hidden(w: dict, s: dict, tokens, fp8: bool = False):
    """Final-norm hidden states (B, T, d) for a batch of sequences (B, T);
    each row is causal, so right-padding never reaches the rows before it."""
    return jax.vmap(lambda t: _hidden_one(w, s, t, fp8))(tokens)


def _hidden_one(w: dict, s: dict, tokens, fp8: bool):
    T = tokens.shape[0]
    H, KV, hd, eps = s["H"], s["KV"], s["hd"], s["eps"]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        h = rmsnorm(x, lw["ln1"], eps)
        q = (mm(h, lw["wq"], fp8) + lw["bq"].astype(jnp.float32)).reshape(T, H, hd)
        k = (mm(h, lw["wk"], fp8) + lw["bk"].astype(jnp.float32)).reshape(T, KV, hd)
        v = (mm(h, lw["wv"], fp8) + lw["bv"].astype(jnp.float32)).reshape(T, KV, hd)
        q, k = _rope(q, pos, s["theta"]), _rope(k, pos, s["theta"])
        k = jnp.repeat(k, H // KV, axis=1)   # query head j reads kv head j // (H/KV)
        v = jnp.repeat(v, H // KV, axis=1)
        sc = einsum("qhd,khd->hqk", q, k, fp8) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = einsum("hqk,khd->qhd", p, v, fp8).reshape(T, H * hd)
        x = x + mm(o, lw["wo"], fp8)
        h = rmsnorm(x, lw["ln2"], eps)
        g = jax.nn.silu(mm(h, lw["w_gate"], fp8)) * mm(h, lw["w_up"], fp8)
        return x + mm(g, lw["w_down"], fp8), None

    per_layer = {n: w[n] for n in ("ln1", "wq", "wk", "wv", "bq", "bk", "bv",
                                   "wo", "ln2", "w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(layer, x, per_layer)
    return rmsnorm(x, w["final_norm"], eps)


def to_program(w: dict, embed_rows: int, gate) -> dict:
    """The program's parameter tree (``repro.models.transformer``) holding
    these arrays; the embedding is padded with zero rows to the program's
    vocabulary padding."""
    emb = jnp.pad(w["embed"], ((0, embed_rows - w["embed"].shape[0]), (0, 0)))
    return {
        "embed": {"table": emb},
        "layers": {
            "ln1": {"scale": w["ln1"]},
            "attn": {n: w[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
            "ln2": {"scale": w["ln2"]},
            "mlp": {n: w[n] for n in ("w_gate", "w_up", "w_down")},
        },
        "final_norm": {"scale": w["final_norm"]},
        "head": {"gate": gate},
    }


def token_flops(s: dict, ctx: int) -> int:
    """Model operations of one token with ``ctx`` positions in view."""
    from work import dense_token_flops
    return dense_token_flops(s["d"], s["H"], s["KV"], s["hd"], s["ff"], s["L"], ctx)


def token_bytes(s: dict, ctx: int) -> int:
    """Least bytes of one slot's cache that a decode token moves: the
    bfloat16 keys and values of the ``ctx`` positions in view read (its
    own among them) and its own row written, in every layer."""
    row = 2 * s["KV"] * s["hd"] * 2
    return s["L"] * row * (ctx + 1)
