"""Mamba-2 (arXiv:2405.21060; huggingface.co/state-spaces/mamba2-130m
config.json, with the defaults of mamba_ssm's ``Mamba2`` block and
``MixerModel`` that the config leaves unstated): a stack of SSD blocks,
attention-free. Plain float32, the recurrence run token by token.

Per layer, on the residual stream ``x``:

    u = RMSNorm(x)
    z, xs, B, C, dt = in-projections of u      (d_inner, d_inner, G·N, G·N, H)
    xs, B, C = SiLU(causal depthwise conv of width d_conv, with bias)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)            (per head)
    h_t = exp(dt_t·A)·h_{t-1} + dt_t·x_t ⊗ B_t                (H, P, N)
    y_t = h_t·C_t + D·x_t
    y = RMSNorm(y · SiLU(z)) · norm_scale      (the gate before the norm)
    x = x + y @ W_out

then a final RMSNorm. Heads share B and C within a group (G = 1 here).

Departures from the published block, which the program makes too:
- the in-projection is three matrices (z and x; B and C; dt) where the
  published block has one, fused; the products are the same;
- the depthwise conv's weight is held as (d_conv, channels), the tap
  ``d_conv - 1`` on the current token;
- no clamp on dt (the published ``dt_limit`` is (0, inf), none).
The published block keeps its inference state in the model's dtype; the
program keeps the recurrent state in float32, as this reference does.

``init`` makes random weights from a key, in the program's dtypes
(bfloat16 matrices, float32 norm scales and per-head parameters), stacked
over layers; the harness hands the same arrays to the program, and the
reference makes them again from the seed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.common import einsum, mm, normal, rmsnorm

PER_LAYER = ("ln", "in_zx", "in_bc", "in_dt", "conv_w", "conv_b", "A_log",
             "dt_bias", "D", "norm_scale", "out_proj")


def sizes(cfg: dict) -> dict:
    b = cfg["defaults"]
    d, P = cfg["d_model"], b["headdim"]
    di = b["expand"] * d
    G, N = b["ngroups"], b["d_state"]
    return dict(d=d, L=cfg["n_layer"], vocab=cfg["vocab_size"], di=di, P=P,
                H=di // P, G=G, N=N, W=b["d_conv"], conv=di + 2 * G * N,
                eps=float(b["norm_epsilon"]), A_range=tuple(b["A_init_range"]),
                dt_range=(float(b["dt_min"]), float(b["dt_max"])))


def init(key, s: dict) -> dict:
    """Random weights: unit-variance embeddings, projections scaled by
    1/sqrt(fan-in), A and dt's bias drawn as the published block
    initialises them (A uniform in ``A_init_range``, dt log-uniform in
    [dt_min, dt_max])."""
    L, d, di, H, G, N, W = s["L"], s["d"], s["di"], s["H"], s["G"], s["N"], s["W"]
    k = jax.random.split(key, 13)
    f32 = jnp.float32
    lo, hi = s["A_range"]
    A = jax.random.uniform(k[6], (L, H), f32, lo, hi)
    dt = jnp.exp(jax.random.uniform(k[7], (L, H), f32, math.log(s["dt_range"][0]),
                                    math.log(s["dt_range"][1])))
    return {
        "embed": normal(k[0], (s["vocab"], d), 1.0),
        "ln": 1.0 + normal(k[1], (L, d), 0.1, f32),
        "in_zx": normal(k[2], (L, d, 2 * di), d ** -0.5),
        "in_bc": normal(k[3], (L, d, 2 * G * N), d ** -0.5),
        "in_dt": normal(k[4], (L, d, H), d ** -0.5),
        "conv_w": normal(k[5], (L, W, s["conv"]), W ** -0.5),
        "conv_b": normal(k[8], (L, s["conv"]), 0.1),
        "A_log": jnp.log(A),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),      # softplus(dt_bias) = dt
        "D": 1.0 + normal(k[9], (L, H), 0.1, f32),
        "norm_scale": 1.0 + normal(k[10], (L, di), 0.1, f32),
        "out_proj": normal(k[11], (L, di, d), di ** -0.5),
        "final_norm": 1.0 + normal(k[12], (d,), 0.1, f32),
    }


def hidden(w: dict, s: dict, tokens, fp8: bool = False):
    """Final-norm hidden states (B, T, d) for a batch of sequences (B, T);
    each row is causal, so right-padding never reaches the rows before it."""
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, lw):
        return x + _mixer(lw, s, rmsnorm(x, lw["ln"], s["eps"]), fp8), None

    x, _ = jax.lax.scan(layer, x, {n: w[n] for n in PER_LAYER})
    return rmsnorm(x, w["final_norm"], s["eps"])


def _mixer(lw: dict, s: dict, u, fp8: bool):
    """One Mamba-2 mixer over (B, T, d), its state from zero."""
    Bsz, T, _ = u.shape
    di, H, P, G, N, W = s["di"], s["H"], s["P"], s["G"], s["N"], s["W"]
    zx = mm(u, lw["in_zx"], fp8)
    z, xbc = zx[..., :di], jnp.concatenate([zx[..., di:], mm(u, lw["in_bc"], fp8)], -1)
    dt = jax.nn.softplus(mm(u, lw["in_dt"], fp8) + lw["dt_bias"])      # (B, T, H)
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    cw = lw["conv_w"].astype(jnp.float32)
    conv = sum(pad[:, i: i + T] * cw[i] for i in range(W)) + lw["conv_b"].astype(jnp.float32)
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :di].reshape(Bsz, T, H, P)
    heads = lambda t: jnp.repeat(t.reshape(Bsz, T, G, N), H // G, axis=2)   # (B, T, H, N)
    Bm, Cm = heads(xbc[..., di: di + G * N]), heads(xbc[..., di + G * N:])
    A = -jnp.exp(lw["A_log"])

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp                   # (B,H,P) (B,H) (B,H,N) (B,H,N)
        h = jnp.exp(dt_t * A)[..., None, None] * h \
            + einsum("bhp,bhn->bhpn", x_t * dt_t[..., None], b_t, fp8)
        return h, einsum("bhpn,bhn->bhp", h, c_t, fp8)

    tm = lambda t: jnp.moveaxis(t, 1, 0)
    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    _, ys = jax.lax.scan(step, h0, (tm(xs), tm(dt), tm(Bm), tm(Cm)))
    y = jnp.moveaxis(ys, 0, 1) + lw["D"][:, None] * xs                 # (B, T, H, P)
    y = y.reshape(Bsz, T, di) * jax.nn.silu(z)
    return mm(rmsnorm(y, lw["norm_scale"], s["eps"]), lw["out_proj"], fp8)


def to_program(w: dict, embed_rows: int, gate) -> dict:
    """The program's parameter tree (``repro.models.hybrid``, family
    ``ssm``) holding these arrays; the embedding is padded with zero rows
    to the program's vocabulary padding."""
    emb = jnp.pad(w["embed"], ((0, embed_rows - w["embed"].shape[0]), (0, 0)))
    return {
        "embed": {"table": emb},
        "layers": {"ln": {"scale": w["ln"]},
                   "mamba": {n: w[n] for n in PER_LAYER if n != "ln"}},
        "final_norm": {"scale": w["final_norm"]},
        "head": {"gate": gate},
    }


def token_flops(s: dict, ctx: int) -> int:
    """Model operations of one decode token (any context: the state is
    of fixed size): the in- and out-projections, the conv's taps, and the
    recurrence, 3 operations an element of the state to update it and 2
    to read y from it."""
    del ctx
    from work import matmul_flops
    d, di, H, P, N, G, W = s["d"], s["di"], s["H"], s["P"], s["N"], s["G"], s["W"]
    proj = matmul_flops(1, d, 2 * di + 2 * G * N + H) + matmul_flops(1, di, d)
    return s["L"] * (proj + 2 * W * s["conv"] + 5 * H * P * N)


def token_bytes(s: dict, ctx: int) -> int:
    """Least bytes of one slot's state that a decode token moves: the
    float32 recurrent state and the bfloat16 conv window (the last
    ``d_conv - 1`` inputs), each read and written once a layer."""
    del ctx
    ssm = s["H"] * s["P"] * s["N"] * 4
    conv = (s["W"] - 1) * s["conv"] * 2
    return s["L"] * 2 * (ssm + conv)
