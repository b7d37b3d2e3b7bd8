"""Causal flash attention, Pallas TPU target (blocked online softmax).

Grid (B·H, n_q, n_k) with VMEM scratch carrying (acc, m, l) across the kv
axis; strictly-above-diagonal kv blocks are skipped with ``pl.when`` so the
kernel does exact-causal FLOPs. This is the TPU production path for the
prefill cells; the jnp chunked implementation in ``models/layers.py`` is
the lowering used by the CPU dry-run (same math — see tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e9


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, block_q, block_k, n_k):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(iq >= ik)  # causal: skip fully-masked blocks
    def _compute():
        q = q_ref[0]  # (block_q, dh)
        k = k_ref[0]  # (block_k, dh)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        @pl.when(iq == ik)
        def _mask_diag():
            pass  # mask applied below (jnp.where keeps single assignment simple)

        q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,  # (B, H, S, dh)
    k: jax.Array,
    v: jax.Array,
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, S, dh = q.shape
    bq = min(block_q, S)
    bk = min(block_k, S)
    while S % bq:
        bq //= 2
    while S % bk:
        bk //= 2
    scale = 1.0 / (dh ** 0.5)
    qf = q.reshape(B * H, S, dh)
    kf = k.reshape(B * H, S, dh)
    vf = v.reshape(B * H, S, dh)
    n_q, n_k = S // bq, S // bk

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=bq, block_k=bk, n_k=n_k),
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, dh)
