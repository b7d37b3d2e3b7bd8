"""Fused gate→dispatch→retrieve DS-Softmax decode kernel (single launch).

Every other serve path runs the (K, d) gate matvec + top-1 selection as an
XLA pre-pass whose dispatch products (expert indices, grouped buffers)
round-trip through HBM before the retrieval kernel launches. Here the
whole decode step is ONE ``pallas_call``:

* grid ``(n_token_blocks, K, n_vocab_blocks)`` — token blocks outermost
  and ``parallel``; the (expert, vocab) tour is ``arbitrary`` so the
  per-token-block VMEM state survives across it;
* **prologue** (once per token block, at ``e == jv == 0``): fp32 gate
  matmul ``x @ U^T``, softmax normalizer and first-argmax top-1 selection
  — the selected expert and the inverse normalizer (= the paper's
  un-renormalized gate value ``g``, exactly ``top1_gate``'s max softmax
  prob) are held in VMEM scratch. Dispatch never leaves the core;
* **body** (per expert/vocab block): weight-stationary
  ``(block_b, d)×(d, block_v)`` MXU matmul over the expert's packed rows
  — int8 rows are cast in-register to the token dtype and the per-row
  fp32 scale is applied to the accumulator (see ``dss_topk_grouped``) —
  then rows of non-selected experts are masked to ``-inf``. ``-inf``
  strictly undercuts the ``NEG_INF`` padding mask, so the selected
  expert's own padding rows still win ties over foreign experts and the
  emitted ids come only from the token's top-1 expert;
* the running top-k rides the same lane-padded VMEM carry as the grouped
  kernel; the epilogue writes (B, k) values/ids plus the (B,) selected
  GLOBAL expert id (for telemetry — never re-read by the kernel).

Sharded serving passes ``e_base`` (the global id of this shard's first
expert row) via scalar prefetch: gating runs over the full replicated
gate matrix, so every model shard agrees on the selection and only the
owner's rows survive the ``mine`` mask — the caller's O(B·k) merge is
unchanged. There is no capacity concept and hence no overflow: every
token reads exactly its own expert's rows.

Cost: the whole table streams HBM→VMEM once per *token block* — the
right trade at decode shapes (B ≲ 128 ⇒ one pass), where it beats the
grouped path by skipping the dispatch round-trip; at large B prefer
``pallas_grouped``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dss_topk_grouped import (
    NEG_INF,
    _carry_width,
    _merge_topk_carry,
    _pick_block_b,
    _pick_block_v,
)


def _body(ebase_ref, gate_ref, h_ref, w_ref, ids_ref, s_ref,
          vals_ref, idx_ref, eidx_ref, vs_ref, is_ref, es_ref, gs_ref,
          *, k: int, n_e: int, n_vb: int):
    e = pl.program_id(1)
    jv = pl.program_id(2)

    @pl.when((e == 0) & (jv == 0))
    def _prologue():
        # In-kernel gating == top1_gate: fp32 logits, first-argmax top-1,
        # gate value g = max softmax prob = 1 / sum(exp(glog - max)).
        x32 = h_ref[...].astype(jnp.float32)              # (bb, d)
        gw = gate_ref[...].astype(jnp.float32)            # (K_real, d)
        glog = jax.lax.dot_general(
            x32, gw, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bb, K_real)
        m = jnp.max(glog, axis=1, keepdims=True)
        ssum = jnp.sum(jnp.exp(glog - m), axis=1, keepdims=True)
        col = jax.lax.broadcasted_iota(jnp.int32, glog.shape, 1)
        sel = jnp.min(jnp.where(glog == m, col, glog.shape[1]),
                      axis=1, keepdims=True)
        es_ref[...] = sel                                  # (bb, 1) global id
        gs_ref[...] = 1.0 / ssum                           # (bb, 1) gate g
        vs_ref[...] = jnp.full_like(vs_ref, -jnp.inf)
        is_ref[...] = jnp.full_like(is_ref, -1)

    x = h_ref[...]            # (block_b, d) tokens, unscaled
    w = w_ref[0]              # (block_v, d) this expert's packed rows
    row_ids = ids_ref[0]      # (1, block_v); -1 = padding

    if s_ref is not None:
        w = w.astype(x.dtype)  # int8 rows → token dtype for the MXU
    z = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (block_b, block_v)
    if s_ref is not None:
        z = z * s_ref[0]                   # per-row dequant scale
    z = z * gs_ref[...]                    # gate scale AFTER the matmul
    z = jnp.where(row_ids >= 0, z, NEG_INF)
    # Not-my-expert rows drop to -inf — strictly below the selected
    # expert's NEG_INF padding, so foreign rows can never be emitted.
    mine = es_ref[...] == ebase_ref[0] + e  # (bb, 1)
    z = jnp.where(mine, z, -jnp.inf)

    _merge_topk_carry(z, row_ids, vs_ref, is_ref, k=k)

    @pl.when((e == n_e - 1) & (jv == n_vb - 1))
    def _finalize():
        vals_ref[...] = vs_ref[:, :k]
        idx_ref[...] = is_ref[:, :k]
        eidx_ref[...] = es_ref[...]


def _kernel(ebase_ref, gate_ref, h_ref, w_ref, ids_ref,
            vals_ref, idx_ref, eidx_ref, vs_ref, is_ref, es_ref, gs_ref,
            *, k: int, n_e: int, n_vb: int):
    _body(ebase_ref, gate_ref, h_ref, w_ref, ids_ref, None,
          vals_ref, idx_ref, eidx_ref, vs_ref, is_ref, es_ref, gs_ref,
          k=k, n_e=n_e, n_vb=n_vb)


def _kernel_q(ebase_ref, gate_ref, h_ref, w_ref, ids_ref, s_ref,
              vals_ref, idx_ref, eidx_ref, vs_ref, is_ref, es_ref, gs_ref,
              *, k: int, n_e: int, n_vb: int):
    _body(ebase_ref, gate_ref, h_ref, w_ref, ids_ref, s_ref,
          vals_ref, idx_ref, eidx_ref, vs_ref, is_ref, es_ref, gs_ref,
          k=k, n_e=n_e, n_vb=n_vb)


@functools.partial(
    jax.jit, static_argnames=("k", "interpret", "block_v", "block_b")
)
def dss_topk_fused(
    gate_w: jax.Array,   # (K_real, d) — full gate matrix, replicated
    weights: jax.Array,  # (K, V_pad, d) — packed rows (f32/bf16/int8; local)
    ids: jax.Array,      # (K, V_pad) int32, -1 = padding
    h: jax.Array,        # (B, d) tokens (UNscaled — gating runs in-kernel)
    k: int = 8,
    *,
    scales: jax.Array | None = None,  # (K, V_pad) fp32 — required for int8
    e_base: jax.Array | None = None,  # (1,) int32 global id of weights[0]
    interpret: bool | None = None,
    block_v: int | None = None,
    block_b: int | None = None,
):
    """Single-launch decode top-k. Returns ``(vals (B, k) f32, ids (B, k)
    i32, expert_idx (B,) i32)`` with ``expert_idx`` the GLOBAL top-1
    expert per token (== ``top1_gate``'s argmax; telemetry/merge input).
    Tokens whose expert lies outside ``[e_base, e_base + K)`` emit
    ``(-inf, -1)`` rows — the sharded caller masks/merges them."""
    quantized = weights.dtype == jnp.int8
    if quantized and scales is None:
        raise ValueError("int8 weights require the per-row scales operand")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    K, v_pad, d = weights.shape
    B = h.shape[0]
    bv = block_v or _pick_block_v(v_pad, d, weights.dtype.itemsize)
    bb = block_b or _pick_block_b(B)
    if k > bv:
        raise ValueError(f"k={k} must not exceed block_v={bv}")
    k_pad = _carry_width(k)

    # Pad the token axis to whole blocks: zero rows gate to expert 0 with
    # finite values and are sliced off below.
    b_pad = ((B + bb - 1) // bb) * bb
    if b_pad != B:
        h = jnp.pad(h, ((0, b_pad - B), (0, 0)))
    n_tb = b_pad // bb
    v_rounded = ((v_pad + bv - 1) // bv) * bv
    if v_rounded != v_pad:
        weights = jnp.pad(weights, ((0, 0), (0, v_rounded - v_pad), (0, 0)))
        ids = jnp.pad(ids, ((0, 0), (0, v_rounded - v_pad)), constant_values=-1)
        if quantized:
            scales = jnp.pad(scales, ((0, 0), (0, v_rounded - v_pad)),
                             constant_values=1.0)
    n_vb = v_rounded // bv
    grid = (n_tb, K, n_vb)

    if e_base is None:
        e_base = jnp.zeros((1,), jnp.int32)
    else:
        e_base = jnp.reshape(jnp.asarray(e_base, jnp.int32), (1,))

    # Per-row ids and scales ride as (K, 1, V) rows so every block's last
    # two dims are whole-array dims or (8, 128)-aligned (Mosaic tiling).
    K_real = gate_w.shape[0]
    in_specs = [
        pl.BlockSpec((K_real, d), lambda t, e, jv, eb: (0, 0)),
        pl.BlockSpec((bb, d), lambda t, e, jv, eb: (t, 0)),
        pl.BlockSpec((1, bv, d), lambda t, e, jv, eb: (e, jv, 0)),
        pl.BlockSpec((1, 1, bv), lambda t, e, jv, eb: (e, 0, jv)),
    ]
    operands = [gate_w, h, weights, ids[:, None, :]]
    if quantized:
        in_specs.append(
            pl.BlockSpec((1, 1, bv), lambda t, e, jv, eb: (e, 0, jv)))
        operands.append(scales.astype(jnp.float32)[:, None, :])

    kern = functools.partial(_kernel_q if quantized else _kernel,
                             k=k, n_e=K, n_vb=n_vb)
    vals, idxs, eidx = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((bb, k), lambda t, e, jv, eb: (t, 0)),
                pl.BlockSpec((bb, k), lambda t, e, jv, eb: (t, 0)),
                pl.BlockSpec((bb, 1), lambda t, e, jv, eb: (t, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bb, k_pad), jnp.float32),  # running top-k values
                pltpu.VMEM((bb, k_pad), jnp.int32),    # running top-k ids
                pltpu.VMEM((bb, 1), jnp.int32),        # selected expert
                pltpu.VMEM((bb, 1), jnp.float32),      # gate value g
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, k), jnp.int32),
            jax.ShapeDtypeStruct((b_pad, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(e_base, *operands)
    return vals[:B], idxs[:B], eidx[:B, 0]
