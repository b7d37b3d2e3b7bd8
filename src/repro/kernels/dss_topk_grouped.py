"""Expert-grouped streaming DS-Softmax serving kernel (weight-stationary).

The per-token kernel in ``dss_topk.py`` runs a ``(block_v, d)×(d, 1)``
mat*vec* per token (~1/128 MXU utilization) and re-reads each expert's
weight blocks once per *token*. This kernel consumes tokens that the XLA
pre-pass has already grouped by their top-1 expert (the same dispatch the
MoE FFN / sorted-train path uses, ``core.dispatch.dispatch_indices``) into
a dense ``(K, C, d)`` buffer, so the hot loop is a weight-stationary
``(block_b, d)×(d, block_v)`` MXU block matmul:

* grid ``(K, n_token_blocks, n_vocab_blocks)`` — vocab innermost with
  ``arbitrary`` semantics; ``K`` and token blocks are ``parallel``;
* each expert's packed rows stream HBM→VMEM once per (expert, token-block)
  — once per *expert* in the common serving regime where the per-expert
  capacity fits a single token block — double-buffered by the Pallas
  pipeline across grid steps;
* the gate scale is applied to the fp32 logits *after* the matmul (the
  oracle's ``z·g`` order): ids agree exactly with the jnp path for bf16
  and fp32 weights, values up to f32 accumulation-order ulps (a block
  matmul and a batched matvec may round differently over d);
* int8 tables (``weights.dtype == int8`` + a ``scales`` (K, V_pad) fp32
  operand) dequantize IN-REGISTER: the int8 block is cast to the token
  dtype for the MXU matmul and the per-row scale is applied to the fp32
  accumulator exactly like the gate scale — the fp table never exists in
  HBM, so expert rows cost 1 byte/elem to stream;
* a running top-k (values + class ids) is carried in VMEM scratch across
  vocab blocks: only the final ``(K, C, k)`` values/ids — O(B·k), one row
  per dispatched token slot — are written to HBM. There is NO
  ``(B, n_blocks, k)`` candidate spill and no second XLA ``top_k`` merge.

Tie-breaking matches ``jax.lax.top_k`` (lowest packed position wins): the
running candidates are kept left of the fresh block in the merge, and the
arg-max scan takes the first maximal column.

The carry is lane-padded to a full 128-wide tile (``_carry_width``): the
``k+1 .. 128`` pad lanes hold ``(-inf, -1)`` and are re-written every
merge, so Mosaic keeps the scratch on natural lane boundaries without a
relayout per vocab block. ``-inf`` strictly undercuts the ``NEG_INF``
(-1e9) padding-row mask, so a pad lane can never win an extraction round
and leak its ``-1`` id into the emitted top-k (regression-tested in
``tests/test_quantize.py``); the (K, C, k) outputs slice the real lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e9
_LANES = 128


def _carry_width(k: int) -> int:
    """Running-carry width: k lane-padded up to a whole 128-lane tile."""
    return ((k + _LANES - 1) // _LANES) * _LANES


def _pick_block_v(v_pad: int, d: int, dtype_bytes: int, budget: int = 4 * 2 ** 20) -> int:
    """Largest 128-multiple vocab block that divides v_pad within budget."""
    for cand in (1024, 512, 256, 128):
        if v_pad % cand == 0 and cand * d * dtype_bytes <= budget:
            return cand
    return min(v_pad, 128)


def _pick_block_b(capacity: int) -> int:
    """Token-block rows: one block when the expert capacity is small (the
    common serving regime — weights then stream once per expert)."""
    if capacity <= 256:
        return max(8, ((capacity + 7) // 8) * 8)
    return 128


def _merge_topk_carry(z, row_ids, vs_ref, is_ref, *, k: int):
    """Merge a fresh (bb, block_v) logit block into the lane-padded running
    top-k carry. Carry candidates sit LEFT of the block so ties resolve to
    earlier packed positions, matching ``jax.lax.top_k``; the ``-inf`` pad
    lanes can never be extracted (every real candidate is ≥ NEG_INF)."""
    vcat = jnp.concatenate([vs_ref[...], z], axis=1)        # (bb, k_pad+bv)
    icat = jnp.concatenate(
        [is_ref[...], jnp.broadcast_to(row_ids, z.shape).astype(jnp.int32)],
        axis=1,
    )
    col = jax.lax.broadcasted_iota(jnp.int32, vcat.shape, 1)
    sentinel = vcat.shape[1]
    new_v, new_i = [], []
    for _ in range(k):  # k is small and static — unrolled extraction
        m = jnp.max(vcat, axis=1, keepdims=True)
        am = jnp.min(jnp.where(vcat == m, col, sentinel), axis=1, keepdims=True)
        hit = col == am
        new_v.append(m[:, 0])
        new_i.append(jnp.sum(jnp.where(hit, icat, 0), axis=1))
        vcat = jnp.where(hit, -jnp.inf, vcat)
    k_pad = vs_ref.shape[1]
    if k_pad > k:  # restore the pad lanes alongside the new carry
        bb = z.shape[0]
        new_v.extend([jnp.full((bb,), -jnp.inf, jnp.float32)] * (k_pad - k))
        new_i.extend([jnp.full((bb,), -1, jnp.int32)] * (k_pad - k))
    vs_ref[...] = jnp.stack(new_v, axis=1)
    is_ref[...] = jnp.stack(new_i, axis=1)


def _body(buf_ref, g_ref, w_ref, ids_ref, s_ref, vals_ref, idx_ref,
          vs_ref, is_ref, *, k: int, n_vb: int):
    jv = pl.program_id(2)

    @pl.when(jv == 0)
    def _init():
        vs_ref[...] = jnp.full_like(vs_ref, -jnp.inf)
        is_ref[...] = jnp.full_like(is_ref, -1)

    x = buf_ref[0]            # (block_b, d) — grouped tokens, unscaled
    w = w_ref[0]              # (block_v, d) — this expert's packed rows
    g = g_ref[0]              # (block_b, 1) — fp32 gate values
    row_ids = ids_ref[0]      # (1, block_v) — class id per row; -1 = padding

    if s_ref is not None:
        w = w.astype(x.dtype)  # int8 rows → token dtype for the MXU

    # Weight-stationary MXU block matmul with fp32 accumulation.
    z = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (block_b, block_v)
    if s_ref is not None:
        z = z * s_ref[0]                         # per-row dequant scale
    z = z * g                                    # gate scale AFTER the matmul
    z = jnp.where(row_ids >= 0, z, NEG_INF)      # mask table padding

    _merge_topk_carry(z, row_ids, vs_ref, is_ref, k=k)

    @pl.when(jv == n_vb - 1)
    def _finalize():
        vals_ref[0] = vs_ref[:, :k]
        idx_ref[0] = is_ref[:, :k]


def _kernel(buf_ref, g_ref, w_ref, ids_ref, vals_ref, idx_ref, vs_ref, is_ref,
            *, k: int, n_vb: int):
    _body(buf_ref, g_ref, w_ref, ids_ref, None, vals_ref, idx_ref,
          vs_ref, is_ref, k=k, n_vb=n_vb)


def _kernel_q(buf_ref, g_ref, w_ref, ids_ref, s_ref, vals_ref, idx_ref,
              vs_ref, is_ref, *, k: int, n_vb: int):
    _body(buf_ref, g_ref, w_ref, ids_ref, s_ref, vals_ref, idx_ref,
          vs_ref, is_ref, k=k, n_vb=n_vb)


@functools.partial(
    jax.jit, static_argnames=("k", "interpret", "block_v", "block_b")
)
def dss_topk_grouped(
    weights: jax.Array,  # (K, V_pad, d) — packed expert tables (f32/bf16/int8)
    ids: jax.Array,      # (K, V_pad) int32, -1 = padding
    buf: jax.Array,      # (K, C, d) — expert-grouped tokens (UNscaled)
    g_buf: jax.Array,    # (K, C) fp32 — gate value per slot (0 for empty)
    k: int = 8,
    *,
    scales: jax.Array | None = None,  # (K, V_pad) fp32 — required for int8
    interpret: bool | None = None,
    block_v: int | None = None,
    block_b: int | None = None,
):
    """Fused grouped serve top-k. Returns (vals (K, C, k) f32, ids (K, C, k)
    i32) in the grouped layout; the caller un-scatters to (B, k) and applies
    the bounded capacity-overflow fallback (see core.dssoftmax.serve_topk).

    int8 ``weights`` require the per-row ``scales``: rows are dequantized
    in-register (cast + scale on the fp32 accumulator), never in HBM."""
    quantized = weights.dtype == jnp.int8
    if quantized and scales is None:
        raise ValueError("int8 weights require the per-row scales operand")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    K, v_pad, d = weights.shape
    _, capacity, _ = buf.shape
    bv = block_v or _pick_block_v(v_pad, d, weights.dtype.itemsize)
    bb = block_b or _pick_block_b(capacity)
    if k > bv:
        raise ValueError(f"k={k} must not exceed block_v={bv}")
    k_pad = _carry_width(k)

    # Pad the capacity axis to a whole number of token blocks. Padded slots
    # carry g=0 and are never gathered back, so their outputs are ignored.
    c_pad = ((capacity + bb - 1) // bb) * bb
    if c_pad != capacity:
        buf = jnp.pad(buf, ((0, 0), (0, c_pad - capacity), (0, 0)))
        g_buf = jnp.pad(g_buf, ((0, 0), (0, c_pad - capacity)))
    n_tb = c_pad // bb
    # Pad the vocab axis likewise (explicit serve_pad / block_v need not
    # divide): padded rows get id -1, which the kernel masks to NEG_INF —
    # flooring n_vb instead would silently skip the trailing rows.
    v_rounded = ((v_pad + bv - 1) // bv) * bv
    if v_rounded != v_pad:
        weights = jnp.pad(weights, ((0, 0), (0, v_rounded - v_pad), (0, 0)))
        ids = jnp.pad(ids, ((0, 0), (0, v_rounded - v_pad)), constant_values=-1)
        if quantized:
            scales = jnp.pad(scales, ((0, 0), (0, v_rounded - v_pad)),
                             constant_values=1.0)
    n_vb = v_rounded // bv
    grid = (K, n_tb, n_vb)

    # Per-slot gate values ride as a (K, C, 1) column and per-row ids and
    # scales as (K, 1, V) rows, so every block's last two dims are
    # whole-array dims or (8, 128)-aligned (Mosaic tiling).
    in_specs = [
        pl.BlockSpec((1, bb, d), lambda e, t, jv: (e, t, 0)),
        pl.BlockSpec((1, bb, 1), lambda e, t, jv: (e, t, 0)),
        pl.BlockSpec((1, bv, d), lambda e, t, jv: (e, jv, 0)),
        pl.BlockSpec((1, 1, bv), lambda e, t, jv: (e, 0, jv)),
    ]
    operands = [buf, g_buf[..., None], weights, ids[:, None, :]]
    if quantized:
        in_specs.append(pl.BlockSpec((1, 1, bv), lambda e, t, jv: (e, 0, jv)))
        operands.append(scales.astype(jnp.float32)[:, None, :])

    kern = functools.partial(_kernel_q if quantized else _kernel,
                             k=k, n_vb=n_vb)
    vals, idxs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bb, k), lambda e, t, jv: (e, t, 0)),
            pl.BlockSpec((1, bb, k), lambda e, t, jv: (e, t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, c_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((K, c_pad, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, k_pad), jnp.float32),
            pltpu.VMEM((bb, k_pad), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(*operands)
    if c_pad != capacity:
        vals = vals[:, :capacity]
        idxs = idxs[:, :capacity]
    return vals, idxs
