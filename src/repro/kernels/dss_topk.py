"""Fused DS-Softmax serving kernel — per-token streaming variant (legacy).

Per token: gather the chosen expert's packed rows HBM→VMEM in blocks via a
*scalar-prefetch index map* (the expert id steers the BlockSpec — no
materialized (B, V_pad, d) gather), MXU matmul per block, pad-mask, and an
in-VMEM per-block top-k. A host-side merge over the spilled
``(B, n_blocks, k)`` candidates yields the exact global top-k.

Kernel-path matrix for ``core.dssoftmax.serve_topk`` (B tokens, K experts,
V_pad packed rows/expert, d features, wb weight bytes/elem — 4/2 for
fp32/bf16 tables, 1 for an int8 ``QuantizedServeTable``, which adds a
4-byte fp32 scale per packed row, amortized over d; the legacy ``pallas``
path has no scales operand and is infeasible on quantized tables):

    path            engine   expert-row HBM reads    extra HBM traffic
    --------------  -------  ----------------------  ---------------------------
    jnp             XLA      B·V_pad·d·wb (/token)   (B,V_pad,d) gather material.
    grouped         XLA      K·V_pad·d·wb (/expert)  (K,C,V_pad) fp32 logit spill
                                                     + (K,C,d) dispatch
                                                     round-trip
    pallas (this)   Pallas   B·V_pad·d·wb (/token)   (B,n_blocks,k) candidates
                                                     + second XLA top_k merge
    pallas_grouped  Pallas   K·V_pad·d·wb (/expert)  (K,C,d) dispatch round-trip
                                                     — top-k carried in VMEM,
                                                     only O(B·k) outputs
    pallas_fused    Pallas   ⌈B/bb⌉·K·V_pad·d·wb     none — gate matvec + top-1
                    (one     (/token-BLOCK; = one    selection run in the kernel
                    launch)  table pass at B ≤ bb)   prologue, no dispatch
                                                     indices ever reach HBM

Roofline argument: serving is memory-bound, so bytes-per-expert beats
bytes-per-token as soon as tokens share experts (B > K, i.e. any real
batch). This per-token kernel still re-reads each expert block once per
token and runs a ``(block_v, d)×(d, 1)`` mat*vec* (~1/128 MXU utilization);
it remains the right shape only for tiny/latency-critical batches (B ≲ K,
every token on a different expert) where the grouped dispatch pre-pass
would be pure overhead. For everything else use ``pallas_grouped``
(``dss_topk_grouped.py``): expert-grouped token blocks, weight-stationary
``(block_b, d)×(d, block_v)`` MXU matmuls, running top-k in VMEM scratch.

When each path wins:

* ``jnp`` — debugging oracle, any backend; never fastest.
* ``grouped`` — CPU/GPU serving via plain XLA; beats ``jnp`` wall-clock
  once B ≫ K (measured in ``benchmarks/serve_topk.py``), pays a
  (K,C,V_pad) logit spill the fused kernel avoids.
* ``pallas`` — TPU, B ≲ K decode edge case; fp tables only.
* ``pallas_grouped`` — TPU large-batch serving default (ServeSession
  prefill / big batches); int8 rows dequantize in-register.
* ``pallas_fused`` — TPU decode (B ≲ bb = one token block): single
  launch, in-kernel gating, whole decode step in one table pass —
  skips the grouped path's (K,C,d)+(K,C) dispatch round-trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9


def _block_v(v_pad: int, d: int, dtype_bytes: int = 2, budget: int = 4 * 2 ** 20) -> int:
    for cand in (1024, 512, 256, 128):
        if v_pad % cand == 0 and cand * d * dtype_bytes <= budget:
            return cand
    return 128


def _kernel(eidx_ref, w_ref, ids_ref, h_ref, g_ref, vals_ref, idx_ref, *,
            k: int):
    del eidx_ref  # consumed by the index maps
    w = w_ref[0]        # (block_v, d)
    h = h_ref[0]        # (1, d)
    ids = ids_ref[0]    # (1, block_v)
    z = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (1, block_v)
    z = z * g_ref[0]    # gate scale AFTER the matmul, like the oracle
    z = jnp.where(ids >= 0, z, NEG_INF)
    col = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    # unrolled top-k within the block (k is small and static); the first
    # maximal column wins ties, like jax.lax.top_k
    vals, idxs = [], []
    for _ in range(k):
        m = jnp.max(z, axis=1, keepdims=True)
        am = jnp.min(jnp.where(z == m, col, z.shape[1]), axis=1, keepdims=True)
        hit = col == am
        vals.append(m[:, 0])
        idxs.append(jnp.sum(jnp.where(hit, ids, 0), axis=1))
        z = jnp.where(hit, -jnp.inf, z)
    vals_ref[0, 0] = jnp.stack(vals, axis=1)
    idx_ref[0, 0] = jnp.stack(idxs, axis=1)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "block_v"))
def dss_topk(
    weights: jax.Array,   # (K, V_pad, d)
    ids: jax.Array,       # (K, V_pad) int32, -1 = padding
    h: jax.Array,         # (B, d) tokens, unscaled
    expert_idx: jax.Array,  # (B,) int32
    g: jax.Array,         # (B,) fp32 gate value per token
    k: int = 8,
    *,
    interpret: bool | None = None,
    block_v: int | None = None,
):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    K, v_pad, d = weights.shape
    B = h.shape[0]
    bv = block_v or _block_v(v_pad, d, weights.dtype.itemsize)
    n_blocks = v_pad // bv
    grid = (B, n_blocks)

    # Per-expert and per-token rows ride a unit middle axis so every block's
    # last two dims are whole-array dims or (8, 128)-aligned (Mosaic tiling).
    vals, idxs = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bv, d), lambda b, j, eidx: (eidx[b], j, 0)),
                pl.BlockSpec((1, 1, bv), lambda b, j, eidx: (eidx[b], 0, j)),
                pl.BlockSpec((1, 1, d), lambda b, j, eidx: (b, 0, 0)),
                pl.BlockSpec((1, 1, 1), lambda b, j, eidx: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, k), lambda b, j, eidx: (b, j, 0, 0)),
                pl.BlockSpec((1, 1, 1, k), lambda b, j, eidx: (b, j, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, n_blocks, 1, k), jnp.float32),
            jax.ShapeDtypeStruct((B, n_blocks, 1, k), jnp.int32),
        ],
        interpret=interpret,
    )(expert_idx, weights, ids[:, None, :], h[:, None, :],
      g.astype(jnp.float32)[:, None, None])

    # exact global top-k from the per-block candidates
    cand_v = vals.reshape(B, n_blocks * k)
    cand_i = idxs.reshape(B, n_blocks * k)
    out_v, pos = jax.lax.top_k(cand_v, k)
    out_i = jnp.take_along_axis(cand_i, pos, axis=1)
    return out_v, out_i
