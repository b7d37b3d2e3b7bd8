"""Jit'd public wrappers over the Pallas kernels (the API models call).

Serve-path selection does not import these wrappers directly: the serve
kernels (including the two Pallas paths here) are described by
``KernelSpec`` entries in ``repro.kernels.registry`` — capabilities,
backend support, and the bytes-moved cost model that ``AutoPolicy`` uses
to pick a path per call site. ``core.dssoftmax.serve_topk`` resolves the
name through that registry and only then dispatches into these wrappers.
"""
from __future__ import annotations

from repro.kernels.dss_topk import dss_topk as _dss_topk_kernel
from repro.kernels.dss_topk_fused import dss_topk_fused as _dss_topk_fused_kernel
from repro.kernels.dss_topk_grouped import dss_topk_grouped as _dss_topk_grouped_kernel
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gate_top1 import gate_top1
from repro.kernels.lasso_prune import lasso_prune


def dss_topk(weights, ids, h, expert_idx, g, k: int = 8, **kw):
    """Serve-path fused top-k (per-token streaming kernel). Matches
    core.dssoftmax.serve_topk semantics: the fp32 gate value scales the
    fp32 logits after the matmul (z = g·(W h))."""
    return _dss_topk_kernel(weights, ids, h, expert_idx, g, k, **kw)


def dss_topk_grouped(weights, ids, buf, g_buf, k: int = 8, *, scales=None, **kw):
    """Expert-grouped streaming serve top-k. ``buf`` (K, C, d) holds the
    tokens already dispatched to their top-1 expert (core.dssoftmax builds
    it with ``dispatch_indices``); ``g_buf`` (K, C) the fp32 gate value per
    slot. Returns (vals, ids) in the grouped (K, C, k) layout — only O(B·k)
    bytes reach HBM, with the top-k carried in VMEM across vocab blocks.
    int8 ``weights`` dequantize in-register via the per-row ``scales``."""
    return _dss_topk_grouped_kernel(weights, ids, buf, g_buf, k,
                                    scales=scales, **kw)


def dss_topk_fused(gate_w, weights, ids, h, k: int = 8, *, scales=None,
                   e_base=None, **kw):
    """Single-launch gate→dispatch→retrieve serve top-k: gating and top-1
    dispatch run in the kernel prologue (no XLA pre-pass, no dispatch
    indices in HBM). Returns (vals (B, k), ids (B, k), expert_idx (B,))
    with the GLOBAL top-1 expert per token; sharded callers pass
    ``e_base`` so the local ``weights`` slice masks foreign tokens."""
    return _dss_topk_fused_kernel(gate_w, weights, ids, h, k, scales=scales,
                                  e_base=e_base, **kw)


__all__ = [
    "dss_topk",
    "dss_topk_fused",
    "dss_topk_grouped",
    "flash_attention",
    "gate_top1",
    "lasso_prune",
]
