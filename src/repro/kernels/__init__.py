"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel ships with a pure-jnp oracle in ``ref.py``. Off TPU the
kernels run in Pallas interpret mode, where the tests sweep shapes and
dtypes against the oracles; interpret mode checks semantics only.
``tests/test_tpu_compile.py`` compiles the DS-head kernels for a
described TPU v5e chip, and ``chip_smoke.py`` runs them on one.
"""
from repro.kernels import ops, ref, registry
from repro.kernels.dss_topk import dss_topk
from repro.kernels.dss_topk_grouped import dss_topk_grouped
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gate_top1 import gate_top1
from repro.kernels.lasso_prune import lasso_prune
from repro.kernels.registry import (
    AutoPolicy,
    FixedPolicy,
    KernelContext,
    KernelPolicy,
    KernelSpec,
    kernel_names,
)

__all__ = [
    "ops",
    "ref",
    "registry",
    "dss_topk",
    "dss_topk_grouped",
    "flash_attention",
    "gate_top1",
    "lasso_prune",
    "AutoPolicy",
    "FixedPolicy",
    "KernelContext",
    "KernelPolicy",
    "KernelSpec",
    "kernel_names",
]
