"""The DS-head Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) checks neither Mosaic's block
tiling nor its VMEM budget. Here each kernel is compiled, from shapes
alone, for one chip of a described (not attached) ``v5e:2x2`` topology at
real head shapes, and must lower to a Mosaic kernel (``tpu_custom_call``).
The topology is described inside a fixture, never at import time: only
one process may load the TPU compiler library, and the tests run under
several pytest workers.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dss_topk import dss_topk
from repro.kernels.dss_topk_fused import dss_topk_fused
from repro.kernels.dss_topk_grouped import dss_topk_grouped

K_TOP = 8
HEADS = {  # (K experts, V_pad packed rows per expert, d)
    "mamba2-130m": (8, 50304, 768),
    "qwen2-1.5b": (16, 151936, 1536),
}
BATCHES = {"decode": 8, "prefill": 64}
KERNELS = [  # (kernel, table dtype); the legacy per-token kernel is fp only
    ("pallas", "bfloat16"),
    ("pallas_grouped", "bfloat16"),
    ("pallas_grouped", "int8"),
    ("pallas_fused", "bfloat16"),
    ("pallas_fused", "int8"),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _lower(one_chip, kernel, wdtype, K, V, d, B):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    w = s((K, V, d), jnp.dtype(wdtype))
    ids = s((K, V), jnp.int32)
    scales = s((K, V), jnp.float32) if wdtype == "int8" else None
    h = s((B, d), jnp.bfloat16)
    if kernel == "pallas":
        fn = lambda w, ids, h, e, g: dss_topk(w, ids, h, e, g, K_TOP,
                                              interpret=False)
        args = (w, ids, h, s((B,), jnp.int32), s((B,), jnp.float32))
    elif kernel == "pallas_grouped":
        C = max(1, round(B / K * 2.0))  # serve_topk's default capacity
        fn = lambda w, ids, buf, gb, sc: dss_topk_grouped(
            w, ids, buf, gb, K_TOP, scales=sc, interpret=False)
        args = (w, ids, s((K, C, d), jnp.bfloat16), s((K, C), jnp.float32),
                scales)
    else:
        fn = lambda gate, w, ids, h, sc: dss_topk_fused(
            gate, w, ids, h, K_TOP, scales=sc, interpret=False)
        args = (s((K, d), jnp.bfloat16), w, ids, h, scales)
    return jax.jit(fn).lower(*args)


@pytest.mark.parametrize("phase", sorted(BATCHES))
@pytest.mark.parametrize("head", sorted(HEADS))
@pytest.mark.parametrize("kernel,wdtype", KERNELS)
def test_ds_kernel_compiles_for_v5e(one_chip, kernel, wdtype, head, phase):
    K, V, d = HEADS[head]
    compiled = _lower(one_chip, kernel, wdtype, K, V, d,
                      BATCHES[phase]).compile()
    assert "tpu_custom_call" in compiled.as_text()
