"""Plain float32 arithmetic shared by the references.

Every matrix product runs at ``Precision.HIGHEST``: on a TPU a float32
product otherwise runs in bfloat16 passes. ``fp8=True`` turns the same
code into the lower-precision control: each operand of each product is
rounded to float8 (e4m3, one absmax scale per operand) first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def to_fp8(x):
    """Round ``x`` to float8 e4m3 with one absmax scale; back in float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, b, fp8: bool = False):
    """``a @ b`` in float32 (or from float8 operands for the control)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = to_fp8(a), to_fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def einsum(spec, a, b, fp8: bool = False):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if fp8:
        a, b = to_fp8(a), to_fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def normal(key, shape, std, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
