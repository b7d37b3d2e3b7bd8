"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod 16×16 (256 chips) or 2-pod 2×16×16 (512 chips) v5e mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic reshapes)."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(axes)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=axis_types)


def make_host_mesh():
    """Whatever devices exist locally as a 1×N (data, model) mesh."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model"))


def parse_mesh(spec: str):
    """``--mesh DxM`` (or ``PxDxM``) → a (data, model) mesh over the first
    D·M local devices (expert-parallel serving: tokens/slots shard over
    ``data``, the packed expert table over ``model``).

    Unlike :func:`make_mesh` this accepts a PREFIX of the local devices,
    so ``--mesh 1x2`` works on an 8-device host (benchmark sweeps build
    1/2/4/8-way meshes in one process).
    """
    import numpy as np
    from jax.sharding import Mesh

    dims = tuple(int(x) for x in spec.lower().split("x"))
    if len(dims) == 2:
        axes = ("data", "model")
    elif len(dims) == 3:
        axes = ("pod", "data", "model")
    else:
        raise ValueError(
            f"--mesh expects DxM or PxDxM (e.g. 2x4), got {spec!r}"
        )
    n = 1
    for d in dims:
        n *= d
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"--mesh {spec} needs {n} devices, only {len(devices)} present "
            "(CPU hosts: XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    return Mesh(np.asarray(devices[:n]).reshape(dims), axes)
