"""In-model sharding hints that degrade to no-ops off-mesh.

Model code calls ``constrain(x, None, "model", ...)`` to pin an
intermediate's layout. The active mesh is the one ``jax.set_mesh`` set;
outside one (single-device serving, CPU smoke tests) the call is a no-op.
Axes absent from the mesh, and axes a ``shard_map`` body has already
split (``Manual``), are dropped. A constraint that still fails raises.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def _active_mesh():
    """The abstract mesh set by ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _auto_axes(mesh) -> set:
    """Mesh axes a sharding constraint may name: inside a ``shard_map``
    body the mapped axes are ``Manual`` and already split."""
    return {a for a, t in zip(mesh.axis_names, mesh.axis_types)
            if t != AxisType.Manual}


REP = "rep"  # sentinel: force this dim replicated (None = leave unconstrained)
BATCH = ("pod", "data")  # logical batch axes (filtered to the active mesh)


def constrain_batch(x, batch_dim: int = 0):
    """Pin the batch dim of an activation to the (pod, data) axes.

    The canonical guard against GSPMD propagating a weight's FSDP sharding
    into the residual stream (observed: batch replicated + d_model→data,
    16× activation bloat)."""
    spec = [None] * x.ndim
    spec[batch_dim] = BATCH
    return constrain(x, *spec)


def constrain_residual(x):
    """Residual-stream (B, S, d) boundary sharding: batch→(pod, data) AND
    sequence→model (Megatron-style sequence parallelism).

    The remat-saved per-layer carries dominate train memory for deep archs
    (L × B_loc × S × d); sharding S over the otherwise-idle model axis cuts
    them 16× for one all-gather per layer entry."""
    if x.ndim != 3 or x.shape[1] < 2:
        return constrain_batch(x)
    return constrain(x, BATCH, "model", None)


def constrain(x, *spec):
    """with_sharding_constraint if a mesh is active; identity otherwise.

    ``None`` entries are UNCONSTRAINED (propagation decides — crucial so a
    hint on one dim doesn't silently un-shard the others); the ``REP``
    sentinel forces replication of a dim.
    """
    mesh = _active_mesh()
    names = _auto_axes(mesh) if mesh is not None else set()
    if not names:
        return x
    U = P.UNCONSTRAINED

    def ok(axis):
        if axis is None:
            return U
        if axis == REP:
            return None
        axes = axis if isinstance(axis, tuple) else (axis,)
        present = tuple(a for a in axes if a in names)
        if not present:
            return U
        return present if len(present) > 1 else present[0]

    spec2 = tuple(ok(a) for a in spec)
    spec2 = spec2[: x.ndim] + (U,) * max(0, x.ndim - len(spec2))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec2)))
