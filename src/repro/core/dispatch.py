"""Sort-based expert dispatch indices (shared by the MoE FFN and the
DS-Softmax head — the paper's sparse mixture IS an MoE over vocabulary
shards, so both use the same machinery).

Everything here is index arithmetic on int32 vectors — the heavy payload
(activations) is moved by the caller with per-k scatters/gathers so no
(assignments × d_model) tensor is ever materialized.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dispatch_indices(e_flat: jax.Array, num_experts: int, capacity: int):
    """Assignment slots for a flat expert-id vector.

    e_flat: (A,) int — expert chosen per assignment (A = tokens·top_k).
    Returns (slot (A,) int32, valid (A,) bool): ``slot`` is the position of
    the assignment inside its expert's capacity buffer (stable order),
    ``valid`` is False where the expert overflowed ``capacity``, and for
    out-of-range ids (the ``num_experts`` sentinel a sharded caller routes
    non-owned tokens to). Every index read here is in bounds: an
    out-of-bounds gather is undefined on TPU (CPU clamps it), and an
    undefined sentinel slot can admit a non-owned token to an owned one.
    """
    A = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(num_experts, dtype=sorted_e.dtype),
                             side="left")
    e_in = jnp.clip(sorted_e, 0, num_experts - 1)
    slot_sorted = jnp.arange(A, dtype=jnp.int32) - first[e_in].astype(jnp.int32)
    slot = jnp.zeros((A,), jnp.int32).at[order].set(slot_sorted)
    valid = (slot < capacity) & (e_flat >= 0) & (e_flat < num_experts)
    return slot, valid


def dispatch_load(e_flat: jax.Array, num_experts: int,
                  valid: jax.Array | None = None):
    """Per-expert load telemetry for one dispatch.

    e_flat: (A,) int expert per assignment (out-of-range ids — e.g. the
    ``K`` sentinel a sharded caller routes non-owned tokens to — are
    dropped from both counts); valid: (A,) bool from
    :func:`dispatch_indices` (None ⇒ nothing overflowed).

    Returns (dispatched (K,), overflow (K,)) int32 — the O(K) accumulators
    the serving circuit-breaker watches: ``overflow/dispatched`` per expert
    is the fraction of that expert's tokens paying the exact-but-slow
    capacity-overflow fixup.
    """
    # out-of-range ids count into a spare bin that is sliced off
    e_bin = jnp.where((e_flat >= 0) & (e_flat < num_experts), e_flat, num_experts)
    bins = jnp.zeros((num_experts + 1,), jnp.int32)
    dispatched = bins.at[e_bin].add(1)[:num_experts]
    if valid is None:
        overflow = jnp.zeros((num_experts,), jnp.int32)
    else:
        overflow = bins.at[e_bin].add((~valid).astype(jnp.int32))[:num_experts]
    return dispatched, overflow
