"""Logical→physical sharding rules (DP / FSDP / TP / EP / SP).

Rules are applied by pytree-path regex over the parameter tree, so every
model family gets consistent sharding without per-model boilerplate:

* vocab-carrying tables (embed, unembed, DS expert rows) → ``model`` (TP),
  second dim → ``data`` (FSDP storage sharding);
* attention/MLP weights → (d_model → ``data``, heads/ff → ``model``);
* MoE expert stacks → (experts → ``model`` [EP], d_model → ``data``);
* per-head vectors / norm scales / small biases → replicated;
* batch dims of activations → (``pod``, ``data``); KV-cache sequence dim →
  ``model`` (flash-decode style split-KV) — cache batching already covers
  ``data``; for batch=1 long-context cells the batch axis is unsharded and
  the sequence picks up both axes.

``data_axes``/``model_axes`` adapt automatically to 2-D (data, model) and
3-D (pod, data, model) production meshes.
"""
from __future__ import annotations

import re
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.utils.tree import map_with_path


def batch_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_size_on(mesh: Mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

_RULES: list[tuple[str, tuple]] = [
    # --- DS-Softmax head (the paper): experts (K, N, d) vocab-TP + FSDP ---
    (r"head/experts$", (None, "model", "data")),
    (r"head/gate$", (None, "data")),
    # --- embeddings: (V, d) ---
    (r"embed/table$", ("model", "data")),
    (r"head/unembed$", ("model", "data")),
    (r"pos_embed$", (None, "data")),
    # --- attention (leading L axis handled generically below) ---
    (r"attn/wq$", ("data", "model")),
    (r"attn/wk$", ("data", "model")),
    (r"attn/wv$", ("data", "model")),
    (r"attn/wo$", ("model", "data")),
    (r"attn/b[qkv]$", ("model",)),
    # --- dense MLP ---
    (r"mlp/w_gate$", ("data", "model")),
    (r"mlp/w_up$", ("data", "model")),
    (r"mlp/w_down$", ("model", "data")),
    # --- MoE (E, d, ff): EP over model, FSDP over data ---
    (r"moe/router$", ("data", None)),
    (r"moe/w_gate$", ("model", "data", None)),
    (r"moe/w_up$", ("model", "data", None)),
    (r"moe/w_down$", ("model", None, "data")),
    # --- mamba2 ---
    (r"mamba/in_zx$", ("data", "model")),
    (r"mamba/in_bc$", ("data", "model")),
    (r"mamba/in_dt$", ("data", None)),
    (r"mamba/out_proj$", ("model", "data")),
    (r"mamba/conv_w$", (None, "model")),
    (r"mamba/conv_b$", ("model",)),
    # small per-head vectors & norms: replicated
    (r"mamba/(A_log|dt_bias|D)$", ()),
    (r"(ln\d?|ln_x|norm|final_norm|enc_norm|dec_norm)/(scale|bias)$", ()),
    (r"norm_scale$", ()),
]

_STACKED_RE = re.compile(r"(^|/)(layers|enc_layers|dec_layers)/")


def param_pspec(path: str, ndim: int) -> P:
    """PartitionSpec for one parameter leaf given its slash path."""
    stacked = bool(_STACKED_RE.search(path))
    for pat, axes in _RULES:
        if re.search(pat, path):
            spec = tuple(axes)
            if stacked:
                spec = (None,) + spec
            # pad/trim to ndim
            spec = spec[:ndim] + (None,) * max(0, ndim - len(spec))
            return P(*spec)
    # default: replicate (correct but wasteful — rules should cover all big leaves)
    return P(*((None,) * ndim))


def _fitted_pspec(path: str, shape: tuple, mesh: Mesh,
                  keep_axes: Optional[tuple] = None) -> P:
    """:func:`param_pspec` validated against the ACTUAL leaf shape.

    Per dim, a rule axis survives only if it exists on the mesh, is wider
    than 1 (a size-1 axis is replication GSPMD would canonicalize away,
    breaking pinned-sharding round-trips), and divides the dim exactly —
    otherwise that dim falls back to replicated, so the resulting
    ``NamedSharding`` is always valid at ``jax.device_put`` time.
    ``keep_axes`` additionally restricts which mesh axes may be used
    (serving FSDP storage: ``('data',)`` only — the ``model`` axis belongs
    to the expert-parallel table).
    """
    spec = param_pspec(path, len(shape))
    out = []
    for dim, ax in zip(shape, spec):
        axes = ax if isinstance(ax, tuple) else (ax,)
        axes = tuple(
            a for a in axes
            if a is not None and a in mesh.axis_names
            and (keep_axes is None or a in keep_axes)
            and mesh.shape[a] > 1
        )
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if not axes or dim % n != 0:
            out.append(None)
        else:
            out.append(axes if len(axes) > 1 else axes[0])
    return P(*out)


def param_shardings(mesh: Mesh, params: Any) -> Any:
    """NamedSharding tree matching ``params`` (works on ShapeDtypeStructs).

    Rule axes that don't divide a leaf's dim (or are absent from the mesh)
    fall back to replication for that dim, never an error at placement
    time."""

    def leaf(path, x):
        return NamedSharding(mesh, _fitted_pspec(path, tuple(x.shape), mesh))

    return map_with_path(leaf, params)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def constrain_like_params(tree: Any) -> Any:
    """Pin a params-shaped tree (e.g. gradients) to the parameter sharding
    rules. No-op outside a mesh context. Applied to grads before the
    optimizer so backward scatter-adds (embedding/expert tables) don't come
    out replicated."""
    from repro.distributed.hints import _active_mesh

    mesh = _active_mesh()
    if mesh is None:
        return tree

    def leaf(path, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, _fitted_pspec(path, tuple(x.shape), mesh))
        )

    return map_with_path(leaf, tree)


# ---------------------------------------------------------------------------
# Activation / batch / cache rules
# ---------------------------------------------------------------------------

def batch_pspec(mesh: Mesh, global_batch: int, extra_dims: int = 1) -> P:
    """Shard the leading batch dim over (pod, data) when divisible."""
    ba = batch_axes(mesh)
    if global_batch % max(1, batch_size_on(mesh)) != 0 or global_batch < batch_size_on(mesh):
        ba = ()
    return P(ba if ba else None, *((None,) * extra_dims))


def input_shardings(mesh: Mesh, cfg: ModelConfig, specs: dict, shape: ShapeConfig) -> dict:
    out = {}
    for k, v in specs.items():
        nd = len(v.shape)
        out[k] = NamedSharding(mesh, batch_pspec(mesh, shape.global_batch, nd - 1))
    return out


def cache_shardings(mesh: Mesh, cfg: ModelConfig, cache: Any, shape: ShapeConfig) -> Any:
    """Decode caches: (L, B, S, KV, dh) → B→(pod,data), S→model (split-KV).
    SSM states: (L, B, H, P, N) → B→(pod,data), H→model when divisible."""
    ba = batch_axes(mesh)
    b_ok = shape.global_batch % batch_size_on(mesh) == 0
    b_ax = ba if (ba and b_ok) else None
    m = mesh.shape.get("model", 1)

    def leaf(path, x):
        nd = len(x.shape)
        if nd == 5 and ("attn" in path or "self_k" in path or "self_v" in path
                        or "cross" in path or path.endswith("k") or path.endswith("v")):
            # (L|napps, B, S, KV, dh): sequence → model
            s_ax = "model" if x.shape[2] % m == 0 else None
            if not b_ok and x.shape[2] % (batch_size_on(mesh) * m) == 0:
                s_ax = tuple(list(ba) + ["model"])  # B=1 long-context: SP over all axes
            return NamedSharding(mesh, P(None, b_ax, s_ax, None, None))
        if nd == 5:  # ssm state (L, B, H, P, N)
            h_ax = "model" if x.shape[2] % m == 0 else None
            return NamedSharding(mesh, P(None, b_ax, h_ax, None, None))
        if nd == 4:  # conv state (L, B, W-1, conv_dim)
            c_ax = "model" if x.shape[3] % m == 0 else None
            return NamedSharding(mesh, P(None, b_ax, None, c_ax))
        return NamedSharding(mesh, P(*((None,) * nd)))

    return map_with_path(leaf, cache)


def serve_table_shardings(mesh: Mesh, table) -> Any:
    """ServeTable: ids (K, V_pad) + weights (K, V_pad, d): V_pad → model.

    This is the TRAIN-style vocab-TP layout (dry-run memory estimates).
    The expert-parallel serving path uses :func:`serve_table_ep_shardings`.
    Quantized tables shard qweights/scales like weights/ids; the (small)
    fallback rows stay replicated.
    """
    if hasattr(table, "qweights"):
        return type(table)(
            ids=NamedSharding(mesh, P(None, "model")),
            qweights=NamedSharding(mesh, P(None, "model", "data")),
            scales=NamedSharding(mesh, P(None, "model")),
            fb_index=NamedSharding(mesh, P(None)),
            fb_weights=NamedSharding(mesh, P(None, None, None)),
        )
    return type(table)(
        ids=NamedSharding(mesh, P(None, "model")),
        weights=NamedSharding(mesh, P(None, "model", "data")),
    )


def serve_table_ep_shardings(mesh: Mesh, table) -> Any:
    """Expert-parallel serving layout: experts K → model (each device
    stores K/ep experts' packed rows — the serve analogue of the MoE EP
    rule above); replicated over the batch axes, which shard tokens at
    call time. K must already divide the model axis
    (``core.dssoftmax.shard_table`` pads it). The specs are
    shape-agnostic over K and V_pad, so the same rule re-places every
    hot-swapped table ``ServeSession.swap_table`` pushes through
    ``shard_table`` — swaps never need new sharding plumbing.

    Quantized tables: the int8 rows + per-row scales follow the expert
    axis; ``fb_weights`` is REPLICATED (``fb_index`` values are global
    rows into it, and it holds at most a few experts' fp rows)."""
    if hasattr(table, "qweights"):
        return type(table)(
            ids=NamedSharding(mesh, P("model", None)),
            qweights=NamedSharding(mesh, P("model", None, None)),
            scales=NamedSharding(mesh, P("model", None)),
            fb_index=NamedSharding(mesh, P("model")),
            fb_weights=NamedSharding(mesh, P(None, None, None)),
        )
    return type(table)(
        ids=NamedSharding(mesh, P("model", None)),
        weights=NamedSharding(mesh, P("model", None, None)),
    )


def serve_cache_shardings(mesh: Mesh, cfg: ModelConfig, cache: Any,
                          n_slots: int) -> Any:
    """Serving decode caches: ONLY the slot (batch) axis is sharded, over
    the (pod, data) axes. Unlike :func:`cache_shardings` (train dry-run:
    split-KV over model), the sequence axis stays whole per device so
    per-slot decode math is bit-identical to the single-device session —
    the model axis' job in serving is the expert-sharded head."""
    # Canonical specs only (no size-1 axes, single names unwrapped, P()
    # when fully replicated): the session pins the cache to this sharding
    # every step, and a spec that GSPMD would rewrite (e.g. ('data',) on a
    # 1-wide axis → P()) costs one spurious decode recompile.
    ba = tuple(a for a in batch_axes(mesh) if mesh.shape[a] > 1)
    nb = batch_size_on(mesh)
    b_ok = ba and nb > 1 and n_slots % nb == 0
    b_ax = (ba[0] if len(ba) == 1 else ba) if b_ok else None

    def leaf(path, x):
        if b_ax is None or len(x.shape) < 2:
            return NamedSharding(mesh, P())
        # trailing Nones trimmed: GSPMD reports P(None, 'data'), and the
        # pinned spec must round-trip exactly
        return NamedSharding(mesh, P(None, b_ax))

    return map_with_path(leaf, cache)


def serve_paged_cache_shardings(mesh: Mesh, cfg: ModelConfig,
                                cache: Any) -> Any:
    """Paged serving arenas: the PAGE axis (position 1 of every leaf —
    where :func:`serve_cache_shardings` shards the slot axis) is sharded
    over the (pod, data) axes. Pages are interchangeable, so any page
    count divisible by the batch-axis width shards; a leaf whose page
    axis the mesh doesn't divide (e.g. a state arena sized differently
    from the KV arena) falls back to replicated per the house
    divisible-or-replicated rule. Canonical specs only — the session
    pins the arena to this sharding every step, so a spec GSPMD would
    rewrite costs a spurious decode recompile."""
    ba = tuple(a for a in batch_axes(mesh) if mesh.shape[a] > 1)
    nb = batch_size_on(mesh)

    def leaf(path, x):
        if not ba or nb <= 1 or len(x.shape) < 2 or x.shape[1] % nb != 0 \
                or x.shape[1] == 0:
            return NamedSharding(mesh, P())
        b_ax = ba[0] if len(ba) == 1 else ba
        return NamedSharding(mesh, P(None, b_ax))

    return map_with_path(leaf, cache)


def topk_out_shardings(mesh: Mesh, global_batch: int):
    b = batch_pspec(mesh, global_batch, 1)
    return NamedSharding(mesh, b)


# ---------------------------------------------------------------------------
# Serving-side FSDP parameter storage + per-layer just-in-time gather
# ---------------------------------------------------------------------------

def serve_param_pspec(path: str, shape: tuple, mesh: Mesh) -> P:
    """FSDP *storage* spec for serving weights: the ``data`` axis only.

    Serving compute is replicated over ``data`` (every device steps every
    resident slot's backbone math bit-identically after the per-layer
    gather), so the ``data`` axis is pure storage capacity — each leaf
    keeps the ``data`` entries of its train rule and drops ``model``
    (reserved for the expert-parallel :class:`ServeTable`) and ``pod``.
    Dims the data axis doesn't divide fall back to replicated, so the
    sharding is always valid at ``jax.device_put`` time.
    """
    return _fitted_pspec(path, tuple(shape), mesh, keep_axes=("data",))


def serve_param_shardings(mesh: Mesh, params: Any) -> Any:
    """NamedSharding tree for FSDP-stored serving weights (works on
    ShapeDtypeStructs): per-device resident bytes drop ~``ndata``× on the
    sharded leaves; :class:`ServeParamGather` reconstructs full layers
    just in time inside the decode/prefill step.

    The tree is PATH-keyed, not shape-keyed: the ``head/gate`` rule
    shards the (K, d) gate as ``(None, 'data')`` regardless of K, so a
    gate with a different expert count (``ServeSession.swap_table``
    after mitosis/pruning) is placed with the spec built at init — no
    re-derivation on swap."""

    def leaf(path, x):
        return NamedSharding(mesh, serve_param_pspec(path, tuple(x.shape), mesh))

    return map_with_path(leaf, params)


class ServeParamGather:
    """Per-layer just-in-time all-gather of FSDP-stored serving weights.

    Params live sharded over the mesh's ``data`` axis
    (:func:`serve_param_shardings`); model code calls back into this
    object to materialize exactly the weights it is about to consume:

    * ``layer(key, lp)``  — one scanned layer's slice, gathered inside the
      ``lax.scan`` body, so the full copy of layer *i* exists only while
      layer *i* runs (XLA's scheduler overlaps the loop-body collective
      with layer *i-1*'s compute — the gathered stack is never resident
      at once);
    * ``full(key, sub)``  — a non-stacked subtree (head gate, hybrid's
      shared attention block), gathered at its single use site;
    * ``rows(key, table, ids)`` — row lookup from a d-sharded ``(N, d)``
      table (embeddings / learned positions): each shard takes its d-slice
      of the rows and only the O(rows·d) activation crosses the wire —
      the full table is NEVER materialized.

    Wire-cost model per decode/prefill step: ``Σ_sharded-leaves
    (1 - 1/ndata)·bytes(leaf)`` over the data axis — the same bytes a
    replicated store would read from local HBM, traded for O(params/ndata)
    resident footprint. Every gather is ``tiled`` concatenation along the
    stored dim, so reconstructed weights are bit-identical and serving
    outputs match the replicated session token-for-token.
    """

    def __init__(self, mesh: Mesh, params: Any):
        from repro.utils.tree import tree_paths

        self.mesh = mesh
        flat, _ = jax.tree_util.tree_flatten(params)
        self._spec = {
            p: serve_param_pspec(p, tuple(x.shape), mesh)
            for p, x in zip(tree_paths(params), flat)
        }

    # -- internals ----------------------------------------------------------

    def _specs_for(self, prefix: str, tree: Any, drop_leading: bool):
        from repro.utils.tree import tree_paths

        paths = tree_paths(tree)
        specs = []
        for p in paths:
            full_path = f"{prefix}/{p}" if p else prefix
            s = self._spec[full_path]
            if drop_leading:
                s = P(*tuple(s)[1:])
            specs.append(s)
        return specs

    def _gather(self, prefix: str, tree: Any, drop_leading: bool) -> Any:
        specs = self._specs_for(prefix, tree, drop_leading)
        if all(all(ax is None for ax in s) for s in specs):
            return tree  # fully replicated (trivial data axis / small leaves)
        flat, treedef = jax.tree_util.tree_flatten(tree)

        def inner(*leaves):
            out = []
            for x, s in zip(leaves, specs):
                for dim, ax in enumerate(s):
                    if ax is None:
                        continue
                    for a in (ax if isinstance(ax, tuple) else (ax,)):
                        x = jax.lax.all_gather(x, a, axis=dim, tiled=True)
                out.append(x)
            return tuple(out)

        out = jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=tuple(specs),
            out_specs=tuple(P(*([None] * len(s))) for s in specs),
            check_vma=False,
        )(*flat)
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- model-facing API ----------------------------------------------------

    def layer(self, key: str, layer_params: Any) -> Any:
        """Gather ONE scanned layer's slice of the stacked ``params[key]``
        collection (leading layer axis already stripped by the scan)."""
        return self._gather(key, layer_params, drop_leading=True)

    def full(self, key: str, sub: Any) -> Any:
        """Gather a non-stacked subtree/leaf ``params[key]`` whole (head
        gate, shared attention block — one layer's worth of weights)."""
        return self._gather(key, sub, drop_leading=False)

    def rows(self, key: str, table: jax.Array, ids: jax.Array) -> jax.Array:
        """``table[ids]`` from a ``(N, d)`` table stored d-sharded: local
        take + one O(ids·d) all-gather of the *activation* rows."""
        path = key if key in self._spec else f"{key}/table"
        spec = self._spec[path]
        d_ax = tuple(spec)[-1]
        if any(ax is not None for ax in tuple(spec)[:-1]):
            # row axis sharded (no serving rule does this): a local take
            # with global ids would be wrong — gather the table whole.
            return jnp.take(self._gather(path, table, False), ids, axis=0)
        if d_ax is None:
            return jnp.take(table, ids, axis=0)

        def inner(tbl, tok):
            rows = jnp.take(tbl, tok, axis=0)
            return jax.lax.all_gather(rows, d_ax, axis=-1, tiled=True)

        return jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=(spec, P(*([None] * ids.ndim))),
            out_specs=P(*([None] * (ids.ndim + 1))),
            check_vma=False,
        )(table, ids)


def tree_shard_bytes(tree: Any) -> int:
    """Per-device resident bytes of a committed pytree (each leaf counted
    at its addressable shard shape — the FSDP memory-ceiling metric)."""
    total = 0
    for x in jax.tree_util.tree_leaves(tree):
        shape = tuple(x.shape)
        if getattr(x, "sharding", None) is not None:
            shape = x.sharding.shard_shape(shape)
        total += int(np.prod(shape)) * jnp.dtype(x.dtype).itemsize
    return total
