"""Output heads: full softmax (baseline) vs DS-Softmax (the paper).

A head is a pytree under ``params['head']`` plus (for DS) a non-trainable
``DSState`` mask. Both heads expose the same two operations:

* ``head_loss``  — mean CE over (B, S) positions + aux-loss dict;
* ``head_topk``  — top-k class retrieval from final hidden states (serving).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import dssoftmax as ds
from repro.models.layers import dense_init


def init_head(key, cfg: ModelConfig):
    if cfg.head == "ds":
        params, state = ds.init(
            key, cfg.d_model, cfg.padded_vocab, cfg.ds, dtype=cfg.jdtype,
            n_valid=cfg.vocab_size,
        )
        return params, state
    if cfg.tie_embeddings:
        return {}, None
    return {"unembed": dense_init(key, (cfg.padded_vocab, cfg.d_model), cfg.jdtype)}, None


def _full_ce(w, h, labels, label_mask):
    """Vocab-parallel CE. w: (N, d); h: (B,S,d); labels: (B,S).

    The gold logit is h·w[labels] (a row gather from the vocab-sharded
    table — the same op as the input embedding lookup), NOT
    ``take_along_axis`` on the logits, which would all-gather the full
    (B,S,N) tensor across the model axis.
    """
    from repro.distributed.hints import BATCH, constrain, constrain_batch

    h = constrain_batch(h)
    B, S, _ = h.shape

    # Streaming CE over sequence chunks (one chunk's (B,cc,N) fp32 logits
    # live at a time; backward recomputes under jax.checkpoint).
    def ce_chunk(_, inp):
        h_i, lab_i = inp  # (B,cc,d), (B,cc)
        z = jnp.einsum("bsd,nd->bsn", h_i, w, preferred_element_type=jnp.float32)
        z = constrain(z, BATCH, None, "model")
        lse = jax.nn.logsumexp(z, axis=-1)
        w_gold = jnp.take(w, lab_i, axis=0)  # (B,cc,d)
        gold = jnp.einsum("bsd,bsd->bs", h_i.astype(jnp.float32), w_gold.astype(jnp.float32))
        return (), lse - gold

    n_chunks = 1
    for cand in (8, 4, 2):
        if S % cand == 0 and S // cand >= 8:
            n_chunks = cand
            break
    if n_chunks > 1:
        cc = S // n_chunks
        h_c = jnp.moveaxis(h.reshape(B, n_chunks, cc, -1), 1, 0)
        l_c = jnp.moveaxis(labels.reshape(B, n_chunks, cc), 1, 0)
        _, ce_c = jax.lax.scan(jax.checkpoint(ce_chunk), (), (h_c, l_c))
        ce = jnp.moveaxis(ce_c, 0, 1).reshape(B, S)
    else:
        _, ce = ce_chunk((), (h, labels))
    if label_mask is not None:
        m = label_mask.astype(jnp.float32)
        return jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(ce)


def head_loss(
    head_params,
    ds_state,
    cfg: ModelConfig,
    h: jax.Array,
    labels: jax.Array,
    embed_table: Optional[jax.Array] = None,
    label_mask: Optional[jax.Array] = None,
):
    """→ (task_ce, aux_losses_dict). h: (B, S, d)."""
    if cfg.head == "ds":
        ce, aux = ds.loss_rows(
            head_params, ds_state, h, labels, cfg.ds, label_mask=label_mask
        )
        dcfg = cfg.ds
        aux_total = (
            dcfg.lambda_lasso * aux.lasso
            + dcfg.lambda_expert * aux.expert_lasso
            + dcfg.lambda_load * aux.load
        )
        return ce, {
            "ds_lasso": aux.lasso,
            "ds_expert_lasso": aux.expert_lasso,
            "ds_load": aux.load,
            "ds_drop_frac": aux.drop_frac,
            "head_aux_total": aux_total,
        }
    w = embed_table if cfg.tie_embeddings else head_params["unembed"]
    ce = _full_ce(w, h, labels, label_mask)
    return ce, {"head_aux_total": jnp.zeros((), jnp.float32)}


@jax.named_scope("ds_head")
def head_topk(
    head_params,
    serve_table,
    cfg: ModelConfig,
    h: jax.Array,
    k: int,
    embed_table: Optional[jax.Array] = None,
    kernel=None,
    mesh=None,
    gather=None,
    capacity_factor: Optional[float] = None,
    with_stats: bool = False,
):
    """Top-k classes from hidden states h (B, d) → (values, ids) (B, k).

    ``kernel`` (a registered name, policy name, or KernelPolicy) overrides
    ``cfg.ds.serve_kernel``; ``None`` uses the config value ('auto' by
    default — per-call-site selection from static shapes). ``mesh`` routes
    the DS head through the expert-parallel ``serve_topk_sharded`` (experts
    over the mesh's ``model`` axis, O(B·k) cross-device merge). ``gather``
    (a :class:`~repro.distributed.sharding.ServeParamGather`) marks the
    head weights as FSDP-stored: the tiny (K, d) DS gate is gathered here,
    just in time (the expert rows already live in ``serve_table``); for
    full-softmax heads the whole (V, d) matmul operand is gathered — the
    documented wire cost of serving a non-DS head from FSDP storage.
    ``capacity_factor`` overrides ``cfg.ds.capacity_factor`` (the serving
    circuit-breaker bumps the effective capacity when overflow stops being
    rare); ``with_stats=True`` appends the O(K) per-expert
    ``{'dispatched', 'overflow'}`` telemetry dict (zeros, shape (1,), for
    non-DS heads — a full softmax has no capacity to overflow).

    ``serve_table`` may be a raw packed
    :class:`~repro.core.dssoftmax.ServeTable` or a versioned
    ``repro.serve.table_manager.TableResource`` — the single unwrap here
    (``ds.as_serve_table``) resolves the resource's CURRENT version at
    trace time, so every family's ``decode_step``/``prefill_chunk``
    accepts a swappable resource unchanged and a wrapper rebuilt after
    ``ServeSession.swap_table`` prices the new ``(K, V_pad)``.
    """
    serve_table = ds.as_serve_table(serve_table)
    if gather is not None:
        if cfg.head == "ds":
            # only the tiny (K, d) gate is consumed — the expert rows live
            # in ``serve_table``; gathering the whole head subtree would
            # drag the packed-away (K, V, d) experts leaf across the wire
            head_params = dict(
                head_params, gate=gather.full("head/gate", head_params["gate"])
            )
        else:
            head_params = gather.full("head", head_params)
            if cfg.tie_embeddings and embed_table is not None:
                embed_table = gather.full("embed/table", embed_table)
    if cfg.head == "ds":
        kern = kernel if kernel is not None else cfg.ds.serve_kernel
        cf = capacity_factor if capacity_factor is not None \
            else cfg.ds.capacity_factor
        if mesh is not None:
            return ds.serve_topk_sharded(
                head_params["gate"], serve_table, h, k, mesh=mesh,
                kernel=kern, capacity_factor=cf, with_stats=with_stats,
            )
        return ds.serve_topk(
            head_params["gate"], serve_table, h, k, kernel=kern,
            capacity_factor=cf, with_stats=with_stats,
        )
    w = embed_table if cfg.tie_embeddings else head_params["unembed"]
    z = jnp.einsum("bd,nd->bn", h.astype(jnp.float32), w.astype(jnp.float32))
    if w.shape[0] > cfg.vocab_size:  # mask TP-padding classes
        z = jnp.where(jnp.arange(w.shape[0])[None, :] < cfg.vocab_size, z, -1e9)
    vals, ids = jax.lax.top_k(z, k)
    if not with_stats:
        return vals, ids
    zero = jnp.zeros((1,), jnp.int32)
    return vals, ids, {"dispatched": zero, "overflow": zero}


def abstract_serve_table(cfg: ModelConfig, quantize: str | None = None):
    """ShapeDtypeStruct serve table for the dry-run (no trained mask yet).

    V_pad defaults to 2·N/K rounded to 128 — the paper's observed ~2× mean
    redundancy (Fig. 5b) spread over K experts. ``quantize='int8'``
    returns the :class:`~repro.core.dssoftmax.QuantizedServeTable`
    shapes (int8 rows + fp32 per-row scales, no fallback experts) so
    dry-run memory estimates price the quantized deployment.
    """
    K = cfg.ds.num_experts
    v_pad = cfg.ds.serve_pad or ds._round_up(max(128, 2 * cfg.padded_vocab // K))
    ids = jax.ShapeDtypeStruct((K, v_pad), jnp.int32)
    if quantize == "int8":
        return ds.QuantizedServeTable(
            ids=ids,
            qweights=jax.ShapeDtypeStruct((K, v_pad, cfg.d_model), jnp.int8),
            scales=jax.ShapeDtypeStruct((K, v_pad), jnp.float32),
            fb_index=jax.ShapeDtypeStruct((K,), jnp.int32),
            fb_weights=jax.ShapeDtypeStruct((0, v_pad, cfg.d_model),
                                            cfg.jdtype),
        )
    if quantize is not None:
        raise ValueError(f"quantize must be None or 'int8', got {quantize!r}")
    return ds.ServeTable(
        ids=ids,
        weights=jax.ShapeDtypeStruct((K, v_pad, cfg.d_model), cfg.jdtype),
    )
