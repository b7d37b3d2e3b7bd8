"""DS-Softmax: the paper's doubly-sparse softmax layer.

Parameters (a plain pytree, shardable by path):
    gate:    U (K, d)      — sparse-mixture gating network
    experts: W (K, N, d)   — per-expert class embeddings (pruned over training)

Non-trainable state:
    mask:    (K, N) bool   — surviving classes per expert (group-lasso pruned)

Three compute paths:

* ``loss(..., dispatch='dense')`` — exact reference: computes every expert's
  logits for every token and selects via the sparse gate. O(K·T·N·d); used
  for smoke tests / small models and as the oracle for the production paths.
* ``loss(..., dispatch='sorted')`` — production: sort tokens by their top-1
  expert (the same machinery an EP MoE uses for its FFN, applied to the
  head), run one dense (C, d)x(d, N) matmul per expert, scatter the CE back.
  O(T·N·d·capacity_factor) — the K× blow-up is gone.
* ``serve_topk`` — inference: gather the chosen expert's packed active rows
  (static ``V_max`` padding for TPU) and top-k the small softmax. The Pallas
  kernel in ``repro/kernels`` fuses this gather→matmul→top-k.

All probabilities follow the paper: logits are scaled by the *un-renormalized*
top-1 gate value (inverse temperature, Eq. 2); pruned classes contribute
``exp(0)`` to the train normalizer in ``mask_mode='zero'`` (faithful — the
rows are literally zero) or are excluded via ``-inf`` in ``'neg_inf'``
(beyond-paper alignment of train and serve normalizers).

Expert-parallel sharded serving (``serve_topk_sharded``)
--------------------------------------------------------

Top-1 retrieval only ever touches the rows of the ONE expert a token
routes to, which makes the packed :class:`ServeTable` naturally shardable
by expert. :func:`shard_table` (or ``table.shard(mesh)``) pads ``K`` to a
multiple of the mesh's ``model`` axis and places ``K → model``; the
``data``/``pod`` axes shard the *token batch* (slots), never the weight
columns — an FSDP-style ``d → data`` split of the serve weights would
re-gather ``K/ep·V_pad·d`` bytes across the interconnect on every call,
destroying the O(B·k) wire bound below.

The merge protocol inside :func:`serve_topk_sharded` (one ``shard_map``
over the whole mesh):

1. **Gating replicated.** Each device computes ``top1_gate`` for its
   B/n_data token rows (the gate matrix ``U`` (K, d) is tiny and
   replicated), so every model-shard agrees on ``expert_idx``/``g``.
2. **Owner-local retrieval.** The shard owning experts
   ``[lo, lo + K/ep)`` runs the *existing* single-device kernel (any
   registered path: ``jnp`` / ``grouped`` / ``pallas_grouped``,
   unchanged) over its local table slice for the tokens it owns;
   non-owned tokens are excluded from the grouped dispatch and the
   bounded overflow fixup, and their outputs forced to (NEG_INF, -1).
3. **O(B·k) cross-device merge.** A single ``all_gather`` over ``model``
   moves only the (ep, B/n_data, k) value/id carries — never logits,
   never V_pad-sized rows — and each token selects its owner's row
   (``owner = expert_idx // (K/ep)``). Exactly one shard owns each
   token, so the merge is a pure select: outputs are token-identical
   (bit-identical ids) to the single-device oracle.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DSSoftmaxConfig
from repro.core import losses as L
from repro.core import pruning
from repro.core.gating import sparse_gate_matrix, top1_gate

NEG_INF = -1e9


class DSState(NamedTuple):
    mask: jax.Array  # (K, N) bool


class DSAux(NamedTuple):
    """Auxiliary losses + diagnostics returned by :func:`loss`."""

    lasso: jax.Array
    expert_lasso: jax.Array
    load: jax.Array
    drop_frac: jax.Array  # sorted dispatch only; 0.0 for dense
    gate_entropy: jax.Array


def init(
    key: jax.Array,
    d: int,
    n_classes: int,
    cfg: DSSoftmaxConfig,
    dtype=jnp.float32,
    n_valid: Optional[int] = None,
):
    """Initialize params + state. Experts start as full softmaxes (paper).

    ``n_classes`` may be TP-padded; columns ≥ ``n_valid`` start (and stay)
    masked out — they behave exactly like permanently-pruned classes.
    """
    kg, ke = jax.random.split(key)
    scale = 1.0 / jnp.sqrt(d)
    params = {
        "gate": (jax.random.normal(kg, (cfg.num_experts, d)) * scale).astype(dtype),
        "experts": (jax.random.normal(ke, (cfg.num_experts, n_classes, d)) * scale).astype(dtype),
    }
    mask = jnp.ones((cfg.num_experts, n_classes), dtype=jnp.bool_)
    if n_valid is not None and n_valid < n_classes:
        mask = mask & (jnp.arange(n_classes) < n_valid)[None, :]
    state = DSState(mask=mask)
    return params, state


def abstract_params(d: int, n_classes: int, cfg: DSSoftmaxConfig, dtype=jnp.bfloat16):
    """ShapeDtypeStruct stand-ins (for the dry-run: no allocation)."""
    params = {
        "gate": jax.ShapeDtypeStruct((cfg.num_experts, d), dtype),
        "experts": jax.ShapeDtypeStruct((cfg.num_experts, n_classes, d), dtype),
    }
    state = DSState(mask=jax.ShapeDtypeStruct((cfg.num_experts, n_classes), jnp.bool_))
    return params, state


# ---------------------------------------------------------------------------
# Training forward / loss
# ---------------------------------------------------------------------------

def _masked_logits(z: jax.Array, mask: jax.Array, mode: str) -> jax.Array:
    """Apply the class mask to raw logits z (…, N) with mask (…, N)."""
    if mode == "zero":
        # Faithful: pruned rows are zero weights => logit exactly 0.
        return z * mask.astype(z.dtype)
    return jnp.where(mask, z, NEG_INF)


def logits_dense(params, state: DSState, h: jax.Array, cfg: DSSoftmaxConfig):
    """Reference path: full (T, N) mixture logits via the sparse gate.

    h: (T, d) → logits (T, N) float32, plus (expert_idx, g, G).
    """
    expert_idx, g, G = top1_gate(params["gate"], h)
    w = pruning.apply_mask(params["experts"], state.mask)  # (K, N, d)
    # All-expert logits then one-hot select (exact; O(K·T·N·d)).
    z_all = jnp.einsum("td,knd->tkn", h.astype(jnp.float32), w.astype(jnp.float32))
    Gs = sparse_gate_matrix(G)  # (T, K) — only top-1 nonzero, grads flow
    z = jnp.einsum("tkn,tk->tn", z_all, Gs)
    sel_mask = state.mask[expert_idx]  # (T, N)
    z = _masked_logits(z, sel_mask, cfg.mask_mode)
    return z, (expert_idx, g, G)


def _sorted_dispatch(expert_idx: jax.Array, T: int, K: int, capacity: int):
    """Group tokens by expert. Returns (order, slot, valid).

    order: (T,) token permutation grouped by expert;
    slot:  (T,) position of token ``order[i]`` inside its expert buffer;
    valid: (T,) False where the token overflowed the expert capacity.
    """
    order = jnp.argsort(expert_idx, stable=True)
    sorted_e = expert_idx[order]
    # Rank within the expert group = i - first_occurrence(sorted_e[i]).
    first = jnp.searchsorted(sorted_e, jnp.arange(K, dtype=sorted_e.dtype), side="left")
    slot = jnp.arange(T, dtype=jnp.int32) - first[sorted_e].astype(jnp.int32)
    valid = slot < capacity
    return order, slot, valid


def loss(
    params,
    state: DSState,
    h: jax.Array,
    labels: jax.Array,
    cfg: DSSoftmaxConfig,
    *,
    dispatch: str = "dense",
    capacity_factor: float = 2.0,
) -> tuple[jax.Array, DSAux]:
    """Mean cross-entropy + the paper's aux losses.

    h: (T, d), labels: (T,) int32. Returns (task_ce, DSAux).
    Total train objective = task_ce + λ_lasso·lasso + λ_expert·expert
    + λ_load·load (assembled by the caller so each λ stays visible).
    """
    T, d = h.shape
    K, N, _ = params["experts"].shape

    if dispatch == "dense":
        z, (expert_idx, g, G) = logits_dense(params, state, h, cfg)
        ce = _ce_from_logits(z, labels)
        drop = jnp.zeros((), jnp.float32)
    elif dispatch == "sorted":
        expert_idx, g, G = top1_gate(params["gate"], h)
        capacity = int(max(1, round(T / K * capacity_factor)))
        order, slot, valid = _sorted_dispatch(expert_idx, T, K, capacity)
        w = pruning.apply_mask(params["experts"], state.mask)
        Gs = sparse_gate_matrix(G)  # (T, K)
        g_kept = jnp.sum(Gs, axis=-1)  # == g but with Eq-1 gradients
        # Dispatch tokens (and their gate scale / labels) into (K, C, ·).
        buf = jnp.zeros((K, capacity, d), h.dtype)
        buf = buf.at[expert_idx[order], slot].set(
            jnp.where(valid[:, None], h[order], 0.0), mode="drop"
        )
        lab_buf = jnp.full((K, capacity), 0, labels.dtype)
        lab_buf = lab_buf.at[expert_idx[order], slot].set(labels[order], mode="drop")
        g_buf = jnp.zeros((K, capacity), jnp.float32)
        g_buf = g_buf.at[expert_idx[order], slot].set(
            jnp.where(valid, g_kept[order], 0.0), mode="drop"
        )
        z = jnp.einsum("kcd,knd->kcn", buf.astype(jnp.float32), w.astype(jnp.float32))
        z = z * g_buf[..., None]
        z = _masked_logits(z, state.mask[:, None, :], cfg.mask_mode)
        ce_buf = _ce_from_logits(z.reshape(K * capacity, N), lab_buf.reshape(-1), mean=False)
        ce_buf = ce_buf.reshape(K, capacity)
        # Gather each token's CE back; overflowed tokens are dropped from the
        # mean (and counted).
        tok_ce = ce_buf[expert_idx[order], jnp.minimum(slot, capacity - 1)]
        tok_ce = jnp.where(valid, tok_ce, 0.0)
        n_valid = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
        ce = jnp.sum(tok_ce) / n_valid
        drop = 1.0 - n_valid / T
    else:
        raise ValueError(f"unknown dispatch {dispatch!r}")

    w = params["experts"]
    aux = DSAux(
        lasso=L.group_lasso(w, state.mask, cfg.gamma),
        expert_lasso=L.expert_lasso(w, state.mask),
        load=L.load_balance(jnp.sum(sparse_gate_matrix(G), axis=tuple(range(G.ndim - 1)))),
        drop_frac=drop,
        gate_entropy=-jnp.mean(jnp.sum(G * jnp.log(G + 1e-10), axis=-1)),
    )
    return ce, aux


def loss_rows(
    params,
    state: DSState,
    h: jax.Array,
    labels: jax.Array,
    cfg: DSSoftmaxConfig,
    *,
    capacity_factor: float = 1.25,
    label_mask: Optional[jax.Array] = None,
) -> tuple[jax.Array, DSAux]:
    """Sorted-dispatch CE over batched rows. h: (B, S, d), labels: (B, S).

    Tokens are grouped by expert *within each row* (vmap over B), so under
    batch→data sharding the argsort/scatter stay device-local — the only
    cross-device traffic for the head is the vocab-sharded expert tables
    (this is the production train path for the big-model heads).
    ``label_mask`` (B, S) excludes positions (e.g. vision-prefix) from CE.
    """
    B, S, d = h.shape
    K, N, _ = params["experts"].shape
    from repro.distributed.hints import constrain, constrain_batch

    h = constrain_batch(h)
    expert_idx, g, G = top1_gate(params["gate"], h)  # (B,S), (B,S), (B,S,K)
    Gs = sparse_gate_matrix(G)
    g_kept = jnp.sum(Gs, axis=-1)  # (B,S) — g with Eq-1 gradients
    capacity = int(max(1, round(S / K * capacity_factor)))
    w = pruning.apply_mask(params["experts"], state.mask)

    def dispatch_row(h_r, lab_r, e_r, g_r):
        order, slot, valid = _sorted_dispatch(e_r, S, K, capacity)
        buf = jnp.zeros((K, capacity, d), h_r.dtype)
        buf = buf.at[e_r[order], slot].set(
            jnp.where(valid[:, None], h_r[order], 0.0), mode="drop"
        )
        lab_buf = jnp.zeros((K, capacity), lab_r.dtype)
        lab_buf = lab_buf.at[e_r[order], slot].set(lab_r[order], mode="drop")
        g_buf = jnp.zeros((K, capacity), jnp.float32)
        g_buf = g_buf.at[e_r[order], slot].set(
            jnp.where(valid, g_r[order], 0.0), mode="drop"
        )
        return buf, lab_buf, g_buf, order, slot, valid

    buf, lab_buf, g_buf, order, slot, valid = jax.vmap(dispatch_row)(
        h, labels, expert_idx, g_kept
    )  # (B,K,C,d), (B,K,C), (B,K,C), (B,S), (B,S), (B,S)

    # One batched matmul for all rows — logits explicitly vocab-sharded
    # (b→batch axes by propagation, n→model), CE is vocab-parallel.
    from repro.distributed.hints import BATCH

    # Streaming vocab-parallel CE: the (B,K,C,N) fp32 logits are never fully
    # materialized — capacity is processed in chunks under jax.checkpoint, so
    # one chunk's logits are live at a time and the backward recomputes them
    # (fused-softmax-CE, the Megatron vocab-parallel recipe).
    n_chunks = 1
    for cand in (8, 4, 2):
        if capacity % cand == 0 and capacity // cand >= 8:
            n_chunks = cand
            break
    cc = capacity // n_chunks

    def ce_chunk(_, inp):
        buf_i, lab_i, g_i = inp  # (B,K,cc,d), (B,K,cc), (B,K,cc)
        z = jnp.einsum("bkcd,knd->bkcn", buf_i, w, preferred_element_type=jnp.float32)
        z = constrain(z, BATCH, None, None, "model")
        z = z * g_i[..., None]
        z = _masked_logits(z, state.mask[None, :, None, :], cfg.mask_mode)
        return (), _ce_from_logits(z, lab_i, mean=False)  # (B,K,cc)

    def split(t):  # (B,K,C,...) -> (nc, B,K,cc,...)
        shp = t.shape
        t = t.reshape(shp[0], shp[1], n_chunks, cc, *shp[3:])
        return jnp.moveaxis(t, 2, 0)

    if n_chunks > 1:
        _, ce_chunks = jax.lax.scan(
            jax.checkpoint(ce_chunk), (), (split(buf), split(lab_buf), split(g_buf))
        )
        ce_buf = jnp.moveaxis(ce_chunks, 0, 2).reshape(B, K, capacity)
    else:
        _, ce_buf = ce_chunk((), (buf, lab_buf, g_buf))

    def gather_row(ce_r, e_r, order, slot, valid):
        tok_ce = ce_r[e_r[order], jnp.minimum(slot, capacity - 1)]
        tok_ce = jnp.where(valid, tok_ce, 0.0)
        inv = jnp.zeros((S,), jnp.int32).at[order].set(jnp.arange(S, dtype=jnp.int32))
        return tok_ce[inv], valid[inv]

    tok_ce, valid = jax.vmap(gather_row)(ce_buf, expert_idx, order, slot, valid)  # (B,S)
    if label_mask is not None:
        valid = jnp.logical_and(valid, label_mask.astype(bool))
    n_valid = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    ce = jnp.sum(jnp.where(valid, tok_ce, 0.0)) / n_valid
    count = B * S if label_mask is None else jnp.sum(label_mask.astype(jnp.float32))
    drop = 1.0 - n_valid / jnp.maximum(count, 1.0)

    we = params["experts"]
    aux = DSAux(
        lasso=L.group_lasso(we, state.mask, cfg.gamma),
        expert_lasso=L.expert_lasso(we, state.mask),
        load=L.load_balance(jnp.sum(Gs, axis=tuple(range(Gs.ndim - 1)))),
        drop_frac=drop,
        gate_entropy=-jnp.mean(jnp.sum(G * jnp.log(G + 1e-10), axis=-1)),
    )
    return ce, aux


def total_loss(params, state, h, labels, cfg: DSSoftmaxConfig, **kw):
    """task CE + λ-weighted aux losses (paper Algorithm 1's L_all)."""
    ce, aux = loss(params, state, h, labels, cfg, **kw)
    full = (
        ce
        + cfg.lambda_lasso * aux.lasso
        + cfg.lambda_expert * aux.expert_lasso
        + cfg.lambda_load * aux.load
    )
    return full, (ce, aux)


def _ce_from_logits(z: jax.Array, labels: jax.Array, mean: bool = True) -> jax.Array:
    """Vocab-parallel-safe CE: the gold logit is extracted with a one-hot
    contraction over the class axis (local partial + all-reduce under
    GSPMD) — ``take_along_axis`` on a model-sharded axis would all-gather
    the full logits tensor."""
    lse = jax.nn.logsumexp(z, axis=-1)
    onehot = jax.nn.one_hot(labels, z.shape[-1], dtype=jnp.bfloat16)
    gold = jnp.einsum("...n,...n->...", z.astype(jnp.float32), onehot.astype(jnp.float32))
    ce = lse - gold
    return jnp.mean(ce) if mean else ce


# ---------------------------------------------------------------------------
# Pruning step (between optimizer steps)
# ---------------------------------------------------------------------------

def update_mask(params, state: DSState, task_loss, cfg: DSSoftmaxConfig) -> DSState:
    new_mask = pruning.prune_step(
        params["experts"],
        state.mask,
        jnp.asarray(task_loss, jnp.float32),
        gamma=cfg.gamma,
        threshold=cfg.prune_task_loss_threshold,
    )
    return DSState(mask=new_mask)


# ---------------------------------------------------------------------------
# Serving: packed experts + top-k retrieval
# ---------------------------------------------------------------------------

class ServeTable(NamedTuple):
    """Static-shape packed experts for TPU serving.

    ids:     (K, V_pad) int32 — class id per packed row; -1 for padding.
    weights: (K, V_pad, d)    — gathered active rows (zeros for padding).

    ``K`` may include all-padding dummy experts appended by
    :func:`shard_table` so the expert axis divides the mesh's ``model``
    axis; gating is computed over the real gate matrix only and never
    routes a token to them.
    """

    ids: jax.Array
    weights: jax.Array

    @property
    def v_pad(self) -> int:
        return self.ids.shape[1]

    def shard(self, mesh) -> "ServeTable":
        """Expert-parallel placement over ``mesh`` (see :func:`shard_table`)."""
        return shard_table(self, mesh)


class QuantizedServeTable(NamedTuple):
    """Int8 serve table with per-expert-row fp32 scales (PR 9).

    Drop-in for :class:`ServeTable` everywhere serving accepts one (the
    ``as_serve_table`` duck-unwrap, ``TableResource``, ``ServeSession``,
    sharded serving). Expert rows are stored symmetric-quantized —
    ``w[k, v] ≈ qweights[k, v] * scales[k, v]`` with
    ``scales[k, v] = max|w[k, v, :]| / 127`` — and dequantized
    *in-register*: every serve path casts the int8 rows to the token
    dtype for the MXU matmul, accumulates in fp32 and applies the row
    scale to the accumulator (exactly like the gate scale), so the
    (K, V_pad, d) table is read at 1 byte/elem and no fp copy of it
    ever exists in HBM.

    Mixed precision: experts whose top-k *ids* flip vs the fp32 oracle
    on calibration traffic (see :func:`calibrate_quantized_table`) keep
    their exact full-precision rows in ``fb_weights`` and are served
    through the gather path. ``fb_index[k]`` is the row of expert ``k``
    in ``fb_weights`` (-1 → int8-served); ``fb_weights.shape[0]`` is a
    static trace-time constant, so a gate-clean table compiles with no
    fallback branch at all.

    ids:        (K, V_pad) int32 — class id per packed row; -1 padding.
    qweights:   (K, V_pad, d) int8 — symmetric-quantized rows.
    scales:     (K, V_pad) float32 — per-row dequant scale (1.0 on
                all-zero/padding rows so dequant is well-defined).
    fb_index:   (K,) int32 — row into ``fb_weights``; -1 = int8-served.
    fb_weights: (n_fb, V_pad, d) source dtype — exact rows of the
                fallback experts (empty when the gate passed clean).
    """

    ids: jax.Array
    qweights: jax.Array
    scales: jax.Array
    fb_index: jax.Array
    fb_weights: jax.Array

    @property
    def v_pad(self) -> int:
        return self.ids.shape[1]

    @property
    def n_fallback(self) -> int:
        return self.fb_weights.shape[0]

    def shard(self, mesh) -> "QuantizedServeTable":
        """Expert-parallel placement over ``mesh`` (see :func:`shard_table`)."""
        return shard_table(self, mesh)


def quantize_table(table: ServeTable, fb_mask=None) -> QuantizedServeTable:
    """Symmetric int8 row quantization of a packed :class:`ServeTable`.

    ``fb_mask`` (K,) bool marks experts kept at full precision (their
    exact rows move to ``fb_weights``; their ``qweights`` stay populated
    but are never read). Host-side numpy, like :func:`pack_experts` —
    a one-off packing step, not a jitted op.
    """
    ids = np.asarray(jax.device_get(table.ids))
    w = np.asarray(jax.device_get(table.weights))
    K = w.shape[0]
    amax = np.abs(w.astype(np.float32)).max(axis=2)  # (K, V_pad)
    scales = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(
        np.rint(w.astype(np.float32) / scales[..., None]), -127, 127
    ).astype(np.int8)
    fb = (np.zeros((K,), bool) if fb_mask is None
          else np.asarray(jax.device_get(fb_mask), bool))
    fb_rows = np.nonzero(fb)[0]
    fb_index = np.full((K,), -1, np.int32)
    fb_index[fb_rows] = np.arange(len(fb_rows), dtype=np.int32)
    return QuantizedServeTable(
        ids=jnp.asarray(ids),
        qweights=jnp.asarray(q),
        scales=jnp.asarray(scales),
        fb_index=jnp.asarray(fb_index),
        fb_weights=jnp.asarray(w[fb_rows]),
    )


def dequantize_table(table: QuantizedServeTable) -> ServeTable:
    """Materialize the fp32 table a :class:`QuantizedServeTable` serves:
    ``q * s`` rows, with fallback experts' exact rows substituted. Debug /
    oracle helper (host-side; the serve paths never build this)."""
    q = np.asarray(jax.device_get(table.qweights))
    s = np.asarray(jax.device_get(table.scales))
    w = q.astype(np.float32) * s[..., None]
    fb_index = np.asarray(jax.device_get(table.fb_index))
    if table.n_fallback:
        fb_w = np.asarray(jax.device_get(table.fb_weights), np.float32)
        for e in np.nonzero(fb_index >= 0)[0]:
            w[e] = fb_w[fb_index[e]]
    return ServeTable(ids=table.ids, weights=jnp.asarray(w))


class ExactnessReport(NamedTuple):
    """Quantized-serving exactness gate (PR 9).

    Produced by :func:`calibrate_quantized_table`: top-k ids of the
    all-int8 table are compared positionally against the fp32 oracle on
    calibration traffic; experts whose token flip rate exceeds
    ``flip_threshold`` fall back to full-precision rows. Flips from
    tokens of non-fallback experts remain *unguarded* — the gate passes
    iff there are none (with the default threshold 0.0 every flipping
    expert falls back, so the served table is measured-exact on the
    calibration trace by construction).
    """

    n_tokens: int
    n_flips_raw: int           # all-int8 table vs fp32 oracle, pre-fallback
    n_unguarded_flips: int     # flips surviving the per-expert fallback
    flip_threshold: float
    per_expert_flip_rate: tuple  # (K,) floats, calibration-token weighted
    fallback_experts: tuple      # expert ids served from full-precision rows

    @property
    def passed(self) -> bool:
        return self.n_unguarded_flips == 0

    def as_dict(self) -> dict:
        return {
            "n_tokens": int(self.n_tokens),
            "n_flips_raw": int(self.n_flips_raw),
            "n_unguarded_flips": int(self.n_unguarded_flips),
            "flip_rate_raw": (float(self.n_flips_raw) / self.n_tokens
                              if self.n_tokens else 0.0),
            "flip_threshold": float(self.flip_threshold),
            "per_expert_flip_rate": [float(r) for r in self.per_expert_flip_rate],
            "fallback_experts": [int(e) for e in self.fallback_experts],
            "n_fallback": len(self.fallback_experts),
            "passed": bool(self.passed),
        }


def calibrate_quantized_table(
    gate_w: jax.Array,
    table: ServeTable,
    calib_h: jax.Array,
    k: int = 8,
    flip_threshold: float = 0.0,
) -> tuple[QuantizedServeTable, ExactnessReport]:
    """Quantize ``table`` to int8 under an exactness gate.

    Runs the jnp oracle on the fp table and on the all-int8 table over
    ``calib_h`` (n, d) calibration activations, compares top-``k`` ids
    positionally, and re-quantizes with per-expert bf16/fp fallback for
    every expert whose flip rate (among the tokens its top-1 gate
    captured) exceeds ``flip_threshold``. Returns the (possibly
    mixed-precision) table and the gate report.
    """
    if not isinstance(table, ServeTable):
        raise TypeError(
            "calibrate_quantized_table expects a full-precision ServeTable, "
            f"got {type(table).__name__}"
        )
    calib_h = jnp.asarray(calib_h)
    qt_all = quantize_table(table)
    _, ids_ref = serve_topk(gate_w, table, calib_h, k, kernel="jnp")
    _, ids_q = serve_topk(gate_w, qt_all, calib_h, k, kernel="jnp")
    eidx = np.asarray(jax.device_get(top1_gate(gate_w, calib_h)[0]))
    flips = np.asarray(jax.device_get(
        (ids_ref != ids_q).any(axis=1)
    ))
    K = table.ids.shape[0]
    tok_e = np.bincount(eidx, minlength=K).astype(np.int64)
    flip_e = np.bincount(eidx, weights=flips.astype(np.float64), minlength=K)
    rate = flip_e / np.maximum(tok_e, 1)
    fb = rate > flip_threshold
    qtable = quantize_table(table, fb_mask=fb) if fb.any() else qt_all
    unguarded = int(flips[~fb[eidx]].sum())
    report = ExactnessReport(
        n_tokens=int(calib_h.shape[0]),
        n_flips_raw=int(flips.sum()),
        n_unguarded_flips=unguarded,
        flip_threshold=float(flip_threshold),
        per_expert_flip_rate=tuple(float(r) for r in rate),
        fallback_experts=tuple(int(e) for e in np.nonzero(fb)[0]),
    )
    return qtable, report


def as_serve_table(table):
    """Unwrap a versioned table resource to its CURRENT table.

    Duck-typed so ``core`` need not import ``repro.serve``: anything
    exposing a ``.table`` attribute that is a :class:`ServeTable` or
    :class:`QuantizedServeTable` (``repro.serve.table_manager.
    TableResource``) unwraps to it; a raw table (or a non-DS head state)
    passes through unchanged. Serving entry points call this, so a
    swappable resource can stand in anywhere a packed table is accepted.
    The unwrap runs at trace time — a jitted wrapper rebuilt after a
    swap (``ServeSession.swap_table``) prices the current
    ``(K, V_pad)`` and dtype, never a stale version.
    """
    inner = getattr(table, "table", None)
    return (inner if isinstance(inner, (ServeTable, QuantizedServeTable))
            else table)


def _round_up(x: int, m: int = 128) -> int:
    return ((x + m - 1) // m) * m


def pack_experts(params, state: DSState, pad: Optional[int] = None,
                 quantize: Optional[str] = None):
    """Compact each expert's surviving rows into a padded static table.

    ``pad`` must cover the largest expert (``pad >= max_k |v_k|``) —
    a smaller pad would silently drop surviving classes from serving, so
    it raises instead.

    ``quantize='int8'`` returns a :class:`QuantizedServeTable` (int8 rows
    + per-row fp32 scales, no fallback experts — run the packed table
    through :func:`calibrate_quantized_table` for the gated
    mixed-precision variant).

    NOTE: sizes come from the concrete mask, so this runs outside jit
    (it is a one-off packing step after training / checkpoint load).
    """
    if quantize not in (None, "int8"):
        raise ValueError(
            f"pack_experts quantize={quantize!r}: only 'int8' is supported"
        )
    mask = jax.device_get(state.mask)
    w = jax.device_get(params["experts"])
    K, N, d = w.shape
    sizes = mask.sum(axis=1)
    max_size = int(sizes.max())
    if pad is not None and int(pad) < max_size:
        over = np.nonzero(sizes > int(pad))[0]
        listing = ", ".join(
            f"expert {int(e)}: {int(sizes[e])} rows" for e in over[:8]
        ) + (f", … ({len(over)} experts total)" if len(over) > 8 else "")
        raise ValueError(
            f"pack_experts pad={int(pad)} is smaller than the surviving-class "
            f"count of {len(over)}/{K} experts ({listing}); packing would "
            "silently truncate surviving rows"
        )
    v_pad = int(pad) if pad else _round_up(max(1, max_size))

    ids = np.full((K, v_pad), -1, np.int32)
    weights = np.zeros((K, v_pad, d), w.dtype)
    for k in range(K):
        idx = np.nonzero(mask[k])[0]
        ids[k, : len(idx)] = idx
        weights[k, : len(idx)] = w[k, idx]
    table = ServeTable(ids=jnp.asarray(ids), weights=jnp.asarray(weights))
    return quantize_table(table) if quantize == "int8" else table


def serve_kernel_context(
    table: ServeTable, h: jax.Array, k: int, capacity_factor: float = 2.0,
    ep: int = 1, ndata: int = 1,
):
    """Static-shape :class:`~repro.kernels.registry.KernelContext` for one
    ``serve_topk`` call site (shapes are trace-time constants, so policies
    resolve per distinct call-site shape — prefill vs decode differ).
    ``ep``/``ndata`` are the expert-parallel and batch-shard degrees of a
    sharded call site (1 on a single device).

    ``wbytes`` always derives from the ACTUAL table row dtype (1 for an
    int8 :class:`QuantizedServeTable`, whose ``quantized`` flag also
    adds the scale-read bytes to the registry's cost model) — every
    serve entry point (local, sharded, head) builds its context here, so
    the bytes model can never drift from what the kernel reads."""
    from repro.kernels.registry import KernelContext

    quantized = isinstance(table, QuantizedServeTable)
    rows = table.qweights if quantized else table.weights
    return KernelContext(
        B=h.shape[0],
        d=h.shape[1],
        K=table.ids.shape[0],
        v_pad=table.ids.shape[1],
        k=k,
        backend=jax.default_backend(),
        capacity_factor=capacity_factor,
        wbytes=jnp.dtype(rows.dtype).itemsize,
        hbytes=jnp.dtype(h.dtype).itemsize,
        ep=ep,
        ndata=ndata,
        quantized=quantized,
    )


def serve_topk(
    gate_w: jax.Array,
    table: ServeTable,
    h: jax.Array,
    k: int,
    *,
    kernel: Union[str, "KernelPolicy"] = "jnp",  # noqa: F821
    capacity_factor: float = 2.0,
    with_stats: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Top-k class retrieval (paper inference). h: (B, d) → values/ids (B, k).

    ``kernel`` is a registered kernel name, a policy name, or a
    ``repro.kernels.registry.KernelPolicy`` resolved **per call site**
    from the static shapes (B, K, V_pad, d, k) and backend:

    kernel='jnp'     — per-token gather + matmul in plain jnp (the oracle;
                       XLA materializes the (B, V_pad, d) gather).
    kernel='grouped' — expert-batched weight-stationary XLA path: tokens
                       dispatched by top-1 expert, one (C, d)×(d, V_pad)
                       matmul per expert, exact overflow fallback.
    kernel='pallas'  — per-token streaming Pallas kernel (legacy; spills
                       (B, n_blocks, k) candidates and re-merges).
    kernel='pallas_grouped' — expert-grouped streaming Pallas kernel: the
                       grouped dispatch feeds (block_b, d)×(d, block_v) MXU
                       matmuls with a running top-k carried in VMEM; only
                       O(B·k) values/ids reach HBM. Production serving path.
    kernel='pallas_fused' — gate→dispatch→retrieve in ONE Pallas launch:
                       the (K, d) gate matvec + top-1 selection run in the
                       kernel prologue (VMEM), so no dispatch indices ever
                       round-trip through HBM. Quantized decode default.
    kernel='auto'    — ``AutoPolicy``: cheapest feasible path by the
                       registry's bytes-moved model (per-token at B ≲ K,
                       grouped at B ≫ K; Pallas paths only on TPU).

    Unknown names raise ValueError. ``capacity_factor`` sizes the grouped
    paths' per-expert buffers (overflow falls back exactly); propagate
    ``DSSoftmaxConfig.capacity_factor`` from model call sites.

    ``with_stats=True`` additionally returns a dict of O(K) per-expert
    load telemetry — ``{'dispatched': (K,), 'overflow': (K,)}`` int32 —
    the accumulators the serving overflow circuit-breaker watches
    (overflow is identically zero on the capacity-free gather paths).
    """
    from repro.distributed.hints import constrain_batch
    from repro.kernels.registry import get_spec, resolve_kernel

    table = as_serve_table(table)
    kernel = resolve_kernel(
        kernel, serve_kernel_context(table, h, k, capacity_factor)
    )
    if get_spec(kernel).sharded:
        raise ValueError(
            f"serve kernel {kernel!r} is an expert-parallel path; call "
            "serve_topk_sharded(..., mesh=...) (or shard the ServeTable and "
            "pass a mesh through ServeSession)"
        )
    h = constrain_batch(h)
    if get_spec(kernel).fused:
        # gating happens inside the kernel prologue — no XLA pre-pass
        return _serve_topk_fused(gate_w, table, h, k, with_stats=with_stats)
    expert_idx, g, _ = top1_gate(gate_w, h)
    return _serve_topk_local(
        table, h, expert_idx, g, k, kernel, capacity_factor=capacity_factor,
        with_stats=with_stats,
    )


def _serve_topk_local(
    table: ServeTable, h: jax.Array, expert_idx: jax.Array, g: jax.Array,
    k: int, kernel: str, *, capacity_factor: float = 2.0,
    owned: Optional[jax.Array] = None, n_experts_global: Optional[int] = None,
    with_stats: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Single-device retrieval over (possibly local) experts, shared by
    ``serve_topk`` and each ``serve_topk_sharded`` shard.

    ``expert_idx`` is already LOCAL to ``table`` (clipped into range by the
    sharded caller). ``owned`` (B,) bool marks tokens this shard is
    responsible for: non-owned tokens are excluded from the grouped
    dispatch and the overflow fixup, and their outputs are (NEG_INF, -1).
    ``n_experts_global`` sizes the grouped capacity by the GLOBAL expert
    count so per-expert buffers match the expected per-expert load (the
    local shard sees the same tokens-per-expert as the global run).
    ``with_stats`` appends the ``{'dispatched', 'overflow'}`` (K,) int32
    telemetry (overflow zero on the capacity-free paths).
    """
    from repro.core.dispatch import dispatch_load
    from repro.distributed.hints import BATCH, constrain

    overflow = None
    if kernel == "pallas":
        from repro.kernels import ops as kops

        vals, ids = kops.dss_topk(table.weights, table.ids, h, expert_idx, g, k)
    elif kernel in ("grouped", "pallas_grouped"):
        vals, ids, overflow = _serve_topk_grouped(
            table, h, expert_idx, g, k,
            capacity_factor=capacity_factor,
            use_pallas=kernel == "pallas_grouped",
            owned=owned, n_experts_global=n_experts_global,
        )
    elif kernel != "jnp":
        raise NotImplementedError(
            f"registered serve kernel {kernel!r} has no dispatch branch"
        )
    else:
        if isinstance(table, QuantizedServeTable):
            z, ids_sel = _exact_rows_logits(table, expert_idx, h)
            ids_sel = constrain(ids_sel, BATCH, "model")
        else:
            w_sel = constrain(table.weights[expert_idx], BATCH, "model", None)  # (B,V_pad,d)
            ids_sel = constrain(table.ids[expert_idx], BATCH, "model")  # (B, V_pad)
            z = jnp.einsum("bvd,bd->bv", w_sel, h, preferred_element_type=jnp.float32)
        z = constrain(z, BATCH, "model")
        z = z * g[:, None]
        z = jnp.where(ids_sel >= 0, z, NEG_INF)
        vals, pos = jax.lax.top_k(z, k)
        ids = jnp.take_along_axis(ids_sel, pos, axis=1)
    if owned is not None:
        vals = jnp.where(owned[:, None], vals, NEG_INF)
        ids = jnp.where(owned[:, None], ids, -1)
    if not with_stats:
        return vals, ids
    K = table.ids.shape[0]
    # non-owned tokens route to the out-of-range sentinel K → dropped
    e_count = expert_idx if owned is None else jnp.where(owned, expert_idx, K)
    dispatched, _ = dispatch_load(e_count, K)
    if overflow is None:
        overflow = jnp.zeros((K,), jnp.int32)
    return vals, ids, {"dispatched": dispatched, "overflow": overflow}


def _exact_rows_logits(table, expert_idx: jax.Array, h: jax.Array):
    """Per-token gather-path logits: (B, V_pad) fp32 UN-gated ``z`` plus the
    gathered (B, V_pad) row ids, for both table kinds.

    Quantized rule (every path must match it bit-for-bit so the kernel,
    grouped-XLA and gather paths emit identical ids): cast the int8 rows
    to the token dtype, matmul with fp32 accumulation, THEN apply the
    per-row scale to the accumulator — never pre-multiply ``q·s`` into
    the operand, which reassociates the rounding. Fallback experts'
    tokens get their exact full-precision rows instead.
    """
    ids_sel = table.ids[expert_idx]
    if isinstance(table, QuantizedServeTable):
        q_sel = table.qweights[expert_idx]  # (B, V_pad, d) int8
        z = jnp.einsum("bvd,bd->bv", q_sel.astype(h.dtype), h,
                       preferred_element_type=jnp.float32)
        z = z * table.scales[expert_idx]
        if table.n_fallback:
            row = table.fb_index[expert_idx]  # (B,) -1 = int8-served
            w_fb = table.fb_weights[jnp.maximum(row, 0)]
            z_fb = jnp.einsum("bvd,bd->bv", w_fb, h,
                              preferred_element_type=jnp.float32)
            z = jnp.where((row >= 0)[:, None], z_fb, z)
    else:
        z = jnp.einsum("bvd,bd->bv", table.weights[expert_idx], h,
                       preferred_element_type=jnp.float32)
    return z, ids_sel


def _group_tokens(h: jax.Array, g: jax.Array, expert_idx: jax.Array,
                  K: int, capacity: int):
    """Grouped-dispatch pre-pass shared by the XLA and Pallas serve paths.

    Scatters tokens (UNscaled) and their fp32 gate values into per-expert
    capacity buffers. Returns (buf (K,C,d), g_buf (K,C), slot, valid).
    Tokens that are not dispatched (overflow, or an out-of-range expert id)
    land in one spare row that is sliced off: no scatter index is ever out
    of bounds."""
    from repro.core.dispatch import dispatch_indices

    d = h.shape[-1]
    slot, valid = dispatch_indices(expert_idx, K, capacity)
    flat = jnp.where(valid, expert_idx * capacity + slot, K * capacity)
    buf = jnp.zeros((K * capacity + 1, d), h.dtype).at[flat].set(h)
    g_buf = jnp.zeros((K * capacity + 1,), jnp.float32).at[flat].set(
        g.astype(jnp.float32))
    return (buf[:-1].reshape(K, capacity, d),
            g_buf[:-1].reshape(K, capacity), slot, valid)


def _overflow_fixup(table, h, g, expert_idx, valid, vals, ids, k,
                    capacity: int):
    """Exact fallback for ~valid tokens via the gather path (capacity
    overflow, and on quantized tables the tokens of full-precision
    fallback experts), processed in fixed O-slot chunks inside a
    dynamic-trip-count loop: cost O(ceil(n_over/O)·O·V_pad·d) —
    proportional to the *actual* overflow (zero loop iterations when
    nothing overflowed), never B·V_pad·d unless everything did.
    O = min(B, max(capacity, K)): one expert capacity in the large-batch
    regime, ~one slot per expert when B ≲ K (where capacity rounds to 1
    and overflow is dominated by experts receiving a second token).
    Every overflowed token is fixed up exactly, however skewed the gate
    distribution."""
    B = h.shape[0]
    K = table.ids.shape[0]
    O = min(B, max(capacity, K))
    # All overflow positions, padded with the sentinel B: row B of the
    # outputs is a spare row, sliced off after the loop.
    over_all = jnp.nonzero(~valid, size=B, fill_value=B)[0]  # (B,)
    n_over = jnp.sum((~valid).astype(jnp.int32))
    n_chunks = (n_over + O - 1) // O  # dynamic — lowers to a while loop

    def chunk(c, carry):
        vals, ids = carry
        idx = jax.lax.dynamic_slice(over_all, (c * O,), (O,))  # (O,)
        take = jnp.minimum(idx, B - 1)  # clamp sentinel rows for the GATHERS
        h_o = h[take]
        z_o, ids_o = _exact_rows_logits(table, expert_idx[take], h_o)
        z_o = z_o * g[take][:, None]
        z_o = jnp.where(ids_o >= 0, z_o, NEG_INF)
        v_o, p_o = jax.lax.top_k(z_o, k)
        i_o = jnp.take_along_axis(ids_o, p_o, axis=1)
        # Scatter through the UNclamped index: sentinel rows (idx == B) land
        # in the spare row — clamping them to B-1 would duplicate that index
        # and could clobber a real fixup of the last token with its stale
        # pre-update value.
        vals = vals.at[idx].set(v_o)
        ids = ids.at[idx].set(i_o)
        return vals, ids

    spare = ((0, 1), (0, 0))
    vals, ids = jax.lax.fori_loop(
        0, n_chunks, chunk, (jnp.pad(vals, spare), jnp.pad(ids, spare)))
    return vals[:B], ids[:B]


def _serve_topk_grouped(
    table, h: jax.Array, expert_idx: jax.Array, g: jax.Array, k: int,
    capacity_factor: float = 2.0, use_pallas: bool = False,
    owned: Optional[jax.Array] = None, n_experts_global: Optional[int] = None,
):
    """Beyond-paper batched serving: tokens grouped by expert, one
    weight-stationary (C, d)×(d, V_pad) contraction per expert — the packed
    tables are read once per *expert*, not once per token (the naive gather
    path moves B·V_pad·d bytes; this moves K·V_pad·d + dispatch).

    ``use_pallas`` routes the matmul+top-k through the fused streaming
    kernel (``kernels.dss_topk_grouped``): the running top-k lives in VMEM
    across vocab blocks and only the (K, C, k) grouped outputs reach HBM.
    Tokens overflowing an expert's capacity fall back to the gather path
    (rare with the load-balance loss; exactness preserved).

    ``owned`` (sharded serving): non-owned tokens are routed to the
    out-of-range expert id K before dispatch, which ``dispatch_indices``
    never marks valid, so they stay out of every capacity buffer; they
    are then masked valid for the fixup (a non-owned token must never
    trigger the gather fallback on this shard). ``n_experts_global``
    sizes ``capacity`` by the global expert count: the shard sees ~B/ep
    of the tokens spread over K/ep experts — the same per-expert load as
    the global run.

    Returns (vals, ids, overflow) with ``overflow`` the (K,) int32
    per-expert count of owned tokens that paid the fixup this call.
    """
    from repro.core.dispatch import dispatch_load
    from repro.distributed.hints import constrain

    quantized = isinstance(table, QuantizedServeTable)
    rows = table.qweights if quantized else table.weights
    B, d = h.shape
    K, v_pad, _ = rows.shape
    capacity = int(max(1, round(B / (n_experts_global or K) * capacity_factor)))
    e_disp = expert_idx if owned is None else jnp.where(owned, expert_idx, K)
    fb_tok = None
    if quantized and table.n_fallback:
        # Mixed-precision rows: tokens of full-precision fallback experts
        # route to the out-of-range sentinel K BEFORE dispatch, so they
        # skip the int8 buffers AND the overflow telemetry (dispatch_load
        # drops out-of-range ids — paying the exact gather fixup is by
        # design here, not capacity pressure, and must never trip the
        # serving overflow breaker).
        fb_tok = table.fb_index[expert_idx] >= 0
        if owned is not None:
            fb_tok = fb_tok & owned
        e_disp = jnp.where(fb_tok, K, e_disp)
    buf, g_buf, slot, valid = _group_tokens(h, g, e_disp, K, capacity)
    # overflow telemetry BEFORE non-owned tokens are masked valid — it must
    # count exactly the owned tokens that pay the fixup on this shard
    _, overflow = dispatch_load(e_disp, K, valid)
    if fb_tok is not None:
        valid = valid & ~fb_tok  # fallback experts always take the gather path
    if owned is not None:
        valid = valid | ~owned  # never fix up a token another shard owns

    if use_pallas:
        from repro.kernels import ops as kops

        vals_b, ids_b = kops.dss_topk_grouped(
            rows, table.ids, buf, g_buf, k,
            scales=table.scales if quantized else None,
        )  # (K, C, k) each — no per-block candidate spill
    else:
        z = jnp.einsum("kcd,kvd->kcv",
                       buf, rows.astype(buf.dtype) if quantized else rows,
                       preferred_element_type=jnp.float32)  # (K, C, V_pad)
        z = constrain(z, None, None, "model")
        if quantized:
            # per-row dequant scale on the fp32 accumulator (like g below)
            z = z * table.scales[:, None, :]
        z = z * g_buf[..., None]
        z = jnp.where(table.ids[:, None, :] >= 0, z, NEG_INF)
        vals_b, pos_b = jax.lax.top_k(z, k)  # (K, C, k)
        ids_b = jnp.take_along_axis(
            jnp.broadcast_to(table.ids[:, None, :], z.shape), pos_b, axis=2
        )
    vals = vals_b[expert_idx, jnp.minimum(slot, capacity - 1)]  # (B, k)
    ids = ids_b[expert_idx, jnp.minimum(slot, capacity - 1)]
    vals, ids = _overflow_fixup(table, h, g, expert_idx, valid, vals, ids, k,
                                capacity)
    return vals, ids, overflow


def _serve_topk_fused(gate_w, table, h: jax.Array, k: int, *,
                      with_stats: bool = False):
    """Single-launch decode: gate matvec, top-1 dispatch and expert-row
    retrieval all inside ``kernels.dss_topk_fused`` — no dispatch-index
    intermediate ever reaches HBM (asserted by a jaxpr walk in the tests).

    On a quantized table with fallback experts, those tokens are fixed up
    exactly outside the kernel via the bounded gather loop — the branch is
    trace-time static (``n_fallback`` is a shape), so a gate-clean table
    compiles to exactly one kernel launch plus the O(B·k) epilogue.
    """
    from repro.core.dispatch import dispatch_load
    from repro.kernels import ops as kops

    quantized = isinstance(table, QuantizedServeTable)
    rows = table.qweights if quantized else table.weights
    vals, ids, eidx = kops.dss_topk_fused(
        gate_w, rows, table.ids, h, k,
        scales=table.scales if quantized else None,
    )
    if quantized and table.n_fallback:
        fb_tok = table.fb_index[eidx] >= 0
        _, g, _ = top1_gate(gate_w, h)  # O(B·K) — tiny next to the table read
        vals, ids = _overflow_fixup(
            table, h, g, eidx, ~fb_tok, vals, ids, k, capacity=1
        )
    if not with_stats:
        return vals, ids
    K = table.ids.shape[0]
    dispatched, _ = dispatch_load(eidx, K)
    return vals, ids, {
        "dispatched": dispatched,
        "overflow": jnp.zeros((K,), jnp.int32),  # capacity-free path
    }


# ---------------------------------------------------------------------------
# Expert-parallel sharded serving (see module docstring for the protocol)
# ---------------------------------------------------------------------------

def _pad_table_experts(table, ep: int):
    """Append all-padding dummy experts so K divides ``ep`` (static shapes;
    gating never routes to them — the gate matrix keeps the real K rows)."""
    K = table.ids.shape[0]
    K_pad = ((K + ep - 1) // ep) * ep
    if K_pad == K:
        return table
    n = K_pad - K
    ids = jnp.concatenate(
        [table.ids, jnp.full((n, table.v_pad), -1, table.ids.dtype)]
    )
    if isinstance(table, QuantizedServeTable):
        return QuantizedServeTable(
            ids=ids,
            qweights=jnp.concatenate(
                [table.qweights,
                 jnp.zeros((n,) + table.qweights.shape[1:],
                           table.qweights.dtype)]
            ),
            # scale 1.0 on dummy rows keeps dequant well-defined
            scales=jnp.concatenate(
                [table.scales, jnp.ones((n, table.v_pad), table.scales.dtype)]
            ),
            fb_index=jnp.concatenate(
                [table.fb_index, jnp.full((n,), -1, table.fb_index.dtype)]
            ),
            fb_weights=table.fb_weights,
        )
    return ServeTable(
        ids=ids,
        weights=jnp.concatenate(
            [table.weights,
             jnp.zeros((n,) + table.weights.shape[1:], table.weights.dtype)]
        ),
    )


def _mesh_degrees(mesh) -> tuple[int, int]:
    """(ep, ndata): expert-parallel degree (``model`` axis) and batch-shard
    degree (product of ``pod``/``data`` axes) of ``mesh``."""
    ep = int(mesh.shape.get("model", 1))
    ndata = 1
    for a in ("pod", "data"):
        ndata *= int(mesh.shape.get(a, 1))
    return ep, ndata


def _table_pspecs(table):
    """Per-field ``shard_map`` PartitionSpecs for a serve table pytree:
    expert rows (and, when quantized, their scales and fallback index)
    split over ``model``; the exact fallback rows replicate —
    ``fb_index`` holds global rows into them, so every shard can gather
    its own fallback experts' weights locally."""
    from jax.sharding import PartitionSpec as P

    if isinstance(table, QuantizedServeTable):
        return QuantizedServeTable(
            ids=P("model", None),
            qweights=P("model", None, None),
            scales=P("model", None),
            fb_index=P("model"),
            fb_weights=P(None, None, None),
        )
    return ServeTable(ids=P("model", None), weights=P("model", None, None))


def shard_table(table, mesh):
    """Expert-parallel placement of a packed serve table (either kind).

    Pads K to a multiple of the ``model`` axis and places experts
    ``K → model`` (each device stores K/ep experts' packed rows — the
    serve-table analogue of the MoE EP rule in
    ``distributed.sharding``). Quantized tables shard their per-row
    scales and ``fb_index`` with the expert rows; the (small) exact
    fallback rows replicate, since ``fb_index`` holds GLOBAL rows into
    them. The ``data``/``pod`` axes shard tokens at call time, so the
    table replicates over them: its second dim stays whole per device,
    keeping every per-device kernel unchanged and the wire traffic at
    the O(B·k) merge carries.
    """
    from repro.distributed.sharding import serve_table_ep_shardings

    ep, _ = _mesh_degrees(mesh)
    table = _pad_table_experts(table, ep)
    return jax.device_put(table, serve_table_ep_shardings(mesh, table))


def serve_topk_sharded(
    gate_w: jax.Array,
    table: ServeTable,
    h: jax.Array,
    k: int,
    *,
    mesh,
    kernel: Union[str, "KernelPolicy"] = "auto",  # noqa: F821
    capacity_factor: float = 2.0,
    with_stats: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Mesh-sharded top-k retrieval: experts over ``model``, tokens over
    ``data``/``pod``, one O(B·k) all-gather merge. h: (B, d) → (B, k).

    Token-identical (bit-identical ids) to the single-device
    :func:`serve_topk`: gating is computed replicated, exactly one shard
    owns each token's expert, and that shard runs the same local kernel
    math over the same packed rows. ``kernel`` resolves through the
    registry with the call site's (ep, ndata) — ``'auto'`` picks among
    the ``*_ep`` sharded specs (HBM + ICI cost); a base name
    (``'grouped'``) forces that local per-device path.

    ``with_stats=True`` appends ``{'dispatched', 'overflow'}`` (K_pad,)
    int32 GLOBAL per-expert telemetry: each model-shard counts the tokens
    it owns (summed over the data axes), and the shards' (K_loc,) rows
    concatenate over ``model`` — O(K) extra wire, never O(B·V_pad).
    """
    from jax.sharding import PartitionSpec as P

    from repro.core.dispatch import dispatch_load
    from repro.kernels.registry import get_spec, resolve_kernel

    table = as_serve_table(table)
    if "model" not in mesh.axis_names:
        return serve_topk(gate_w, table, h, k, kernel=kernel,
                          capacity_factor=capacity_factor,
                          with_stats=with_stats)
    ep, ndata = _mesh_degrees(mesh)
    B = h.shape[0]
    table = _pad_table_experts(table, ep)
    K_pad = table.ids.shape[0]
    K_loc = K_pad // ep
    b_split = ndata if (ndata > 1 and B % ndata == 0) else 1

    name = resolve_kernel(
        kernel,
        serve_kernel_context(table, h, k, capacity_factor,
                             ep=ep, ndata=b_split),
    )
    spec = get_spec(name)
    local_kernel = spec.local_name or spec.name
    fused = spec.fused

    batch_ax = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_ax if (batch_ax and b_split > 1) else None

    def body(gate_w, tbl, h):
        lo = jax.lax.axis_index("model") * K_loc
        if fused:
            # gate + dispatch run INSIDE the kernel over the full gate
            # matrix (replicated), so every shard agrees on the global
            # top-1 expert; e_base offsets the local expert-row slice.
            from repro.kernels import ops as kops

            quantized = isinstance(tbl, QuantizedServeTable)
            rows = tbl.qweights if quantized else tbl.weights
            vals, ids_out, expert_idx = kops.dss_topk_fused(
                gate_w, rows, tbl.ids, h, k,
                scales=tbl.scales if quantized else None,
                e_base=jnp.reshape(lo, (1,)).astype(jnp.int32),
            )
            owned = (expert_idx >= lo) & (expert_idx < lo + K_loc)
            vals = jnp.where(owned[:, None], vals, NEG_INF)
            ids_out = jnp.where(owned[:, None], ids_out, -1)
            if quantized and tbl.n_fallback:
                e_loc = jnp.clip(expert_idx - lo, 0, K_loc - 1)
                fb_tok = owned & (tbl.fb_index[e_loc] >= 0)
                _, g, _ = top1_gate(gate_w, h)
                vals, ids_out = _overflow_fixup(
                    tbl, h, g, e_loc, ~fb_tok, vals, ids_out, k, capacity=1
                )
            if with_stats:
                disp, _ = dispatch_load(
                    jnp.where(owned, expert_idx - lo, K_loc), K_loc
                )
                loc = (None, None,
                       {"dispatched": disp,
                        "overflow": jnp.zeros((K_loc,), jnp.int32)})
        else:
            # 1. gating replicated (per data-shard rows; agrees across model)
            expert_idx, g, _ = top1_gate(gate_w, h)
            owned = (expert_idx >= lo) & (expert_idx < lo + K_loc)
            e_loc = jnp.clip(expert_idx - lo, 0, K_loc - 1)
            # 2. owner-local retrieval with the unchanged per-device kernel
            loc = _serve_topk_local(
                tbl, h, e_loc, g, k, local_kernel,
                capacity_factor=capacity_factor, owned=owned,
                n_experts_global=K_pad, with_stats=with_stats,
            )
            vals, ids_out = loc[0], loc[1]
        # 3. O(B·k) merge: gather the carries, select each token's owner
        vals_all = jax.lax.all_gather(vals, "model")      # (ep, B_loc, k)
        ids_all = jax.lax.all_gather(ids_out, "model")
        owner = expert_idx // K_loc
        rows = jnp.arange(h.shape[0])
        if not with_stats:
            return vals_all[owner, rows], ids_all[owner, rows]
        disp, over = loc[2]["dispatched"], loc[2]["overflow"]  # (K_loc,)
        if bspec is not None:
            # token-sharded call site: each data shard counted its rows
            disp = jax.lax.psum(disp, bspec)
            over = jax.lax.psum(over, bspec)
        return vals_all[owner, rows], ids_all[owner, rows], disp, over

    out = P(bspec, None)
    stat = P("model")  # shards own disjoint K_loc expert rows → concat
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), _table_pspecs(table), P(bspec, None)),
        out_specs=(out, out) + ((stat, stat) if with_stats else ()),
        check_vma=False,
    )
    res = fn(gate_w, table, h)
    if not with_stats:
        return res
    vals, ids_out, disp, over = res
    return vals, ids_out, {"dispatched": disp, "overflow": over}


def serve_full_probs(
    gate_w: jax.Array, table: ServeTable, h: jax.Array, n_classes: int
) -> jax.Array:
    """Full sparse categorical distribution (probability mass only on the
    chosen expert's surviving classes). For evaluation/debug. (B, N)."""
    table = as_serve_table(table)
    expert_idx, g, _ = top1_gate(gate_w, h)
    if isinstance(table, QuantizedServeTable):
        z, ids_sel = _exact_rows_logits(table, expert_idx, h.astype(jnp.float32))
        z = z * g[:, None]
    else:
        w_sel = table.weights[expert_idx]
        ids_sel = table.ids[expert_idx]
        z = jnp.einsum("bvd,bd->bv", w_sel.astype(jnp.float32), h.astype(jnp.float32)) * g[:, None]
    z = jnp.where(ids_sel >= 0, z, NEG_INF)
    p = jax.nn.softmax(z, axis=-1)
    out = jnp.zeros((h.shape[0], n_classes), jnp.float32)
    out = out.at[jnp.arange(h.shape[0])[:, None], jnp.maximum(ids_sel, 0)].add(
        jnp.where(ids_sel >= 0, p, 0.0)
    )
    return out
