"""DS-Softmax head (arXiv:1901.10668): a sparse mixture of sparse experts.

A gate ``U`` (K, d) routes each hidden state ``h`` to its top expert
``e = argmax softmax(U h)``; the expert holds a subset of the classes
(rows ``W_e``), and the head's logits are ``g_e * (W_e h)`` over that
subset, ``g_e`` the un-renormalised gate value.

The served table is made here from the seed, as a trained and pruned head
would leave it: every class lives in ``redundancy`` distinct experts
(the paper's mean redundancy is about 2, Fig. 5b), which the seed picks,
and each expert's classes are packed in ascending order into rows padded
to ``v_pad``, a multiple of 128. No raw (K, N, d) head ever exists.

``gaps`` is the comparison that decides ``correct``: for each served
token, how far the reference places it below its own choice.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from reference.common import mm, normal


def assign(seed: int, K: int, vocab: int, redundancy: int):
    """Host-side class→expert assignment: ``ids`` (K, v_pad) int32 class
    per packed row (-1 padding) and ``rows`` (K, vocab) int32 packed row
    of each class in each expert (-1 where the expert lacks it).

    The i-th class of a seeded permutation goes to experts
    ``(i + j * (1 + (i // K) % (K - 1))) % K`` for j < ``redundancy``
    (distinct while ``redundancy`` <= 2, or K prime): every expert holds
    about ``redundancy * vocab / K`` classes, and how many depends on the
    sizes alone, so ``v_pad`` and every compiled shape are the same for
    every seed. The seed decides which classes share an expert."""
    if redundancy > 2 and any(K % p == 0 for p in range(2, K)):
        raise ValueError("redundancy > 2 needs a prime number of experts")
    perm = np.random.default_rng([int(seed) & (2 ** 64 - 1), 0xD5]).permutation(vocab)
    i = np.arange(vocab)
    step = 1 + (i // K) % (K - 1)
    member = np.zeros((K, vocab), bool)
    for j in range(redundancy):
        member[(i + j * step) % K, perm] = True
    counts = member.sum(1)
    v_pad = int(-(-counts.max() // 128) * 128)
    ids = np.full((K, v_pad), -1, np.int32)
    rows = np.full((K, vocab), -1, np.int32)
    for e in range(K):
        cls = np.nonzero(member[e])[0]
        ids[e, : len(cls)] = cls
        rows[e, cls] = np.arange(len(cls), dtype=np.int32)
    return ids, rows


def init(key, K: int, v_pad: int, d: int, valid):
    """Gate (K, d) and packed expert rows (K, v_pad, d) in bfloat16, zero
    on padding rows; ``valid`` is ``ids >= 0``."""
    kg, kw = jax.random.split(key)
    gate = normal(kg, (K, d), d ** -0.5)
    w = normal(kw, (K, v_pad, d), d ** -0.5)
    return gate, jnp.where(valid[..., None], w, 0).astype(w.dtype)


def _logits(h, gate, w, ids, fp8):
    """Gate logits (T, K), gate values (T, K) and every expert's gated
    logits (T, K, v_pad), -inf on padding rows."""
    gl = mm(h, gate.T, fp8)
    G = jax.nn.softmax(gl, axis=-1)
    z = jnp.stack([mm(h, w[e].T, fp8) for e in range(w.shape[0])], axis=1)
    z = jnp.where(ids[None] >= 0, z * G[..., None], -jnp.inf)
    return gl, G, z


@jax.jit
def gaps(h, served, gate, w, ids, rows):
    """Per position, how far the reference ranks ``served`` below its own
    choice. For each expert e holding the token, two logit gaps: the
    gate's (best gate logit minus e's) and the class's (the best gated
    logit in e minus the token's). A position reads the smaller, over the
    experts holding the token, of the larger of its two gaps: 0 when the
    reference serves the same token, small when a near-tie decided it
    the other way, large or inf when it is wrong. h: (T, d) float32."""
    gl, _, z = _logits(h, gate, w, ids, False)
    gate_gap = jnp.max(gl, axis=1, keepdims=True) - gl            # (T, K)
    r = rows[:, served].T                                           # (T, K)
    z_t = jnp.take_along_axis(z, jnp.maximum(r, 0)[..., None], axis=2)[..., 0]
    tok_gap = jnp.where(r >= 0, jnp.max(z, axis=2) - z_t, jnp.inf)
    return jnp.min(jnp.maximum(gate_gap, tok_gap), axis=1)


@jax.jit
def control_tokens(h, gate, w, ids):
    """The token the float8 control puts first at each position: its own
    gate choice, then its best class there. h: (T, d)."""
    gl, _, z = _logits(h, gate, w, ids, True)
    e = jnp.argmax(gl, axis=1)
    zc = jnp.take_along_axis(z, e[:, None, None], axis=1)[:, 0]    # (T, v_pad)
    return jnp.take_along_axis(ids[e], jnp.argmax(zc, axis=1)[:, None], 1)[:, 0]

