"""The program's Mamba-2 serving path against ``reference/mamba2.py``, on
the CPU at a tiny size: chunked prefill, then decode through the cache,
agree in logits with the reference's full forward on seeded weights."""
from __future__ import annotations

import numpy as np
import pytest

SEED = 2 ** 31 + 515
PROMPT, NEW = 41, 300       # three prefill chunks of 16, the last partial; then decode
# The program runs bfloat16 activations and matrices with float32
# accumulation and a float32 state; the reference runs float32 from the
# same bfloat16 weights. The served top-8 logits (about 1-2 in size)
# stay within 0.02 of the reference's on the seeded weights, and within
# 0.032 where every head keeps its state for the whole sequence (SLOW);
# there a state kept in bfloat16 loses the small increments and reads
# 0.27. The tolerance lies between, 2.5x above the one and 3.4x below
# the other.
LOGIT_TOL = 0.08


def _slow_state(w):
    """Weights on which the recurrent state carries each block's output
    over the whole sequence: no skip (D = 0), A = -1, dt = 0.002."""
    import jax.numpy as jnp

    dt = 0.002
    return dict(w, D=jnp.zeros_like(w["D"]), A_log=jnp.zeros_like(w["A_log"]),
                dt_bias=jnp.full_like(w["dt_bias"], dt + np.log(-np.expm1(-dt))))


WEIGHTS = {"seeded": lambda w: w, "slow_state": _slow_state}


def _served_logits(monkeypatch, weights, state_dtype):
    """One greedy request through ``ServeSession``: the prompt, the
    served tokens, and the head's (vals, ids) behind each of them. The
    weights are the reference's ``init`` passed through ``weights``, for
    the program and the reference alike."""
    import harness
    import tiny
    from generators.common import Spec
    from reference import mamba2
    from repro.train import serve

    init = mamba2.init
    monkeypatch.setattr(mamba2, "init", lambda k, s: WEIGHTS[weights](init(k, s)))
    seen = []
    orig = serve.ServeSession._sample

    def sample(self, vals, ids, sp, n_emitted):
        seen.append((np.array(vals), np.array(ids)))
        return orig(self, vals, ids, sp, n_emitted)

    monkeypatch.setattr(serve.ServeSession, "_sample", sample)
    cell = tiny.mamba2_cell()
    cell.traffic["max_seq_len"] = PROMPT + NEW + 16
    server = harness.build_server(cell, SEED, None)
    sess = server.session
    if state_dtype is not None:     # the state stored in a lower precision
        rnd = lambda c: c._replace(ssm=c.ssm.astype(state_dtype).astype(c.ssm.dtype))
        dec, chunk = sess._decode_fn, sess._chunk_fn
        sess._decode_fn = lambda *a: (lambda o: (o[0], o[1], rnd(o[2])) + o[3:])(dec(*a))
        sess._chunk_fn = lambda *a: (lambda o: (o[0], o[1], rnd(o[2])))(chunk(*a))
    prompt = np.random.default_rng(SEED).integers(0, cell.sizes["vocab"], PROMPT,
                                                  dtype=np.int32)
    drv = harness.Runner(cell, SEED, 1.0)
    drv.server = server
    drv._submit(Spec(prompt, NEW), harness.Record())
    while sess.step():
        pass
    out = np.array(drv.by_req.popitem()[1].req.out_tokens, np.int32)
    assert len(out) == NEW == len(seen)
    return cell, prompt, out, seen


def _gap_to_reference(cell, prompt, out, seen):
    """The widest distance between a served top-8 logit and the
    reference's gated logit of the same class in the same expert (the
    one that holds all eight: the program's choice, which a near-tie of
    the gate may make another than the reference's)."""
    import jax
    import harness
    from reference import ds_head

    s, ref = cell.sizes, cell.reference
    w = ref.init(harness.key_for(SEED, 1), s)
    seq = np.concatenate([prompt, out[:-1]])[None]
    h = np.asarray(ref.hidden(w, s, seq))[0, len(prompt) - 1:]
    gate, table, ids, rows = harness.head_tables(cell, SEED)
    gl, _, z = jax.jit(ds_head._logits, static_argnums=4)(h, gate, table, ids, False)
    gl, z, rows = np.asarray(gl), np.asarray(z), np.asarray(rows)
    worst = 0.0
    for j, (vals, cls) in enumerate(seen):
        holds = np.nonzero((rows[:, cls] >= 0).all(axis=1))[0]
        e = holds[np.argmax(gl[j, holds])]
        worst = max(worst, float(np.abs(vals - z[j, e, rows[e, cls]]).max()))
    return worst


@pytest.mark.parametrize("weights", sorted(WEIGHTS))
def test_chunked_prefill_then_decode_agree_with_the_reference(monkeypatch, weights):
    assert _gap_to_reference(*_served_logits(monkeypatch, weights, None)) < LOGIT_TOL


def test_a_bfloat16_state_fails_the_tolerance(monkeypatch):
    """The same comparison is tight enough to see the state kept in
    bfloat16, where the state carries the output."""
    import jax.numpy as jnp

    gap = _gap_to_reference(*_served_logits(monkeypatch, "slow_state", jnp.bfloat16))
    assert gap > LOGIT_TOL
