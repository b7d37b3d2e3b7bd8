"""The decode step updates the shared KV cache in place.

``transformer.decode_step`` carries the stacked ``(L, ...)`` cache through
its layer scan and writes only each layer's new rows; ``ServeSession``
donates the cache to every program whose result replaces it. So what the
decode program allocates does not grow with depth, its output
aliases the cache it was given, and the buffers a step was handed are
gone after it. The math is the old slab-wise one: a plain per-layer
Python loop over single-layer slabs gives the same top-k and cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.core import dssoftmax as ds
from repro.models import build, heads, transformer
from repro.models.layers import (attention_decode, attention_prefill_chunk,
                                 embed, rmsnorm)
from repro.train import Request, SamplingParams, ServeSession

N_SLOTS, MAX_SEQ = 8, 2048
TOP_K = 8


def _cfg(n_layers):
    base = get_config("qwen2-1.5b")
    return reduce_config(base, vocab=128).replace(
        n_layers=n_layers, dtype="float32",
        ds=base.ds.replace(num_experts=4))


def _model(n_layers):
    bundle = build(_cfg(n_layers))
    params, ds_state = bundle.init(jax.random.PRNGKey(0))
    return bundle, params, ds.pack_experts(params["head"], ds_state)


def _session(n_layers, paged):
    bundle, params, table = _model(n_layers)
    kw = dict(page_size=64) if paged else {}
    return ServeSession(bundle, params, table, n_slots=N_SLOTS,
                        max_seq_len=MAX_SEQ, kernel="jnp", paged=paged,
                        prefill_chunk=64, **kw)


def _decode_args(sess):
    args = [sess.params, sess.table, sess._cache, jnp.asarray(sess._tok),
            jnp.asarray(sess._pos)]
    if sess._mgr is not None:
        args += [jnp.asarray(sess._mgr.tables),
                 jnp.asarray(sess._mgr.state_pid)]
    return args


def _nbytes(tree):
    return sum(x.nbytes for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("paged", [False, True], ids=["contiguous", "paged"])
def test_decode_cache_updated_in_place(paged):
    sessions = {L: _session(L, paged) for L in (2, 6)}
    mem = {L: s._decode_fn.lower(*_decode_args(s)).compile().memory_analysis()
           for L, s in sessions.items()}
    # what the program allocates beyond its arguments: scratch, and outputs
    # not written into a donated argument. It must not grow with depth by
    # as much as one layer's K + V slab, which a rebuilt cache costs.
    own = {L: m.temp_size_in_bytes + m.output_size_in_bytes
           - m.alias_size_in_bytes for L, m in mem.items()}
    per_layer = _nbytes(sessions[2]._cache) // 2
    assert abs(own[6] - own[2]) < per_layer, (own, per_layer)
    for L, s in sessions.items():
        assert mem[L].alias_size_in_bytes == _nbytes(s._cache), (L, mem[L])

    # the buffers a program was handed are gone once it has run
    sess = sessions[2]
    before = jax.tree.leaves(sess._cache)
    sess.submit(Request(prompt=np.arange(1, 70, dtype=np.int32),
                        sampling=SamplingParams(max_new_tokens=4)))
    sess.step()  # admits: chunked prefill and insert, then one decode
    assert all(x.is_deleted() for x in before)
    before = jax.tree.leaves(sess._cache)
    sess.step()
    assert all(x.is_deleted() for x in before)
    assert sess._decode_fn._cache_size() == 1


# ---------------------------------------------------------------------------
# the carried scan against the slab-wise math, layer by layer
# ---------------------------------------------------------------------------

B, S_CACHE, PAGE = 3, 32, 8


def _loop_reference(params, table, cfg, cache, tokens, pos, pages, program):
    """Per-layer Python loop over single-layer slabs, then the head: the
    decode (``tokens`` (B,)) or chunk (``tokens`` (B, C)) step as it ran
    before the cache rode in the scan carry."""
    x = embed(params["embed"], tokens)
    if program == "decode":
        x = x[:, None, :]
    ks, vs = [], []
    for layer in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        attend = attention_decode if program == "decode" \
            else attention_prefill_chunk
        h, nk, nv = attend(lp["attn"], cfg, rmsnorm(lp["ln1"], x),
                           cache.k[layer], cache.v[layer], pos, pages=pages)
        x = x + h
        x = x + transformer._mlp_sublayer(lp, cfg, x)
        ks.append(nk)
        vs.append(nv)
    h = rmsnorm(params["final_norm"], x)[:, -1]
    vals, ids = heads.head_topk(params["head"], table, cfg, h, TOP_K,
                                embed_table=params["embed"]["table"],
                                kernel="jnp")
    return vals, ids, transformer.DecodeCache(k=jnp.stack(ks),
                                              v=jnp.stack(vs))


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("form", ["scalar_pos", "per_row_pos", "paged"])
def test_carried_scan_matches_per_layer_loop(program, form):
    bundle, params, table = _model(3)
    cfg = bundle.cfg
    KV, dh, L = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    rng = np.random.RandomState(0)
    C = 1 if program == "decode" else 4
    pages = None
    if form == "paged":
        n_pg = S_CACHE // PAGE
        # every slot maps pages of its own; page 0 is mapped by none
        pages = jnp.asarray(
            1 + rng.permutation(B * n_pg).reshape(B, n_pg).astype(np.int32))
        shape = (L, 1 + B * n_pg, PAGE, KV, dh)
    else:
        shape = (L, B, S_CACHE, KV, dh)
    cache = transformer.DecodeCache(
        k=jnp.asarray(rng.randn(*shape), jnp.float32),
        v=jnp.asarray(rng.randn(*shape), jnp.float32))
    pos = jnp.int32(9) if form == "scalar_pos" \
        else jnp.asarray(rng.randint(0, S_CACHE - C, B).astype(np.int32))
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, C)), jnp.int32)

    if program == "decode":
        tokens = tokens[:, 0]
        step = jax.jit(lambda c, t, p, pg: bundle.decode_step(
            params, table, c, t, p, k=TOP_K, kernel="jnp", pages=pg))
    else:
        step = jax.jit(lambda c, t, p, pg: bundle.verify_step(
            params, table, c, t, p, k=TOP_K, kernel="jnp", pages=pg))
    ref = jax.jit(lambda c, t, p, pg: _loop_reference(
        params, table, cfg, c, t, p, pg, program))

    vals, ids, new = step(cache, tokens, pos, pages)
    rvals, rids, rnew = ref(cache, tokens, pos, pages)
    vals, ids = vals.reshape(B, -1, TOP_K)[:, -1], ids.reshape(B, -1, TOP_K)[:, -1]
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals),
                               rtol=1e-6, atol=1e-6)
    for got, want in zip(new, rnew):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
