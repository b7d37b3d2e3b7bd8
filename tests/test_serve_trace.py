"""Host spans and named scopes of the serving path, read back the way a
profiler trace reader sees them.

* Under ``jax.profiler`` every ``ServeSession.step()`` records one
  ``serve.step``, each decode one ``serve.decode.dispatch`` and one
  ``serve.decode.fetch`` inside it, and each admitted request one
  ``serve.prefill`` (inside ``serve.admit``) holding one
  ``serve.prefill.fetch``.
* The decode and prefill-chunk programs carry the ``layers``, ``attn``,
  ``attn/kv_write``, ``mlp`` and ``ds_head`` scopes in their HLO
  ``op_name`` metadata.
* ``stats()['prefill_chunks']`` counts the chunks of the contiguous path.
* Tracing changes no token.
"""
import glob
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduce_config
from repro.models import build
from repro.train import Request, SamplingParams, ServeSession

CHUNK = 4
PROMPT_LENS = (5, 8, 3, 11)


@pytest.fixture(scope="module")
def tiny():
    cfg = reduce_config(get_config("qwen2-1.5b"), vocab=128)
    cfg = cfg.replace(ds=get_config("qwen2-1.5b").ds.replace(num_experts=4))
    bundle = build(cfg)
    params, ds_state = bundle.init(jax.random.PRNGKey(0))
    return bundle, params, ds_state


def _session(tiny, prefill_chunk=CHUNK):
    bundle, params, ds_state = tiny
    return ServeSession(bundle, params, ds_state, n_slots=2, max_seq_len=32,
                        prefill_chunk=prefill_chunk)


def _requests(vocab=128):
    rng = np.random.RandomState(3)
    return [Request(prompt=rng.randint(1, vocab, n).astype(np.int32),
                    sampling=SamplingParams(max_new_tokens=3 + i % 3))
            for i, n in enumerate(PROMPT_LENS)]


def _drive(sess, reqs) -> int:
    """Submit ``reqs`` and step until done; returns the step() calls."""
    for r in reqs:
        sess.submit(r)
    calls = 1
    while sess.step():
        calls += 1
    return calls


def _traced(tiny, trace_dir, prefill_chunk=CHUNK):
    sess = _session(tiny, prefill_chunk)
    reqs = _requests()
    sess.submit(Request(prompt=np.arange(1, 4, dtype=np.int32),
                        sampling=SamplingParams(max_new_tokens=2)))
    while sess.step():   # compile outside the trace
        pass
    decodes0, chunks0 = sess.n_steps, sess.stats()["prefill_chunks"]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        calls = _drive(sess, reqs)
    finally:
        jax.profiler.stop_trace()
    return sess, reqs, calls, sess.n_steps - decodes0, \
        sess.stats()["prefill_chunks"] - chunks0


def _serve_spans(trace_dir):
    """``serve.*`` host spans as (name, start, end, stats) per host line."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("serve.")]
            if evs:
                lines.append(evs)
    assert len(lines) == 1, "serve.* spans expected on the session's thread only"
    return lines[0]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _parent(span, spans, name):
    cover = [s for s in spans if s[0] == name and _inside(span, s) and s is not span]
    assert len(cover) == 1, (span, name)
    return cover[0]


def test_serve_spans_one_per_step_decode_and_admission(tiny, tmp_path):
    sess, reqs, n_calls, n_decodes, n_chunks = _traced(tiny, tmp_path)
    spans = _serve_spans(tmp_path)
    by = lambda n: [s for s in spans if s[0] == n]

    assert len(by("serve.step")) == n_calls
    assert len(by("serve.admit")) == n_calls
    assert len(by("serve.decode.dispatch")) == n_decodes
    assert len(by("serve.decode.fetch")) == n_decodes
    assert len(by("serve.sample_emit")) == n_decodes
    assert len(by("serve.prefill")) == len(reqs)
    assert len(by("serve.prefill.fetch")) == len(reqs)
    assert not by("serve.adapt")      # no adapt policy set

    for s in by("serve.admit") + by("serve.decode.dispatch") \
            + by("serve.decode.fetch") + by("serve.sample_emit"):
        _parent(s, spans, "serve.step")
    for s in by("serve.prefill"):
        _parent(_parent(s, spans, "serve.admit"), spans, "serve.step")
    for s in by("serve.prefill.fetch"):
        _parent(s, spans, "serve.prefill")
    for s in by("serve.decode.fetch"):
        assert s[3]["arrays"] == 4
    assert sorted(s[3]["tokens"] for s in by("serve.prefill")) == sorted(PROMPT_LENS)
    assert {s[3]["slot"] for s in by("serve.prefill")} <= {0, 1}
    want = sorted(math.ceil(n / CHUNK) for n in PROMPT_LENS)
    assert sorted(s[3]["chunks"] for s in by("serve.prefill")) == want
    assert n_chunks == sum(want)
    steps = sorted(s[3]["step_num"] for s in by("serve.step"))
    assert steps[0] >= 0 and steps[-1] <= sess.n_steps


def _op_names(hlo_text: str) -> set:
    return set(part for line in hlo_text.splitlines()
               for part in line.split('op_name="')[1:2])


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_programs_carry_named_scopes(tiny, program):
    sess = _session(tiny)
    if program == "decode":
        low = sess._decode_fn.lower(
            sess.params, sess.table, sess._cache, jnp.asarray(sess._tok),
            jnp.asarray(sess._pos))
    else:
        low = sess._chunk_fn.lower(
            sess.params, sess.table, sess._row_zero,
            jnp.zeros((1, CHUNK), jnp.int32), 0, CHUNK)
    names = _op_names(low.compile().as_text())
    for scope in ("/layers/", "/attn/", "/attn/kv_write/", "/mlp/", "/ds_head/",
                  "/embed/", "/final_norm/"):
        assert any(scope in n for n in names), (scope, sorted(names)[:20])
    # attention and MLP sit inside the layer scan, the head outside it
    assert all("/layers/" in n for n in names if "/attn/" in n or "/mlp/" in n)
    assert not any("/layers/" in n for n in names if "/ds_head/" in n)


@pytest.mark.parametrize("prefill_chunk", [3, CHUNK])
def test_prefill_chunks_counted_on_contiguous_path(tiny, prefill_chunk):
    sess = _session(tiny, prefill_chunk)
    assert sess.stats()["prefill_chunks"] == 0
    _drive(sess, _requests())
    assert "paged" not in sess.stats()
    assert sess.stats()["prefill_chunks"] == sum(
        math.ceil(n / prefill_chunk) for n in PROMPT_LENS)


def test_whole_prompt_prefill_counts_no_chunks(tiny):
    sess = _session(tiny, prefill_chunk=None)
    _drive(sess, _requests())
    assert sess.stats()["prefill_chunks"] == 0


def test_tokens_identical_with_profiler_on_and_off(tiny, tmp_path):
    _, traced, _, _, _ = _traced(tiny, tmp_path)
    plain_sess = _session(tiny)
    plain_sess.submit(Request(prompt=np.arange(1, 4, dtype=np.int32),
                              sampling=SamplingParams(max_new_tokens=2)))
    while plain_sess.step():
        pass
    plain = _requests()
    _drive(plain_sess, plain)
    assert [r.out_tokens for r in traced] == [r.out_tokens for r in plain]
    assert all(len(r.out_tokens) == r.sampling_params.max_new_tokens
               for r in plain)
