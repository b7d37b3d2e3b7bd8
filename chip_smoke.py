#!/usr/bin/env python3
"""Smoke run of the DS-Softmax serving path on TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the multi-chip serving paths only

One chip: mamba2-130m at full width (24 layers, d_model 768, vocab 50280,
K=8 experts; random weights from PRNGKey(0)) served through
``ServeSession`` with a contiguous cache, 8 slots, 64-token prefill chunks
and the default ``'auto'`` head kernel. Sixteen seeded requests (prompts
of 32-200 tokens, 32 new tokens each; 14 greedy, 2 sampled at temperature
0.8 with top_k 8) must all complete with no breaker trip and one decode
compile. Each local Pallas head path is then checked at full width
against ``kernel='jnp'``, and must have compiled to a Mosaic kernel
(``tpu_custom_call``), not the interpreter. Last, the greedy requests run
in a session forced to ``pallas_fused`` and in a ``jnp`` session, and
their tokens are compared.

Four chips: the expert-parallel session (``mesh=1x4``, two experts per
chip) and the FSDP session (``param_mode='fsdp'`` on ``2x2``), each
compared token for token with a single-chip ``jnp`` session.

The times it prints are those of a smoke run, not a benchmark. The script
exits non-zero without a TPU, and its last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import dssoftmax as ds  # noqa: E402
from repro.core.gating import gate_values  # noqa: E402
from repro.kernels.registry import AutoPolicy  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import parse_mesh  # noqa: E402
from repro.models import build  # noqa: E402
from repro.train import Request, RequestStatus, SamplingParams, ServeSession  # noqa: E402

ARCH = "mamba2-130m"
N_SLOTS = 8
PREFILL_CHUNK = 64
MAX_SEQ_LEN = 256          # 200-token prompt rounded up to whole chunks
NEW_TOKENS = 32
N_REQUESTS, N_GREEDY = 16, 14
K = 8                      # top-k the head returns
N_HEAD_ROWS = 256          # hidden states in the per-kernel head check
REF_ROWS = 32              # of them per jnp reference call (2.5 GB gather)
LOCAL_PALLAS = ("pallas", "pallas_grouped", "pallas_fused")
# Two head paths accumulate the same bf16 products in different orders,
# so ids may swap only where jnp's neighbouring values lie within this
# relative distance of each other.
TIE_RTOL = 1e-3
# A greedy divergence is replayed through whole-prompt prefill, whose bf16
# backbone rounds differently from the decode recurrence; the two tokens'
# replayed logits must lie within this relative distance.
REPLAY_RTOL = 1e-2


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


class CompileLog:
    """Backend compiles seen through ``jax.monitoring``: the number per
    jitted function, their seconds, and persistent-cache hits."""

    def __init__(self):
        self.count = collections.Counter()
        self.secs = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count[kw.get("fun_name")] += 1
            self.secs += secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_bytes(key: str) -> str:
    """``memory_stats()[key]`` of every device, as ``id:bytes`` pairs."""
    return ", ".join(f"{d.id}:{d.memory_stats()[key]}" for d in jax.devices())


def make_specs(vocab: int, seed: int = 0):
    """(prompt, SamplingParams) pairs: 14 greedy, then 2 sampled."""
    rng = np.random.RandomState(seed)
    specs = []
    for i in range(N_REQUESTS):
        prompt = rng.randint(0, vocab, int(rng.randint(32, 201))).astype(np.int32)
        if i < N_GREEDY:
            sp = SamplingParams(max_new_tokens=NEW_TOKENS)
        else:
            sp = SamplingParams(max_new_tokens=NEW_TOKENS, temperature=0.8,
                                top_k=8, seed=i)
        specs.append((prompt, sp))
    return specs


def serve(label, bundle, params, state_or_table, specs, log, *, kernel, **kw):
    """Run ``specs`` through a fresh session; checks every request
    completed, no breaker trip, and one decode compile."""
    n0, s0, h0 = log.count["jit(_decode)"], log.secs, log.cache_hits
    sess = ServeSession(bundle, params, state_or_table, n_slots=N_SLOTS,
                        max_seq_len=MAX_SEQ_LEN, prefill_chunk=PREFILL_CHUNK,
                        kernel=kernel, **kw)
    t0 = time.perf_counter()
    out = sess.run([Request(prompt=p, sampling=s) for p, s in specs])
    wall = time.perf_counter() - t0
    st = sess.stats()
    n_tok = sum(len(r.out_tokens) for r in out)
    n_decode = log.count["jit(_decode)"] - n0
    print(f"[{label}] smoke run, not a benchmark: {len(out)} requests, "
          f"{n_tok} tokens in {wall:.3f} s wall (compiles included); "
          f"compile {log.secs - s0:.3f} s, persistent-cache hits "
          f"{log.cache_hits - h0}; decode compiles {n_decode}")
    bad = [r.status.name for r in out if r.status is not RequestStatus.COMPLETED]
    check(not bad, f"[{label}] requests not COMPLETED: {bad}")
    check(st["breaker_trips"] == 0, f"[{label}] breaker tripped")
    check(st["n_failed"] == 0, f"[{label}] non-finite quarantine")
    check(n_decode == 1, f"[{label}] decode compiled {n_decode} times")
    return sess, [list(r.out_tokens) for r in out]


def print_resolved(label, history):
    sites = {N_SLOTS: "decode", 1: "prefill chunk (head on the last token)"}
    for b, name in history:
        print(f"[{label}] kernel at {sites.get(b, 'call site')} B={b}: {name}")


def compare_greedy(label, bundle, params, table, specs, ref, got):
    """Token agreement of two greedy streams; a first divergence must be
    a replayed near-tie of the two tokens."""
    same = total = 0
    first = None
    for r, (a, b) in enumerate(zip(ref, got)):
        n = min(len(a), len(b))
        total += max(len(a), len(b))
        d = next((t for t in range(n) if a[t] != b[t]), n)
        same += d
        if d < max(len(a), len(b)) and (first is None or d < first[1]):
            first = (r, d)
    print(f"[{label}] greedy token agreement: {same}/{total} "
          "(tokens before each request's first divergence)")
    if first is None:
        return
    r, t = first
    seq = np.concatenate([specs[r][0], np.asarray(ref[r][:t], np.int32)])
    vals, ids, _ = jax.jit(partial(bundle.prefill, k=K, kernel="jnp"))(
        params, table, {"tokens": jnp.asarray(seq[None])})
    vals, ids = np.asarray(vals[0]), np.asarray(ids[0])
    va = vals[ids == ref[r][t]]
    vb = vals[ids == got[r][t]]
    gap = (float(abs(va[0] - vb[0]) / max(abs(va[0]), 1e-30))
           if len(va) and len(vb) else float("inf"))
    print(f"[{label}] first divergence: request {r} step {t}: "
          f"{ref[r][t]} vs {got[r][t]}, replayed jnp logit gap {gap:.3e} "
          f"relative (tolerance {REPLAY_RTOL})")
    check(gap <= REPLAY_RTOL, f"[{label}] divergence is not a near-tie")


def check_head(gate, table, d, dtype):
    """Each local Pallas path against ``jnp`` on seeded hidden states."""
    h = jax.random.normal(jax.random.PRNGKey(1), (N_HEAD_ROWS, d), dtype)
    # jnp gathers a (B, V_pad, d) copy of the rows: 32 tokens at a time
    ref = jax.jit(partial(ds.serve_topk, k=K + 1, kernel="jnp"))
    parts = [ref(gate, table, h[i:i + REF_ROWS])
             for i in range(0, N_HEAD_ROWS, REF_ROWS)]
    rv = np.concatenate([np.asarray(v) for v, _ in parts])
    ri = np.concatenate([np.asarray(i) for _, i in parts])
    gap = np.abs(np.diff(rv, axis=1)) / np.maximum(
        np.maximum(np.abs(rv[:, :-1]), np.abs(rv[:, 1:])), 1e-30)
    sep = gap > TIE_RTOL                              # (B, K): rank j vs j+1
    decided = sep & np.concatenate([np.ones((len(rv), 1), bool), sep[:, :-1]], 1)
    # a token whose two best experts near-tie may route either way
    gv = np.sort(np.asarray(gate_values(gate, h)), axis=1)
    routed = (gv[:, -1] - gv[:, -2]) > TIE_RTOL * gv[:, -1]
    decided &= routed[:, None]
    rv, ri = rv[:, :K], ri[:, :K]
    print(f"head check: {N_HEAD_ROWS} rows x top-{K} at full width, "
          f"{int((~decided).sum())} near-tie positions excluded")
    for name in LOCAL_PALLAS:
        compiled = jax.jit(partial(ds.serve_topk, k=K, kernel=name)).lower(
            gate, table, h).compile()
        mosaic = "tpu_custom_call" in compiled.as_text()
        v, i = (np.asarray(x) for x in compiled(gate, table, h))
        ids_ok = bool((i == ri)[decided].all())
        dv = float((np.abs(v - rv) / np.maximum(np.abs(rv), 1e-30)).max())
        print(f"head {name}: tpu_custom_call {mosaic}, ids equal outside "
              f"near-ties {ids_ok} ({int((i == ri).sum())}/{i.size} equal "
              f"in all), max relative value error {dv:.3e}")
        check(mosaic, f"head {name} did not compile to a Mosaic kernel")
        check(ids_ok, f"head {name} top-k ids differ from jnp")
        check(dv <= TIE_RTOL, f"head {name} values differ from jnp")


def one_chip(log):
    cfg = get_config(ARCH)
    bundle = build(cfg)
    params, ds_state = bundle.init(jax.random.PRNGKey(0))
    specs = make_specs(cfg.vocab_size)
    greedy = specs[:N_GREEDY]

    history = []
    sess, _ = serve("auto", bundle, params, ds_state, specs, log,
                    kernel=AutoPolicy(history=history))
    print_resolved("auto", history)
    table = sess.table
    del sess

    check_head(params["head"]["gate"], table, cfg.d_model, cfg.jdtype)

    _, ref = serve("jnp", bundle, params, table, greedy, log, kernel="jnp")
    _, got = serve("pallas_fused", bundle, params, table, greedy, log,
                   kernel="pallas_fused")
    compare_greedy("pallas_fused vs jnp", bundle, params, table, greedy, ref, got)


def four_chips(log):
    check(len(jax.devices()) >= 4, "--four-chips needs 4 devices")
    cfg = get_config(ARCH)
    bundle = build(cfg)
    params, ds_state = bundle.init(jax.random.PRNGKey(0))
    greedy = make_specs(cfg.vocab_size)[:N_GREEDY]

    sess, ref = serve("1 chip jnp", bundle, params, ds_state, greedy, log,
                      kernel="jnp")
    table = sess.table
    del sess
    for label, mesh, mode in (("ep 1x4", "1x4", "replicated"),
                              ("fsdp 2x2", "2x2", "fsdp")):
        history = []
        sess, got = serve(label, bundle, params, table, greedy, log,
                          kernel=AutoPolicy(history=history),
                          mesh=parse_mesh(mesh), param_mode=mode)
        print_resolved(label, history)
        print(f"[{label}] bytes_in_use per device: {device_bytes('bytes_in_use')}")
        del sess
        compare_greedy(f"{label} vs 1 chip jnp", bundle, params, table,
                       greedy, ref, got)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip serving paths")
    args = ap.parse_args()

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devs[0].platform}", file=sys.stderr)
        sys.exit(1)
    cache = use_compile_cache()
    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"compile cache {cache}")
    log = CompileLog()
    t0 = time.perf_counter()
    (four_chips if args.four_chips else one_chip)(log)
    print(f"total: {time.perf_counter() - t0:.3f} s wall, compile "
          f"{log.secs:.3f} s over {sum(log.count.values())} programs, "
          f"persistent-cache hits {log.cache_hits}")
    print(f"peak_bytes_in_use per device: {device_bytes('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
