"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--reduced]``.

Drives a continuous-batching :class:`~repro.train.ServeSession`: more
requests than ``--slots`` exercises mid-flight slot reuse (finished
requests free their slot, queued prompts prefill into it). Reports the
per-outcome counts from ``session.stats()`` and exits non-zero if any
request ``FAILED`` (runtime fault — quarantined slot or raising
callback), so a scripted smoke run surfaces poisoned serving. With
``--paged`` the session serves from the fixed-size-page KV/state arena
and the report adds the page-arena metrics: pages in use, copy-on-write
copies, preemptions, and the shared-prefix hit rate / prefill chunks
saved (give it ``--shared-prefix N --prefill-chunk C`` so there is a
common system prompt to share). ``--quantize int8`` serves the DS table
from int8 rows under the exactness gate and prints the gate report
(exits non-zero when unguarded id flips survive the fallback).
``--draft <arch> --gamma G`` turns on exact speculative decoding: the
draft proposes G tokens per slot per step, the target verifies every
resident's block in one batched chunk-shaped step, and the report adds
accepted-tokens/step and the acceptance rate.
"""
import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import get_config, reduce_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import build
from repro.train import (
    AdaptPolicy,
    Request,
    RequestStatus,
    SamplingParams,
    ServeSession,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8, help="number of requests")
    ap.add_argument("--slots", type=int, default=4, help="decode slots")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--kernel", default=None,
                    help="serve kernel/policy name (default: cfg 'auto')")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill-into-slots: one compiled prefill "
                         "for every prompt length (all families but encdec)")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="expert-parallel serving mesh, e.g. 1x8: slots "
                         "shard over data, the DS expert table over model "
                         "(CPU: set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N before launch)")
    ap.add_argument("--param-mode", default="replicated",
                    choices=("replicated", "fsdp"),
                    help="fsdp (requires --mesh): store backbone weights "
                         "sharded over the mesh's data axis and gather "
                         "them per layer, just in time, inside the step "
                         "(~data-way lower per-device param bytes, "
                         "token-identical output)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="bound the admission queue: overflow sheds the "
                         "lowest-priority / newest request (REJECTED) "
                         "instead of growing without bound")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="per-request deadline in decode steps: requests "
                         "still queued or decoding past it end TIMED_OUT")
    ap.add_argument("--paged", action="store_true",
                    help="fixed-size-page KV/state arena with copy-on-write "
                         "prefix sharing and priority preemption instead of "
                         "per-slot contiguous cache rows")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--page-arena", type=int, default=None,
                    help="allocatable KV pages; undersizing below "
                         "slots*max_seq_len/page_size turns overload into "
                         "preempt-and-requeue (default: contiguous capacity)")
    ap.add_argument("--state-arena", type=int, default=None,
                    help="allocatable ssm/hybrid state pages (paged mode)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable copy-on-write prefix sharing")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many shared system-prompt tokens to "
                         "every request so prefix sharing has work to do")
    ap.add_argument("--adapt", action="store_true",
                    help="traffic-adaptive serving: when the windowed "
                         "overflow rate exceeds --adapt-overflow-threshold, "
                         "repack the DS table to the observed traffic "
                         "(optional re-prune + selective mitosis of "
                         "overflowing experts) and hot-swap it between "
                         "steps — residents keep decoding, tokens "
                         "identical from the swap point")
    ap.add_argument("--adapt-interval", type=int, default=32,
                    help="decode steps between adaptation checks")
    ap.add_argument("--adapt-overflow-threshold", type=float, default=0.05,
                    help="windowed overflow rate that triggers a repack")
    ap.add_argument("--adapt-prune-gamma", type=float, default=None,
                    help="group-lasso gamma for re-pruning during repack "
                         "(default: no re-pruning)")
    ap.add_argument("--adapt-max-swaps", type=int, default=4,
                    help="cap on hot-swaps per session")
    ap.add_argument("--stats-window", type=int, default=128,
                    help="step-stamped per-expert stats window length "
                         "(what the adaptation loop reads)")
    ap.add_argument("--quantize", default=None, choices=("int8",),
                    help="serve the DS table from int8 rows + per-row fp32 "
                         "scales, under the exactness gate (experts whose "
                         "top-k ids flip vs the fp32 oracle beyond "
                         "--quantize-flip-threshold fall back to fp rows); "
                         "the gate report prints after the run and a "
                         "failing gate exits non-zero")
    ap.add_argument("--quantize-calib", type=int, default=256,
                    help="calibration activations drawn for the exactness "
                         "gate")
    ap.add_argument("--quantize-flip-threshold", type=float, default=0.0,
                    help="per-expert flip-rate bound before fp fallback "
                         "(0.0: measured-exact by construction; 1.0: pure "
                         "int8, no fallback)")
    ap.add_argument("--draft", default=None, metavar="ARCH",
                    help="speculative decoding: a (small) zoo config to "
                         "propose --gamma tokens per slot per step, "
                         "verified by the target in one batched "
                         "chunk-shaped step; reduced to the target's "
                         "vocab so token ids line up. Greedy output is "
                         "bit-identical to the non-speculative stream")
    ap.add_argument("--gamma", type=int, default=4,
                    help="draft tokens proposed per slot per speculative "
                         "step (verify block width is gamma+1)")
    args = ap.parse_args()
    if args.param_mode == "fsdp" and not args.mesh:
        ap.error("--param-mode fsdp requires --mesh")
    use_compile_cache()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import parse_mesh

        mesh = parse_mesh(args.mesh)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    bundle = build(cfg)
    params, ds_state = bundle.init(jax.random.PRNGKey(0))
    smax = args.prompt_len + args.new_tokens
    if args.prefill_chunk:
        # tail chunks write a full chunk of (masked) rows into the cache
        smax = max(smax, -(-args.prompt_len // args.prefill_chunk) * args.prefill_chunk)
    draft = None
    if args.draft:
        dcfg = get_config(args.draft)
        if args.reduced:
            dcfg = reduce_config(dcfg)
        # token ids must line up: force the draft head onto the target vocab
        if dcfg.vocab_size != cfg.vocab_size:
            dcfg = dcfg.replace(vocab_size=cfg.vocab_size)
        dbundle = build(dcfg)
        dparams, dstate = dbundle.init(jax.random.PRNGKey(1))
        draft = (dbundle, dparams, dstate)
        smax += args.gamma  # verify blocks may write gamma rows past the tip
    if args.paged:
        smax = -(-smax // args.page_size) * args.page_size
    session = ServeSession(
        bundle, params, ds_state,
        n_slots=min(args.slots, args.batch),
        max_seq_len=smax,
        kernel=args.kernel,
        mesh=mesh,
        param_mode=args.param_mode,
        prefill_chunk=args.prefill_chunk,
        queue_limit=args.queue_limit,
        paged=args.paged,
        page_size=args.page_size,
        page_arena=args.page_arena,
        state_arena=args.state_arena,
        prefix_sharing=not args.no_prefix_sharing,
        stats_window=args.stats_window,
        adapt_policy=(AdaptPolicy(
            interval=args.adapt_interval,
            overflow_threshold=args.adapt_overflow_threshold,
            prune_gamma=args.adapt_prune_gamma,
            max_swaps=args.adapt_max_swaps,
        ) if args.adapt else None),
        quantize=args.quantize,
        quantize_calib=args.quantize_calib,
        quantize_flip_threshold=args.quantize_flip_threshold,
        draft=draft,
        gamma=args.gamma,
    )
    rng = np.random.RandomState(0)
    sysp = rng.randint(0, cfg.vocab_size,
                       args.shared_prefix).astype(np.int32)
    tail_len = max(1, args.prompt_len - args.shared_prefix)
    reqs = [
        Request(prompt=np.concatenate(
                    [sysp, rng.randint(0, cfg.vocab_size,
                                       tail_len).astype(np.int32)]),
                sampling=SamplingParams(max_new_tokens=args.new_tokens,
                                        deadline_steps=args.deadline_steps,
                                        priority=int(rng.rand() < 0.25)))
        for _ in range(args.batch)
    ]
    t0 = time.time()
    out = session.run(reqs)
    dt = time.time() - t0
    n = sum(len(r.out_tokens) for r in out)
    stats = session.stats()
    print(f"{n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s; "
          f"{stats['n_admitted']} admits over {session.n_slots} slots)")
    print("outcomes: " + ", ".join(
        f"{k.removeprefix('n_')}={stats[k]}"
        for k in ("n_completed", "n_rejected", "n_cancelled",
                  "n_timed_out", "n_failed", "n_shed")))
    if args.paged:
        pg = stats["paged"]
        print(f"paged arena: {pg['pages_in_use']}/{pg['pages_total']} pages "
              f"in use (page_size={pg['page_size']}), "
              f"cow_copies={pg['cow_copies']}, "
              f"preemptions={pg['preemptions']}")
        print(f"prefix sharing: hit_rate={pg['prefix_hit_rate']:.2f} "
              f"({pg['prefix_hits']}/{pg['prefix_queries']}), "
              f"tokens_reused={pg['prefix_tokens_reused']}, "
              f"prefill_chunks={pg['prefill_chunks']} "
              f"(saved {pg['prefill_chunks_saved']})")
    if args.adapt:
        print(f"adaptive table: version={stats['table_version']} "
              f"swaps={stats['n_swaps']} "
              f"decode_builds={stats['decode_builds']}, "
              f"window overflow={stats['overflow_rate_window']:.3f} "
              f"over {stats['window_steps']} steps, "
              f"effective capacity_factor="
              f"{stats['effective_capacity_factor']}")
    if args.draft:
        sp = stats["speculative"]
        print(f"speculative (gamma={sp['gamma']}): "
              f"{sp['emitted_per_step']:.2f} tokens/step "
              f"({sp['accepted_per_step']:.2f} draft-accepted/step, "
              f"accept_rate={sp['accept_rate']:.2f}) "
              f"over {sp['spec_steps']} verify steps")
    if args.quantize:
        rep = stats["quantize_report"]
        print(f"quantized serving ({stats['quantize']}): exactness gate "
              f"{'PASSED' if rep['passed'] else 'FAILED'} — "
              f"{rep['n_flips_raw']}/{rep['n_tokens']} raw id flips "
              f"(rate {rep['flip_rate_raw']:.3f}), "
              f"{rep['n_fallback']} experts on fp fallback "
              f"{rep['fallback_experts']}, "
              f"{rep['n_unguarded_flips']} unguarded flips "
              f"(threshold {rep['flip_threshold']})")
        if not rep["passed"]:
            print("exactness gate FAILED: unguarded id flips survive the "
                  "per-expert fallback; raise fallback coverage (lower "
                  "--quantize-flip-threshold) or serve fp", file=sys.stderr)
            sys.exit(1)
    if stats["n_failed"]:
        for r in out:
            if r.status is RequestStatus.FAILED:
                print(f"  failed: {r.error}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
