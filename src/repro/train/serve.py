"""Continuous-batching serving: ``ServeSession`` + ``Scheduler``.

True slot-based continuous batching (vLLM-style): a fixed number of
decode slots share one KV/state cache and one jitted decode step; every
slot carries its **own** sequence position (the per-row ``pos`` vector
threaded through ``attention_decode``), so finished requests release
their slot mid-flight and queued prompts are prefilled into the freed
slot while the other slots keep decoding. Per-request
:class:`SamplingParams` control ``max_new_tokens``, ``eos_id``,
greedy/temperature sampling, a ``deadline_steps`` budget and a shed
``priority`` exactly per request; a ``stream_cb`` hook observes every
emitted token.

Request lifecycle (this module's robustness contract):

* every request ends in exactly one terminal :class:`RequestStatus` —
  ``COMPLETED`` | ``REJECTED`` | ``CANCELLED`` | ``TIMED_OUT`` |
  ``FAILED`` — with ``Request.error`` carrying the reason for the
  non-completed outcomes;
* ``submit`` validates every :class:`SamplingParams` field and the
  prompt BEFORE any compute or slot admission (a bad request is
  ``REJECTED`` with a ``ValueError`` naming the offending field and
  never perturbs residents);
* the admission queue is bounded (``queue_limit``): overflow sheds the
  lowest-priority / newest request with status ``REJECTED`` instead of
  growing without bound, and ``pop_next`` admits the highest-priority /
  oldest first;
* a non-finite top-k output quarantines ONLY the poisoned slot
  (``FAILED``, slot released); surviving batchmates keep decoding
  bit-identically — per-slot decode math never mixes rows;
* an overflow circuit-breaker watches the DS head's per-expert
  capacity-overflow rate and degrades gracefully: trip 1 doubles the
  effective ``capacity_factor``, trip 2 falls back to the always-exact
  ``'jnp'`` serve path (each trip rebuilds the jitted decode step —
  jit closures capture trace-time constants).

Prefill-into-slot has two flavors:

* whole-prompt (default) — one ``bundle.prefill`` at the exact prompt
  length (a compile per distinct length), bit-identical to a standalone
  B=1 prefill;
* chunked (``prefill_chunk=C``) — the prompt streams through
  ``bundle.prefill_chunk`` in fixed (1, C) chunks against the slot's
  cache region, so every prompt length shares ONE compiled prefill
  (the tail chunk is right-padded and masked). Covers every decoder
  family this session serves: transformers run chunks against the KV
  cache; ssm/hybrid carry the per-layer conv/ssm recurrent state
  through the cache row (state-passing chunked SSD prefill — padded
  tail rows are exact ``dt = 0`` no-ops in the recurrence). Only
  encdec has no chunked path (per-request encoder frames).

Kernel choice is no longer a string frozen at engine init: ``kernel``
accepts a registered name, a policy name, or a
``repro.kernels.registry.KernelPolicy`` — the default (``None`` →
``cfg.ds.serve_kernel`` = ``'auto'``) resolves per call site, so the
B=1 prefill head and the B=n_slots decode head can lower to different
serve kernels inside one session.

Passing ``mesh=`` turns the session expert-parallel: the packed DS table
shards experts over the mesh's ``model`` axis, the shared KV/state cache
places its slot axis over ``data``/``pod``, and every head call runs
``core.dssoftmax.serve_topk_sharded`` (gating replicated, owner-local
retrieval, one O(B·k) all-gather merge) — token-identical to the
single-device session with the decode step still compiled exactly once.
``param_mode='fsdp'`` additionally stores the backbone weights sharded
over the ``data`` axis and gathers them per layer, just in time, inside
the step (``distributed.sharding.ServeParamGather``) — the full-stack
per-device memory ceiling drops from O(params) to O(params/ndata) while
outputs stay bit-identical.

``paged=True`` replaces the per-slot contiguous cache rows with a
fixed-size-page arena (``repro.serve.paged_cache``): a host-side page
table maps each slot's logical positions to arena pages, N requests
sharing a chunk-aligned prompt prefix prefill it ONCE and share the
pages copy-on-write, and under arena pressure the session
**preempts-and-requeues** the lowest-priority resident (a metadata swap
— its pages are decref'd, shared prefix pages survive through their
co-owners) instead of only shedding from the queue. A preempted request
resumes by re-prefilling ``prompt ++ out_tokens[:-1]`` and continues
its sampling stream at the preserved ``n_emitted`` counter, so its
tokens are identical from the preemption point. Paged tokens are
bit-identical to the contiguous cache (the gathered page view feeds the
exact same attention math), and the decode step still compiles exactly
once — page tables are data, not shapes.

The packed DS table is no longer frozen at construction: the session
owns a versioned :class:`~repro.serve.table_manager.TableResource` and
``swap_table(new_table)`` hot-swaps a re-packed / re-pruned / mitosed
table strictly BETWEEN decode steps — the incoming table is re-sharded
onto the session mesh first, the jitted decode/prefill fns are rebuilt
exactly ONCE per swap (the table is a jit *argument*, but a changed
``(K, V_pad)`` would otherwise grow every compile cache), and resident
requests' tokens are bit-identical from the swap point to a fresh
session on the new table. ``adapt_policy=`` closes the loop online: the
step-stamped per-expert stats window becomes a
:class:`~repro.serve.table_manager.TrafficProfile`, and
``repack_for_traffic`` re-packs (optionally re-prunes and selectively
clones persistently-overflowing experts) when the windowed overflow
rate says the table no longer fits the traffic.

``draft=`` turns on exact draft–verify **speculative decoding**: a small
draft model (its own bundle/params/table, same vocab) proposes ``gamma``
tokens per resident per step from a private contiguous cache, and the
target scores every resident's ``gamma+1``-token block in ONE batched
``verify_step`` call — the chunked-prefill-shaped path with a per-slot
``pos`` vector, so every decoder family shares it and the session still
compiles a bounded set of shapes (one draft decode + one verify). The
head runs on all ``B x (gamma+1)`` positions at once — the batch regime
where the grouped/pallas serve kernels win (see
``kernels/registry.py``). Acceptance is exact: greedy emits the longest
draft prefix matching the target's argmax plus the target's correction
token — bit-identical to the non-speculative stream — and sampled
requests run rejection sampling adapted to the head's top-k-truncated
candidate distributions, with every uniform keyed by ``(seed, absolute
emission index)`` so the stream is invariant to block alignment and
survives preempt-resume. Attention KV needs no rollback (stale rows
stay masked / are overwritten before read); ssm/hybrid recurrent state
cannot be rolled back, so verify leaves it untouched and a separate
``commit_block`` pass advances each row by its accepted prefix using
the exact sequential decode recurrence.

Sampling itself is pure host-side numpy: a counter-based Philox stream
keyed by ``(seed, emission index)`` drives Gumbel-max top-k sampling —
zero per-token jax dispatches (the old per-token
``PRNGKey``/``fold_in``/``categorical`` chain cost one device round-trip
per emitted token).
"""
from __future__ import annotations

import collections
import enum
from dataclasses import dataclass, field
from typing import Callable, Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig
from repro.core import dssoftmax as ds
from repro.models.model_zoo import (
    ModelBundle,
    cache_kv_leaves,
    cache_seq_axes,
    cache_specs,
    paged_cache_specs,
)
from repro.serve.paged_cache import (
    N_RESERVED,
    PagedCacheManager,
    prefix_hash,
)
from repro.serve.table_manager import (
    AdaptPolicy,
    TableResource,
    TrafficProfile,
    repack_for_traffic,
)
from repro.utils import get_logger

log = get_logger("serve")

# -- host-side sampling RNG --------------------------------------------------
# One independent Philox uniform stream per decision kind, all keyed by
# (seed, absolute emission index m). Any prefix of the token stream pins
# the same uniforms regardless of how speculative blocks were aligned, so
# preempt-resume and swap_table replay the stream identically — and the
# whole sampler stays on the host (zero per-token jax dispatches).
_SALT_SAMPLE = 0x5A17_0001   # plain top-k Gumbel-max sampling
_SALT_DRAFT = 0x5A17_0002    # draft proposal sampling
_SALT_ACCEPT = 0x5A17_0003   # speculative accept/reject uniform
_SALT_RESID = 0x5A17_0004    # speculative residual draw


def _fetch_overflow(stats) -> tuple[np.ndarray, np.ndarray]:
    """The head's per-expert ``dispatched`` and ``overflow`` counts of one
    step, read to the host in that order."""
    return (np.asarray(stats["dispatched"], np.int64),
            np.asarray(stats["overflow"], np.int64))


def _uniforms(seed: int, salt: int, m: int, n: int) -> np.ndarray:
    """``n`` iid U[0,1) doubles from a counter-based Philox stream —
    pure host math, a function of (seed, salt, m) alone. The emission
    index seeds the high counter word; the generator's own draws bump
    the low words, so distinct ``m`` streams never overlap."""
    bg = np.random.Philox(
        key=np.array([seed & 0xFFFF_FFFF_FFFF_FFFF, salt], np.uint64),
        counter=np.array([0, 0, 0, m], np.uint64),
    )
    return np.random.Generator(bg).random(n)


class RequestStatus(enum.Enum):
    """Request lifecycle states. ``QUEUED``/``ACTIVE`` are transient;
    the rest are terminal — a request reaches exactly one member of
    :data:`TERMINAL` and never transitions out of it."""

    QUEUED = "queued"        # submitted, waiting for a free slot
    ACTIVE = "active"        # resident in a decode slot
    COMPLETED = "completed"  # finished normally (eos or max_new_tokens)
    REJECTED = "rejected"    # failed validation, or shed by the bounded queue
    CANCELLED = "cancelled"  # aborted via ServeSession.cancel()
    TIMED_OUT = "timed_out"  # deadline_steps exceeded (queued or mid-decode)
    FAILED = "failed"        # runtime fault (non-finite output, raising stream_cb)


TERMINAL = frozenset({
    RequestStatus.COMPLETED,
    RequestStatus.REJECTED,
    RequestStatus.CANCELLED,
    RequestStatus.TIMED_OUT,
    RequestStatus.FAILED,
})


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding controls.

    ``temperature <= 0`` is greedy; otherwise tokens are sampled from the
    softmax over the head's top-k candidates (top-k sampling — the DS
    head already returns the k best classes). ``top_k`` optionally
    narrows sampling to the first ``top_k`` of those candidates; the
    head only ever RETURNS the session's ``k`` candidates, so values
    above it are rejected at ``submit()`` (they could not widen the
    distribution and would silently alias ``top_k=k``).
    ``eos_id`` stops the request the moment it is emitted (the eos token
    IS appended). ``deadline_steps`` bounds the request's lifetime in
    session decode steps counted from ``submit()`` — exceeded while
    queued or mid-decode, the request ends ``TIMED_OUT`` (keeping any
    tokens already emitted). ``priority`` (higher = more important)
    orders admission and picks shed victims when the bounded queue
    overflows; ties break FIFO (oldest admitted first, newest shed
    first).
    """

    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    temperature: float = 0.0
    seed: int = 0
    top_k: Optional[int] = None
    deadline_steps: Optional[int] = None
    priority: int = 0


@dataclass(eq=False)  # identity equality: queue membership/removal must
class Request:        # never compare prompt arrays elementwise
    prompt: np.ndarray          # (S,) int32
    # legacy shorthand for Request(prompt, sampling=SamplingParams(
    # max_new_tokens=n)); setting BOTH it and ``sampling`` is an error —
    # SamplingParams is the single source of truth
    max_new_tokens: Optional[int] = None
    out_tokens: List[int] = field(default_factory=list)
    sampling: Optional[SamplingParams] = None
    status: RequestStatus = RequestStatus.QUEUED
    error: Optional[str] = None      # reason for REJECTED/TIMED_OUT/FAILED
    submit_step: Optional[int] = None  # session n_steps at submit() time

    @property
    def done(self) -> bool:
        """True once the request reached a terminal status."""
        return self.status in TERMINAL

    @property
    def sampling_params(self) -> SamplingParams:
        if self.sampling is not None:
            if self.max_new_tokens is not None:
                raise ValueError(
                    "Request sets both the legacy max_new_tokens field "
                    f"({self.max_new_tokens}) and sampling= (max_new_tokens="
                    f"{self.sampling.max_new_tokens}); SamplingParams is the "
                    "single source of truth — drop the legacy field"
                )
            return self.sampling
        if self.max_new_tokens is not None:
            return SamplingParams(max_new_tokens=self.max_new_tokens)
        return SamplingParams()


@dataclass
class _Slot:
    """Host-side state of one occupied decode slot."""

    req: Request
    prompt_len: int
    n_emitted: int = 0
    admit_seq: int = 0  # monotonic admission order (preemption tiebreak)

    @property
    def pos(self) -> int:
        """Cache position the next decode step writes for this slot (the
        last emitted token is fed back there)."""
        return self.prompt_len + self.n_emitted - 1


class Scheduler:
    """Bounded priority admission queue + slot map (pure host-side
    bookkeeping).

    ``admit``/``release`` are the continuous-batching core: a finished
    request frees its slot immediately and the next queued prompt is
    prefilled into it while the remaining slots keep decoding.

    ``queue_limit`` bounds the queue: ``submit`` on a full queue sheds
    the lowest-priority request (newest among ties — the incoming
    request itself when nothing queued ranks below it) and returns the
    victim so the session can mark it ``REJECTED``; an unbounded queue
    (the default) always returns ``None``. ``pop_next`` admits the
    highest-priority, oldest-first.
    """

    def __init__(self, n_slots: int, queue_limit: Optional[int] = None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if queue_limit is not None and queue_limit < 1:
            raise ValueError(f"queue_limit must be >= 1, got {queue_limit}")
        self.n_slots = n_slots
        self.queue_limit = queue_limit
        self.queue: Deque[Request] = collections.deque()
        self.slots: List[Optional[_Slot]] = [None] * n_slots
        self.n_admitted = 0
        self.n_released = 0
        self.n_shed = 0

    def submit(self, req: Request) -> Optional[Request]:
        """Enqueue; returns the shed victim when the bounded queue is
        full (possibly ``req`` itself), else ``None``."""
        if req.sampling_params.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.queue_limit is not None and len(self.queue) >= self.queue_limit:
            victim = self._shed_victim(req)
            self.n_shed += 1
            if victim is req:
                return req
            self.queue.remove(victim)
            self.queue.append(req)
            return victim
        self.queue.append(req)
        return None

    def _shed_victim(self, incoming: Request) -> Request:
        # lowest priority loses; among equals the newest arrival does
        # (the incoming request is the newest candidate of all)
        victim_i, vp = 0, self.queue[0].sampling_params.priority
        for i, r in enumerate(self.queue):
            p = r.sampling_params.priority
            if p <= vp:  # <= keeps scanning → newest among equal priorities
                victim_i, vp = i, p
        if incoming.sampling_params.priority <= vp:
            return incoming
        return self.queue[victim_i]

    def pop_next(self) -> Request:
        """Remove and return the highest-priority request (FIFO within a
        priority class)."""
        best_i, bp = 0, self.queue[0].sampling_params.priority
        for i, r in enumerate(self.queue):
            p = r.sampling_params.priority
            if p > bp:  # strict > keeps the oldest among equals
                best_i, bp = i, p
        req = self.queue[best_i]
        del self.queue[best_i]
        return req

    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def active(self) -> List[tuple[int, _Slot]]:
        return [(i, s) for i, s in enumerate(self.slots) if s is not None]

    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def requeue(self, req: Request) -> None:
        """Put a preempted resident back at the FRONT of the queue: it
        keeps its seniority within its priority class (``pop_next`` is
        FIFO per class), so equal-priority churn cannot starve it."""
        self.queue.appendleft(req)

    def admit(self, i: int, req: Request, prompt_len: int) -> _Slot:
        assert self.slots[i] is None
        self.n_admitted += 1
        slot = _Slot(req=req, prompt_len=prompt_len,
                     admit_seq=self.n_admitted)
        self.slots[i] = slot
        return slot

    def release(self, i: int) -> None:
        assert self.slots[i] is not None
        self.slots[i] = None
        self.n_released += 1


class ServeSession:
    """Continuous-batching serving session over one model bundle.

    Args:
        bundle/params: the model (``repro.models.build``).
        ds_state_or_table: the DS mask state, an already-packed
            :class:`~repro.core.dssoftmax.ServeTable`, or the head state
            for non-DS heads.
        n_slots: decode slots (the jitted decode batch size).
        max_seq_len: shared cache length; every request must satisfy
            ``prompt_len + max_new_tokens - 1 <= max_seq_len``.
        k: top-k width returned by the head (candidates for sampling).
        kernel: serve-kernel override (name, policy name, or
            KernelPolicy); ``None`` uses ``cfg.ds.serve_kernel``.
        mesh: optional ``jax.sharding.Mesh`` for expert-parallel serving.
            The packed DS table is sharded experts → ``model`` (each
            device stores K/ep experts; ``core.dssoftmax.shard_table``
            pads non-divisible K), the shared KV/state cache places its
            slot axis over the ``data``/``pod`` axes, and the head runs
            ``serve_topk_sharded`` — gating replicated, owner-local
            retrieval, one O(B·k) all-gather merge. The decode step is
            still lowered ONCE (the mesh is a trace-time constant), and
            outputs are token-identical to the single-device session.
        param_mode: how backbone weights live on the mesh.
            ``'replicated'`` (default) keeps a full copy per device;
            ``'fsdp'`` (requires ``mesh=``) stores every param sharded
            over the mesh's ``data`` axis
            (``distributed.sharding.serve_param_shardings``) and gathers
            each layer's weights just in time inside the decode/prefill
            step (``ServeParamGather``: layer *i*'s all-gather overlaps
            layer *i-1*'s compute; the full stack is never resident).
            Per-device resident param bytes drop ~``ndata``×; outputs
            stay token-identical and the decode step still compiles
            exactly once (param shardings are pinned every step).
        prefill_chunk: if set, prompts prefill through
            ``bundle.prefill_chunk`` in (1, C) chunks — one compile for
            all prompt lengths (every family except encdec).
        stream_cb: ``cb(request, token)`` called for every emitted token.
            A raising callback FAILs only its own request — the step
            loop and the other residents are untouched.
        queue_limit: bound on the admission queue; ``None`` (default) is
            unbounded. A full queue sheds the lowest-priority / newest
            request with status ``REJECTED`` (see :class:`Scheduler`).
        overflow_threshold / overflow_window: the DS-head overflow
            circuit-breaker. When the mean capacity-overflow rate over
            the last ``overflow_window`` decode steps exceeds
            ``overflow_threshold``, the session degrades: trip 1 doubles
            the effective ``capacity_factor``; trip 2 falls back to the
            always-exact ``'jnp'`` serve path. Each trip rebuilds the
            jitted decode step (one extra compile per trip).
        paged: replace the per-slot contiguous cache rows with the
            fixed-size-page arena (``repro.serve.paged_cache``). Tokens
            are bit-identical to the contiguous cache; what changes is
            capacity behavior — chunk-aligned prompt prefixes are
            prefilled once and shared copy-on-write (with
            ``prefill_chunk``), and arena exhaustion preempts-and-
            requeues the lowest-priority resident instead of failing.
            Requires ``max_seq_len % page_size == 0`` (and
            ``% prefill_chunk == 0`` when chunked).
        page_size: cache positions per page (paged mode).
        page_arena: allocatable KV pages in the arena. Default
            ``n_slots * max_seq_len / page_size`` — the contiguous
            capacity, so nothing preempts unless prompts stop sharing.
            Smaller arenas trade memory for preemption pressure.
        state_arena: allocatable conv/ssm state pages (ssm/hybrid
            families): one live page per resident plus boundary
            snapshots for prefix sharing. Default ``4 * n_slots``.
        prefix_sharing: register/adopt shared prompt prefixes (paged +
            chunked only). ``False`` keeps the arena but prefills every
            prompt in full.
        stats_window: length (in decode steps) of the step-stamped
            per-expert dispatch/overflow window behind
            ``stats()['expert_dispatched_window']`` and
            :meth:`traffic_profile` — O(K) host memory per step, so
            recent skew stays visible on a long-lived session whose
            cumulative counters have flattened out.
        adapt_policy: optional
            :class:`~repro.serve.table_manager.AdaptPolicy` enabling the
            online adaptation loop: every ``interval`` steps the session
            inspects its windowed :class:`TrafficProfile` and, when the
            overflow rate exceeds the policy threshold, runs
            ``repack_for_traffic`` and :meth:`swap_table`'s the result
            in — strictly between decode steps. Requires a DS head and
            the raw DS mask state (``ds_state_or_table`` must NOT be a
            pre-packed table: repacking needs the (head, mask) pair).
        quantize: ``'int8'`` serves the DS table from int8 rows with
            per-row fp32 scales (PR 9). The table is quantized under
            the exactness gate
            (:func:`~repro.core.dssoftmax.calibrate_quantized_table`):
            experts whose top-k ids flip vs the fp32 oracle on the
            calibration activations beyond ``quantize_flip_threshold``
            serve full-precision fallback rows. The resulting
            :class:`~repro.core.dssoftmax.ExactnessReport` is exposed at
            ``stats()['quantize_report']``. Every later
            :meth:`swap_table` of a raw fp table (including the online
            adaptation loop's repacks) re-runs the same gate, so the
            session stays quantized across swaps.
        quantize_calib: calibration activations for the exactness gate —
            an ``(n, d_model)`` array of representative hidden states,
            or an int ``n`` to draw that many from a fixed unit
            gaussian (default 256).
        quantize_flip_threshold: per-expert flip-rate bound above which
            an expert falls back to full-precision rows. The default
            0.0 makes the served table measured-exact on the
            calibration trace by construction; 1.0 disables fallback
            (pure int8, report still measured).
        draft: ``(draft_bundle, draft_params, draft_ds_state_or_table)``
            — a small same-vocab model enabling exact draft–verify
            speculative decoding. Each step the draft proposes ``gamma``
            tokens per resident (sequential B=n_slots draft decodes
            against a private contiguous cache), the target scores all
            residents' ``gamma+1``-token blocks in ONE batched
            ``verify_step`` (the chunked-prefill-shaped path: per-slot
            ``pos`` vector, head over all B·(gamma+1) positions), and a
            host-side acceptance pass emits the longest valid prefix
            plus one target token. Greedy output is bit-identical to the
            non-speculative stream; sampled output is distribution-exact
            (rejection sampling over the top-k-truncated candidates,
            uniforms keyed by ``(seed, emission index)`` so the stream
            is block-alignment-invariant). Requests must additionally
            leave ``gamma`` cache positions of headroom (checked at
            ``submit``). The draft's cache is always contiguous, even
            when the target is paged.
        gamma: draft tokens proposed per slot per speculative step
            (block width is ``gamma + 1``).
    """

    def __init__(self, bundle: ModelBundle, params, ds_state_or_table, *,
                 n_slots: int = 8, max_seq_len: int = 256, k: int = 8,
                 kernel=None, mesh=None, param_mode: str = "replicated",
                 prefill_chunk: Optional[int] = None,
                 stream_cb: Optional[Callable[[Request, int], None]] = None,
                 queue_limit: Optional[int] = None,
                 overflow_threshold: float = 0.5,
                 overflow_window: int = 8,
                 paged: bool = False, page_size: int = 16,
                 page_arena: Optional[int] = None,
                 state_arena: Optional[int] = None,
                 prefix_sharing: bool = True,
                 stats_window: int = 128,
                 adapt_policy: Optional[AdaptPolicy] = None,
                 quantize: Optional[str] = None,
                 quantize_calib=256,
                 quantize_flip_threshold: float = 0.0,
                 draft: Optional[tuple] = None,
                 gamma: int = 4):
        cfg = bundle.cfg
        if cfg.family == "encdec":
            raise ValueError(
                "ServeSession drives token-only prompts; the encdec family "
                "needs per-request encoder frames"
            )
        if prefill_chunk is not None and bundle.prefill_chunk is None:
            # only encdec lands here: every token-only decoder family
            # (transformer, ssm, hybrid) has a chunked prefill path.
            raise ValueError(
                f"family {cfg.family!r} has no chunked prefill; "
                "use whole-prompt prefill (prefill_chunk=None)"
            )
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if param_mode not in ("replicated", "fsdp"):
            raise ValueError(
                f"param_mode must be 'replicated' or 'fsdp', got {param_mode!r}"
            )
        if param_mode == "fsdp" and mesh is None:
            raise ValueError("param_mode='fsdp' requires mesh=")
        if quantize not in (None, "int8"):
            raise ValueError(f"quantize must be None or 'int8', got {quantize!r}")
        if quantize is not None and cfg.head != "ds":
            raise ValueError("quantize= requires a DS head (serve table)")
        if draft is not None:
            if gamma < 1:
                raise ValueError(f"gamma must be >= 1, got {gamma}")
            if bundle.verify_step is None:
                raise ValueError(
                    f"family {cfg.family!r} has no verify_step; speculative "
                    "decoding needs the chunk-shaped verify path"
                )
            d_bundle = draft[0]
            if d_bundle.cfg.family == "encdec":
                raise ValueError("the draft model must be a token-only decoder")
            if d_bundle.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab_size ({d_bundle.cfg.vocab_size}) must match "
                    f"the target's ({cfg.vocab_size}) — acceptance compares "
                    "token ids across the two distributions"
                )
            if prefill_chunk is not None and d_bundle.prefill_chunk is None:
                raise ValueError(
                    f"draft family {d_bundle.cfg.family!r} has no chunked "
                    "prefill; use whole-prompt prefill (prefill_chunk=None)"
                )
        if paged:
            if max_seq_len % page_size:
                raise ValueError(
                    f"paged mode needs max_seq_len ({max_seq_len}) divisible "
                    f"by page_size ({page_size})"
                )
            if prefill_chunk is not None and max_seq_len % prefill_chunk:
                # a preempted request resumes by re-prefilling
                # prompt ++ emitted tokens; the tail chunk's padded writes
                # round that length up to a prefill_chunk multiple, which
                # must never index a page past the per-slot table
                raise ValueError(
                    f"paged chunked prefill needs max_seq_len ({max_seq_len}) "
                    f"divisible by prefill_chunk ({prefill_chunk})"
                )
        self.bundle = bundle
        self.cfg = cfg
        self.params = params
        self.param_mode = param_mode
        self.n_slots = n_slots
        self.max_seq_len = max_seq_len
        self.k = k
        self.prefill_chunk = prefill_chunk
        self.stream_cb = stream_cb
        self.requests: List[Request] = []
        self.n_steps = 0
        self.mesh = mesh

        self._head_params = None    # replicated (head, mask) pair tracked
        self._ds_state = None       # across swaps so repacks compound
        self._quantize = quantize
        self._quantize_flip_threshold = float(quantize_flip_threshold)
        self._quantize_calib = quantize_calib
        self._quantize_report: Optional[ds.ExactnessReport] = None
        if cfg.head == "ds":
            if isinstance(ds_state_or_table,
                          (ds.ServeTable, ds.QuantizedServeTable)):
                table = ds_state_or_table
            else:
                self._ds_state = ds_state_or_table
                table = ds.pack_experts(params["head"], ds_state_or_table)
            self._head_params = params["head"]
            if quantize is not None and isinstance(table, ds.ServeTable):
                # exactness-gated int8 quantization against the serving
                # gate; a pre-quantized table passes through (no report)
                table = self._quantize_pack(table, params["head"]["gate"])
            # TableResource places onto the mesh (experts → model axis,
            # K padded to a multiple of ep) on the way in — at init and
            # on every later swap_table()
            self._table_res = TableResource(table, gate=params["head"]["gate"],
                                            mesh=mesh)
            log.info("packed serve table: V_pad=%d kernel=%s n_slots=%d mesh=%s",
                     self.table.v_pad, kernel or cfg.ds.serve_kernel, n_slots,
                     dict(mesh.shape) if mesh is not None else None)
        else:
            self._table_res = TableResource(ds_state_or_table)
        self._kernel = kernel
        self._adapt_policy = adapt_policy
        self._n_swaps = 0
        self._last_adapt_step = 0
        self._n_decode_builds = 0
        if adapt_policy is not None:
            if cfg.head != "ds":
                raise ValueError("adapt_policy requires a DS head")
            if self._ds_state is None:
                raise ValueError(
                    "adapt_policy needs the raw DS mask state to repack; "
                    "pass ds_state, not a pre-packed ServeTable"
                )

        # ---- request-lifecycle / degradation state ------------------------
        self._outcomes: collections.Counter = collections.Counter()
        self._overflow_threshold = overflow_threshold
        self._overflow_hist: Deque[float] = collections.deque(
            maxlen=max(1, overflow_window))
        self._breaker_trips = 0
        self._eff_kernel = kernel              # trip 2 forces 'jnp'
        self._eff_capacity_factor = None       # None → cfg.ds.capacity_factor
        self._expert_dispatched: Optional[np.ndarray] = None
        self._expert_overflow: Optional[np.ndarray] = None
        # step-stamped window over the same per-expert counters: each
        # entry is (n_steps stamp, dispatched (K,), overflow (K,))
        self._stats_window = max(1, stats_window)
        self._win: Deque[tuple] = collections.deque(maxlen=self._stats_window)

        self._gather = None
        self._param_shardings = None
        if param_mode == "fsdp":
            # FSDP storage AFTER table packing (pack_experts reads the
            # replicated head): every backbone leaf shards over the data
            # axis where divisible, and the jitted steps gather per layer
            from repro.distributed.sharding import (
                ServeParamGather,
                serve_param_shardings,
                tree_shard_bytes,
            )

            self._param_shardings = serve_param_shardings(mesh, params)
            self.params = params = jax.device_put(params, self._param_shardings)
            self._gather = ServeParamGather(mesh, params)
            log.info(
                "fsdp param storage: %.2f MB/device (replicated would be %.2f)",
                tree_shard_bytes(params) / 1e6,
                sum(x.nbytes for x in jax.tree.leaves(params)) / 1e6,
            )

        self._mgr: Optional[PagedCacheManager] = None
        self._prefix_sharing = prefix_sharing and paged \
            and prefill_chunk is not None
        self._n_preempted = 0
        self._n_prefill_chunks = 0
        self._n_prefill_chunks_saved = 0
        if paged:
            from repro.models.hybrid import n_attn_apps

            has_state = cfg.family in ("ssm", "hybrid")
            has_kv = cfg.family in ("dense", "moe", "vlm") \
                or (cfg.family == "hybrid" and n_attn_apps(cfg) > 0)
            n_alloc = page_arena if page_arena is not None \
                else n_slots * (max_seq_len // page_size)
            n_state = (state_arena if state_arena is not None
                       else 4 * n_slots) if has_state else 0
            self._mgr = PagedCacheManager(
                n_slots=n_slots, n_pages=N_RESERVED + n_alloc,
                page_size=page_size, max_seq_len=max_seq_len,
                has_state=has_state, has_kv=has_kv,
                n_state_pages=(N_RESERVED + n_state) if has_state else None,
            )
            self._kv_leaf = cache_kv_leaves(cfg)
            specs = paged_cache_specs(cfg, N_RESERVED + n_alloc, page_size,
                                      (N_RESERVED + n_state) if has_state
                                      else 0)
            log.info("paged cache: %d pages x %d positions (+%d state pages)",
                     n_alloc, page_size, n_state)
        else:
            shape = ShapeConfig(name="serve", seq_len=max_seq_len,
                                global_batch=n_slots, kind="decode")
            specs = cache_specs(cfg, shape)
        self._cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
        self._cache_shardings = None
        if mesh is not None:
            # slots (or arena pages) → (pod, data); sequence stays whole per
            # device so per-slot decode math is bit-identical to the
            # unsharded session
            from repro.distributed.sharding import (
                serve_cache_shardings,
                serve_paged_cache_shardings,
            )

            if paged:
                self._cache_shardings = serve_paged_cache_shardings(
                    mesh, cfg, specs)
            else:
                self._cache_shardings = serve_cache_shardings(mesh, cfg, specs,
                                                              n_slots)
            self._cache = jax.device_put(self._cache, self._cache_shardings)
        if prefill_chunk is not None and not paged:
            self._row_zero = jax.tree.map(
                lambda s: jnp.zeros((s.shape[0], 1) + s.shape[2:], s.dtype), specs
            )
            if mesh is not None:
                # the (·, 1, ·) per-request row is replicated on the mesh —
                # committed up front so every chunk call (fresh row AND a
                # previous chunk's output) shares one compiled signature
                from jax.sharding import NamedSharding, PartitionSpec

                self._row_sharding = NamedSharding(mesh, PartitionSpec())
                self._row_zero = jax.tree.map(
                    lambda x: jax.device_put(x, self._row_sharding),
                    self._row_zero,
                )
        axes = cache_seq_axes(cfg)
        self.scheduler = Scheduler(n_slots, queue_limit=queue_limit)
        self._tok = np.zeros(n_slots, np.int32)
        self._pos = np.zeros(n_slots, np.int32)

        # ---- speculative decoding (draft model) ---------------------------
        self.gamma = int(gamma)
        self._draft = None
        self._verify_fn = None
        self._commit_fn = None
        self._draft_commit_fn = None
        self._spec_stats = {"steps": 0, "slot_steps": 0, "accepted": 0,
                            "emitted": 0}
        if draft is not None:
            d_bundle, d_params, d_state = draft
            if d_bundle.cfg.head == "ds":
                if isinstance(d_state, (ds.ServeTable, ds.QuantizedServeTable)):
                    d_table = d_state
                else:
                    d_table = ds.pack_experts(d_params["head"], d_state)
            else:
                d_table = d_state
            # the draft's cache is ALWAYS a contiguous (n_slots, S_max)
            # block, even when the target is paged: the draft is small,
            # and keeping it off the arena means speculative mode never
            # changes page pressure accounting beyond the +gamma verify
            # headroom
            d_specs = cache_specs(d_bundle.cfg, ShapeConfig(
                name="serve_draft", seq_len=max_seq_len,
                global_batch=n_slots, kind="decode"))
            self._draft = {"bundle": d_bundle, "params": d_params,
                           "table": d_table}
            self._draft_cache = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), d_specs)
            if prefill_chunk is not None:
                self._draft_row_zero = jax.tree.map(
                    lambda s: jnp.zeros((s.shape[0], 1) + s.shape[2:],
                                        s.dtype), d_specs)
            self._build_draft_fns()
            log.info("speculative decoding: draft=%s gamma=%d (verify block "
                     "B=%d x W=%d)", d_bundle.cfg.name, self.gamma, n_slots,
                     self.gamma + 1)

        self._build_decode_fn()
        self._build_prefill_fns()

        if paged:
            kvl = self._kv_leaf
            ps = page_size

            def _copy_page(c, src, dst):
                # one-page KV copy (CoW): page ids are traced scalars, so
                # every (src, dst) pair shares ONE compile
                return self._pin(jax.tree.map(
                    lambda sh, kv: sh.at[:, dst].set(sh[:, src]) if kv else sh,
                    c, kvl))

            def _zero_kv_page(c, pid):
                return self._pin(jax.tree.map(
                    lambda sh, kv: sh.at[:, pid].set(0) if kv else sh,
                    c, kvl))

            def _copy_state_page(c, src, dst):
                return self._pin(jax.tree.map(
                    lambda sh, kv: sh if kv else sh.at[:, dst].set(sh[:, src]),
                    c, kvl))

            def _zero_state_page(c, pid):
                return self._pin(jax.tree.map(
                    lambda sh, kv: sh if kv else sh.at[:, pid].set(0),
                    c, kvl))

            self._copy_page_fn = jax.jit(_copy_page, donate_argnums=0)
            self._zero_kv_page_fn = jax.jit(_zero_kv_page, donate_argnums=0)
            self._copy_state_page_fn = jax.jit(_copy_state_page,
                                               donate_argnums=0)
            self._zero_state_page_fn = jax.jit(_zero_state_page,
                                               donate_argnums=0)

            def _insert_paged(c, row, page_row, state_pid):
                # Scatter a whole-prompt (·, 1, S, ·) prefilled cache into
                # the arena along the slot's page row. Positions past S in
                # the final page keep stale (finite) garbage — masked to
                # an exact 0 contribution, like the contiguous stale tail.
                def put(sh, r, kv):
                    if kv:
                        pos = jnp.arange(r.shape[2])
                        return sh.at[:, page_row[pos // ps], pos % ps].set(
                            r[:, 0].astype(sh.dtype))
                    return sh.at[:, state_pid].set(r[:, 0].astype(sh.dtype))

                return self._pin(jax.tree.map(put, c, row, kvl))

            self._insert_paged_fn = jax.jit(_insert_paged, donate_argnums=0)
        else:
            def _insert(shared, row, slot):
                # Write a (·, 1, S, ·) prefilled request cache into slot
                # ``slot`` of the (·, n_slots, S_max, ·) shared cache. Leaves
                # with a sequence axis keep positions >= S stale — they stay
                # masked (arange <= pos) until the slot's own decode steps
                # overwrite them; state leaves (ssm/conv) are fully replaced.
                def put(sh, r, ax):
                    if ax == 2:
                        return sh.at[:, slot, : r.shape[2]].set(
                            r[:, 0].astype(sh.dtype))
                    return sh.at[:, slot].set(r[:, 0].astype(sh.dtype))

                return self._pin(jax.tree.map(put, shared, row, axes))

            self._insert_fn = jax.jit(_insert, donate_argnums=0)

            def _scrub(shared, slot):
                # Zero EVERY cache row of slot ``slot``. Run after a FAILED
                # (poisoned) request: inserts only overwrite the next
                # prompt's length, so a residual NaN row past it — masked
                # but still multiplied (0·NaN = NaN) — would re-poison the
                # slot's next tenant.
                return self._pin(
                    jax.tree.map(lambda sh: sh.at[:, slot].set(0), shared))

            self._scrub_fn = jax.jit(_scrub, donate_argnums=0)

    # -- versioned table resource -------------------------------------------

    @property
    def table(self):
        """The CURRENT table version (a packed
        :class:`~repro.core.dssoftmax.ServeTable` for DS heads). Passed
        to every jitted step as an ARGUMENT — readers always see the
        version resident when the step was launched, never a mid-step
        mix (swaps happen strictly between steps)."""
        return self._table_res.table

    @property
    def table_version(self) -> int:
        return self._table_res.version

    # -- sharding fixed points ----------------------------------------------

    def _pin(self, cache):
        # Keep the cache's sharding a fixed point of every jitted step:
        # without the constraint XLA may re-layout the carried cache,
        # and a changed input sharding re-traces the decode step (the
        # compile-count == 1 invariant the mesh must not break).
        if self._cache_shardings is None:
            return cache
        return jax.tree.map(jax.lax.with_sharding_constraint, cache,
                            self._cache_shardings)

    def _pin_p(self, p):
        # Same fixed-point treatment for FSDP-stored params: pinned
        # every step so GSPMD canonicalization can never migrate the
        # storage sharding (and so the per-layer gathers stay the ONLY
        # collectives touching weights).
        if self._param_shardings is None:
            return p
        return jax.tree.map(jax.lax.with_sharding_constraint, p,
                            self._param_shardings)

    def _build_decode_fn(self) -> None:
        """(Re)build the jitted decode step. Called once at init and again
        whenever (a) the overflow breaker changes the effective capacity
        factor or kernel, or (b) ``swap_table`` installs a new table
        version — jit closures capture their constants at trace time, so
        mutating ``self._eff_*`` alone would silently do nothing; the
        jit object must be replaced. ``_n_decode_builds`` counts these
        rebuilds (the swap protocol asserts exactly one per swap)."""
        self._n_decode_builds += 1
        bundle, k = self.bundle, self.k

        if self._mgr is not None:
            def _decode(p, t, c, tok, pos, pages, spages):
                # pages/spages are DATA (host page tables re-uploaded every
                # step as same-shape int32 arrays), not shapes — the step
                # still compiles exactly once
                out = bundle.decode_step(
                    self._pin_p(p), t, c, tok, pos, k=k,
                    kernel=self._eff_kernel, mesh=self.mesh,
                    gather=self._gather,
                    capacity_factor=self._eff_capacity_factor,
                    with_stats=True, pages=pages, state_pages=spages,
                )
                vals, ids, c, stats = out
                return vals, ids, self._pin(c), stats
        else:
            def _decode(p, t, c, tok, pos):
                out = bundle.decode_step(
                    self._pin_p(p), t, c, tok, pos, k=k,
                    kernel=self._eff_kernel,
                    mesh=self.mesh, gather=self._gather,
                    capacity_factor=self._eff_capacity_factor, with_stats=True,
                )
                vals, ids, c, stats = out
                return vals, ids, self._pin(c), stats

        # Every program whose result replaces ``self._cache`` is handed it
        # donated: the step then writes its rows into the buffer it was
        # given, and no second cache is ever allocated or copied.
        self._decode_fn = jax.jit(_decode, donate_argnums=2)

        if self._draft is None:
            return
        # speculative verify (+ state commit): rebuilt together with the
        # decode step so swap_table's changed (K, V_pad) reprices them too.
        # Shapes are static — (n_slots, gamma+1) blocks + the per-slot pos
        # vector — so each compiles exactly once per table version.
        if self._mgr is not None:
            def _verify(p, t, c, toks, pos0, pages, spages):
                out = bundle.verify_step(
                    self._pin_p(p), t, c, toks, pos0, k=k,
                    kernel=self._eff_kernel, mesh=self.mesh,
                    gather=self._gather,
                    capacity_factor=self._eff_capacity_factor,
                    with_stats=True, pages=pages, state_pages=spages,
                )
                vals, ids, c, stats = out
                return vals, ids, self._pin(c), stats
        else:
            def _verify(p, t, c, toks, pos0):
                out = bundle.verify_step(
                    self._pin_p(p), t, c, toks, pos0, k=k,
                    kernel=self._eff_kernel, mesh=self.mesh,
                    gather=self._gather,
                    capacity_factor=self._eff_capacity_factor,
                    with_stats=True,
                )
                vals, ids, c, stats = out
                return vals, ids, self._pin(c), stats

        self._verify_fn = jax.jit(_verify, donate_argnums=2)

        if not bundle.verify_needs_state_commit:
            return
        if self._mgr is not None:
            def _commit(p, c, toks, pos0, nv, pages, spages):
                return self._pin(bundle.commit_block(
                    self._pin_p(p), c, toks, pos0, nv,
                    gather=self._gather, pages=pages, state_pages=spages,
                ))
        else:
            def _commit(p, c, toks, pos0, nv):
                return self._pin(bundle.commit_block(
                    self._pin_p(p), c, toks, pos0, nv, gather=self._gather,
                ))

        self._commit_fn = jax.jit(_commit, donate_argnums=1)

    def _build_prefill_fns(self) -> None:
        """(Re)build the jitted prefill closures. Like the decode step,
        these take the table as an argument but are rebuilt on every
        ``swap_table`` so a changed ``(K, V_pad)`` cannot grow their
        compile caches. The paged page-copy/insert/scrub jits are
        table-independent and are built once in ``__init__``."""
        bundle, k = self.bundle, self.k

        self._prefill_fn = jax.jit(
            lambda p, t, b: bundle.prefill(self._pin_p(p), t, b, k=k,
                                           kernel=self._kernel,
                                           mesh=self.mesh,
                                           gather=self._gather)
        )
        if self.prefill_chunk is None:
            return
        if self._mgr is not None:
            def _chunk(p, t, c, toks, pos0, nv, pages, spages):
                # chunked prefill straight into the SHARED arena: the
                # (1, n_pg) page row scatters the chunk's K/V into the
                # slot's prepared pages (state families update their
                # live state page in place)
                vals, ids, c = bundle.prefill_chunk(
                    self._pin_p(p), t, c, toks, pos0, nv, k=k,
                    kernel=self._kernel, mesh=self.mesh,
                    gather=self._gather, pages=pages, state_pages=spages,
                )
                return vals, ids, self._pin(c)
        else:
            def _chunk(p, t, c, toks, pos0, nv):
                vals, ids, c = bundle.prefill_chunk(
                    self._pin_p(p), t, c, toks, pos0, nv, k=k,
                    kernel=self._kernel, mesh=self.mesh,
                    gather=self._gather
                )
                if self.mesh is not None:
                    c = jax.tree.map(
                        lambda x: jax.lax.with_sharding_constraint(
                            x, self._row_sharding), c)
                return vals, ids, c

        # the paged chunk updates the shared arena, donated like the decode
        # step's cache; a contiguous chunk's row starts from the kept
        # ``_row_zero``, so it is never donated
        self._chunk_fn = jax.jit(
            _chunk, donate_argnums=2 if self._mgr is not None else ())

    def _build_draft_fns(self) -> None:
        """Jitted closures over the draft model, built ONCE at init (the
        draft table never swaps). The draft serves single-device with the
        default kernel resolution — it is small by construction, and
        keeping it off the mesh/FSDP machinery means speculative mode
        adds exactly three compiled shapes: draft decode (B=n_slots),
        draft prefill (whole-prompt or chunked), and — for state-family
        drafts — the commit pass."""
        d = self._draft
        db, k = d["bundle"], self.k

        def _ddecode(p, t, c, tok, pos):
            vals, ids, c = db.decode_step(p, t, c, tok, pos, k=k)
            return vals, ids, c

        self._draft_decode_fn = jax.jit(_ddecode)
        self._draft_prefill_fn = jax.jit(
            lambda p, t, b: db.prefill(p, t, b, k=k))
        if self.prefill_chunk is not None:
            def _dchunk(p, t, c, toks, pos0, nv):
                return db.prefill_chunk(p, t, c, toks, pos0, nv, k=k)

            self._draft_chunk_fn = jax.jit(_dchunk)

        d_axes = cache_seq_axes(db.cfg)

        def _dinsert(shared, row, slot):
            def put(sh, r, ax):
                if ax == 2:
                    return sh.at[:, slot, : r.shape[2]].set(
                        r[:, 0].astype(sh.dtype))
                return sh.at[:, slot].set(r[:, 0].astype(sh.dtype))

            return jax.tree.map(put, shared, row, d_axes)

        self._draft_insert_fn = jax.jit(_dinsert)
        self._draft_scrub_fn = jax.jit(
            lambda sh, slot: jax.tree.map(lambda x: x.at[:, slot].set(0), sh))
        if db.verify_needs_state_commit:
            def _dcommit(p, c, toks, pos0, nv):
                return db.commit_block(p, c, toks, pos0, nv)

            self._draft_commit_fn = jax.jit(_dcommit)

    # -- table hot-swap + online adaptation ---------------------------------

    def _quantize_pack(self, table: ds.ServeTable,
                       gate_w) -> ds.QuantizedServeTable:
        """Quantize a raw fp table under the exactness gate (PR 9) and
        record the :class:`~repro.core.dssoftmax.ExactnessReport` behind
        ``stats()['quantize_report']``. Calibration activations come
        from ``quantize_calib`` (an (n, d_model) array, or n gaussian
        draws from a fixed key so repeated swaps gate identically)."""
        calib = self._quantize_calib
        if isinstance(calib, int):
            calib = jax.random.normal(
                jax.random.PRNGKey(17), (calib, self.cfg.d_model),
                jnp.float32)
        qtable, report = ds.calibrate_quantized_table(
            jnp.asarray(gate_w), table, jnp.asarray(calib), k=self.k,
            flip_threshold=self._quantize_flip_threshold)
        self._quantize_report = report
        log.info(
            "int8 quantize: %d/%d calib flips raw, %d experts on fp "
            "fallback, %d unguarded (gate %s)",
            report.n_flips_raw, report.n_tokens,
            len(report.fallback_experts), report.n_unguarded_flips,
            "PASSED" if report.passed else "FAILED",
        )
        return qtable

    def swap_table(self, new_table: ds.ServeTable,
                   new_gate: Optional[jax.Array] = None, *,
                   capacity_factor: Optional[float] = None) -> int:
        """Hot-swap the serve table (and optionally its matching gate)
        between decode steps. Returns the new table version.

        The swap protocol, in order:

        1. **Version fencing** — the incoming (unpadded) table is placed
           on the session mesh via the :class:`TableResource`
           (``shard_table``'s dummy-expert padding rules), and only then
           becomes the current version; the old table retires to the
           back buffer, so a launched step always reads one complete
           version.
        2. **Gate update** — a new gate (required when K changed) swaps
           as one pair with the table; under FSDP it is placed with the
           path-keyed ``head/gate`` sharding built at init (the
           ``(None, 'data')`` rule is K-independent, so the spec stays
           valid across swaps).
        3. **Per-version telemetry reset** — cumulative + windowed
           per-expert counters and the breaker's overflow history clear
           (K/V_pad may have changed shape; the breaker re-evaluates
           against the new table from a fresh window).
        4. **Rebuild-once** — the jitted decode and prefill fns are
           rebuilt exactly once (``_n_decode_builds`` += 1). The table
           is a jit *argument*, but without the rebuild a changed
           ``(K, V_pad)`` would silently grow every compile cache and
           keep serving kernel choices priced against the OLD table —
           ``serve_kernel_context`` reads shapes at trace time, so the
           rebuild reprices ``KernelContext``/``AutoPolicy`` for free.

        Identity-from-swap-point: backbone params and the KV/state cache
        are table-independent, so resident requests' tokens after the
        swap are bit-identical to a fresh session on the new table
        replaying ``prompt ++ pre_swap_tokens``.

        A session built with ``quantize='int8'`` preserves its mode: a
        raw fp ``ServeTable`` is re-quantized under the exactness gate
        (against the post-step-2 serving gate) before placement, and the
        fresh :class:`~repro.core.dssoftmax.ExactnessReport` replaces
        ``stats()['quantize_report']``. A pre-quantized table swaps in
        as-is.
        """
        if self.cfg.head != "ds":
            raise ValueError("swap_table requires a DS head")
        if not isinstance(new_table, (ds.ServeTable, ds.QuantizedServeTable)):
            raise ValueError(
                "swap_table takes a packed, unpadded ServeTable (the "
                "resource re-pads for the mesh)"
            )
        if new_gate is None:
            if new_table.ids.shape[0] != self.params["head"]["gate"].shape[0]:
                raise ValueError(
                    f"table has {new_table.ids.shape[0]} experts but the "
                    f"resident gate has {self.params['head']['gate'].shape[0]}"
                    " rows; pass new_gate — gate and table swap as one pair"
                )
        else:
            if new_gate.shape[0] != new_table.ids.shape[0]:
                raise ValueError(
                    f"gate rows ({new_gate.shape[0]}) must match table "
                    f"experts ({new_table.ids.shape[0]}) — gate and table "
                    "swap as one versioned pair"
                )
            gate = jnp.asarray(new_gate)
            if self._param_shardings is not None:
                gate = jax.device_put(gate,
                                      self._param_shardings["head"]["gate"])
            head = dict(self.params["head"], gate=gate)
            self.params = dict(self.params, head=head)
        if self._quantize is not None and isinstance(new_table, ds.ServeTable):
            # A quantized session stays quantized across swaps: raw fp
            # tables (incl. the online adaptation loop's repacks) re-run
            # the exactness gate against the just-updated serving gate.
            new_table = self._quantize_pack(new_table,
                                           self.params["head"]["gate"])
        version = self._table_res.swap(
            new_table, gate=self.params["head"]["gate"])
        self._n_swaps += 1
        if capacity_factor is not None:
            self._eff_capacity_factor = float(capacity_factor)
        # per-expert telemetry is per table version (K/V_pad can change
        # shape across swaps); the breaker window restarts too
        self._expert_dispatched = None
        self._expert_overflow = None
        self._win.clear()
        self._overflow_hist.clear()
        self._build_decode_fn()
        self._build_prefill_fns()
        log.info(
            "table swap -> v%d: K=%d V_pad=%d capacity_factor=%s "
            "(decode/prefill rebuilt once)",
            version, self.table.ids.shape[0], self.table.v_pad,
            self._eff_capacity_factor,
        )
        return version

    def traffic_profile(self) -> Optional[TrafficProfile]:
        """The stats window as a
        :class:`~repro.serve.table_manager.TrafficProfile`, sliced to
        the REAL expert count (a sharded session's stats cover
        ``shard_table``'s dummy-expert padding rows; dummies receive no
        traffic). ``None`` until the current table version has served at
        least one decode step with per-expert stats."""
        if not self._win:
            return None
        disp = np.sum([d for _, d, _ in self._win], axis=0, dtype=np.int64)
        over = np.sum([o for _, _, o in self._win], axis=0, dtype=np.int64)
        if self._head_params is not None:
            kreal = int(self._head_params["gate"].shape[0])
            disp, over = disp[:kreal], over[:kreal]
        return TrafficProfile(
            dispatched=disp, overflow=over, steps=len(self._win),
            start_step=self._win[0][0], end_step=self._win[-1][0],
        )

    def adapt_now(self) -> bool:
        """Force one adaptation pass immediately (the policy's interval
        and overflow threshold are ignored; a non-empty stats window is
        still required). Returns True when a swap happened."""
        if self._adapt_policy is None:
            raise ValueError("adapt_now() requires adapt_policy=")
        prof = self.traffic_profile()
        if prof is None:
            return False
        self._last_adapt_step = self.n_steps
        return self._adapt(prof)

    def _maybe_adapt(self) -> None:
        """End-of-step adaptation check — swaps only ever happen HERE or
        in :meth:`adapt_now`, strictly between decode steps."""
        pol = self._adapt_policy
        if pol is None or self._n_swaps >= pol.max_swaps:
            return
        if self.n_steps - self._last_adapt_step < pol.interval:
            return
        prof = self.traffic_profile()
        if prof is None or prof.steps < pol.min_window_steps:
            return
        self._last_adapt_step = self.n_steps
        if prof.overflow_rate <= pol.overflow_threshold:
            return
        self._adapt(prof)

    def _adapt(self, prof: TrafficProfile) -> bool:
        pol = self._adapt_policy
        if self._n_swaps >= pol.max_swaps:
            return False
        key = jax.random.fold_in(jax.random.PRNGKey(pol.seed), self._n_swaps)
        res = repack_for_traffic(
            self._head_params, self._ds_state, prof, key=key,
            prune_gamma=pol.prune_gamma,
            mitosis_overflow_threshold=pol.mitosis_overflow_threshold,
            headroom=pol.headroom, noise=pol.noise,
            base_capacity_factor=(self._eff_capacity_factor
                                  if self._eff_capacity_factor is not None
                                  else self.cfg.ds.capacity_factor),
        )
        # evolve the tracked (head, mask) pair so later repacks compound
        self._head_params, self._ds_state = res.head_params, res.state
        log.info(
            "adaptive repack at step %d: window overflow %.3f over %d "
            "steps; cloned=%s pruned=%d rows",
            self.n_steps, prof.overflow_rate, prof.steps, res.cloned,
            res.rows_pruned,
        )
        self.swap_table(res.table, new_gate=res.head_params["gate"],
                        capacity_factor=res.capacity_factor)
        return True

    # -- public API ---------------------------------------------------------

    def submit(self, req: Request) -> bool:
        """Validate and enqueue a request (admitted into a slot on the
        next step). Returns True if the request was accepted, False if
        the bounded queue shed it (status ``REJECTED``).

        ALL validation happens HERE, before any compute or slot
        admission — a bad request must never abort a mid-flight decode
        step (or vanish half-admitted) for the residents. Invalid
        parameters raise ``ValueError`` naming the offending field, and
        the request is left with status ``REJECTED`` + ``error``.
        """
        if req.submit_step is not None or req.status is not RequestStatus.QUEUED:
            raise ValueError(
                f"request was already submitted (status={req.status.value!r})"
            )

        def reject(msg: str) -> None:
            self._finish(req, RequestStatus.REJECTED, msg)
            raise ValueError(msg)

        try:
            sp = req.sampling_params
        except ValueError as e:  # legacy max_new_tokens AND sampling= set
            reject(str(e))
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        S = len(prompt)
        if sp.max_new_tokens < 1:
            reject(f"max_new_tokens must be >= 1, got {sp.max_new_tokens}")
        if not np.isfinite(sp.temperature) or sp.temperature < 0.0:
            reject(f"temperature must be finite and >= 0 (0 = greedy), "
                   f"got {sp.temperature}")
        if sp.top_k is not None and sp.top_k < 1:
            reject(f"top_k must be >= 1, got {sp.top_k}")
        if sp.top_k is not None and sp.top_k > self.k:
            # the head only returns this session's k candidates — a wider
            # top_k cannot widen the distribution; rejecting beats silently
            # serving an effective top_k of k
            reject(f"top_k ({sp.top_k}) exceeds the head's candidate width "
                   f"k ({self.k}); the head only returns k candidates")
        if sp.deadline_steps is not None and sp.deadline_steps < 1:
            reject(f"deadline_steps must be >= 1, got {sp.deadline_steps}")
        if S < 1:
            reject("empty prompt")
        if prompt.min() < 0 or prompt.max() >= self.cfg.vocab_size:
            bad = prompt[(prompt < 0) | (prompt >= self.cfg.vocab_size)][0]
            reject(f"prompt contains token id {bad} outside "
                   f"[0, {self.cfg.vocab_size})")
        # speculative sessions write gamma draft positions past the last
        # emitted token before the verify step prunes them
        spec_pad = self.gamma if self._draft is not None else 0
        if S + sp.max_new_tokens - 1 + spec_pad > self.max_seq_len:
            reject(
                f"prompt_len ({S}) + max_new_tokens ({sp.max_new_tokens})"
                f" - 1"
                + (f" + speculative headroom ({spec_pad})" if spec_pad else "")
                + f" exceeds max_seq_len ({self.max_seq_len})"
            )
        if self.prefill_chunk is not None:
            # The tail chunk writes a full `prefill_chunk` rows (padding
            # included); a write past the cache end would be start-clamped
            # by dynamic_update_slice and silently corrupt earlier K/V.
            cp = self.prefill_chunk
            needed = -(-S // cp) * cp
            if needed > self.max_seq_len:
                reject(
                    f"chunked prefill rounds the prompt up to a multiple of"
                    f" prefill_chunk ({cp}): needs {needed} cache rows >"
                    f" max_seq_len ({self.max_seq_len}); raise max_seq_len"
                    " or lower prefill_chunk"
                )
        if self._mgr is not None and self._mgr.has_kv:
            # worst-case page footprint must fit the arena ALONE — a
            # request that cannot run even with every resident preempted
            # is rejected up front rather than wedging the queue
            worst = S + sp.max_new_tokens - 1 + spec_pad
            if self.prefill_chunk is not None:
                worst = max(worst, -(-S // self.prefill_chunk)
                            * self.prefill_chunk)
            need = -(-worst // self._mgr.page_size)
            if need > self._mgr.allocatable:
                reject(
                    f"request needs {need} pages at its max length but the"
                    f" arena only has {self._mgr.allocatable}; raise"
                    " page_arena or shorten the request"
                )
        req.submit_step = self.n_steps
        self.requests.append(req)
        victim = self.scheduler.submit(req)
        if victim is not None:
            self._finish(
                victim, RequestStatus.REJECTED,
                f"shed: queue full (queue_limit={self.scheduler.queue_limit})",
            )
        return victim is not req

    def cancel(self, req: Request) -> bool:
        """Abort a request mid-flight. A queued request leaves the queue;
        an active one releases its slot before the next decode step —
        batchmates are untouched (slots, cache rows and RNG streams are
        per-request, so survivors stay token-identical). Safe to call
        from inside ``stream_cb``. Returns False if the request already
        reached a terminal status (or was never submitted here)."""
        if req.status in TERMINAL:
            return False
        if req in self.scheduler.queue:
            self.scheduler.queue.remove(req)
            self._finish(req, RequestStatus.CANCELLED)
            return True
        for i, slot in self.scheduler.active():
            if slot.req is req:
                self._finish_slot(i, RequestStatus.CANCELLED)
                return True
        return False

    def step(self) -> bool:
        """Expire overdue queued requests, admit into free slots, then run
        ONE jitted decode step over the slot batch (or one speculative
        draft–verify block when ``draft=`` is set). Returns True while
        work remains.

        Under ``jax.profiler`` the step records host spans: ``serve.step``
        around all of it, ``serve.admit`` (with one ``serve.prefill`` and
        its ``serve.prefill.fetch`` per admitted request), then
        ``serve.decode.dispatch``, ``serve.decode.fetch`` (every
        device-to-host read of the decode's outputs),
        ``serve.sample_emit`` and ``serve.adapt``."""
        with jax.profiler.StepTraceAnnotation("serve.step",
                                              step_num=self.n_steps):
            self._expire_queue()
            with jax.profiler.TraceAnnotation("serve.admit"):
                self._admit()
            if self._draft is not None:
                return self._step_speculative()
            return self._step_decode()

    def _step_decode(self) -> bool:
        if self._mgr is not None:
            self._prepare_decode_writes()
        act = self.scheduler.active()
        if not act:
            return self.scheduler.has_work()
        with jax.profiler.TraceAnnotation("serve.decode.dispatch"):
            if self._mgr is not None:
                vals, ids, self._cache, stats = self._decode_fn(
                    self.params, self.table, self._cache,
                    jnp.asarray(self._tok), jnp.asarray(self._pos),
                    jnp.asarray(self._mgr.tables),
                    jnp.asarray(self._mgr.state_pid),
                )
            else:
                vals, ids, self._cache, stats = self._decode_fn(
                    self.params, self.table, self._cache,
                    jnp.asarray(self._tok), jnp.asarray(self._pos),
                )
        self.n_steps += 1
        with jax.profiler.TraceAnnotation("serve.decode.fetch", arrays=4):
            vals, ids = np.asarray(vals), np.asarray(ids)
            disp, over = _fetch_overflow(stats)
        self._record_overflow(disp, over)
        with jax.profiler.TraceAnnotation("serve.sample_emit"):
            for i, slot in act:
                if self.scheduler.slots[i] is not slot:
                    continue  # released mid-loop (e.g. cancel from a stream_cb)
                if not np.isfinite(vals[i]).all() or ids[i, 0] < 0:
                    # quarantine ONLY this slot: per-slot decode math never
                    # mixes rows, so the survivors' outputs are unaffected.
                    # ids[0] < 0 is the masked-NaN signature: XLA's top_k can
                    # sort NaN scores BELOW the finite NEG_INF padding, so a
                    # poisoned row surfaces as all-padding ids rather than
                    # NaN values.
                    self._finish_slot(
                        i, RequestStatus.FAILED,
                        "non-finite decode output (slot quarantined)",
                    )
                    continue
                t = self._sample(vals[i], ids[i], slot.req.sampling_params,
                                 slot.n_emitted)
                self._emit(i, slot, t)
        if self._adapt_policy is not None:
            # adaptation swaps strictly BETWEEN steps: the decode above
            # ran to completion on the old table version
            with jax.profiler.TraceAnnotation("serve.adapt"):
                self._maybe_adapt()
        return self.scheduler.has_work()

    def _step_speculative(self) -> bool:
        """One draft–verify block: gamma sequential draft proposals per
        slot, ONE batched target verify over every resident's
        (gamma+1)-token block, host-side exact acceptance, then the
        state-commit passes and per-slot emission.

        Ordering is load-bearing: commits run BEFORE the finite guard /
        emission so a quarantined slot's state is still well-defined when
        it is scrubbed, and emission releases slots only after every
        batched device call of the step has launched."""
        W = self.gamma + 1
        if self._mgr is not None:
            # verify writes the whole block: secure [pos, pos+W) per slot
            self._prepare_decode_writes(width=W)
        act = self.scheduler.active()
        if not act:
            return self.scheduler.has_work()
        n = self.n_slots
        base_tok = self._tok.copy()
        base_pos = self._pos.copy()

        # -- draft proposals: gamma sequential B=n_slots draft decodes ----
        # jnp arrays are immutable, so holding the pre-block draft cache
        # is a free snapshot — the commit pass re-advances it by each
        # row's accepted prefix only
        d = self._draft
        d_cache0 = self._draft_cache
        dtok = base_tok.copy()
        dpos = base_pos.copy()
        props = np.zeros((n, self.gamma), np.int32)
        # per (slot, j): the draft's (vals, ids) behind proposal j, or
        # None when the proposal is a point mass (greedy draft rows need
        # no q; poisoned draft rows fall back to a token-0 point mass —
        # acceptance stays exact, the target supplies the real token)
        prop_q: list = [[None] * self.gamma for _ in range(n)]
        for j in range(self.gamma):
            dvals, dids, self._draft_cache = self._draft_decode_fn(
                d["params"], d["table"], self._draft_cache,
                jnp.asarray(dtok), jnp.asarray(dpos))
            dvals, dids = np.asarray(dvals), np.asarray(dids)
            for i, slot in act:
                if self.scheduler.slots[i] is not slot:
                    continue
                sp = slot.req.sampling_params
                m = slot.n_emitted + j  # absolute index of the proposed token
                if not np.isfinite(dvals[i]).all() or dids[i, 0] < 0:
                    props[i, j] = 0
                    dtok[i] = 0
                    continue
                if sp.temperature <= 0.0:
                    t = int(dids[i, 0])
                else:
                    k_eff = dids.shape[1] if sp.top_k is None \
                        else min(sp.top_k, dids.shape[1])
                    u = _uniforms(sp.seed, _SALT_DRAFT, m, k_eff)
                    with np.errstate(divide="ignore"):
                        g = -np.log(-np.log(u))
                    t = int(dids[i, int(np.argmax(
                        dvals[i, :k_eff].astype(np.float64) / sp.temperature
                        + g))])
                    prop_q[i][j] = (dvals[i].copy(), dids[i].copy())
                props[i, j] = t
                dtok[i] = t
            dpos += 1

        # -- ONE batched chunk-shaped verify over every block -------------
        blocks = np.zeros((n, W), np.int32)
        blocks[:, 0] = base_tok
        blocks[:, 1:] = props
        if self._mgr is not None:
            vvals, vids, self._cache, stats = self._verify_fn(
                self.params, self.table, self._cache, jnp.asarray(blocks),
                jnp.asarray(base_pos), jnp.asarray(self._mgr.tables),
                jnp.asarray(self._mgr.state_pid))
        else:
            vvals, vids, self._cache, stats = self._verify_fn(
                self.params, self.table, self._cache, jnp.asarray(blocks),
                jnp.asarray(base_pos))
        self.n_steps += 1
        vvals, vids = np.asarray(vvals), np.asarray(vids)
        self._record_overflow(*_fetch_overflow(stats))

        # -- host-side exact acceptance -----------------------------------
        emitted: dict = {}
        n_valid = np.ones(n, np.int32)
        poisoned: List[int] = []
        for i, slot in act:
            if self.scheduler.slots[i] is not slot:
                continue
            if not np.isfinite(vvals[i]).all() or (vids[i, :, 0] < 0).any():
                poisoned.append(i)
                continue
            toks, n_acc = self._accept_block(
                vvals[i], vids[i], props[i], prop_q[i],
                slot.req.sampling_params, slot.n_emitted)
            emitted[i] = toks
            n_valid[i] = n_acc + 1
            self._spec_stats["slot_steps"] += 1
            self._spec_stats["accepted"] += n_acc
        self._spec_stats["steps"] += 1

        # -- commit accepted prefixes (state families cannot roll back) ---
        nv = jnp.asarray(n_valid)
        if self._commit_fn is not None:
            if self._mgr is not None:
                self._cache = self._commit_fn(
                    self.params, self._cache, jnp.asarray(blocks),
                    jnp.asarray(base_pos), nv,
                    jnp.asarray(self._mgr.tables),
                    jnp.asarray(self._mgr.state_pid))
            else:
                self._cache = self._commit_fn(
                    self.params, self._cache, jnp.asarray(blocks),
                    jnp.asarray(base_pos), nv)
        if self._draft_commit_fn is not None:
            # state-family draft: re-advance from the pre-block snapshot.
            # (A transformer draft needs neither rollback nor commit: its
            # next proposal round overwrites position pos' before reading
            # it, and stale rows past pos' stay masked.)
            self._draft_cache = self._draft_commit_fn(
                d["params"], d_cache0, jnp.asarray(blocks),
                jnp.asarray(base_pos), nv)

        # -- quarantine, then emit ----------------------------------------
        for i in poisoned:
            self._finish_slot(
                i, RequestStatus.FAILED,
                "non-finite verify output (slot quarantined)",
            )
        for i, slot in act:
            if self.scheduler.slots[i] is not slot:
                continue
            for t in emitted.get(i, ()):
                self._emit(i, slot, t)
                # count tokens actually emitted (a slot hitting eos or
                # max_new truncates its accepted block mid-emission)
                self._spec_stats["emitted"] += 1
                if self.scheduler.slots[i] is not slot:
                    break  # finished (eos/max/deadline) or cb released it
        if self._adapt_policy is not None:
            self._maybe_adapt()
        return self.scheduler.has_work()

    def _accept_block(self, vals_w: np.ndarray, ids_w: np.ndarray,
                      props: np.ndarray, prop_q: list, sp: SamplingParams,
                      m0: int) -> tuple:
        """Exact acceptance for one slot's verified block. Returns
        ``(tokens_to_emit, n_accepted)`` — the accepted draft prefix plus
        exactly one target-sampled token (correction on first mismatch /
        rejection, bonus after a clean sweep).

        Greedy is a literal prefix match against the target argmax chain,
        so the emitted tokens are bit-identical to non-speculative greedy
        decoding. Sampled mode is standard speculative rejection sampling
        adapted to the head's top-k-truncated distributions: accept
        proposal d ~ q with probability min(1, p(d)/q(d)); on rejection
        draw from the residual (p - q)^+ mapped onto the TARGET's
        candidate support. For any discrete p, q — including point-mass
        fallbacks and disjoint supports — the emitted token is
        distributed exactly as p. All uniforms key on ``(seed, absolute
        emission index)``, making the stream invariant to how blocks were
        aligned (preempt-resume restarts at a block boundary and still
        replays identically)."""
        gamma = len(props)
        out: List[int] = []
        n_acc = 0
        if sp.temperature <= 0.0:
            for j in range(gamma):
                tgt = int(ids_w[j, 0])
                out.append(tgt)
                if int(props[j]) != tgt:
                    return out, n_acc  # correction token emitted
                n_acc += 1
            out.append(int(ids_w[gamma, 0]))  # bonus token
            return out, n_acc
        k = ids_w.shape[1]
        k_eff = k if sp.top_k is None else min(sp.top_k, k)
        for j in range(gamma):
            m = m0 + j
            pv = vals_w[j, :k_eff].astype(np.float64) / sp.temperature
            pv -= pv.max()
            p = np.exp(pv)
            p /= p.sum()
            pid = ids_w[j, :k_eff].astype(np.int64)
            d_tok = int(props[j])
            q_on_p = np.zeros_like(p)  # q mapped onto the target support
            if prop_q[j] is None:
                qd = 1.0  # point mass on the proposal
                hits = np.nonzero(pid == d_tok)[0]
                if len(hits):
                    q_on_p[hits[0]] = 1.0
            else:
                dvals, dids = prop_q[j]
                dk_eff = len(dids) if sp.top_k is None \
                    else min(sp.top_k, len(dids))
                qv = dvals[:dk_eff].astype(np.float64) / sp.temperature
                qv -= qv.max()
                q = np.exp(qv)
                q /= q.sum()
                did = dids[:dk_eff].astype(np.int64)
                qd = float(q[np.nonzero(did == d_tok)[0][0]])
                for a, cid in enumerate(pid):
                    hit = np.nonzero(did == cid)[0]
                    if len(hit):
                        q_on_p[a] = q[hit[0]]
            hits = np.nonzero(pid == d_tok)[0]
            pd = float(p[hits[0]]) if len(hits) else 0.0
            u = float(_uniforms(sp.seed, _SALT_ACCEPT, m, 1)[0])
            if u * qd <= pd:  # accept with prob min(1, p/q)
                out.append(d_tok)
                n_acc += 1
                continue
            res = np.maximum(p - q_on_p, 0.0)
            z = res.sum()
            # z == P(reject under p's support); z <= 0 only when q covers
            # p exactly on this support (then rejection implies the mass
            # lives outside — defensively resample from p itself)
            res = res / z if z > 0.0 else p
            r = float(_uniforms(sp.seed, _SALT_RESID, m, 1)[0])
            idx = int(np.searchsorted(np.cumsum(res), r, side="right"))
            out.append(int(pid[min(idx, k_eff - 1)]))
            return out, n_acc
        # clean sweep: the bonus token is the PLAIN stream sample from the
        # last row — exactly p_gamma, keyed like any other emission
        out.append(self._sample(vals_w[gamma], ids_w[gamma], sp, m0 + gamma))
        return out, n_acc

    def run(self, requests: Optional[List[Request]] = None) -> List[Request]:
        """Submit ``requests`` (if given) and step until the queue drains.
        Returns every request this session has served."""
        for r in requests or ():
            self.submit(r)
        while self.step():
            pass
        return self.requests

    def stats(self) -> dict:
        """Host-side counters snapshot: queue/slot occupancy, per-outcome
        request counts, shed count, prefill chunks run on any chunked
        path (``prefill_chunks``), per-expert dispatch/overflow totals
        AND the step-stamped window over them (``*_window`` keys with
        ``window_start_step``/``window_end_step`` stamps — what
        :meth:`traffic_profile` consumes), the circuit-breaker state,
        and the table-swap accounting (``table_version``, ``n_swaps``,
        ``decode_builds``)."""
        o = self._outcomes
        hist = self._overflow_hist
        if self.cfg.head == "ds":
            eff_cf = (self._eff_capacity_factor
                      if self._eff_capacity_factor is not None
                      else self.cfg.ds.capacity_factor)
        else:
            eff_cf = None
        out = {
            "n_admitted": self.scheduler.n_admitted,
            "n_released": self.scheduler.n_released,
            "n_steps": self.n_steps,
            "prefill_chunks": self._n_prefill_chunks,
            "n_queued": len(self.scheduler.queue),
            "queue_depth": len(self.scheduler.queue),
            "n_active": len(self.scheduler.active()),
            "n_completed": o[RequestStatus.COMPLETED],
            "n_rejected": o[RequestStatus.REJECTED],
            "n_cancelled": o[RequestStatus.CANCELLED],
            "n_timed_out": o[RequestStatus.TIMED_OUT],
            "n_failed": o[RequestStatus.FAILED],
            "n_shed": self.scheduler.n_shed,
            "overflow_rate": (sum(hist) / len(hist)) if hist else 0.0,
            "expert_dispatched": (
                self._expert_dispatched.tolist()
                if self._expert_dispatched is not None else None),
            "expert_overflow": (
                self._expert_overflow.tolist()
                if self._expert_overflow is not None else None),
            "breaker_trips": self._breaker_trips,
            "effective_capacity_factor": eff_cf,
            "effective_kernel": self._eff_kernel,
            "table_version": self._table_res.version,
            "n_swaps": self._n_swaps,
            "decode_builds": self._n_decode_builds,
            "quantize": self._quantize,
            "quantize_report": (self._quantize_report.as_dict()
                                if self._quantize_report is not None else None),
        }
        if self._win:
            wd = np.sum([d for _, d, _ in self._win], axis=0, dtype=np.int64)
            wo = np.sum([ov for _, _, ov in self._win], axis=0,
                        dtype=np.int64)
            out["expert_dispatched_window"] = wd.tolist()
            out["expert_overflow_window"] = wo.tolist()
            out["window_start_step"] = self._win[0][0]
            out["window_end_step"] = self._win[-1][0]
            out["window_steps"] = len(self._win)
            out["overflow_rate_window"] = \
                float(wo.sum()) / max(1.0, float(wd.sum()))
        else:
            out["expert_dispatched_window"] = None
            out["expert_overflow_window"] = None
            out["window_start_step"] = None
            out["window_end_step"] = None
            out["window_steps"] = 0
            out["overflow_rate_window"] = 0.0
        if self._mgr is not None:
            out["paged"] = {
                **self._mgr.stats(),
                "preemptions": self._n_preempted,
                "prefill_chunks": self._n_prefill_chunks,
                "prefill_chunks_saved": self._n_prefill_chunks_saved,
            }
        if self._draft is not None:
            ss = self._spec_stats
            steps = max(1, ss["steps"])
            out["speculative"] = {
                "gamma": self.gamma,
                "spec_steps": ss["steps"],
                "draft_accepted": ss["accepted"],
                "spec_emitted": ss["emitted"],
                # per VERIFY step, summed over resident slots; > 1 per
                # resident means speculation is paying (each step emits
                # the baseline's one token plus accepted drafts)
                "emitted_per_step": ss["emitted"] / steps,
                "accepted_per_step": ss["accepted"] / steps,
                # fraction of proposed draft tokens the target accepted
                "accept_rate": ss["accepted"]
                / max(1, ss["slot_steps"] * self.gamma),
            }
        return out

    # -- internals ----------------------------------------------------------

    def _finish(self, req: Request, status: RequestStatus,
                error: Optional[str] = None) -> None:
        """Record a request's terminal outcome (single choke point — every
        terminal transition goes through here)."""
        assert status in TERMINAL
        req.status = status
        if error is not None:
            req.error = error
        self._outcomes[status] += 1
        if status is RequestStatus.FAILED:
            log.warning("request FAILED: %s", error)

    def _finish_slot(self, i: int, status: RequestStatus,
                     error: Optional[str] = None) -> None:
        """Terminal outcome for a resident request: release the slot and
        zero its feedback token/position (the row decodes garbage-free
        dummy tokens until re-admission, exactly like a drained slot)."""
        slot = self.scheduler.slots[i]
        self._finish(slot.req, status, error)
        self.scheduler.release(i)
        self._tok[i] = 0
        self._pos[i] = 0
        if self._mgr is not None:
            # drop every page reference; scrub (zero) the pages that
            # actually free when the tenant failed poisoned — a shared
            # page survives through its co-owners and is scrubbed by
            # whichever failing sharer drops the LAST reference
            self._release_slot_pages(i, scrub=status is RequestStatus.FAILED)
        elif status is RequestStatus.FAILED:
            # decontaminate: the slot's cache rows are non-finite and a
            # later (shorter) tenant's insert would not overwrite all of
            # them — masked attention still multiplies them (0·NaN=NaN)
            self._cache = self._scrub_fn(self._cache, i)
        if self._draft is not None and status is RequestStatus.FAILED:
            # the draft's contiguous row may carry the same poison
            self._draft_cache = self._draft_scrub_fn(self._draft_cache, i)

    def _expire_queue(self) -> None:
        overdue = [
            r for r in self.scheduler.queue
            if r.sampling_params.deadline_steps is not None
            and self.n_steps - r.submit_step
            >= r.sampling_params.deadline_steps
        ]
        for req in overdue:
            self.scheduler.queue.remove(req)
            self._finish(
                req, RequestStatus.TIMED_OUT,
                f"deadline_steps={req.sampling_params.deadline_steps} "
                "exceeded while queued",
            )

    def _record_overflow(self, disp: np.ndarray, over: np.ndarray) -> None:
        """Account one step's per-expert dispatch/overflow counts (host
        arrays, see :func:`_fetch_overflow`) and run the breaker."""
        if self._expert_dispatched is None \
                or self._expert_dispatched.shape != disp.shape:
            # first step on this table version (swap_table resets the
            # accumulators; the shape guard is defensive — K can change)
            self._expert_dispatched = np.zeros_like(disp)
            self._expert_overflow = np.zeros_like(over)
            self._win.clear()
        self._expert_dispatched += disp
        self._expert_overflow += over
        # n_steps was already incremented for the step these stats came
        # from, so the stamp is the 1-based id of the completed step
        self._win.append((self.n_steps, disp, over))
        rate = float(over.sum()) / max(float(disp.sum()), 1.0)
        self._overflow_hist.append(rate)
        self._maybe_trip_breaker()

    def _maybe_trip_breaker(self) -> None:
        """Graceful degradation when capacity overflow stops being rare.

        Overflowed tokens are still EXACT (the grouped kernels re-run
        them through the fixup path), but a sustained overflow rate means
        the capacity buffers are mis-sized for the live token mix and the
        fixup dominates the step. Trip 1 doubles the effective
        ``capacity_factor``; trip 2 abandons capacity buffers entirely
        and falls back to the always-exact ``'jnp'`` path (which never
        overflows, so the breaker naturally stops here)."""
        if self.cfg.head != "ds" or self._breaker_trips >= 2:
            return
        hist = self._overflow_hist
        if len(hist) < hist.maxlen:
            return
        rate = sum(hist) / len(hist)
        if rate <= self._overflow_threshold:
            return
        self._breaker_trips += 1
        if self._breaker_trips == 1:
            base = self.cfg.ds.capacity_factor
            self._eff_capacity_factor = 2.0 * base
            log.warning(
                "overflow breaker trip 1: mean rate %.3f > %.3f over %d "
                "steps; capacity_factor %.2f -> %.2f (decode step rebuilt)",
                rate, self._overflow_threshold, hist.maxlen, base,
                self._eff_capacity_factor,
            )
        else:
            self._eff_kernel = "jnp"
            log.warning(
                "overflow breaker trip 2: mean rate %.3f still > %.3f after "
                "capacity bump; falling back to the always-exact 'jnp' "
                "serve path (decode step rebuilt)",
                rate, self._overflow_threshold,
            )
        self._overflow_hist.clear()
        self._build_decode_fn()

    def _admit(self) -> None:
        sched = self.scheduler
        while sched.queue:
            i = sched.free_slot()
            if i is None:
                return
            req = sched.pop_next()
            prompt = np.asarray(req.prompt, np.int32).reshape(-1)
            S = len(prompt)  # validated in submit()
            sp = req.sampling_params
            n_resume = len(req.out_tokens)
            if n_resume:
                # resuming a preempted request: re-prefill everything it
                # had produced except the last token, which is fed back
                # as the next decode input — the sampling stream then
                # continues at the preserved n_emitted counter, so its
                # tokens are identical from the preemption point
                toks = np.concatenate(
                    [prompt, np.asarray(req.out_tokens[:-1], np.int32)])
            else:
                toks = prompt
            with jax.profiler.TraceAnnotation(
                    "serve.prefill", slot=i, tokens=len(toks)) as span:
                chunks0 = self._n_prefill_chunks
                out = self._prefill_into_slot(toks, i, sp.priority)
                span.set_metadata(chunks=self._n_prefill_chunks - chunks0)
                if out is not None:
                    vals, ids, pending = out
                    with jax.profiler.TraceAnnotation("serve.prefill.fetch"):
                        vals, ids = np.asarray(vals), np.asarray(ids)
            if out is None:
                # paged arena exhausted with nothing preemptible below
                # this priority: requeue at the FRONT and wait for the
                # residents to finish — unless nothing is resident to
                # wait for (cannot happen when submit() validated the
                # worst case; defensive terminal)
                if sched.active():
                    sched.requeue(req)
                    return
                self._finish(
                    req, RequestStatus.FAILED,
                    "page arena exhausted with no resident to wait for",
                )
                continue
            if not np.isfinite(vals[0]).all() or ids[0, 0] < 0:
                # quarantine BEFORE admission: the slot stays free and
                # its poisoned cache rows are scrubbed so the next
                # tenant (whose prompt may be shorter than this one)
                # never inherits a residual NaN row
                # (ids[0] < 0 = masked-NaN signature, see step())
                self._finish(
                    req, RequestStatus.FAILED,
                    "non-finite prefill output (request quarantined)",
                )
                if self._mgr is not None:
                    self._release_slot_pages(i, scrub=True)
                else:
                    self._cache = self._scrub_fn(self._cache, i)
                continue
            # register shared prefixes only AFTER the finite guard: a
            # poisoned prefill must never become adoptable
            for key, length, snap in pending:
                self._mgr.register_prefix(i, key, length,
                                          state_snapshot=snap)
            slot = sched.admit(i, req, S)
            req.status = RequestStatus.ACTIVE
            if self._draft is not None:
                # the draft mirrors the slot's token history in its own
                # contiguous cache (re-prefilled from scratch on resume)
                self._draft_prefill_slot(toks, i)
            if n_resume:
                # the re-prefill's head output is discarded: those tokens
                # were already emitted before preemption
                slot.n_emitted = n_resume
                self._tok[i] = req.out_tokens[-1]
                self._pos[i] = slot.pos
            else:
                t0 = self._sample(vals[0], ids[0], sp, 0)
                self._emit(i, slot, t0)

    def _prefill_into_slot(self, toks: np.ndarray, i: int, priority: int):
        """Prefill ``toks`` into slot ``i``; returns ``(vals, ids,
        pending_prefixes)`` or ``None`` when the paged arena could not
        supply the pages even after preemption (the slot is left with
        nothing mapped)."""
        S = len(toks)
        m = self._mgr
        pending: List[tuple] = []
        if m is not None and not self._alloc_state_page(i, priority):
            return None
        if self.prefill_chunk is None:
            if m is not None:
                if not self._prepare_kv_write_range(i, 0, S, priority):
                    self._release_slot_pages(i, scrub=False)
                    return None
                m.activate_slot(i)
            vals, ids, row = self._prefill_fn(
                self.params, self.table, {"tokens": jnp.asarray(toks[None])}
            )
            if m is not None:
                self._cache = self._insert_paged_fn(
                    self._cache, row, jnp.asarray(m.tables[i]),
                    int(m.state_pid[i]))
            else:
                self._cache = self._insert_fn(self._cache, row, i)
            return vals, ids, pending
        cp = self.prefill_chunk
        if m is None:
            row = self._row_zero
            for lo in range(0, S, cp):
                tail = toks[lo: lo + cp]
                buf = np.zeros(cp, np.int32)
                buf[: len(tail)] = tail
                vals, ids, row = self._chunk_fn(
                    self.params, self.table, row, jnp.asarray(buf[None]),
                    lo, len(tail),
                )
                self._n_prefill_chunks += 1
            self._cache = self._insert_fn(self._cache, row, i)
            return vals, ids, pending
        # paged chunked prefill, straight into the shared arena
        pos0 = 0
        if self._prefix_sharing:
            # max_len = S - 1: at least one tail chunk always runs and
            # produces the head's top-k for this prompt
            e = m.match_prefix(toks, cp, S - 1)
            if e is not None:
                m.adopt_prefix(i, e)
                if e.state is not None:
                    self._cache = self._copy_state_page_fn(
                        self._cache, e.state[0], int(m.state_pid[i]))
                pos0 = e.length
                self._n_prefill_chunks_saved += pos0 // cp
        m.activate_slot(i)
        vals = ids = None
        for lo in range(pos0, S, cp):
            tail = toks[lo: lo + cp]
            # the chunk writes its FULL cp rows (tail padding included),
            # so the prepared range is page-exact for the whole chunk
            if not self._prepare_kv_write_range(i, lo, lo + cp, priority):
                self._release_slot_pages(i, scrub=False)
                return None
            buf = np.zeros(cp, np.int32)
            buf[: len(tail)] = tail
            vals, ids, self._cache = self._chunk_fn(
                self.params, self.table, self._cache,
                jnp.asarray(buf[None]), lo, len(tail),
                jnp.asarray(m.tables[i])[None],
                np.asarray(m.state_pid[i: i + 1], np.int32),
            )
            self._n_prefill_chunks += 1
            hi = lo + len(tail)
            if self._prefix_sharing and hi == lo + cp \
                    and not m.has_prefix(prefix_hash(toks[:hi]), hi):
                # snapshot this full-chunk boundary for later sharers;
                # state families need a copied state page (opportunistic:
                # never preempt anyone just for a snapshot)
                snap = None
                if m.has_state:
                    snap = m.alloc_state()
                    if snap is None:
                        continue
                    self._cache = self._copy_state_page_fn(
                        self._cache, int(m.state_pid[i]), snap)
                    m.state_holdings[i].append(snap)
                pending.append((prefix_hash(toks[:hi]), hi, snap))
        return vals, ids, pending

    def _draft_prefill_slot(self, toks: np.ndarray, i: int) -> None:
        """Prefill the draft's contiguous cache row for slot ``i`` with
        the slot's full token history (head output discarded — the draft
        first speaks in the next proposal round). Mirrors the session's
        prefill mode so a chunked session keeps one compiled draft
        prefill shape."""
        d = self._draft
        if self.prefill_chunk is None:
            _, _, row = self._draft_prefill_fn(
                d["params"], d["table"],
                {"tokens": jnp.asarray(np.asarray(toks, np.int32)[None])})
        else:
            cp = self.prefill_chunk
            row = self._draft_row_zero
            for lo in range(0, len(toks), cp):
                tail = toks[lo: lo + cp]
                buf = np.zeros(cp, np.int32)
                buf[: len(tail)] = tail
                _, _, row = self._draft_chunk_fn(
                    d["params"], d["table"], row, jnp.asarray(buf[None]),
                    lo, len(tail))
        self._draft_cache = self._draft_insert_fn(self._draft_cache, row, i)

    # -- paged-arena management ---------------------------------------------

    def _alloc_state_page(self, i: int, priority: int) -> bool:
        """Give slot ``i`` a private, ZEROED live state page (ssm/hybrid
        recurrence starts from zeros, exactly like the contiguous row)."""
        m = self._mgr
        if not m.has_state:
            return True
        while True:
            pid = m.alloc_state()
            if pid is not None:
                break
            if not self._preempt_lowest_below(priority):
                return False
        m.state_pid[i] = pid
        self._cache = self._zero_state_page_fn(self._cache, pid)
        return True

    def _prepare_kv_write_range(self, i: int, lo: int, hi: int,
                                priority: int) -> bool:
        """Make every page covering positions ``[lo, hi)`` of slot ``i``
        exclusively writable — allocating fresh pages, running CoW copies
        for shared ones, preempting strictly-lower-priority residents
        while the arena is exhausted. False when even that failed."""
        m = self._mgr
        if not m.has_kv:
            return True
        for j in range(lo // m.page_size, (hi - 1) // m.page_size + 1):
            while True:
                plan = m.prepare_write(i, j)
                if plan is not None:
                    break
                if not self._preempt_lowest_below(priority):
                    return False
            if plan.kind == "cow":
                self._cache = self._copy_page_fn(self._cache, plan.src,
                                                 plan.dst)
        return True

    def _prepare_decode_writes(self, width: int = 1) -> None:
        """Before the decode step, secure each resident's write positions
        (``width`` of them — 1 for plain decode, gamma+1 for a
        speculative verify block). A resident that cannot get its pages
        even after preempting every lower-priority batchmate preempts
        ITSELF — its freed pages unblock the survivors, and it resumes
        token-identically once capacity returns."""
        for i, slot in list(self.scheduler.active()):
            if self.scheduler.slots[i] is not slot:
                continue  # preempted by an earlier iteration
            pos = int(self._pos[i])
            pr = slot.req.sampling_params.priority
            if not self._prepare_kv_write_range(i, pos, pos + width, pr):
                self._preempt_slot(i)

    def _preempt_lowest_below(self, priority: int) -> bool:
        """Preempt the lowest-priority resident strictly below
        ``priority`` (newest admission among ties). False when nobody
        qualifies — equal priority never preempts equal priority."""
        victim = None
        for i, slot in self.scheduler.active():
            p = slot.req.sampling_params.priority
            if p >= priority:
                continue
            if victim is None:
                victim = (i, slot)
                continue
            vp = victim[1].req.sampling_params.priority
            if p < vp or (p == vp and slot.admit_seq > victim[1].admit_seq):
                victim = (i, slot)
        if victim is None:
            return False
        self._preempt_slot(victim[0])
        return True

    def _preempt_slot(self, i: int) -> None:
        """Preempt-and-requeue resident ``i``: a pure metadata swap. Its
        page references drop (shared prefix pages survive through their
        co-owners), the request goes back to the FRONT of the queue
        still holding its emitted tokens, and on re-admission it
        re-prefills ``prompt ++ out_tokens[:-1]`` and continues its
        sampling stream at the preserved ``n_emitted``."""
        slot = self.scheduler.slots[i]
        req = slot.req
        self._release_slot_pages(i, scrub=False)
        self.scheduler.release(i)
        self._tok[i] = 0
        self._pos[i] = 0
        req.status = RequestStatus.QUEUED
        self.scheduler.requeue(req)
        self._n_preempted += 1
        log.info(
            "preempted slot %d (priority=%d, %d tokens emitted); requeued",
            i, req.sampling_params.priority, slot.n_emitted,
        )

    def _release_slot_pages(self, i: int, scrub: bool) -> None:
        """Drop every page reference slot ``i`` holds and reset its table
        row to the garbage sink. ``scrub`` zeroes each page that actually
        returns to the free list (FAILED tenants: the rows may be
        non-finite, and page reuse must never leak NaN into a later
        tenant — a still-shared page is scrubbed by whichever failing
        co-owner drops the last reference)."""
        m = self._mgr
        for pid in m.mapped_kv_pages(i):
            if m.decref(pid) and scrub:
                self._cache = self._zero_kv_page_fn(self._cache, pid)
        if m.has_state:
            live = int(m.state_pid[i])
            if live >= N_RESERVED and m.decref_state(live) and scrub:
                self._cache = self._zero_state_page_fn(self._cache, live)
            for pid in list(m.state_holdings[i]):
                if m.decref_state(pid) and scrub:
                    self._cache = self._zero_state_page_fn(self._cache, pid)
        m.reset_slot(i)

    def _sample(self, vals: np.ndarray, ids: np.ndarray, sp: SamplingParams,
                n_emitted: int) -> int:
        """One token from the head's (k,) top-k candidates. Depends only on
        (vals, ids, sp, n_emitted) — a request samples identically whether
        it runs solo or batched with others (token-identity invariant).

        Pure host-side numpy: Gumbel-max over a counter-based Philox
        stream keyed by (seed, n_emitted). The previous implementation
        built a fresh PRNGKey + fold_in + jax.random.categorical PER
        TOKEN — one device dispatch/round-trip per emitted token, easily
        dominating small-model decode steps."""
        if sp.temperature <= 0.0:
            return int(ids[0])
        k_eff = len(ids) if sp.top_k is None else min(sp.top_k, len(ids))
        u = _uniforms(sp.seed, _SALT_SAMPLE, n_emitted, k_eff)
        with np.errstate(divide="ignore"):
            g = -np.log(-np.log(u))  # Gumbel(0,1); u=0 -> -inf, never picked
        scores = np.asarray(vals[:k_eff], np.float64) / sp.temperature + g
        return int(ids[int(np.argmax(scores))])

    def _emit(self, i: int, slot: _Slot, token: int) -> None:
        req = slot.req
        sp = req.sampling_params
        req.out_tokens.append(token)
        slot.n_emitted += 1
        if self.stream_cb is not None:
            try:
                self.stream_cb(req, token)
            except Exception as e:
                # contain: one raising callback fails ONLY its request;
                # the step loop and the other residents keep going
                self._finish_slot(i, RequestStatus.FAILED,
                                  f"stream_cb raised: {e!r}")
                return
        if req.status is not RequestStatus.ACTIVE:
            return  # cancelled (or otherwise finished) inside the callback
        finished = (sp.eos_id is not None and token == sp.eos_id) \
            or slot.n_emitted >= sp.max_new_tokens
        if finished:
            self._finish_slot(i, RequestStatus.COMPLETED)
            return
        if sp.deadline_steps is not None \
                and self.n_steps - req.submit_step >= sp.deadline_steps:
            self._finish_slot(
                i, RequestStatus.TIMED_OUT,
                f"deadline_steps={sp.deadline_steps} exceeded mid-decode "
                f"({slot.n_emitted} tokens emitted)",
            )
            return
        self._tok[i] = token
        self._pos[i] = slot.pos
