"""Declarative kernel-policy registry for the serving top-k hot path.

Every ``serve_topk`` compute path registers a :class:`KernelSpec` —
capabilities (grouped dispatch? Pallas?), backend support, and a
bytes-moved cost model lifted from the PR 1 roofline — and kernel
selection becomes a first-class, testable object instead of a raw string
fixed at engine init:

* ``serve_topk(kernel="grouped")`` — a registered name, validated here
  (unknown names raise, same message as before the registry existed).
* ``serve_topk(kernel="auto")`` / ``kernel=AutoPolicy()`` — resolved
  **per call site** from the static shapes (B, K, V_pad, d, k, dtype
  bytes) and the runtime backend: the cheapest *feasible* path wins.
  Prefill (large B) and decode (B = n_slots) inside one engine therefore
  resolve to different kernels — the ROADMAP's batch-size-aware
  selection open item.
* ``serve_topk(kernel=MyPolicy())`` — any object with a
  ``resolve(ctx) -> str`` method; the returned name is validated.

The cost model is *bytes moved* because serving is memory-bound (see
``benchmarks/serve_topk.py``, which reuses these exact formulas for its
roofline column): per-token paths re-read the packed expert rows once per
TOKEN, grouped paths once per EXPERT, so the grouped paths win as soon as
B ≫ K and lose (dispatch + K-row overhead) when B ≲ K. The crossover sits
near B ≈ K/2: the per-token ``jnp`` path pays its (B, V_pad, d) gather
materialization twice (spill + re-read), the grouped paths pay the full
K·V_pad·d table plus their per-slot spill. Speculative decoding shifts
decode along exactly this axis: the draft–verify step batches the head
over every resident's whole candidate block — B = (gamma+1)·n_slots
rows instead of n_slots — so a session whose plain decode sat below the
crossover lands in the grouped regime at verify time. No pricing change
is needed here: ``serve_kernel_context`` reads B from the head batch at
trace time, so the verify step's context prices (and ``AutoPolicy``
picks) the grouped paths automatically. Pallas paths are only feasible
on TPU, where they compile to Mosaic kernels; on other backends they run
in interpret mode, which is for tests, so :class:`AutoPolicy` never
selects them off-TPU.

Sharded (expert-parallel) variants register as first-class ``*_ep``
specs: their HBM model is the base path evaluated at the PER-DEVICE
shapes (K/ep experts, B/ndata tokens) and they carry a second cost term —
**ICI bytes**, the O(B·k) all-gather merge traffic of
``core.dssoftmax.serve_topk_sharded``. :class:`AutoPolicy` trades HBM
reads against gather traffic with a per-byte ICI:HBM penalty (interconnect
bandwidth is ~16× scarcer than HBM on a v5e-class part), so a call site
picks the sharded path exactly when the per-device table-read savings
beat the merge cost. Sharded specs are feasible only at ``ctx.ep > 1``
(and base specs only at ``ctx.ep == 1``), so a policy can never hand a
sharded name to the single-device ``serve_topk`` or vice versa.

Calibration (closing the ROADMAP open item): pass
``AutoPolicy(calibration=load_bench_calibration())`` to replace the unit
bytes-are-time assumption with measured µs/byte per (backend, path,
wbytes) from ``BENCH_serve_topk.json`` (the ``wbytes`` key keeps int8 /
bf16 / fp32 measurements from mixing). Scores switch to estimated µs only
when every feasible path is calibrated at the call site's ``wbytes`` —
mixing measured and modeled scales would be incoherent — and modeled
bytes remain the fallback.

Quantized serving (PR 9): ``KernelContext.quantized`` marks an int8
table (``wbytes == 1`` + per-row fp32 scales, priced by the cost
formulas); specs with ``quantized_ok=False`` (the legacy per-token
``pallas`` path) are infeasible there. The ``pallas_fused`` spec is the
single-launch gate→dispatch→retrieve decode kernel — its cost model has
no dispatch round-trip term, which is exactly why AutoPolicy picks it at
decode shapes (B ≳ K, one 128-row token block).
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Tuple

__all__ = [
    "KernelContext",
    "KernelSpec",
    "KernelPolicy",
    "FixedPolicy",
    "AutoPolicy",
    "register_kernel",
    "get_spec",
    "kernel_names",
    "resolve_kernel",
    "load_bench_calibration",
    "ICI_HBM_BYTE_RATIO",
]

# Per-byte cost of interconnect traffic relative to HBM traffic (v5e-class:
# ~819 GB/s HBM vs ~50 GB/s per ICI direction). Used by AutoPolicy to fold
# the sharded paths' all-gather term into one comparable scalar.
ICI_HBM_BYTE_RATIO = 16.0


@dataclass(frozen=True)
class KernelContext:
    """Static call-site shapes for kernel selection (all trace-time ints).

    ``wbytes``/``hbytes`` are the per-element sizes of the packed expert
    weights and the hidden states (bf16 serving => 2/2, fp32 oracle =>
    4/4); ``backend`` is ``jax.default_backend()`` at trace time.

    Contexts are built at trace time from the CURRENT serve table
    (``core.dssoftmax.serve_kernel_context`` reads ``table.ids.shape``),
    so they always price the table actually being served: when
    ``ServeSession.swap_table`` installs a table with a different
    ``(K, v_pad)``, its rebuild-once re-trace reprices every policy
    decision automatically — no construction-time constants survive a
    swap.
    """

    B: int                    # tokens in this serve_topk call
    d: int                    # hidden size
    K: int                    # experts
    v_pad: int                # padded active rows per expert
    k: int = 8                # top-k width
    backend: str = "cpu"      # 'cpu' | 'gpu' | 'tpu'
    capacity_factor: float = 2.0
    wbytes: int = 4
    hbytes: int = 4
    ep: int = 1               # expert-parallel degree (mesh 'model' axis)
    ndata: int = 1            # batch-shard degree (mesh 'pod'×'data' axes)
    quantized: bool = False   # int8 rows + per-row fp32 scales (wbytes == 1)

    @property
    def capacity(self) -> int:
        """Per-expert slot count of the grouped dispatch (mirrors
        ``core.dssoftmax._serve_topk_grouped``)."""
        return int(max(1, round(self.B / self.K * self.capacity_factor)))

    @property
    def out_bytes(self) -> int:
        """fp32 values + int32 ids reaching HBM — every path pays this."""
        return self.B * self.k * 8

    def local(self) -> "KernelContext":
        """The per-device view of a sharded call site: K/ep experts,
        B/ndata token rows, degrees reset to 1 (what one shard's kernel
        actually sees inside ``serve_topk_sharded``'s shard_map).
        ``capacity_factor`` is scaled by 1/ep so the derived ``capacity``
        matches the runtime's: the sharded grouped dispatch sizes its
        buffers by the GLOBAL expert count (B_loc/(K_loc·ep)·cf), not by
        the local one — without the rescale the modeled dispatch/spill
        terms would be ep× the bytes actually moved."""
        return replace(
            self,
            B=-(-self.B // self.ndata),
            K=-(-self.K // self.ep),
            capacity_factor=self.capacity_factor / self.ep,
            ep=1,
            ndata=1,
        )


@dataclass(frozen=True)
class KernelSpec:
    """One registered serve path: capabilities + bytes-moved cost model.

    ``cost`` is per-device HBM bytes; ``ici`` is per-device interconnect
    bytes (0 for single-device paths). ``sharded`` specs describe the
    expert-parallel execution of the base path named ``local_name`` and
    are only feasible at sharded call sites (``ctx.ep > 1``).
    """

    name: str
    description: str
    cost: Callable[[KernelContext], int] = field(compare=False)
    grouped: bool = False          # uses the expert-grouped dispatch pre-pass
    pallas: bool = False           # fused Pallas kernel (vs XLA lowering)
    backends: Optional[Tuple[str, ...]] = None  # None => native everywhere
    ici: Callable[[KernelContext], int] = field(compare=False,
                                                default=lambda c: 0)
    sharded: bool = False          # expert-parallel shard_map execution
    local_name: Optional[str] = None  # per-device kernel a sharded spec runs
    fused: bool = False            # in-kernel gating (no XLA dispatch pre-pass)
    quantized_ok: bool = True      # can serve int8 rows + per-row scales

    def supports(self, backend: str) -> bool:
        return self.backends is None or backend in self.backends

    def feasible(self, ctx: KernelContext) -> bool:
        """Runnable at this call site: backend-native AND matching the
        call's sharding (sharded specs need ep > 1; base specs need the
        single-device path) AND able to serve the table's precision."""
        return (self.supports(ctx.backend)
                and self.sharded == (ctx.ep > 1)
                and (self.quantized_ok or not ctx.quantized))

    def bytes_moved(self, ctx: KernelContext) -> int:
        """Per-device HBM bytes the path moves for one call at ``ctx``."""
        return int(self.cost(ctx))

    def ici_bytes(self, ctx: KernelContext) -> int:
        """Per-device interconnect bytes (the cross-device merge traffic)."""
        return int(self.ici(ctx))


_REGISTRY: dict[str, KernelSpec] = {}


def register_kernel(spec: KernelSpec) -> KernelSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"serve kernel {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def kernel_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def get_spec(name: str) -> KernelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown serve kernel {name!r} "
            f"(expected one of {' | '.join(map(repr, _REGISTRY))}, "
            "a policy name like 'auto', or a KernelPolicy)"
        ) from None


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------

class KernelPolicy:
    """Resolves a kernel name from call-site static shapes (trace time)."""

    def resolve(self, ctx: KernelContext) -> str:
        raise NotImplementedError


class FixedPolicy(KernelPolicy):
    """Always the same (validated) kernel — a string with a type."""

    def __init__(self, name: str):
        self.name = get_spec(name).name

    def resolve(self, ctx: KernelContext) -> str:
        return self.name


class AutoPolicy(KernelPolicy):
    """Cheapest feasible path by the cost model (HBM + weighted ICI bytes).

    Feasible = the spec supports ``ctx.backend`` natively (Pallas paths
    are TPU-only; XLA paths run everywhere) AND matches the call site's
    sharding (``*_ep`` specs at ep > 1, base specs otherwise). Pass
    ``history=[]`` to record ``(B, chosen)`` per *resolution* — i.e. once
    per jit trace, which is exactly once per distinct call-site shape.

    ``calibration`` maps ``(backend, base_path, wbytes) -> measured
    µs/byte`` (build one with :func:`load_bench_calibration`). The
    ``wbytes`` key keeps int8 / bf16 / fp32 measurements separate — a
    µs/byte rate measured streaming 4-byte rows must never price a
    1-byte table (different arithmetic intensity per byte). When EVERY
    feasible path at a call site is calibrated at the call site's
    ``wbytes``, scores become estimated µs (measured HBM rate per path +
    the ICI penalty on the merge bytes); otherwise modeled bytes remain
    the fallback for all of them — mixing measured and modeled scales
    would make the comparison incoherent.
    """

    def __init__(self, history: Optional[List[Tuple[int, str]]] = None,
                 calibration: Optional[Dict[Tuple[str, str, int], float]] = None):
        self.history = history
        self.calibration = calibration

    def _score(self, spec: KernelSpec, ctx: KernelContext,
               upb_ici: Optional[float]) -> float:
        hbm, ici = spec.bytes_moved(ctx), spec.ici_bytes(ctx)
        if upb_ici is not None:
            upb = self.calibration[
                (ctx.backend, spec.local_name or spec.name, ctx.wbytes)
            ]
            return hbm * upb + ici * upb_ici
        return hbm + ici * ICI_HBM_BYTE_RATIO

    def resolve(self, ctx: KernelContext) -> str:
        feasible = [s for s in _REGISTRY.values() if s.feasible(ctx)]
        if not feasible:
            raise ValueError(f"no serve kernel supports backend {ctx.backend!r}")
        upb_ici = None
        if self.calibration is not None and all(
            (ctx.backend, s.local_name or s.name, ctx.wbytes) in self.calibration
            for s in feasible
        ):
            # One interconnect rate for everyone: the merge traffic is the
            # same wire bytes whichever local kernel runs, so price it off
            # the backend's fastest measured HBM rate (the hardware-peak
            # proxy), never off each path's own — a slow local kernel must
            # not have identical ICI bytes scored as costlier.
            upb_ici = ICI_HBM_BYTE_RATIO * min(
                upb for (be, _, _), upb in self.calibration.items()
                if be == ctx.backend
            )
        best = min(feasible,
                   key=lambda s: (self._score(s, ctx, upb_ici), s.name))
        if self.history is not None:
            self.history.append((ctx.B, best.name))
        return best.name


def load_bench_calibration(
    path: str = "BENCH_serve_topk.json",
) -> Optional[Dict[Tuple[str, str, int], float]]:
    """Measured µs/byte per (backend, path, wbytes) from a serve_topk sweep.

    Reads the benchmark's rows (each carries ``us`` wall time, the
    registry's own ``bytes_model`` for identical shapes, and the table's
    ``wbytes``) and returns the median µs/byte per key — the per-backend
    read-rate calibration the ROADMAP asked to feed back into
    :class:`AutoPolicy`. Keying by ``wbytes`` keeps int8 / bf16 / fp32
    sweeps apart (rows predating PR 9 carry no ``wbytes`` field and key
    as the fp32 default 4). Returns ``None`` when the file is absent or
    holds no timed rows (modeled bytes stay the fallback), so callers
    can pass the result straight through:
    ``AutoPolicy(calibration=load_bench_calibration())``.
    """
    if not os.path.exists(path):
        return None
    try:
        with open(path) as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return None
    backend = data.get("config", {}).get("backend", "cpu")
    rates: Dict[Tuple[str, str, int], List[float]] = {}
    for row in data.get("rows", []):
        us, nbytes = row.get("us"), row.get("bytes_model")
        if us and nbytes:
            key = (backend, row["path"], int(row.get("wbytes", 4)))
            rates.setdefault(key, []).append(us / nbytes)
    if not rates:
        return None
    return {key: sorted(v)[len(v) // 2] for key, v in rates.items()}


_POLICIES: dict[str, KernelPolicy] = {}


def resolve_kernel(kernel, ctx: KernelContext) -> str:
    """str | KernelPolicy → validated registered kernel name.

    Strings naming a policy ('auto') resolve through it; strings naming a
    registered kernel pass through; anything else raises the familiar
    ``unknown serve kernel`` ValueError.
    """
    if isinstance(kernel, KernelPolicy):
        return get_spec(kernel.resolve(ctx)).name
    if isinstance(kernel, str):
        if kernel in _POLICIES:
            return get_spec(_POLICIES[kernel].resolve(ctx)).name
        return get_spec(kernel).name
    raise TypeError(
        f"kernel must be a registered name, policy name, or KernelPolicy; "
        f"got {type(kernel).__name__}"
    )


# ---------------------------------------------------------------------------
# The serve paths (cost formulas shared with benchmarks/serve_topk.py).
# wb/hb = weight/hidden bytes; every formula ends with the O(B·k) outputs.
# Quantized tables (wb == 1) additionally read the (K, V_pad) fp32 per-row
# scales alongside the rows they dequantize — priced via _scale_bytes so
# int8 is never modeled as a free 4×/2× win.
# ---------------------------------------------------------------------------

def _scale_bytes_grouped(c: KernelContext) -> int:
    # Per-row fp32 scales stream once alongside the (K, V_pad, d) rows.
    return c.K * c.v_pad * 4 if c.quantized else 0


def _cost_jnp(c: KernelContext) -> int:
    # Expert rows re-read once per TOKEN, *plus* the (B, V_pad, d) gather
    # XLA materializes in HBM before the matvec (write + re-read ≈ 2×).
    # Quantized: the gathered (B, V_pad) scales spill + re-read likewise.
    scale = 2 * c.B * c.v_pad * 4 if c.quantized else 0
    return (2 * c.B * c.v_pad * c.d * c.wbytes + scale
            + c.B * c.d * c.hbytes + c.out_bytes)


def _cost_grouped(c: KernelContext) -> int:
    # Rows once per EXPERT + the dispatch round-trip (the (K, C, d) grouped
    # buffers are scattered to HBM by the pre-pass and re-read by the
    # matmul — the traffic the fused path deletes), and XLA spills the
    # (K, C, V_pad) fp32 logits to HBM (write + read for the top-k).
    return (c.K * c.v_pad * c.d * c.wbytes + _scale_bytes_grouped(c)
            + 2 * c.K * c.capacity * c.d * c.hbytes
            + 2 * c.K * c.capacity * c.v_pad * 4 + c.out_bytes)


def _cost_pallas(c: KernelContext) -> int:
    # Streams rows per token (no gather spill) but spills per-block top-k
    # candidates and re-merges. No int8 variant (quantized_ok=False).
    n_blocks = max(1, c.v_pad // 128)
    return (c.B * c.v_pad * c.d * c.wbytes + c.B * c.d * c.hbytes
            + c.B * n_blocks * c.k * 8 + c.out_bytes)


def _cost_pallas_grouped(c: KernelContext) -> int:
    # Rows once per expert + the dispatch round-trip of the grouped
    # buffers; logits + running top-k never leave VMEM.
    return (c.K * c.v_pad * c.d * c.wbytes + _scale_bytes_grouped(c)
            + 2 * c.K * c.capacity * c.d * c.hbytes
            + c.K * c.capacity * c.k * 8 + c.out_bytes)


def _cost_pallas_fused(c: KernelContext) -> int:
    # Gate + dispatch in the kernel prologue: no dispatch round-trip at
    # all — tokens are read ONCE (B·d) and the whole table streams once
    # per 128-row token block (decode ⇒ one pass), plus the tiny gate
    # matrix and the (B,) expert-id telemetry output.
    passes = -(-c.B // 128)
    return (passes * (c.K * c.v_pad * c.d * c.wbytes + _scale_bytes_grouped(c))
            + c.K * c.d * 4 + c.B * c.d * c.hbytes + c.B * 4 + c.out_bytes)


register_kernel(KernelSpec(
    name="jnp",
    description="per-token gather + matvec in plain jnp (oracle/debug)",
    cost=_cost_jnp,
))
register_kernel(KernelSpec(
    name="grouped",
    description="expert-batched weight-stationary XLA matmul",
    cost=_cost_grouped,
    grouped=True,
))
register_kernel(KernelSpec(
    name="pallas",
    description="legacy per-token streaming Pallas kernel",
    cost=_cost_pallas,
    pallas=True,
    backends=("tpu",),
    quantized_ok=False,
))
register_kernel(KernelSpec(
    name="pallas_grouped",
    description="expert-grouped streaming Pallas kernel, in-VMEM top-k carry",
    cost=_cost_pallas_grouped,
    grouped=True,
    pallas=True,
    backends=("tpu",),
))
register_kernel(KernelSpec(
    name="pallas_fused",
    description="single-launch gate→dispatch→retrieve Pallas decode kernel",
    cost=_cost_pallas_fused,
    pallas=True,
    backends=("tpu",),
    fused=True,
))


# --- expert-parallel sharded variants (serve_topk_sharded execution) -------
#
# HBM model: the base path at the PER-DEVICE shapes (ctx.local(): K/ep
# experts, B/ndata token rows — per-token local paths still stream all
# local rows, owned or not, which the local() view captures). ICI model:
# the O(B·k) merge — each device receives the other ep-1 shards' (B_loc, k)
# fp32 value + int32 id carries from the ring all-gather.

def _ici_merge(c: KernelContext) -> int:
    return (c.ep - 1) * -(-c.B // c.ndata) * c.k * 8


def _register_sharded(base: KernelSpec) -> None:
    register_kernel(KernelSpec(
        name=f"{base.name}_ep",
        description=f"expert-parallel shard_map over '{base.name}' "
                    "(K/ep experts per device, O(B·k) all-gather merge)",
        cost=lambda c, _b=base: _b.cost(c.local()),
        grouped=base.grouped,
        pallas=base.pallas,
        backends=base.backends,
        ici=_ici_merge,
        sharded=True,
        local_name=base.name,
        fused=base.fused,
        quantized_ok=base.quantized_ok,
    ))


for _base in list(_REGISTRY.values()):
    _register_sharded(_base)

_POLICIES["auto"] = AutoPolicy()
