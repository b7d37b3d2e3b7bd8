"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

On a TPU the trace has a device plane (``/device:TPU:0``...) whose
``XLA Modules`` line holds one event per program run (named
``jit__decode(<hash>)`` for the decode step) and whose ``XLA Ops`` line
holds the operations, nested where a loop holds its body (named
``%dss_topk_fused.1 = ...`` for a Pallas DS kernel). The host plane
holds the harness's ``bench.*`` spans, which bound the traced window and
say what the host was doing in each idle gap.
"""
from __future__ import annotations

import collections
import glob
import os
from dataclasses import dataclass, field

HOST_SPAN_PREFIX = "bench."
DS_KERNEL_PREFIX = "dss_topk"   # the Pallas DS-head kernels: dss_topk*


@dataclass
class Ev:
    name: str
    start: int   # ns
    end: int     # ns


@dataclass
class Reduction:
    window_s: float
    busy_s: float                       # union of device op intervals, mean over chips
    n_devices: int
    modules: dict = field(default_factory=dict)   # program -> [durations s]
    ds_kernel_s: dict = field(default_factory=dict)  # program -> kernel seconds
    device_ops: list = field(default_factory=list)   # [[op, seconds]] top 10
    idle_gaps: list = field(default_factory=list)    # [[host activity, seconds]] top 10


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """``%dss_topk_fused.1 = (...) custom-call(`` -> ``dss_topk_fused``."""
    head = event_name.split(" = ", 1)[0].lstrip("%").strip()
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def module_name(event_name: str) -> str:
    """``jit__decode(8350723620439062008)`` -> ``jit__decode``."""
    return event_name.split("(", 1)[0]


def union_ns(intervals) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def leaf_ops(ops: list[Ev]) -> list[Ev]:
    """Ops that hold no other op (a loop's event holds its body's)."""
    ops = sorted(ops, key=lambda e: (e.start, -e.end))
    leaves = []
    for i, e in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is None or nxt.start >= e.end:
            leaves.append(e)
    return leaves


def _events(line):
    return [Ev(e.name, int(e.start_ns), int(e.end_ns)) for e in line.events]


def read_planes(path: str):
    """(device op lines, device module lines, host spans) from a trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev_ops, dev_mods, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = _events(line)
                elif line.name == "XLA Modules":
                    mods = _events(line)
            if ops or mods:
                dev_ops.append(ops)
                dev_mods.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.append((line.name, _events(line)))
    return dev_ops, dev_mods, host


def reduce(dev_ops, dev_mods, host) -> Reduction:
    """Everything the readers need, over the window the ``bench.*`` host
    spans cover."""
    spans = [e for _, evs in host for e in evs
             if e.name.startswith(HOST_SPAN_PREFIX)]
    if not spans or not dev_ops:
        raise ValueError("trace holds no bench.* host span or no device op")
    lo = min(e.start for e in spans)
    hi = max(e.end for e in spans)
    clip = lambda evs: [Ev(e.name, max(e.start, lo), min(e.end, hi))
                        for e in evs if e.end > lo and e.start < hi]

    busy, modules, ds_kernel = [], collections.defaultdict(list), \
        collections.defaultdict(float)
    op_time = collections.Counter()
    all_gaps = []
    for ops, mods in zip(dev_ops, dev_mods):
        ops_w = clip(ops)
        busy.append(union_ns((e.start, e.end) for e in ops_w))
        for m in mods:
            if lo <= m.start and m.end <= hi:
                modules[module_name(m.name)].append((m.end - m.start) / 1e9)
        kern = [e for e in ops if op_name(e.name).startswith(DS_KERNEL_PREFIX)]
        for m in mods:
            if lo <= m.start and m.end <= hi:
                inside = sum(k.end - k.start for k in kern
                             if m.start <= k.start and k.end <= m.end)
                ds_kernel[module_name(m.name)] += inside / 1e9
        for e in leaf_ops(ops_w):
            op_time[op_name(e.name)] += (e.end - e.start) / 1e9
        all_gaps += gaps_ns([(e.start, e.end) for e in ops_w], lo, hi)
    n_dev = len(dev_ops)
    for name in op_time:
        op_time[name] /= n_dev

    return Reduction(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / n_dev / 1e9,
        n_devices=n_dev,
        modules=dict(modules),
        ds_kernel_s={k: v / n_dev for k, v in ds_kernel.items()},
        device_ops=[[n, t] for n, t in op_time.most_common(10)],
        idle_gaps=_label_gaps(all_gaps, host),
    )


def _label_gaps(gaps, host, top: int = 10) -> list:
    """The longest idle gaps, each named by what the host was doing at its
    midpoint: the ``bench.*`` span, then the innermost runtime span on the
    same thread."""
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    out = []
    for s, e in gaps:
        mid = (s + e) // 2
        label = "no host span"
        for _, evs in host:
            cover = [x for x in evs if x.start <= mid < x.end]
            bench = [x for x in cover if x.name.startswith(HOST_SPAN_PREFIX)]
            if not bench:
                continue
            inner = min(cover, key=lambda x: x.end - x.start)
            label = bench[0].name if inner is bench[0] \
                else f"{bench[0].name}: {inner.name}"
            break
        out.append([label, (e - s) / 1e9])
    return out


def reduce_dir(trace_dir: str) -> Reduction:
    return reduce(*read_planes(find_xplane(trace_dir)))
