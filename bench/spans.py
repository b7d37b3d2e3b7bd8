#!/usr/bin/env python3
"""Where a traced window's device time goes: the idle time by the
program's ``serve.*`` host spans, and the decode program's busy time by
its named scopes.

    python3 bench/spans.py --workload <cell> --seed <n>

serves the cell's traffic for a traced window of ``harness.TRACE_S``
seconds, as ``run.py --trace 1`` does, and prints the per-layer metrics
of both ``BENCHMARK.json`` and this reduction (``METRICS``), a table of
the decode step by scope, and last a JSON line. It compares nothing with
the reference. It exits non-zero without a TPU.

**Idle.** Every nanosecond of the window (the stretch the harness's
``bench.*`` spans bound) in which no device op ran, the gaps that
``idle_share`` counts, goes to the innermost ``serve.*`` span covering
it on the session's thread, by interval intersection: a
``serve.*.fetch`` span (the host waiting on a device-to-host read) makes
it ``fetch``, any other ``serve.*`` span ``host``, none ``caller`` (the
harness, between ``step()`` calls).

**Busy.** The leaf ops inside the decode program's runs are grouped by
the innermost named scope in their HLO ``op_name``: ``attn`` (with its
``kv_write``), ``mlp``, ``ds_head``, ``embed``, ``final_norm``, and
``layers`` alone for the layer scan's own slicing and restacking of the
per-layer cache and weights (``layer_scan``). An op under no scope is
the remainder. The ``op_name`` comes from the op event's own stats where
the trace carries it there, else from the program's HLO, which the
profiler stores in the ``/host:metadata`` plane.

The trace is read here with ``google.protobuf`` from a schema of the
few ``XSpace`` and ``HloProto`` fields it needs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import bisect  # noqa: E402
import collections  # noqa: E402
import functools  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
for _p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if _p not in sys.path:   # as a script, Python has put BENCH there
        sys.path.insert(0, _p)

import harness  # noqa: E402
import trace_reduce  # noqa: E402
from trace_reduce import Ev, gaps_ns, leaf_ops, module_name  # noqa: E402

SERVE_PREFIX = "serve."
FETCH_SUFFIX = ".fetch"
DECODE = "jit__decode"
# the program's named scopes; an op counts under the innermost one in its
# op_name, and ``layers`` alone is the layer scan's own work
SCOPES = ("kv_write", "attn", "mlp", "ds_head", "embed", "final_norm", "layers")
OP_NAME_STATS = ("tf_op", "op_name")   # where an op event may carry op_name
METRICS = ("idle_fetch_ms_per_step", "idle_host_ms_per_step",
           "idle_caller_ms_per_step", "layer_scan_ms_per_step",
           "attention_ms_per_step", "mlp_ms_per_step", "prefill_chunks_per_step")


@dataclass
class Op(Ev):
    op_name: str = ""
    module: str = ""      # the program run it belongs to, where the trace says


@dataclass
class Trace:
    ops: list                     # per device: [Op] of its XLA Ops line
    modules: list                 # per device: [Ev] program runs
    host: list                    # [(line name, [Ev])] host spans
    op_name_source: str = ""      # 'event stat <name>', 'hlo metadata' or ''


@dataclass
class Spans:
    """The reduction: seconds, means over the chips."""
    n_decode: int                 # runs of the decode program in the window
    decode_s: float               # their device time
    idle_s: dict = field(default_factory=dict)   # fetch / host / caller; {}: no serve.* span
    scope_s: dict = field(default_factory=dict)  # innermost scope (None: none)
    between_ops_s: float = 0.0    # decode-run time no leaf op covers
    op_name_source: str = ""

    def per_step_ms(self, seconds: float) -> Optional[float]:
        return 1e3 * seconds / self.n_decode if self.n_decode else None


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------

def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` in an op_name such as
    ``jit(_decode)/layers/while/body/attn/kv_write/scatter``, or None."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


def span_kind(name: str) -> str:
    return "fetch" if name.endswith(FETCH_SUFFIX) else "host"


def idle_split(gaps, spans) -> dict:
    """ns of each gap [s, e) by the innermost ``serve.*`` span covering
    each part of it (spans of one thread nest); ``caller`` where none."""
    out = {"fetch": 0, "host": 0, "caller": 0}
    spans = sorted(spans, key=lambda x: x.start)
    starts = [x.start for x in spans]
    longest = max((x.end - x.start for x in spans), default=0)
    for s, e in gaps:
        lo = bisect.bisect_left(starts, s - longest)
        hi = bisect.bisect_left(starts, e)
        near = [x for x in spans[lo:hi] if x.end > s]
        cuts = sorted({s, e} | {t for x in near for t in (x.start, x.end) if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            cover = [x for x in near if x.start <= a and b <= x.end]
            if cover:
                inner = min(cover, key=lambda x: x.end - x.start)
                out[span_kind(inner.name)] += b - a
            else:
                out["caller"] += b - a
    return out


def window(host) -> tuple[int, int]:
    """The stretch the harness's ``bench.*`` spans bound."""
    spans = [e for _, evs in host for e in evs
             if e.name.startswith(trace_reduce.HOST_SPAN_PREFIX)]
    if not spans:
        raise ValueError("trace holds no bench.* host span")
    return min(e.start for e in spans), max(e.end for e in spans)


def session_spans(host) -> list:
    """The ``serve.*`` spans of the thread that runs the session: the
    host line holding the most of them."""
    best = max((evs for _, evs in host), default=[],
               key=lambda evs: sum(e.name.startswith(SERVE_PREFIX) for e in evs))
    return [e for e in best if e.name.startswith(SERVE_PREFIX)]


def reduce(trace: Trace) -> Spans:
    lo, hi = window(trace.host)
    spans = session_spans(trace.host)
    n_dev = max(1, len(trace.ops))
    idle = collections.Counter()
    scope_ns = collections.Counter()
    n_decode, decode_ns, between = 0, 0, 0
    for ops, mods in zip(trace.ops, trace.modules):
        ops_w = [(max(e.start, lo), min(e.end, hi)) for e in ops
                 if e.end > lo and e.start < hi]
        idle.update(idle_split(gaps_ns(ops_w, lo, hi), spans))
        runs = sorted((m.start, m.end) for m in mods
                      if module_name(m.name) == DECODE and lo <= m.start and m.end <= hi)
        n_decode += len(runs)
        decode_ns += sum(b - a for a, b in runs)
        leaves = sorted(leaf_ops(ops), key=lambda e: e.start)
        starts = [e.start for e in leaves]
        for a, b in runs:
            inside = leaves[bisect.bisect_left(starts, a):bisect.bisect_left(starts, b)]
            inside = [e for e in inside if e.end <= b]
            for e in inside:
                scope_ns[scope_of(e.op_name)] += e.end - e.start
            between += (b - a) - sum(e.end - e.start for e in inside)
    return Spans(
        n_decode=round(n_decode / n_dev), decode_s=decode_ns / n_dev / 1e9,
        idle_s={k: idle[k] / n_dev / 1e9 for k in ("fetch", "host", "caller")}
        if spans else {},
        scope_s={k: v / n_dev / 1e9 for k, v in scope_ns.items()},
        between_ops_s=between / n_dev / 1e9,
        op_name_source=trace.op_name_source)


def scope_table(sp: Spans, head_ms: Optional[float] = None) -> str:
    """One line: ms per decode step by scope, the remainder included; the
    rows add up to the mean decode step."""
    ms = lambda s: sp.per_step_ms(s) or 0.0
    g = collections.defaultdict(float, sp.scope_s)
    rows = [("layer_scan", ms(g["layers"])),
            ("attention", ms(g["attn"] + g["kv_write"])),
            ("of which kv_write", ms(g["kv_write"])),
            ("mlp", ms(g["mlp"])),
            ("ds_head", ms(g["ds_head"])),
            ("of which dss_topk kernels", head_ms),
            ("embed", ms(g["embed"])), ("final_norm", ms(g["final_norm"])),
            ("no scope", ms(g[None])), ("between ops", ms(sp.between_ops_s))]
    body = ", ".join(f"{n} {v:.3f}" for n, v in rows if v is not None)
    return (f"decode step by scope, ms per step over {sp.n_decode} runs "
            f"(op_name from {sp.op_name_source or 'nowhere'}): {body}; "
            f"decode step {ms(sp.decode_s):.3f}")


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _messages():
    """Message classes for the fields of ``XSpace`` (tsl xplane.proto) and
    ``HloProto`` (xla hlo.proto) read here, by their field numbers."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    F = descriptor_pb2.FieldDescriptorProto
    T = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64, "double": F.TYPE_DOUBLE,
         "string": F.TYPE_STRING, "bytes": F.TYPE_BYTES}
    schema = {
        "XSpace": [("planes", 1, "XPlane*")],
        "XPlane": [("name", 2, "string"), ("lines", 3, "XLine*"),
                   ("event_metadata", 4, "EventMetadataEntry*"),
                   ("stat_metadata", 5, "StatMetadataEntry*")],
        "EventMetadataEntry": [("key", 1, "int64"), ("value", 2, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, "int64"), ("value", 2, "XStatMetadata")],
        "XLine": [("name", 2, "string"), ("timestamp_ns", 3, "int64"),
                  ("events", 4, "XEvent*")],
        "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
                   ("duration_ps", 3, "int64"), ("stats", 4, "XStat*")],
        "XEventMetadata": [("id", 1, "int64"), ("name", 2, "string"),
                           ("stats", 5, "XStat*")],
        "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
                  ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
                  ("str_value", 5, "string"), ("bytes_value", 6, "bytes"),
                  ("ref_value", 7, "uint64")],
        "XStatMetadata": [("id", 1, "int64"), ("name", 2, "string")],
        "HloProto": [("hlo_module", 1, "HloModuleProto")],
        "HloModuleProto": [("name", 1, "string"),
                           ("computations", 3, "HloComputationProto*")],
        "HloComputationProto": [("name", 1, "string"),
                                ("instructions", 2, "HloInstructionProto*"),
                                ("id", 5, "int64"), ("root_id", 6, "int64")],
        "HloInstructionProto": [("name", 1, "string"), ("metadata", 7, "OpMetadata"),
                                ("id", 35, "int64"),
                                ("called_computation_ids", 38, "int64*")],
        "OpMetadata": [("op_name", 2, "string")],
    }
    pkg = "bench_trace"
    fd = descriptor_pb2.FileDescriptorProto(name=f"{pkg}.proto", package=pkg,
                                            syntax="proto2")
    for msg, fields in schema.items():
        m = fd.message_type.add(name=msg)
        for name, number, typ in fields:
            rep = typ.endswith("*")
            typ = typ.rstrip("*")
            f = m.field.add(name=name, number=number,
                            label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL)
            if typ in T:
                f.type = T[typ]
            else:
                f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{typ}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return {n: message_factory.GetMessageClass(pool.FindMessageTypeByName(f"{pkg}.{n}"))
            for n in ("XSpace", "HloProto")}


def _stat_value(s, names):
    for f in ("ref_value", "str_value", "int64_value", "uint64_value",
              "double_value", "bytes_value"):
        if s.HasField(f):
            return names.get(s.ref_value, "") if f == "ref_value" else getattr(s, f)
    return None


def _stats(stats, names) -> dict:
    return {names.get(s.metadata_id, ""): _stat_value(s, names) for s in stats}


def _op_name_stat(stats: dict):
    """(stat, op_name) from an op event's stats, or None. The profiler
    writes ``tf_op`` as ``<op_name>:<op type>``, the type empty for XLA."""
    for k in OP_NAME_STATS:
        if stats.get(k):
            v = stats[k]
            return k, v.rsplit(":", 1)[0] if k == "tf_op" else v
    return None


def _hlo_op_names(hlo_bytes: bytes) -> dict:
    """instruction name -> op_name, over every computation of a module. An
    instruction without one (a fusion, often) takes the first found from
    the root back in the computations it calls: an op that the compiler
    added (a convert, a copy) carries none."""
    hp = _messages()["HloProto"]()
    hp.ParseFromString(hlo_bytes)
    comps = {c.id: c for c in hp.hlo_module.computations}

    def name(i, depth=0):
        if i.metadata.op_name or depth > 8:
            return i.metadata.op_name
        for cid in i.called_computation_ids:
            for j in reversed(comps[cid].instructions if cid in comps else ()):
                found = name(j, depth + 1)
                if found:
                    return found
        return ""

    return {i.name: name(i) for c in comps.values() for i in c.instructions}


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def read_trace(path: str) -> Trace:
    """Device ops with their op_name, program runs and host spans, from a
    ``.xplane.pb``. On a TPU the ops and runs are the device planes'
    ``XLA Ops`` and ``XLA Modules`` lines; on the CPU, the host's op
    events (stat ``hlo_op``), one run per ``run_id``."""
    xs = _messages()["XSpace"]()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    hlo: dict = {}            # module as its runs are named -> {instr: op_name}
    dev_ops, dev_mods, host = [], [], []
    cpu_ops = []
    sources = set()
    for pl in xs.planes:
        names = {e.key: e.value.name for e in pl.stat_metadata}
        meta = {e.key: e.value for e in pl.event_metadata}
        if pl.name == "/host:metadata":
            for m in meta.values():
                for k, v in _stats(m.stats, names).items():
                    if isinstance(v, bytes) and "hlo" in k.lower():
                        hlo[m.name] = v
            continue
        device = pl.name.startswith("/device:") and "CUSTOM" not in pl.name
        if not (device or pl.name.startswith("/host:")):
            continue
        ops, mods = [], []
        op_names: dict = {}   # metadata id -> op_name from its stats
        for line in pl.lines:
            t0 = line.timestamp_ns * 1000
            evs = []
            for e in line.events:
                start = t0 + e.offset_ps
                evs.append((e, (start // 1000, (start + e.duration_ps) // 1000)))
            if device and line.name == "XLA Modules":
                mods = [Ev(meta[e.metadata_id].name, s, t) for e, (s, t) in evs]
            elif device and line.name == "XLA Ops":
                for e, (s, t) in evs:
                    if e.metadata_id not in op_names:
                        op_names[e.metadata_id] = _op_name_stat(
                            _stats(meta[e.metadata_id].stats, names))
                    found = op_names[e.metadata_id] or _op_name_stat(_stats(e.stats, names))
                    op = Op(meta[e.metadata_id].name, s, t, found[1] if found else "")
                    if found:
                        sources.add(f"event stat {found[0]}")
                    ops.append(op)
            elif not device:
                spans = []
                for e, (s, t) in evs:
                    name = meta[e.metadata_id].name
                    if e.stats and not name.startswith(SERVE_PREFIX):
                        st = _stats(e.stats, names)
                        if "hlo_op" in st:
                            cpu_ops.append((st, Op(name, s, t)))
                            continue
                    spans.append(Ev(name, s, t))
                host.append((line.name, spans))
        if device and (ops or mods):
            dev_ops.append(ops)
            dev_mods.append(mods)
    if not dev_ops and cpu_ops:
        runs = {}
        for st, op in cpu_ops:
            op.module = f"{st.get('hlo_module')}({st.get('program_id')})"
            op.name = st["hlo_op"]
            a, b = runs.get(st.get("run_id"), (op.module, op.start, op.end))[1:]
            runs[st.get("run_id")] = (op.module, min(a, op.start), max(b, op.end))
        dev_ops = [[op for _, op in cpu_ops]]
        dev_mods = [[Ev(m, a, b) for m, a, b in runs.values()]]
    if hlo:
        maps = {m: _hlo_op_names(b) for m, b in hlo.items()}
        for ops, mods in zip(dev_ops, dev_mods):
            _name_from_hlo(ops, mods, maps, sources)
    return Trace(dev_ops, dev_mods, host, ", ".join(sorted(sources)))


def _name_from_hlo(ops, mods, maps, sources):
    """op_name of each op that has none, from the HLO of the program whose
    run holds it."""
    mods = sorted(mods, key=lambda m: m.start)
    starts = [m.start for m in mods]
    for op in ops:
        if op.op_name:
            continue
        mod = op.module
        if not mod:
            i = bisect.bisect_right(starts, op.start) - 1
            if i < 0 or op.end > mods[i].end:
                continue
            mod = mods[i].name
        name = maps.get(mod, {}).get(instruction(op.name))
        if name:
            op.op_name = name
            sources.add("hlo metadata")


def reduce_file(path: str) -> Spans:
    return reduce(read_trace(path))


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------

def window_counters(sess) -> dict:
    st = sess.stats()
    return {"prefill_chunks": st.get("prefill_chunks"), "n_steps": st["n_steps"]}


class CountingRunner(harness.Runner):
    """The harness's runner, recording the session's counters as the
    window opens and closes."""

    def _start_trace(self, trace_dir):
        self.counters = {"open": window_counters(self.server.session)}
        super()._start_trace(trace_dir)

    def _stop_trace(self, trace_dir):
        super()._stop_trace(trace_dir)
        self.counters["close"] = window_counters(self.server.session)


def measure(cell, seed: int, seconds: float, peak: dict) -> dict:
    """One traced window of the cell: the per-layer metrics of
    ``BENCHMARK.json`` and of ``METRICS``, the table, the reduction."""
    import importlib
    import shutil

    from metrics.common import Readings

    clog = harness.CompileLog()
    drv = CountingRunner(cell, seed, seconds)
    drv.server = server = harness.build_server(cell, seed, drv.on_token)
    trace_dir = os.path.join(harness.ROOT, "bench_out", f"spans-{cell.name}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    rec = drv.run(clog, trace_dir, T_START)
    rec.window_counters = drv.counters
    path = trace_reduce.find_xplane(trace_dir)
    planes = trace_reduce.read_planes(path)
    red = trace_reduce.reduce(*planes) if planes[0] else None   # None: no device plane
    sp = reduce_file(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    r = Readings(rec, red, cell.sizes, cell.reference, cell.traffic["slots"],
                 cell.config["ds_head"]["num_experts"], server.v_pad, peak)
    r.spans = sp
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]
             if cell.name in m.get("workloads", [cell.name])] + list(METRICS)
    metrics = {}
    for n in names:
        v = importlib.import_module(f"metrics.{n.split('.')[0]}").read(r)
        if v is not None:
            metrics[n] = v
    harness.free_program_state(server)
    return {"metrics": metrics, "table": scope_table(sp, metrics.get("head_ms_per_step")),
            "spans": sp, "idle_s": red.window_s - red.busy_s if red else None,
            "compiles_in_window": rec.compiles_in_window}


def main() -> int:
    import argparse
    import json
    from dataclasses import asdict

    from work import peaks

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    dev = harness.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"spans: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {dev['count']} {dev['platform']} device(s)", file=sys.stderr)
        return 2
    print(f"device: {dev}; compile cache {harness.cache_every_program()}", flush=True)
    out = measure(cell, args.seed, harness.TRACE_S, peaks(dev["kind"]))
    sp = out["spans"]
    print(out["table"], flush=True)
    print("idle ms per step: " + ", ".join(
        f"{k} {sp.per_step_ms(v):.3f}" for k, v in sp.idle_s.items())
        + f"; their sum {sp.per_step_ms(sum(sp.idle_s.values())):.3f}, the "
        f"trace's idle {sp.per_step_ms(out['idle_s']):.3f}", flush=True)
    line = {"metrics": out["metrics"], "compiles_in_window": out["compiles_in_window"],
            "spans": {**asdict(sp), "scope_s": {str(k): v for k, v in sp.scope_s.items()}}}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
