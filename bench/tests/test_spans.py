"""The spans reduction (``spans.py``) and its readers, on the CPU: idle
time by ``serve.*`` span on a hand-built timeline, device time by named
scope, a trace recorded here of a tiny cell, and the readers with and
without the reduction. Run from the root of the checkout:
``python -m pytest bench/tests``."""
from __future__ import annotations

import importlib
import time

import numpy as np
import pytest

MS = 1_000_000   # ns
NEW = ("idle_fetch_ms_per_step", "idle_host_ms_per_step", "idle_caller_ms_per_step",
       "layer_scan_ms_per_step", "attention_ms_per_step", "mlp_ms_per_step",
       "prefill_chunks_per_step")


def _read(name, r):
    return importlib.import_module(f"metrics.{name}").read(r)


def _timeline(ops_names=None):
    """100 ms traced by two ``bench.step`` spans; one step() with nested
    ``serve.*`` spans; device busy over [0,10) [16,30) [45,70) [80,90)."""
    from spans import Op, Trace
    from trace_reduce import Ev

    host = [("python", [
        Ev("bench.step", 0, 60 * MS), Ev("bench.step", 60 * MS, 100 * MS),
        Ev("serve.step", 10 * MS, 60 * MS),
        Ev("serve.admit", 10 * MS, 20 * MS),
        Ev("serve.prefill", 12 * MS, 18 * MS),
        Ev("serve.prefill.fetch", 15 * MS, 18 * MS),
        Ev("serve.decode.dispatch", 20 * MS, 25 * MS),
        Ev("serve.decode.fetch", 25 * MS, 40 * MS),
        Ev("serve.sample_emit", 40 * MS, 55 * MS)]),
        ("other thread", [Ev("serve.step", 0, 100 * MS)])]
    names = ops_names or {}
    ops = [Op("%fusion.1 = f", 0, 10 * MS, names.get(1, "")),
           Op("%fusion.2 = f", 16 * MS, 30 * MS, names.get(2, "")),
           Op("%while.3 = w", 45 * MS, 70 * MS, ""),
           Op("%fusion.4 = f", 45 * MS, 55 * MS, names.get(4, "")),
           Op("%fusion.5 = f", 55 * MS, 62 * MS, names.get(5, "")),
           Op("%fusion.6 = f", 62 * MS, 68 * MS, names.get(6, "")),
           Op("%fusion.7 = f", 80 * MS, 90 * MS, names.get(7, ""))]
    mods = [Ev("jit__decode(9)", 45 * MS, 70 * MS), Ev("jit__chunk(8)", 16 * MS, 30 * MS),
            Ev("jit__decode(9)", 80 * MS, 90 * MS)]
    return Trace([ops], [mods], host, "hand")


def test_idle_split_by_hand():
    """Gaps [10,16) [30,45) [70,80) [90,100): the first crosses admit,
    prefill and prefill.fetch; the second straddles decode.fetch and
    sample_emit; the last two lie outside step()."""
    from spans import idle_split
    from trace_reduce import Ev

    gaps = [(10 * MS, 16 * MS), (30 * MS, 45 * MS), (70 * MS, 80 * MS),
            (90 * MS, 100 * MS)]
    _, evs = _timeline().host[0]
    spans = [e for e in evs if e.name.startswith("serve.")]
    assert idle_split(gaps, spans) == {
        "fetch": (1 + 10) * MS,          # [15,16) prefill.fetch, [30,40) decode.fetch
        "host": (2 + 3 + 5) * MS,        # [10,12) admit, [12,15) prefill, [40,45) sample_emit
        "caller": (10 + 10) * MS}
    # a gap wholly inside one span, and no span at all
    assert idle_split([(26 * MS, 27 * MS)], spans)["fetch"] == MS
    assert idle_split([(0, 5)], [Ev("serve.step", 10, 20)]) == \
        {"fetch": 0, "host": 0, "caller": 5}


def test_idle_parts_add_up_to_the_idle_share():
    from spans import reduce
    from trace_reduce import reduce as reduce_device

    tr = _timeline()
    sp = reduce(tr)
    red = reduce_device(tr.ops, tr.modules, tr.host)
    assert sp.n_decode == 2
    assert sp.idle_s == pytest.approx({"fetch": 0.011, "host": 0.010, "caller": 0.020})
    # the three add up to idle share x window / decode steps
    idle_share = 1.0 - red.busy_s / red.window_s
    assert sum(sp.per_step_ms(v) for v in sp.idle_s.values()) == \
        pytest.approx(1e3 * idle_share * red.window_s / sp.n_decode, rel=1e-12)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_decode)/layers/while/body/attn/kv_write/dynamic_update_slice", "kv_write"),
    ("jit(_decode)/layers/while/body/attn/dot_general", "attn"),
    ("jit(_decode)/layers/while/body/mlp/jit(silu)/logistic", "mlp"),
    ("jit(_decode)/layers/while/body/dynamic_slice", "layers"),
    ("jit(_decode)/layers/while/body/closed_call", "layers"),
    ("jit(_decode)/ds_head/pallas_call", "ds_head"),
    ("jit(_decode)/embed/jit(take)/gather", "embed"),
    ("jit(_chunk)/final_norm/mul", "final_norm"),
    ("jit(_decode)/mlp_layers/add", None),
    ("jit(_decode)/copy", None),
    ("", None),
])
def test_scope_of_op_names(op_name, scope):
    from spans import scope_of

    assert scope_of(op_name) == scope


def test_scope_times_and_readers_by_hand():
    """Leaf ops of the two decode runs, by scope: the while op holds its
    body and does not count; the chunk run is no decode step."""
    from spans import reduce, scope_table

    layers = "jit(_decode)/layers/while/body/"
    tr = _timeline({2: layers + "attn/dot_general",          # chunk run: left out
                    4: layers + "dynamic_slice",             # 10 ms
                    5: layers + "attn/kv_write/scatter",     # 7 ms
                    6: layers + "mlp/dot_general",           # 6 ms
                    7: layers + "attn/dot_general"})         # 10 ms
    sp = reduce(tr)
    assert sp.scope_s == pytest.approx({"layers": 0.010, "kv_write": 0.007,
                                        "mlp": 0.006, "attn": 0.010})
    assert sp.between_ops_s == pytest.approx(0.002)        # [68,70) in the first run
    assert sp.decode_s == pytest.approx(0.035)
    r = _readings(spans=sp)
    assert _read("layer_scan_ms_per_step", r) == pytest.approx(5.0)
    assert _read("attention_ms_per_step", r) == pytest.approx(8.5)
    assert _read("mlp_ms_per_step", r) == pytest.approx(3.0)
    assert _read("idle_fetch_ms_per_step", r) == pytest.approx(5.5)
    assert _read("idle_host_ms_per_step", r) == pytest.approx(5.0)
    assert _read("idle_caller_ms_per_step", r) == pytest.approx(10.0)
    line = scope_table(sp)
    rows = dict(part.rsplit(" ", 1) for part in line.split(": ", 1)[1].split("; ")[0].split(", "))
    total = sum(float(v) for k, v in rows.items() if not k.startswith("of which"))
    assert total == pytest.approx(17.5) and line.endswith("decode step 17.500")


def _readings(spans=None, counters=None):
    """The hand run of ``test_bench.test_per_layer_readers_on_a_hand_run``."""
    from types import SimpleNamespace as NS

    from harness import Record, Tracked
    from metrics.common import Readings
    from test_bench import _sizes
    from trace_reduce import Reduction
    from work import peaks

    ref, _, s = _sizes("qwen2-1.5b")
    done = NS(status=NS(name="COMPLETED"), done=True)
    rec = Record(t_open=0.0, t_close=1.0)
    rec.occupancy = [1.0, 0.5]
    rec.steps = [(0.0, 0.5, 16), (0.5, 1.0, 8)]
    rec.tracked = [Tracked(NS(prompt=np.zeros(4)), done, 0.0, [0.1, 0.4, 0.9])]
    if counters is not None:
        rec.window_counters = counters
    red = Reduction(window_s=1.0, busy_s=0.8, n_devices=1,
                    modules={"jit__decode": [0.02, 0.03], "jit__chunk": [0.004]},
                    ds_kernel_s={"jit__decode": 0.01})
    r = Readings(rec, red, s, ref, 64, 16, 19072, peaks("TPU v5 lite"))
    if spans is not None:
        r.spans = spans
    return r


def test_existing_readers_read_the_same_with_the_spans_field():
    from spans import reduce
    from test_bench import PER_LAYER

    plain, with_spans = _readings(), _readings(spans=reduce(_timeline()))
    for n in PER_LAYER:
        assert _read(n, plain) == _read(n, with_spans), n
    assert all(_read(n, plain) is None for n in NEW)


def test_a_program_without_spans_or_scopes_gives_nothing_to_read():
    """A trace of a program that has no ``serve.*`` span and no named
    scope: the readers of the reduction read nothing."""
    from spans import reduce

    tr = _timeline({4: "jit(_decode)/while/body/dynamic_slice"})
    tr.host = [(n, [e for e in evs if not e.name.startswith("serve.")])
               for n, evs in tr.host]
    r = _readings(spans=reduce(tr))
    assert r.spans.n_decode == 2 and r.spans.idle_s == {}
    assert all(_read(n, r) is None for n in NEW)


@pytest.mark.parametrize("stats, want", [
    ({"tf_op": "jit(_decode)/ds_head/jit(dss_topk_fused)/pallas_call:"},
     ("tf_op", "jit(_decode)/ds_head/jit(dss_topk_fused)/pallas_call")),
    ({"tf_op": "jit(_decode)/layers/while/body/closed_call/mlp/...d,df->...f/dot_general:"},
     ("tf_op", "jit(_decode)/layers/while/body/closed_call/mlp/...d,df->...f/dot_general")),
    ({"op_name": "jit(_decode)/embed/gather"}, ("op_name", "jit(_decode)/embed/gather")),
    ({"hlo_category": "copy"}, None),
])
def test_op_name_from_event_stats(stats, want):
    from spans import _op_name_stat

    assert _op_name_stat(stats) == want


def test_prefill_chunks_per_step_reader():
    c = {"open": {"prefill_chunks": 10, "n_steps": 100},
         "close": {"prefill_chunks": 46, "n_steps": 300}}
    assert _read("prefill_chunks_per_step", _readings(counters=c)) == pytest.approx(0.18)
    # a program without the counter, or a window without a step
    c["open"]["prefill_chunks"] = c["close"]["prefill_chunks"] = None
    assert _read("prefill_chunks_per_step", _readings(counters=c)) is None


def test_a_trace_recorded_on_the_cpu(tmp_path):
    """A tiny cell's traced window through ``spans.read_trace`` and
    ``reduce``: op names come from the HLO in ``/host:metadata``, every
    scope of the decode program shows, and the idle parts add up."""
    import harness
    import spans
    import tiny
    from trace_reduce import find_xplane, gaps_ns, read_planes

    cell = tiny.qwen2_cell()
    drv = spans.CountingRunner(cell, 2 ** 31 + 11, 1.0)
    drv.server = harness.build_server(cell, 2 ** 31 + 11, drv.on_token)
    rec = drv.run(harness.CompileLog(), str(tmp_path), time.perf_counter())
    path = find_xplane(str(tmp_path))
    tr = spans.read_trace(path)
    sp = spans.reduce(tr)

    c0, c1 = drv.counters["open"], drv.counters["close"]
    assert sp.n_decode == c1["n_steps"] - c0["n_steps"] > 0
    assert c1["prefill_chunks"] > c0["prefill_chunks"]
    assert tr.op_name_source == "hlo metadata"
    for scope in ("layers", "attn", "kv_write", "mlp", "ds_head"):
        assert sp.scope_s.get(scope, 0.0) > 0.0, scope
    lo, hi = spans.window(tr.host)
    ops_w = [(max(e.start, lo), min(e.end, hi)) for e in tr.ops[0]
             if e.end > lo and e.start < hi]
    idle = sum(b - a for a, b in gaps_ns(ops_w, lo, hi))
    assert sum(sp.idle_s.values()) == pytest.approx(idle / 1e9, rel=1e-9)
    serve = [e for e in spans.session_spans(tr.host) if e.name == "serve.step"]
    assert len(serve) == len(rec.steps)
    # the host spans read here are those the profiler's own reader sees
    ours = sorted((e.start, e.end) for _, evs in tr.host for e in evs
                  if e.name == "bench.step")
    theirs = sorted((e.start, e.end) for _, evs in read_planes(path)[2] for e in evs
                    if e.name == "bench.step")
    assert len(ours) == len(theirs) > 0
    assert all(abs(a - c) <= 1 and abs(b - d) <= 1 for (a, b), (c, d) in zip(ours, theirs))


def test_the_command_finds_its_imports_and_refuses_without_a_tpu():
    """Run as ``python3 bench/spans.py`` from the root of the checkout, with
    only its own directory on ``sys.path``: it finds the program, and exits
    2 on a machine without a TPU."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    run = lambda *args: subprocess.run([sys.executable, *args], cwd=root, env=env,
                                       capture_output=True, text=True, timeout=300)
    as_script = run("-c", "import os, sys; sys.path.insert(0, os.path.abspath('bench')); "
                    "import spans, repro.train")
    assert as_script.returncode == 0, as_script.stderr[-2000:]
    out = run("bench/spans.py", "--workload", "qwen2-1.5b.decode_backlog",
              "--seed", str(2 ** 31 + 3))
    assert out.returncode == 2, out.stderr[-2000:]
    assert "needs 1 TPU chip(s)" in out.stderr
