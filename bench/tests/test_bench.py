"""The benchmark's own arithmetic, generators and trace reduction, on the
CPU. Run from the root of the checkout: ``python -m pytest bench/tests``."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BIG_SEED = 2 ** 31 + 987654321


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------

def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["decode_backlog"])
def test_generator_is_seed_determined(mix):
    import itertools
    import importlib

    t = _traffic(mix)
    gen = importlib.import_module(f"generators.{t['kind']}")
    take = lambda seed: list(itertools.islice(gen.generate(t, seed, 50000, 10.0), 300))
    a, b, c = take(BIG_SEED), take(BIG_SEED), take(BIG_SEED + 1)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", ["decode_backlog"])
def test_lengths_are_clipped_and_the_same_multiset_for_every_seed(mix):
    import itertools
    import importlib

    t = _traffic(mix)
    gen = importlib.import_module(f"generators.{t['kind']}")
    block = t["block"]
    take = lambda seed: list(itertools.islice(gen.generate(t, seed, 1000, 60.0), block))
    for seed in (1, BIG_SEED):
        specs = take(seed)
        p = [len(s.prompt) for s in specs]
        o = [s.max_new_tokens for s in specs]
        assert min(p) >= t["prompt"]["min"] and max(p) <= t["prompt"]["max"]
        assert min(o) >= t["output"]["min"] and max(o) <= t["output"]["max"]
        assert all(s.prompt.max() < 1000 for s in specs)
        # every request fits the cache the cell serves
        assert max(a + b - 1 for a, b in zip(p, o)) <= t["max_seq_len"]
    assert sorted(len(s.prompt) for s in take(1)) == \
        sorted(len(s.prompt) for s in take(BIG_SEED))


# ---------------------------------------------------------------------------
# work and peaks
# ---------------------------------------------------------------------------

def _sizes(name):
    import importlib

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    return importlib.import_module(f"reference.{cfg['reference']}"), \
        cfg, importlib.import_module(f"reference.{cfg['reference']}").sizes(cfg)


def test_qwen2_token_flops_by_hand():
    ref, _, s = _sizes("qwen2-1.5b")
    # per layer: q 1536x1536, k and v 1536x256 each, o 1536x1536,
    # gate/up/down 3 x 1536x8960; attention 12 heads x 128 x ctx, twice
    per_layer = 2 * (1536 * 1536 + 2 * 1536 * 256 + 1536 * 1536 + 3 * 1536 * 8960)
    ctx = 700
    attn = 2 * 2 * 12 * 128 * ctx
    assert ref.token_flops(s, ctx) == 28 * (per_layer + attn)
    assert 28 * per_layer == 2 * 1_310_195_712   # 1.31e9 matrix parameters


def test_head_work_by_hand():
    from work import head_bytes, head_flops, least_time, peaks

    # qwen2-1.5b decode: 64 tokens, 16 experts all hit, v_pad 19072
    assert head_flops(64, 16, 19072, 1536) == 2 * 64 * 1536 * 16 + 2 * 64 * 1536 * 19072
    b = head_bytes(64, 16, 19072, 1536, n_hit=16)
    assert b == 16 * 19072 * 1536 * 2 + 16 * 1536 * 2 + 64 * 1536 * 2
    t, bound = least_time(head_flops(64, 16, 19072, 1536), b, peaks("TPU v5 lite"))
    assert bound == "memory" and abs(t - b / 819e9) < 1e-12


def test_unknown_device_kind_raises():
    from work import peaks

    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v99 imaginary")


def test_served_table_shape_is_seed_independent():
    from reference import ds_head

    for K, V in ((16, 151936), (8, 50277)):
        a, ra = ds_head.assign(1, K, V, 2)
        b, rb = ds_head.assign(BIG_SEED, K, V, 2)
        assert a.shape == b.shape and a.shape[1] % 128 == 0
        assert ((ra >= 0).sum(0) == 2).all() and ((rb >= 0).sum(0) == 2).all()
        assert not np.array_equal(a, b)
        for e in range(K):      # rows and ids invert each other
            cls = a[e][a[e] >= 0]
            assert (ra[e, cls] == np.arange(len(cls))).all()


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def test_union_gaps_and_leaf_ops():
    from trace_reduce import Ev, gaps_ns, leaf_ops, op_name, module_name, union_ns

    iv = [(0, 10), (5, 20), (30, 40), (35, 38)]
    assert union_ns(iv) == 30
    assert gaps_ns(iv, 0, 50) == [(20, 30), (40, 50)]
    ops = [Ev("%while.2 = (...) while(", 0, 100), Ev("%fusion.1 = f32", 10, 20),
           Ev("%dss_topk_fused.1 = (f32[64,8]) custom-call(", 30, 60)]
    assert [op_name(e.name) for e in leaf_ops(ops)] == ["fusion", "dss_topk_fused"]
    assert module_name("jit__decode(8350723620439062008)") == "jit__decode"


def test_reduce_on_a_synthetic_device_timeline():
    from trace_reduce import Ev, reduce

    ms = 1_000_000
    mods = [Ev("jit__decode(1)", 0, 10 * ms), Ev("jit__chunk(2)", 12 * ms, 14 * ms),
            Ev("jit__decode(1)", 20 * ms, 30 * ms)]
    ops = [Ev("%while.1 = w", 0, 8 * ms), Ev("%fusion.3 = f", 1 * ms, 7 * ms),
           Ev("%dss_topk_fused.1 = k", 8 * ms, 10 * ms),
           Ev("%fusion.9 = f", 12 * ms, 14 * ms),
           Ev("%while.1 = w", 20 * ms, 28 * ms), Ev("%fusion.3 = f", 21 * ms, 27 * ms),
           Ev("%dss_topk_fused.1 = k", 28 * ms, 30 * ms)]
    host = [("python", [Ev("bench.step", 0, 15 * ms), Ev("bench.submit", 15 * ms, 20 * ms),
                        Ev("bench.step", 20 * ms, 40 * ms),
                        Ev("np.asarray(jax.Array)", 31 * ms, 39 * ms)])]
    red = reduce([ops], [mods], host)
    assert red.window_s == pytest.approx(0.040)
    assert red.busy_s == pytest.approx(0.010 + 0.002 + 0.010)
    assert red.modules["jit__decode"] == [pytest.approx(0.010)] * 2
    assert red.ds_kernel_s["jit__decode"] == pytest.approx(0.004)
    assert red.idle_gaps[0] == ["bench.step: np.asarray(jax.Array)", pytest.approx(0.010)]
    assert ["bench.submit", pytest.approx(0.006)] in red.idle_gaps
    assert red.device_ops[0] == ["fusion", pytest.approx(0.014)]
    assert ["bench.step", pytest.approx(0.002)] in red.idle_gaps


def test_reduce_reads_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    from trace_reduce import find_xplane, read_planes

    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    _, _, host = read_planes(find_xplane(str(tmp_path)))
    spans = [e for _, evs in host for e in evs if e.name == "bench.step"]
    assert len(spans) == 3 and all(e.end > e.start for e in spans)


# ---------------------------------------------------------------------------
# the harness loop and the metric arithmetic, on a tiny cell
# ---------------------------------------------------------------------------

def test_quantile_matches_statistics():
    import statistics
    from harness import quantile

    xs = list(np.random.default_rng(0).random(101))
    q = statistics.quantiles(xs, n=100, method="inclusive")
    assert quantile(xs, 0.95) == q[94] and quantile(xs, 0.5) == q[49]


def test_end_to_end_arithmetic_on_a_hand_record():
    from types import SimpleNamespace as NS

    from harness import Record, Tracked, end_to_end

    done = NS(status=NS(name="COMPLETED"), done=True)
    rec = Record(t_open=10.0, t_close=12.0, setup_s=3.0)
    rec.tracked = [
        Tracked(NS(prompt=np.zeros(4)), done, 10.0, [10.1, 10.2, 10.3, 10.4]),
        Tracked(NS(prompt=np.zeros(4)), done, 11.0, [11.5, 11.9, 12.5]),
    ]
    m = end_to_end(rec, ["output_tokens_per_s", "tpot_p50_ms", "tpot_p95_ms",
                         "setup_s"])
    assert m["output_tokens_per_s"]["value"] == pytest.approx(6 / 2.0)
    assert m["setup_s"]["value"] == 3.0
    # only the first request finished inside the window: gaps of 0.1 s
    assert m["tpot_p50_ms"]["value"] == pytest.approx(100.0)
    assert m["tpot_p95_ms"]["value"] == pytest.approx(100.0)


PER_LAYER = ("itl_p50_ms", "itl_p95_ms", "prefill_chunk_ms", "batch_occupancy",
             "decode_step_ms", "head_ms_per_step", "ds_head_roofline", "idle_share",
             "decode_mfu")


def test_per_layer_readers_on_a_hand_run():
    """Every reader's arithmetic on a hand-made record and trace reduction
    at qwen2-1.5b's sizes, and nothing read where the run gave nothing."""
    import importlib
    from types import SimpleNamespace as NS

    from harness import Record, Tracked
    from metrics.common import Readings
    from trace_reduce import Reduction
    from work import head_bytes, head_flops, peaks

    ref, _, s = _sizes("qwen2-1.5b")
    peak = peaks("TPU v5 lite")
    done = NS(status=NS(name="COMPLETED"), done=True)
    rec = Record(t_open=0.0, t_close=1.0)
    rec.occupancy = [1.0, 0.5]
    rec.steps = [(0.0, 0.5, 16), (0.5, 1.0, 8)]
    # 4 prompt tokens; the first token from prefill, two more from decode
    rec.tracked = [Tracked(NS(prompt=np.zeros(4)), done, 0.0, [0.1, 0.4, 0.9])]
    red = Reduction(window_s=1.0, busy_s=0.8, n_devices=1,
                    modules={"jit__decode": [0.02, 0.03], "jit__chunk": [0.004]},
                    ds_kernel_s={"jit__decode": 0.01})
    r = Readings(rec, red, s, ref, 64, 16, 19072, peak)
    read = {n: importlib.import_module(f"metrics.{n}").read(r) for n in PER_LAYER}
    least = [max(head_flops(64, 16, 19072, 1536) / 197e12,
                 head_bytes(64, 16, 19072, 1536, hit) / 819e9) for hit in (16, 8)]
    flops = ref.token_flops(s, 5) + ref.token_flops(s, 6) \
        + 2 * head_flops(1, 16, 19072, 1536)
    assert read == pytest.approx({
        "itl_p50_ms": 400.0, "itl_p95_ms": 490.0,  # gaps of 0.3 and 0.5 s
        "prefill_chunk_ms": 4.0, "batch_occupancy": 75.0,
        "decode_step_ms": 25.0, "head_ms_per_step": 5.0,
        "ds_head_roofline": 100 * np.mean(least) / 5e-3, "idle_share": 20.0,
        "decode_mfu": 100 * flops / 197e12})
    empty = Readings(Record(), None, s, ref, 64, 16, 19072, peak)
    assert all(importlib.import_module(f"metrics.{n}").read(empty) is None
               for n in PER_LAYER)


def test_rehearsal_of_a_run_on_the_cpu():
    """The whole run of a tiny cell, the command's chip check aside: the
    result line's keys, the metrics of the cell, and ``correct``."""
    import harness
    import run
    import tiny
    from work import peaks

    cell = tiny.qwen2_cell()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    name = cell.name = "qwen2-1.5b.decode_backlog"
    res = run.run_cell(cell, BIG_SEED, 3.0, False, bench, peaks("TPU v5 lite"),
                       harness.device_info())
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    want = {m["name"] for m in bench["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(res["metrics"]) == want
    assert res["attempted"] > 0 and res["failed"] == 0
