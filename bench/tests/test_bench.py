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


def _specs(t, seed, vocab, n):
    """The first ``n`` requests of a mix's window (an open loop's from
    its due time 0 on)."""
    import itertools
    import importlib

    gen = importlib.import_module(f"generators.{t['kind']}")
    items = gen.generate(t, seed, vocab, 60.0)
    if getattr(gen, "LOOP", None) == "open":
        items = (spec for due, spec in items if due >= 0)
    return list(itertools.islice(items, n))


MIXES = ["decode_backlog", "chat_bursty"]


@pytest.mark.parametrize("mix", MIXES)
def test_generator_is_seed_determined(mix):
    t = _traffic(mix)
    take = lambda seed: _specs(t, seed, 50000, 300)
    a, b, c = take(BIG_SEED), take(BIG_SEED), take(BIG_SEED + 1)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_are_clipped_and_the_same_multiset_for_every_seed(mix):
    t = _traffic(mix)
    block = t["block"]
    take = lambda seed: _specs(t, seed, 1000, block)
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


def test_open_arrivals_offer_the_same_gaps_to_every_seed_at_the_rate():
    """Seed-determined due times; each block of the window the same
    multiset of gaps whatever the seed, half of it sampled; a mean rate
    within 1% of ``rate``; and ``warm_s`` seconds of arrivals before 0."""
    import itertools

    from generators import open as gen_open

    t = _traffic("chat_bursty")
    block, rate = t["block"], t["rate"]
    take = lambda seed: list(itertools.islice(gen_open.generate(t, seed, 1000, 60.0), 4 * block))
    a, b, c = take(BIG_SEED), take(BIG_SEED), take(7)
    assert [x[0] for x in a] == [x[0] for x in b]
    assert [x[0] for x in a] != [x[0] for x in c]
    for run in (a, c):
        due = np.array([x[0] for x in run])
        assert (np.diff(due) >= 0).all()
        warm = due[due < 0]
        assert warm[0] == -t["warm_s"] and warm[-1] > -t["warm_s"] * 0.2
        window = [x for x in run if x[0] >= 0]
        gaps = np.diff([x[0] for x in window[: 3 * block + 1]])
        for k in range(3):
            g = gaps[k * block:(k + 1) * block]
            assert np.allclose(np.sort(g), gen_open.gaps(t, block))
            assert abs(block / g.sum() - rate) < 0.01 * rate
            smp = [s for _, s in window[k * block:(k + 1) * block] if s.temperature > 0]
            assert len(smp) == round(block * t["sampled"]["share"])
            assert all(s.top_k == t["sampled"]["top_k"] for s in smp)
    g = gen_open.gaps(t, block)
    assert g.std() / g.mean() == pytest.approx(t["cv"], rel=0.05)


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


def test_mamba2_token_flops_and_bytes_by_hand():
    ref, cfg, s = _sizes("mamba2-130m")
    # d 768, d_inner 1536, 24 heads of 64, state 128, one group, conv 4
    # over 1536 + 2 * 128 = 1792 channels
    assert (s["di"], s["H"], s["P"], s["N"], s["conv"]) == (1536, 24, 64, 128, 1792)
    per_layer = 2 * 768 * (2 * 1536 + 2 * 128 + 24) + 2 * 1536 * 768 \
        + 2 * 4 * 1792 + 5 * 24 * 64 * 128
    assert ref.token_flops(s, 1) == ref.token_flops(s, 999) == 24 * per_layer
    state = 24 * 64 * 128 * 4          # 786,432 bytes of float32 state a layer
    conv = 3 * 1792 * 2                # the last three inputs, bfloat16
    assert ref.token_bytes(s, 7) == 24 * 2 * (state + conv)
    assert 24 * state == 18_874_368    # 18.9 MB a slot


def test_qwen2_token_bytes_and_backbone_bytes_by_hand():
    from work import backbone_bytes

    ref, _, s = _sizes("qwen2-1.5b")
    # 28 layers x (K and V) x 2 heads x 128 x 2 bytes, ctx rows read + one written
    assert ref.token_bytes(s, 300) == 28 * 2 * 2 * 128 * 2 * 301
    # the matrices in bfloat16 (1.31e9, as above) and the biases, norm
    # scales in float32; the embedding left out
    mats = 1_310_195_712 * 2
    bias = 28 * (1536 + 2 * 256) * 2
    norms = (2 * 28 * 1536 + 1536) * 4
    assert backbone_bytes(ref, s) == mats + bias + norms
    m_ref, _, m = _sizes("mamba2-130m")
    per_layer = 768 * (2 * 1536 + 2 * 128 + 24) * 2 + 1536 * 768 * 2 \
        + 4 * 1792 * 2 + 1792 * 2 + (3 * 24 + 1536 + 768) * 4
    assert backbone_bytes(m_ref, m) == 24 * per_layer + 768 * 4


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


def test_ttft_and_gen_lag_on_a_hand_record():
    """TTFT from the due time, the close standing in for a first token
    that never came; a request due before the window is not counted; the
    generator's lag is sent - due."""
    from types import SimpleNamespace as NS

    from harness import Record, Tracked, end_to_end, quantile, ttft_s
    from metrics import gen_lag_p95_ms, ttft_p95_ms
    from metrics.common import Readings

    done = NS(status=NS(name="COMPLETED"), done=True)
    live = NS(status=NS(name="ACTIVE"), done=False)
    spec = NS(prompt=np.zeros(4), temperature=0.0)
    rec = Record(t_open=10.0, t_close=12.0)
    rec.tracked = [
        Tracked(spec, done, 9.6, [9.7, 9.8], due=9.5),           # before the window
        Tracked(spec, done, 10.0, [10.2, 10.3], due=10.0),       # 200 ms
        Tracked(spec, done, 10.6, [11.0, 11.1], due=10.5),       # 500 ms, sent 100 late
        Tracked(spec, live, 11.0, [12.5], due=11.0),             # none by the close: 1000
        Tracked(spec, live, 11.9, [], due=11.9),                  # none at all: 100
    ]
    m = end_to_end(rec, ["ttft_p50_ms"])
    ttft = [0.2, 0.5, 1.0, 0.1]
    assert ttft_s(rec) == pytest.approx(ttft)
    assert m["ttft_p50_ms"]["value"] == pytest.approx(350.0)
    r = Readings(rec, None, {}, None, 4, 4, 128, {})
    assert ttft_p95_ms.read(r) == pytest.approx(1e3 * quantile(ttft, 0.95))
    lag = [0.0, 0.1, 0.0, 0.0]
    assert gen_lag_p95_ms.read(r) == pytest.approx(1e3 * quantile(lag, 0.95))
    rec.tracked = [Tracked(spec, done, 10.0, [10.2])]           # a backlog: no due times
    assert "ttft_p50_ms" not in end_to_end(rec, ["ttft_p50_ms"])
    assert gen_lag_p95_ms.read(r) is None and ttft_p95_ms.read(r) is None


class _FakeClock:
    def __init__(self):
        self.t = 100.0
        self.sleeps = []

    def now(self):
        return self.t

    def sleep(self, s):
        self.sleeps.append((self.t, s))
        self.t += s


class _StubSession:
    """Admits every queued request at a step's start; each step takes
    ``STEP`` seconds and gives every resident one token."""

    STEP = 0.010

    def __init__(self, clock, on_token):
        import collections
        from types import SimpleNamespace as NS

        self.clock, self.on_token, self.slots = clock, on_token, []
        self.scheduler = NS(queue=collections.deque(),
                            has_work=lambda: bool(self.scheduler.queue or self.slots))

    def submit(self, req):
        self.scheduler.queue.append(req)

    def step(self):
        from repro.train import RequestStatus

        assert self.scheduler.has_work()
        self.clock.t += self.STEP
        self.slots += self.scheduler.queue
        self.scheduler.queue.clear()
        for req in list(self.slots):
            req.out_tokens.append(1)
            self.on_token(req, 1)
            if len(req.out_tokens) >= req.sampling_params.max_new_tokens:
                req.status = RequestStatus.COMPLETED
                self.slots.remove(req)
        return self.scheduler.has_work()


def test_run_open_submits_at_due_times_and_sleeps_when_idle():
    """On a fake clock: a request due while the loop is idle goes out at
    its due time, one due during a step at the step's end; the loop
    sleeps only with nothing to serve, and never past the next due time;
    the warm stretch's arrivals precede the window, whose own are due from
    its open."""
    from types import SimpleNamespace as NS

    import harness
    from generators.common import Spec

    clock = _FakeClock()
    cell = NS(traffic={"warm_s": 1.0})
    drv = harness.Runner(cell, 1, 1.0)
    drv.clock, drv.sleep = clock.now, clock.sleep
    stub = _StubSession(clock, drv.on_token)
    drv.server = NS(session=stub)
    spec = lambda n: Spec(np.zeros(4, np.int32), n)
    arrivals = [(-0.5, spec(2)), (-0.05, spec(2)), (0.0, spec(3)), (0.003, spec(1)),
                (0.5, spec(1)), (0.9999, spec(5)), (2.0, spec(1))]
    rec = harness.Record()
    sleeps_with_work = []
    real_sleep = clock.sleep

    def sleep(s):
        sleeps_with_work.append(stub.scheduler.has_work())
        real_sleep(s)

    drv.sleep = sleep
    drv._run_open(iter(arrivals), rec, NS(total=0), None, 90.0, 1.0)
    assert not any(sleeps_with_work)
    assert rec.setup_s == pytest.approx(11.0) and rec.t_open == pytest.approx(101.0)
    assert rec.t_close == pytest.approx(102.0, abs=stub.STEP)
    due = [t.due for t in rec.tracked]
    sent = [t.sent for t in rec.tracked]
    assert due == pytest.approx([100.5, 100.95, 101.0, 101.003, 101.5, 101.9999])
    # idle before each but the one due mid-step (sent as that step ends)
    assert sent == pytest.approx([100.5, 100.95, 101.0, 101.01, 101.5, 101.9999])
    assert all(t.times[0] == pytest.approx(t.sent + stub.STEP) for t in rec.tracked)
    # the loop slept until each due time, and up to the window's open
    wake = [round(t + s, 6) for t, s in clock.sleeps]
    assert wake == pytest.approx([100.5, 100.95, 101.0, 101.5, 101.9999])
    assert rec.queue_open == 0 and rec.queue_close == 0


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
               for n in PER_LAYER + ("decode_roofline", "gen_lag_p95_ms", "ttft_p95_ms"))


@pytest.mark.parametrize("config", ["qwen2-1.5b", "mamba2-130m"])
def test_decode_roofline_on_a_hand_run(config):
    """The whole decode step's least time over its device time: per step,
    the active slots' tokens (a prefill's first token is not one), their
    cache or state bytes, the weights once and the head's least work."""
    from types import SimpleNamespace as NS

    from harness import Record, Tracked
    from metrics import decode_roofline
    from metrics.common import Readings
    from trace_reduce import Reduction
    from work import backbone_bytes, head_bytes, head_flops, peaks

    ref, _, s = _sizes(config)
    d, K, v_pad = s["d"], 8, 12672
    done = NS(status=NS(name="COMPLETED"), done=True)
    rec = Record(t_open=0.0, t_close=1.0)
    rec.steps = [(0.0, 0.5, 3), (0.5, 1.0, 2)]
    rec.tracked = [Tracked(NS(prompt=np.zeros(4)), done, 0.0, [0.1, 0.4, 0.9]),
                   Tracked(NS(prompt=np.zeros(9)), done, 0.0, [0.6, 0.7])]
    red = Reduction(window_s=1.0, busy_s=0.8, n_devices=1,
                    modules={"jit__decode": [0.02, 0.03]})
    r = Readings(rec, red, s, ref, 128, K, v_pad, peaks("TPU v5 lite"))

    def least(ctxs, hit):
        B = len(ctxs)
        f = sum(ref.token_flops(s, c) for c in ctxs) + head_flops(B, K, v_pad, d)
        b = backbone_bytes(ref, s) + B * d * 2 + sum(ref.token_bytes(s, c) for c in ctxs) \
            + head_bytes(B, K, v_pad, d, hit)
        return max(f / 197e12, b / 819e9)

    want = 100 * np.mean([least([5], 3), least([6, 10], 2)]) / 25e-3
    assert decode_roofline.read(r) == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("name", ["qwen2-1.5b.decode_backlog", "mamba2-130m.chat_bursty"])
def test_rehearsal_of_a_run_on_the_cpu(name):
    """The whole run of a tiny cell, the command's chip check aside: the
    result line's keys, the metrics of the cell, and ``correct``."""
    import harness
    import run
    import tiny
    from work import peaks

    cell = tiny.qwen2_cell() if name.startswith("qwen2") else tiny.mamba2_cell()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell.name = name
    res = run.run_cell(cell, BIG_SEED, 3.0, False, bench, peaks("TPU v5 lite"),
                       harness.device_info())
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    want = {m["name"] for m in bench["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(res["metrics"]) == want
    assert res["attempted"] > 0 and res["failed"] == 0


def test_every_per_layer_metric_has_a_reader_and_moves_what_its_cells_report():
    """``BENCHMARK.json``: each per-layer metric has its reader under
    ``metrics/`` (``<name>.<part>``, a quantity split by what it moves,
    that of ``<name>``), and every cell it lists reports the end-to-end
    metric it moves; every cell reports ``setup_s``, another end-to-end
    metric and a per-layer one."""
    bench = json.load(open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")))
    cells = [w["name"] for w in bench["workloads"]]
    reports = {c: {m["name"] for m in bench["end_to_end"]
                   if c in m.get("workloads", cells)} for c in cells}
    for m in bench["per_layer"]:
        reader = m["name"].split(".")[0]
        assert os.path.exists(os.path.join(BENCH, "metrics", reader + ".py")), m
        assert all(m["moves"] in reports[c] for c in m.get("workloads", cells)), m
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
        assert any(c in m.get("workloads", cells) for m in bench["per_layer"])
