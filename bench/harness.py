"""The benchmark's moving parts, callable without the command line.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``);
the mix names its generator (``generators/<kind>.py``) and the
configuration its reference (``reference/<name>.py``). Nothing here
knows a cell, a configuration or a mix by name.

The system under test is ``repro.train.ServeSession`` driven through its
public ``submit``/``step``/``stats``/``stream_cb``. The weights and the
packed DS table are made here from the seed; the program never makes
anything the reference reads.
"""
from __future__ import annotations

import collections
import gc
import importlib
import json
import math
import os
import statistics
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

TRACE_S = 5.0            # the window of a --trace 1 run, all of it traced
SAMPLE_TOKENS = 600      # served tokens the comparison aims at ...
SAMPLE_MIN_REQUESTS = 6  # ... in at least this many requests (slots differ) ...
SAMPLE_REQUESTS = 8      # ... and at most this many
SAMPLE_MIN_TOKENS = 128  # fewer compared tokens fail the run
GAP_BLOCK = 256          # positions per call of the head comparison


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int = 1

    @property
    def reference(self):
        return importlib.import_module(f"reference.{self.config['reference']}")

    @property
    def generator(self):
        return importlib.import_module(f"generators.{self.traffic['kind']}")

    @property
    def sizes(self) -> dict:
        return self.reference.sizes(self.config)


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(root, "BENCHMARK.json")
    for w in bench["workloads"]:
        if w["name"] == name:
            conf = next(c for c in bench["configs"] if c["name"] == w["config"])
            return Cell(name, load_json(root, conf["file"]),
                        load_json(BENCH_DIR, "traffic", w["traffic"] + ".json"),
                        w["chips"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


# ---------------------------------------------------------------------------
# seeds, compiles, device
# ---------------------------------------------------------------------------

def key_for(seed: int, salt: int):
    """A JAX key from a seed of any size (more than 32 bits) and a salt."""
    import jax

    words = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), salt]) \
        .generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


class CompileLog:
    """Backend compiles seen through ``jax.monitoring``: the number, their
    seconds, and persistent-cache hits."""

    def __init__(self):
        import jax

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

    @property
    def total(self) -> int:
        return sum(self.count.values())


def cache_every_program():
    """Persistent-cache every program of the cell, not only slow ones, in
    ``.jax_cache`` at the root of this checkout: a fixed path, so that only
    a checkout's first run of a cell compiles, and one that no other
    checkout shares. The program's own cache helper is given the same."""
    import jax
    from repro.launch.compile_cache import use_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the served system
# ---------------------------------------------------------------------------

@dataclass
class Server:
    cell: Cell
    session: object
    cfg: object                 # the program's ModelConfig
    v_pad: int
    kernels: list               # AutoPolicy history: (B, kernel)


def program_config(cell: Cell):
    from repro.configs import get_config

    prog = cell.config["program"]
    cfg = get_config(prog["arch"]).replace(**prog.get("overrides", {}))
    return cfg.replace(ds=cfg.ds.replace(
        num_experts=cell.config["ds_head"]["num_experts"]))


def make_inputs(cell: Cell, seed: int, embed_rows: int):
    """Weights in the program's tree, and the packed table's ids and rows,
    all made on the device from the seed (one jitted call for the
    weights)."""
    import jax

    s, ref = cell.sizes, cell.reference
    gate, w, ids, _ = head_tables(cell, seed)
    params = jax.jit(lambda k, g: ref.to_program(ref.init(k, s), embed_rows, g))(
        key_for(seed, 1), gate)
    return params, ids, w


def build_server(cell: Cell, seed: int, stream_cb) -> Server:
    """The session as the cell serves it, with its inputs made from the
    seed."""
    import jax
    from repro.core.dssoftmax import ServeTable
    from repro.kernels.registry import AutoPolicy
    from repro.models import build
    from repro.train import ServeSession

    cfg = program_config(cell)
    bundle = build(cfg)
    abstract = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))[0]
    embed_rows = abstract["embed"]["table"].shape[0]
    params, ids, rows = make_inputs(cell, seed, embed_rows)
    _check_tree(params, abstract)
    table = ServeTable(ids=ids, weights=rows)
    serve = cell.config["serve"]
    history: list = []   # the 'auto' policy, recording what each call site chose
    sess = ServeSession(
        bundle, params, table, n_slots=cell.traffic["slots"],
        max_seq_len=cell.traffic["max_seq_len"], k=serve["k"],
        kernel=AutoPolicy(history=history),
        prefill_chunk=serve["prefill_chunk"], stream_cb=stream_cb)
    return Server(cell, sess, cfg, int(ids.shape[1]), history)


def raw_head_arrays(server: Server) -> int:
    """Live device arrays shaped like a raw (K, N, d) head: there should
    be none, only the packed (K, v_pad, d) table."""
    import jax

    K = server.cell.config["ds_head"]["num_experts"]
    d = server.cell.sizes["d"]
    return sum(1 for a in jax.live_arrays()
               if a.ndim == 3 and a.shape[0] == K and a.shape[2] == d
               and a.shape[1] >= server.cfg.vocab_size)


def _check_tree(params, abstract):
    """The weights must be the program's own tree, leaf for leaf, apart
    from the head, of which the serving path reads only the gate."""
    import jax

    want = dict(abstract)
    want["head"] = {"gate": abstract["head"]["gate"]}
    a = jax.tree_util.tree_structure(params)
    b = jax.tree_util.tree_structure(want)
    if a != b:
        raise ValueError(f"weights tree {a} is not the program's {b}")
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"leaf {x.shape} {x.dtype} is not the "
                             f"program's {y.shape} {y.dtype}")


# ---------------------------------------------------------------------------
# driving the traffic
# ---------------------------------------------------------------------------

@dataclass
class Tracked:
    spec: object
    req: object
    sent: float           # host clock
    times: list = field(default_factory=list)
    due: Optional[float] = None   # host clock; an open loop's schedule


@dataclass
class Record:
    t_open: float = 0.0
    t_close: float = 0.0
    tracked: list = field(default_factory=list)
    # recorded only inside a traced window, before and around each step:
    occupancy: list = field(default_factory=list)   # active / slots
    steps: list = field(default_factory=list)       # (t0, t1, experts hit)
    compiles_in_window: int = 0
    setup_s: float = 0.0
    warm_s: float = 0.0        # seconds the warm-up took, part of set-up
    queue_open: Optional[int] = None    # an open loop's queue as the window opens ...
    queue_close: Optional[int] = None   # ... and as it closes


class Runner:
    """Submits the traffic and steps the session; records when every
    token of every request arrived (``stream_cb``). ``clock`` and
    ``sleep`` are the host's, and a test may put its own in their place."""

    clock = staticmethod(time.perf_counter)
    sleep = staticmethod(time.sleep)

    def __init__(self, cell: Cell, seed: int, seconds: float):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.by_req: dict = {}
        self.server: Optional[Server] = None

    def on_token(self, req, token):
        t = self.by_req.get(id(req))
        if t is not None:
            t.times.append(self.clock())

    def _submit(self, spec, rec: Record, due: Optional[float] = None):
        from repro.train import Request, SamplingParams

        import jax

        sp = SamplingParams(max_new_tokens=spec.max_new_tokens,
                            temperature=spec.temperature, top_k=spec.top_k,
                            seed=spec.seed)
        req = Request(prompt=spec.prompt, sampling=sp)
        with jax.profiler.TraceAnnotation("bench.submit"):
            tr = Tracked(spec, req, self.clock(), due=due)
            self.by_req[id(req)] = tr
            self.server.session.submit(req)
        rec.tracked.append(tr)

    def _step(self, rec: Record, detail: bool):
        import jax

        sess = self.server.session
        if detail:
            st = sess.stats()
            rec.occupancy.append(st["n_active"] / sess.n_slots)
            before = np.asarray(st["expert_dispatched"] or 0)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            sess.step()
        t1 = time.perf_counter()
        if detail:
            after = np.asarray(sess.stats()["expert_dispatched"] or 0)
            hit = int(np.count_nonzero(after - before)) if np.ndim(after) else 0
            rec.steps.append((t0, t1, hit))

    def warm_up(self, stream_seed: int):
        """Compile every program the window runs, on requests that are not
        part of the traffic: a prompt of more than one chunk, decoding."""
        from generators.common import Spec

        sess = self.server.session
        cp = self.cell.config["serve"]["prefill_chunk"]
        rng = np.random.default_rng(stream_seed)
        vocab = self.cell.sizes["vocab"]
        dummy = Record()
        for n in (cp + 1, 3):
            self._submit(Spec(rng.integers(0, vocab, n, dtype=np.int32), 3), dummy)
        while sess.step():
            pass
        self.by_req.clear()

    def run(self, log: CompileLog, trace_dir: Optional[str], t_start: float) -> Record:
        """Set-up already built ``self.server``; warm up, open the window,
        drive it, close. A traced run records a window of at most
        ``TRACE_S`` seconds, all of it traced. The traffic's generator
        picks the driver: one that declares ``LOOP = "open"`` yields
        ``(due_s, Spec)`` on a schedule; any other, a backlog of specs."""
        cell, rec = self.cell, Record()
        seconds = min(self.seconds, TRACE_S) if trace_dir else self.seconds
        gen = cell.generator
        items = gen.generate(cell.traffic, self.seed, cell.sizes["vocab"], seconds)
        t = time.perf_counter()
        self.warm_up(self.seed ^ 0x5EED)
        rec.warm_s = time.perf_counter() - t
        if getattr(gen, "LOOP", None) == "open":
            self._run_open(items, rec, log, trace_dir, t_start, seconds)
        else:
            self._run_backlog(items, rec, log, trace_dir, t_start, seconds)
        return rec

    def _top_up(self, specs, rec):
        sched = self.server.session.scheduler
        free = sum(s is None for s in sched.slots) - len(sched.queue)
        for _ in range(max(0, free)):
            self._submit(next(specs), rec)

    def _run_backlog(self, specs, rec, log, trace_dir, t_start, seconds):
        sess = self.server.session
        while any(s is None for s in sess.scheduler.slots):   # fill every slot
            self._top_up(specs, rec)
            self._step(rec, False)
        n0 = log.total                                   # the fill is set-up
        rec.setup_s = time.perf_counter() - t_start
        self._start_trace(trace_dir)
        rec.t_open = time.perf_counter()
        end = rec.t_open + seconds
        while time.perf_counter() < end:
            self._top_up(specs, rec)
            self._step(rec, trace_dir is not None)
        rec.t_close = time.perf_counter()
        self._stop_trace(trace_dir)
        rec.compiles_in_window = log.total - n0

    def _run_open(self, arrivals, rec, log, trace_dir, t_start, seconds):
        """An open loop. Before each step, every request whose due time
        has come is submitted; with nothing to serve, the loop sleeps until
        the next one is due. First ``warm_s`` seconds of the arrival
        process (due times before 0, set-up), then the window, whose own
        arrivals are due from its open on."""
        sched = self.server.session.scheduler
        arrivals = iter(arrivals)
        t_warm = self.clock() + float(self.cell.traffic["warm_s"])
        nxt = self._drive(arrivals, next(arrivals, None), t_warm, 0.0, t_warm,
                          rec, False)
        n0 = log.total                                   # the warm stretch is set-up
        rec.setup_s = self.clock() - t_start
        self._start_trace(trace_dir)
        rec.t_open = self.clock()
        rec.queue_open = len(sched.queue)
        self._drive(arrivals, nxt, rec.t_open, math.inf, rec.t_open + seconds,
                    rec, trace_dir is not None)
        rec.t_close = self.clock()
        rec.queue_close = len(sched.queue)
        self._stop_trace(trace_dir)
        rec.compiles_in_window = log.total - n0

    def _drive(self, arrivals, nxt, base, stop, end, rec, detail):
        """Serve until the clock reaches ``end``: submit each arrival
        ``(due_s, spec)`` with ``due_s < stop`` once ``base + due_s`` has
        come, then step, or sleep while there is nothing to serve. Returns
        the first arrival not submitted."""
        import jax

        sched = self.server.session.scheduler
        while True:
            now = self.clock()
            if now >= end:
                return nxt
            while nxt is not None and nxt[0] < stop and base + nxt[0] <= now:
                self._submit(nxt[1], rec, due=base + nxt[0])
                nxt = next(arrivals, None)
            if sched.has_work():
                self._step(rec, detail)
                continue
            wake = base + nxt[0] if nxt is not None and nxt[0] < stop else end
            with jax.profiler.TraceAnnotation("bench.idle"):
                self.sleep(max(0.0, min(wake, end) - now))

    def _start_trace(self, trace_dir):
        import jax

        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)

    def _stop_trace(self, trace_dir):
        import jax

        if trace_dir:
            jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def quantile(xs, q: float) -> float:
    """The q-quantile, as Python's ``statistics.quantiles`` places it."""
    xs = sorted(xs)
    if len(xs) == 1:
        return float(xs[0])
    cuts = statistics.quantiles(xs, n=100, method="inclusive")
    return float(cuts[int(round(q * 100)) - 1])


def ttft_s(rec: Record) -> list:
    """Time to first token, from the due time, of every request due in the
    window; one with no token by the close counts as close - due. Empty
    in a closed loop, whose requests have no due time."""
    return [min(t.times[0] if t.times else rec.t_close, rec.t_close) - t.due
            for t in rec.tracked
            if t.due is not None and rec.t_open <= t.due < rec.t_close]


def end_to_end(rec: Record, metric_names) -> dict:
    """Every end-to-end metric of the cell, from host-clock timestamps. A
    name ``<quantity>.<part>`` is that quantity under a bound of its own,
    for the cells it lists (``tpot_p50_ms.open``: the open loop's pace)."""
    window = rec.t_close - rec.t_open
    out = {}
    toks = sum(1 for t in rec.tracked for x in t.times
               if rec.t_open <= x < rec.t_close)
    done = [t for t in rec.tracked if t.req.status.name == "COMPLETED"
            and len(t.times) > 1 and rec.t_open <= t.times[-1] < rec.t_close]
    tpot = [(t.times[-1] - t.times[0]) / (len(t.times) - 1) for t in done]
    ttft = ttft_s(rec)
    values = {
        "output_tokens_per_s": (toks / window, "tokens/s"),
        "tpot_p50_ms": (1e3 * quantile(tpot, 0.50) if tpot else None, "ms"),
        "tpot_p95_ms": (1e3 * quantile(tpot, 0.95) if tpot else None, "ms"),
        "ttft_p50_ms": (1e3 * quantile(ttft, 0.50) if ttft else None, "ms"),
        "setup_s": (rec.setup_s, "s"),
    }
    for name in metric_names:
        v, unit = values[name.split(".")[0]]
        if v is not None:
            out[name] = {"value": v, "unit": unit}
    return out


def attempted_failed(rec: Record) -> tuple[int, int]:
    sent = [t for t in rec.tracked if rec.t_open <= t.sent < rec.t_close]
    bad = [t for t in sent if t.req.done and t.req.status.name != "COMPLETED"]
    return len(sent), len(bad)


# ---------------------------------------------------------------------------
# correct: the served tokens against the plain reference
# ---------------------------------------------------------------------------

def pick_sample(rec: Record, seed: int):
    """Finished greedy requests, drawn from the seed, the longest first,
    until ``SAMPLE_TOKENS`` served tokens in ``SAMPLE_MIN_REQUESTS``
    requests or more, or ``SAMPLE_REQUESTS`` requests. A sampled token is
    not the reference's best, so sampled requests are not drawn."""
    done = [t for t in rec.tracked if t.req.status.name == "COMPLETED"
            and t.spec.temperature <= 0]
    if not done:
        return []
    longest = max(done, key=lambda t: len(t.req.out_tokens))
    rest = [t for t in done if t is not longest]
    order = np.random.default_rng([int(seed) & (2 ** 64 - 1), 0xC4]).permutation(len(rest))
    pick, n = [longest], len(longest.req.out_tokens)
    for i in order:
        if (n >= SAMPLE_TOKENS and len(pick) >= SAMPLE_MIN_REQUESTS) \
                or len(pick) >= SAMPLE_REQUESTS:
            break
        pick.append(rest[i])
        n += len(rest[i].req.out_tokens)
    return [(np.asarray(t.spec.prompt, np.int32),
             np.asarray(t.req.out_tokens, np.int32)) for t in pick]


def reference_hidden(cell: Cell, seed: int, sample, fp8: bool = False):
    """For each sampled request, the reference's final hidden states at
    the positions that produced its served tokens: (n_tokens, d) each.
    Sequences are padded to ``max_seq_len`` so one program serves all."""
    import jax
    import jax.numpy as jnp

    s, ref = cell.sizes, cell.reference
    w = jax.jit(lambda k: ref.init(k, s))(key_for(seed, 1))
    T = cell.traffic["max_seq_len"]
    batch = int(cell.config.get("reference_batch", 1))
    run = jax.jit(lambda w, t: ref.hidden(w, s, t, fp8))
    out = []
    for i in range(0, len(sample), batch):
        part = sample[i:i + batch]
        toks = np.zeros((batch, T), np.int32)
        for j, (p, o) in enumerate(part):
            seq = np.concatenate([p, o[:-1]])
            toks[j, : len(seq)] = seq
        h = np.asarray(run(w, jnp.asarray(toks)))
        for j, (p, o) in enumerate(part):
            out.append(h[j, len(p) - 1: len(p) - 1 + len(o)])
    del w
    return out


def head_tables(cell: Cell, seed: int):
    """The DS head made from the seed: the gate (K, d), the packed rows
    (K, v_pad, d), the class of each packed row (K, v_pad), and the packed
    row of each class in each expert (K, vocab)."""
    import jax
    import jax.numpy as jnp
    from reference import ds_head

    s, head = cell.sizes, cell.config["ds_head"]
    ids, rows = ds_head.assign(seed, head["num_experts"], s["vocab"],
                               head["redundancy"])
    ids_d = jnp.asarray(ids)
    gate, w = jax.jit(ds_head.init, static_argnums=(1, 2, 3))(
        key_for(seed, 2), ids.shape[0], ids.shape[1], s["d"], ids_d >= 0)
    return gate, w, ids_d, jnp.asarray(rows)


def blocked(fn, h, *rest):
    """``fn`` over (n, d) rows in fixed blocks of GAP_BLOCK (one compile)."""
    n = h.shape[0]
    outs = []
    for i in range(0, n, GAP_BLOCK):
        hb = np.zeros((GAP_BLOCK, h.shape[1]), np.float32)
        hb[: min(GAP_BLOCK, n - i)] = h[i: i + GAP_BLOCK]
        extra = []
        for r in rest:
            rb = np.zeros((GAP_BLOCK,), r.dtype)
            rb[: min(GAP_BLOCK, n - i)] = r[i: i + GAP_BLOCK]
            extra.append(rb)
        outs.append(np.asarray(fn(hb, *extra))[: min(GAP_BLOCK, n - i)])
    return np.concatenate(outs) if outs else np.zeros((0,))


def logit_gaps(cell: Cell, seed: int, sample, hs, served=None):
    """Per sampled request, the gap of each served token (or of
    ``served``, a replacement token list per request)."""
    from reference import ds_head

    gate, w, ids, rows = head_tables(cell, seed)
    out = []
    for i, (h, (_, o)) in enumerate(zip(hs, sample)):
        toks = o if served is None else served[i]
        out.append(blocked(lambda hb, tb: ds_head.gaps(hb, tb, gate, w, ids, rows),
                           h, np.asarray(toks, np.int32)))
    return out


def control_served(cell: Cell, seed: int, sample):
    """The tokens the float8 control puts first at the same positions."""
    from reference import ds_head

    gate, w, ids, _ = head_tables(cell, seed)
    hs = reference_hidden(cell, seed, sample, fp8=True)
    return [blocked(lambda hb: ds_head.control_tokens(hb, gate, w, ids), h)
            for h in hs]


def free_program_state(server: Server):
    """Drop every array the program holds before the reference runs."""
    import jax

    server.session = None
    gc.collect()
    jax.clear_caches()
