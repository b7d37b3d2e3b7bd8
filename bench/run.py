#!/usr/bin/env python3
"""On-chip benchmark of the DS-Softmax serving path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process runs one cell of ``BENCHMARK.json`` on the chips of this
machine: it makes the weights and the packed DS table from the seed,
serves the cell's traffic through ``repro.train.ServeSession`` for
``--seconds``, then compares a seeded sample of the greedy answers with
the float32 reference. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the first seconds of the window.

It exits non-zero, printing no result, without a TPU or with fewer chips
than the cell asks for. The last line of standard output is one JSON
object; the numbers compared for ``correct``, each beside its limit, are
the last key there and the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import numpy as np  # noqa: E402

import harness  # noqa: E402
from metrics.common import Readings  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    cell = harness.load_cell(args.workload)
    dev = harness.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"finds {dev['count']} {dev['platform']} device(s)", file=sys.stderr)
        return 2
    from work import peaks
    peak = peaks(dev["kind"])
    log(f"device: {dev}; compile cache {harness.cache_every_program()}")
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), bench,
                      peak, dev)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} "
              f"({'at least' if c.get('at_least') else 'at most'} {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def run_cell(cell, seed: int, seconds: float, traced: bool, bench: dict,
             peak: dict, dev: dict, control: bool = False) -> dict:
    """One run of one cell: set-up, the window, the metrics, then the
    comparison with the reference. Returns the result line's object.

    With ``control`` the tokens that the reference computed from float8
    operands puts first stand in for the served ones, and go through the
    same comparison and limits: the control, which must not be correct.
    The benchmark's own runs never set it."""
    clog = harness.CompileLog()
    drv = harness.Runner(cell, seed, seconds)
    server = harness.build_server(cell, seed, drv.on_token)
    drv.server = server
    built_s = time.perf_counter() - T_START
    trace_dir = None
    if traced:
        trace_dir = os.path.join(harness.ROOT, "bench_out", f"trace-{cell.name}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = drv.run(clog, trace_dir, T_START)
    for b, name in server.kernels:
        log(f"DS head kernel at the call site of batch {b}: {name}")
    log(f"set-up: process start to session built {built_s:.3f} s, warm-up "
        f"{rec.warm_s:.3f} s, then to the window {rec.setup_s - built_s - rec.warm_s:.3f} s")
    log(f"window {rec.t_close - rec.t_open:.3f} s; requests queued at its open "
        f"{rec.queue_open}, at its close {rec.queue_close}")
    ttft = harness.ttft_s(rec)
    if ttft:
        q = ", ".join(f"p{round(100 * p)} {1e3 * harness.quantile(ttft, p):.3f}"
                      for p in (0.5, 0.9, 0.95, 0.99))
        log(f"ttft ms over the {len(ttft)} requests due in the window: {q}, "
            f"max {1e3 * max(ttft):.3f}")
    log(f"V_pad {server.v_pad}; setup_s {rec.setup_s:.3f}; compiles "
        f"{clog.total} ({clog.secs:.3f} s), persistent-cache hits "
        f"{clog.cache_hits}; compiles inside the window {rec.compiles_in_window}")
    raw_head = harness.raw_head_arrays(server)
    dev = dict(dev, memory_peak_bytes=harness.memory_peak_bytes())
    log(f"peak_bytes_in_use {dev['memory_peak_bytes']}")
    attempted, failed = harness.attempted_failed(rec)

    result = {"correct": False, "attempted": attempted, "failed": failed}
    breakdown = None
    if traced:
        red = reduce_trace(trace_dir)
        dev["busy_s"], dev["window_s"] = red.busy_s, red.window_s
        r = Readings(rec, red, cell.sizes, cell.reference, cell.traffic["slots"],
                     cell.config["ds_head"]["num_experts"], server.v_pad, peak)
        metrics = {}
        for m in bench["per_layer"]:
            if cell.name not in m.get("workloads", [cell.name]):
                continue
            # ``<name>.<part>``, a quantity split by the end-to-end metric it
            # moves in some cells, reads with ``metrics/<name>.py``
            v = importlib.import_module(f"metrics.{m['name'].split('.')[0]}").read(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": red.device_ops, "idle_gaps": red.idle_gaps}
    else:
        names = [m["name"] for m in bench["end_to_end"]
                 if cell.name in m.get("workloads", [cell.name])]
        metrics = harness.end_to_end(rec, names)

    sample = harness.pick_sample(rec, seed)
    harness.free_program_state(server)
    del drv, server
    t_ref = time.perf_counter()
    hs = harness.reference_hidden(cell, seed, sample)
    gaps = harness.logit_gaps(cell, seed, sample, hs)
    n_tok = int(sum(g.size for g in gaps))
    widest, mean = _widest_mean(gaps)
    log(f"reference: {len(sample)} requests, {n_tok} served tokens, "
        f"widest gap {widest}, mean gap {mean}, {time.perf_counter() - t_ref:.3f} s")
    if control:
        served = harness.control_served(cell, seed, sample)
        gaps = harness.logit_gaps(cell, seed, sample, hs, served=served)
        widest, mean = _widest_mean(gaps)
        agree = np.mean(np.concatenate([c == o for c, (_, o) in zip(served, sample)]))
        log(f"float8 control in the served tokens' place: widest gap {widest}, "
            f"mean gap {mean}, the same first token at {agree:.4f} of the positions")

    checks = {
        "logit_gap": {"value": widest,
                      "limit": cell.config["correct"]["logit_gap_limit"]},
        "tokens_compared": {"value": n_tok, "limit": harness.SAMPLE_MIN_TOKENS,
                            "at_least": True},
        "compiles_in_window": {"value": rec.compiles_in_window, "limit": 0},
        "raw_head_arrays": {"value": raw_head, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and ((c["value"] >= c["limit"]) if c.get("at_least")
                  else (c["value"] <= c["limit"])) for c in checks.values())
    if widest is not None and not np.isfinite(widest):
        checks["logit_gap"]["value"] = None   # JSON has no infinity
    result.update({"correct": bool(ok), "metrics": metrics, "device": dev})
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _widest_mean(gaps) -> tuple:
    """The widest and the mean gap over every compared token (None if none)."""
    flat = np.concatenate([g for g in gaps if g.size]) if gaps else np.zeros(0)
    if not flat.size:
        return None, None
    return float(flat.max()), float(flat.mean())


def reduce_trace(trace_dir: str):
    import trace_reduce

    red = trace_reduce.reduce_dir(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return red


if __name__ == "__main__":
    sys.exit(main())
