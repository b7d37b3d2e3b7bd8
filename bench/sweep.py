#!/usr/bin/env python3
"""The knee of an open-loop cell, found on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r,r,...>

One process builds the cell's session once, then serves the cell's
traffic at each rate in turn (req/s, in place of the traffic's ``rate``):
the cell's warm stretch, then a window of ``--seconds``, with the gap
and size blocks of the window's count (``block`` = rate x seconds), as
the cell sets them. The rates go up in the order given, each starting
from the requests still in flight at the last one's close, which stay
tracked. Each rate prints one JSON line: the tokens per second
offered (rate x the mean output of the traffic's lengths) and completed,
the queue as the window opens and closes, TTFT and TPOT. The
knee is the highest rate at which the completed tokens per second fall
short of the offered by 3% at most (a window may also read more: it
finishes requests that arrived before it) and the queue at the close is
no longer than at the open; the cell's rate is set at 4/5 of it. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import numpy as np  # noqa: E402

import harness  # noqa: E402
from generators.common import length_quantiles  # noqa: E402

TAILS = ("output_tokens_per_s", "ttft_p50_ms", "tpot_p50_ms", "tpot_p95_ms")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    dev = harness.device_info()
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        print(f"sweep: cell {cell.name} needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    harness.cache_every_program()
    clog = harness.CompileLog()
    drv = harness.Runner(cell, args.seed, args.seconds)
    drv.server = harness.build_server(cell, args.seed, drv.on_token)
    drv.warm_up(args.seed ^ 0x5EED)
    t = cell.traffic
    base = dict(t)
    carry = []
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic = dict(base, rate=rate, block=round(rate * args.seconds))
        rec = harness.Record(tracked=carry)
        mean_out = float(np.mean(length_quantiles(t["output"], cell.traffic["block"])))
        items = cell.generator.generate(cell.traffic, args.seed, cell.sizes["vocab"],
                                        args.seconds)
        drv._run_open(items, rec, clog, None, time.perf_counter(), args.seconds)
        m = {k: v["value"] for k, v in harness.end_to_end(rec, TAILS).items()}
        ttft = harness.ttft_s(rec)
        m["ttft_p95_ms"] = 1e3 * harness.quantile(ttft, 0.95) if ttft else None
        offered = rate * mean_out
        print(json.dumps({
            "rate": rate, "offered_tokens_per_s": offered,
            "completed_share": m["output_tokens_per_s"] / offered,
            "queue_open": rec.queue_open, "queue_close": rec.queue_close,
            "compiles_in_window": rec.compiles_in_window, **m}), flush=True)
        carry = [tr for tr in rec.tracked if not tr.req.done]
    print(json.dumps({"device": dict(dev, memory_peak_bytes=harness.memory_peak_bytes())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
