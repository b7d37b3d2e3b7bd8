#!/usr/bin/env python3
"""The float8 control of a cell, on the chip, at the cell's own size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n,n,...>

For each seed, one run of the cell as ``run.py`` makes it (the served
window at the cell's own load), with the tokens that the float32
reference computed from float8 operands puts first standing in for the
served ones in the comparison (``run.run_cell(control=True)``). Each run
prints its checks and ``correct``, which must be false: the control's
widest gap is the upper reading of the ``logit_gap`` limit. The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402
import run  # noqa: E402
from work import peaks  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    dev = harness.device_info()
    if dev["platform"] != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    harness.cache_every_program()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run.T_START = time.perf_counter()
        res = run.run_cell(cell, seed, args.seconds, False, bench,
                           peaks(dev["kind"]), dev, control=True)
        readings.append(res["checks"]["logit_gap"]["value"])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    print(f"control readings (widest gap) {readings}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
