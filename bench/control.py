#!/usr/bin/env python3
"""The float8 control of a cell, on the chip, at the cell's own size.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n,n,...> [--fault bf16_state]

For each seed, one run of the cell as ``run.py`` makes it (the served
window at the cell's own load), with the tokens that the float32
reference computed from float8 operands puts first standing in for the
served ones in the comparison (``run.run_cell(control=True)``). Each run
prints its checks and ``correct``, which must be false: the control's
widest gap is the upper reading of the ``logit_gap`` limit.

With ``--fault bf16_state`` the program itself serves, with its recurrent
state rounded to bfloat16 wherever a step stores it (the one-token step
and the prefill chunk of ``repro.models.hybrid``): the precision below
the float32 state that the configuration states, planted underneath the
timed path. The benchmark's own runs never run this.
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


def plant_bf16_state() -> None:
    """Round the state that each SSD step returns through bfloat16."""
    import jax.numpy as jnp
    from repro.models import hybrid

    def rounded(fn):
        def step(*args, **kwargs):
            out, conv, ssm = fn(*args, **kwargs)
            return out, conv, ssm.astype(jnp.bfloat16).astype(ssm.dtype)
        return step

    hybrid.mamba2_decode = rounded(hybrid.mamba2_decode)
    hybrid.mamba2_prefill_chunk = rounded(hybrid.mamba2_prefill_chunk)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", choices=("bf16_state",))
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    dev = harness.device_info()
    if dev["platform"] != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    harness.cache_every_program()
    if args.fault:
        plant_bf16_state()
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run.T_START = time.perf_counter()
        res = run.run_cell(cell, seed, args.seconds, False, bench,
                           peaks(dev["kind"]), dev, control=not args.fault)
        readings.append(res["checks"]["logit_gap"]["value"])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    print(f"{args.fault or 'control'} readings (widest gap) {readings}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
