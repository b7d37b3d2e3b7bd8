"""What every per-layer reader is given, and the helpers they share.

A reader is ``metrics/<metric>.py`` with ``read(r: Readings)`` returning
the metric's value, or None where the run gave it nothing to read (the
harness then leaves the metric out of the line)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

DECODE = "jit__decode"   # the program's jitted decode step
CHUNK = "jit__chunk"     # its jitted prefill chunk


@dataclass
class Readings:
    rec: object                 # harness.Record of the run
    trace: Optional[object]     # trace.Reduction of the traced stretch
    sizes: dict                 # the configuration's sizes (reference.sizes)
    reference: object           # the configuration's reference module
    n_slots: int
    K: int
    v_pad: int
    peak: dict                  # peaks.json entry of the chip


def traced_steps(r: Readings) -> list:
    """(t0, t1, experts hit) of each step inside the traced stretch."""
    return r.rec.steps


def token_gaps(r: Readings) -> list:
    """Host-clock seconds between consecutive tokens of one request, both
    inside the traced stretch, over every request."""
    lo, hi = r.rec.t_open, r.rec.t_close
    return [b - a for t in r.rec.tracked for a, b in zip(t.times, t.times[1:])
            if lo <= a and b < hi]


def mean(xs) -> Optional[float]:
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None
