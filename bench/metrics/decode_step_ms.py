"""Model step, decode: device time per run of the jitted decode program
(its ``XLA Modules`` events in the trace)."""
from metrics.common import DECODE, mean


def read(r):
    if r.trace is None:
        return None
    m = mean(r.trace.modules.get(DECODE, []))
    return None if m is None else 1e3 * m
