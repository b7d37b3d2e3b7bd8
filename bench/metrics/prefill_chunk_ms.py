"""Model step, prefill: device time per run of the jitted prefill-chunk
program (its ``XLA Modules`` events in the trace)."""
from metrics.common import CHUNK, mean


def read(r):
    if r.trace is None:
        return None
    m = mean(r.trace.modules.get(CHUNK, []))
    return None if m is None else 1e3 * m
