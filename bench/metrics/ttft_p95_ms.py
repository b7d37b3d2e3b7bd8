"""Scheduler, open loop: p95, over the requests due in the traced
stretch, of the time to first token from the due time
(``harness.ttft_s``). In the window it swings with whether a burst fills
every slot, so it is read here, unbounded, beside the bounded median.
Nothing to read in a closed loop, whose requests have no due time."""
from harness import quantile, ttft_s


def read(r):
    ttft = ttft_s(r.rec)
    return 1e3 * quantile(ttft, 0.95) if ttft else None
