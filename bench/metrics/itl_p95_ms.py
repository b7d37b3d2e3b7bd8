"""Scheduler: the 95th percentile gap between consecutive tokens of a
request in the traced stretch (host clock, ``stream_cb``). In the
backlog every slot emits once per step, so a gap is one pass of the
serve loop; one that carries a replacement's prefill is longer."""
from harness import quantile
from metrics.common import token_gaps


def read(r):
    gaps = token_gaps(r)
    return 1e3 * quantile(gaps, 0.95) if gaps else None
