"""Harness: how late the open loop sent its requests, p95 over the
requests due in the traced stretch of (sent - due), host clock. The loop
is one thread: a request that falls due while a step runs waits for the
step's end, and that wait is part of its time to first token. Nothing to
read in a closed loop, whose requests have no due time."""
from harness import quantile


def read(r):
    lo, hi = r.rec.t_open, r.rec.t_close
    lag = [t.sent - t.due for t in r.rec.tracked
           if t.due is not None and lo <= t.due < hi]
    return 1e3 * quantile(lag, 0.95) if lag else None
