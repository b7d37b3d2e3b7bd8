"""Scheduler: active slots over ``n_slots``, sampled from
``ServeSession.stats()['n_active']`` before every step of the window."""
from metrics.common import mean


def read(r):
    m = mean(r.rec.occupancy)
    return None if m is None else 100.0 * m
