"""Device: the share of the traced stretch in which no operation ran
(1 - union of the device op intervals / stretch), in %."""


def read(r):
    if r.trace is None or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
