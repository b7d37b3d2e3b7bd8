"""Scheduler: prefill chunks the session ran per decode step over the
window, from ``ServeSession.stats()['prefill_chunks']`` and ``n_steps``
as the window opened and closed. Nothing to read where the record holds
no such counters or the program has no such counter."""


def read(r):
    c = getattr(r.rec, "window_counters", None)
    if not c or "close" not in c:
        return None
    a, b = c["open"], c["close"]
    if a["prefill_chunks"] is None or b["n_steps"] == a["n_steps"]:
        return None
    return (b["prefill_chunks"] - a["prefill_chunks"]) / (b["n_steps"] - a["n_steps"])
