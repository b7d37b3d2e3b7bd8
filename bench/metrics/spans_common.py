"""Helpers of the readers of the spans reduction (``spans.Spans``), which
a run hands them as ``r.spans``: the idle time by ``serve.*`` span, and
the decode program's device time by named scope, both per decode step.
Each returns None where the run has no such reduction, or it holds no
decode step, no ``serve.*`` span or nothing under the scope."""
from typing import Optional


def _spans(r):
    sp = getattr(r, "spans", None)
    return sp if sp is not None and sp.n_decode else None


def idle_ms_per_step(r, kind: str) -> Optional[float]:
    """Idle ms per decode step under ``kind``: fetch, host or caller."""
    sp = _spans(r)
    return None if sp is None or kind not in sp.idle_s \
        else sp.per_step_ms(sp.idle_s[kind])


def scope_ms_per_step(r, *scopes: str) -> Optional[float]:
    """Device ms per decode step of the ops whose innermost scope is one
    of ``scopes``."""
    sp = _spans(r)
    if sp is None or not any(s in sp.scope_s for s in scopes):
        return None
    return sp.per_step_ms(sum(sp.scope_s.get(s, 0.0) for s in scopes))
