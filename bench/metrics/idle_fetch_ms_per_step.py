"""Device: idle time under a ``serve.*.fetch`` span (the session waiting
on a device-to-host read of a step's outputs), ms per decode step.
Nothing to read without the spans reduction (``spans.py``)."""
from metrics.spans_common import idle_ms_per_step


def read(r):
    return idle_ms_per_step(r, "fetch")
