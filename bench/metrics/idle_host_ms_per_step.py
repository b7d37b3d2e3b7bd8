"""Device: idle time under any other ``serve.*`` span (admission,
prefill dispatch, sampling, emitting), ms per decode step. Nothing to
read without the spans reduction (``spans.py``)."""
from metrics.spans_common import idle_ms_per_step


def read(r):
    return idle_ms_per_step(r, "host")
