"""Model step: device time of the decode program's ops under the
``attn`` scope (ln1, the attention and its cache write ``kv_write``, the
residual add), ms per decode step. Nothing to read without the spans
reduction (``spans.py``)."""
from metrics.spans_common import scope_ms_per_step


def read(r):
    return scope_ms_per_step(r, "attn", "kv_write")
