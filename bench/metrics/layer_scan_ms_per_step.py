"""Model step: device time of the decode program's ops under the
``layers`` scope and under neither ``attn`` nor ``mlp`` (the layer
scan's slicing and restacking of the per-layer cache and weights), ms
per decode step. Nothing to read without the spans reduction
(``spans.py``)."""
from metrics.spans_common import scope_ms_per_step


def read(r):
    return scope_ms_per_step(r, "layers")
