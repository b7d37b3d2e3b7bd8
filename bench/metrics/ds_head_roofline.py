"""DS head kernels: the least time the head's work could take on this
chip over the time its Pallas kernels took, per decode step, in %.

The work is the model's, whichever kernel runs: per step the rows of
every expert that got at least one of the ``n_slots`` tokens, the gate
and the hidden states (bytes), and ``2·B·v_pad·d + 2·B·K·d`` (FLOPs).
The least time is the larger of FLOPs over peak and bytes over HBM
bandwidth (``work.least_time``)."""
from metrics import head_ms_per_step
from metrics.common import mean, traced_steps
from work import head_bytes, head_flops, least_time


def read(r):
    kernel_ms = head_ms_per_step.read(r)
    steps = traced_steps(r)
    if kernel_ms is None or not steps:
        return None
    d, B = r.sizes["d"], r.n_slots
    flops = head_flops(B, r.K, r.v_pad, d)
    least = mean(least_time(flops, head_bytes(B, r.K, r.v_pad, d, hit),
                            r.peak)[0] for _, _, hit in steps if hit)
    return None if not least else 100.0 * least / (kernel_ms / 1e3)
