"""Model step, decode: the least time the whole decode step's work could
take on this chip over the device time a decode step took, in %.

The work is the model's, for the slots that were active: per traced step,
the FLOPs of each decode token (``reference.<name>.token_flops``) and
of the DS head for those tokens; the bytes of the backbone's weights once
(``work.backbone_bytes``), an embedding row a token, each active slot's
cache or state traffic (``reference.<name>.token_bytes``: keys and
values in view for a transformer, the recurrent and conv state read and
written for an SSM), and the head's least bytes (``work.head_bytes``).
The least time is the larger of FLOPs over peak and bytes over HBM
bandwidth (``work.least_time``); idle slots count as nothing."""
import bisect

from metrics import decode_step_ms
from metrics.common import mean, traced_steps
from work import backbone_bytes, head_bytes, head_flops, least_time


def read(r):
    step_ms = decode_step_ms.read(r)
    steps = traced_steps(r)
    if step_ms is None or not steps:
        return None
    s, ref, d = r.sizes, r.reference, r.sizes["d"]
    starts = [t0 for t0, _, _ in steps]
    ctxs = [[] for _ in steps]      # context of each decode token of each step
    for t in r.rec.tracked:
        plen = len(t.spec.prompt)
        for n, x in enumerate(t.times[1:], start=1):   # token 0 comes from prefill
            i = bisect.bisect_right(starts, x) - 1
            if i >= 0 and x <= steps[i][1]:
                ctxs[i].append(plen + n)
    weights = backbone_bytes(ref, s)
    least = []
    for (_, _, hit), cs in zip(steps, ctxs):
        if not cs:
            continue
        B = len(cs)
        flops = sum(ref.token_flops(s, c) for c in cs) + head_flops(B, r.K, r.v_pad, d)
        nbytes = weights + B * d * 2 + sum(ref.token_bytes(s, c) for c in cs) \
            + head_bytes(B, r.K, r.v_pad, d, hit)
        least.append(least_time(flops, nbytes, r.peak)[0])
    return 100.0 * mean(least) / (step_ms / 1e3) if least else None
