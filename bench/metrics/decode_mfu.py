"""Whole decode step: model FLOPs of the decode tokens served in the
traced stretch over (that stretch's host-clock time x peak bf16), in %.

Model FLOPs count each active slot's token once: the backbone at the
slot's real context length (``reference.<name>.token_flops``) and the DS
head's gate plus one expert's ``v_pad`` rows; idle slots and padding are
not model work."""
from metrics.common import traced_steps
from work import head_flops


def read(r):
    steps = traced_steps(r)
    if not steps:
        return None
    lo, hi = steps[0][0], steps[-1][1]
    flops = 0
    head = head_flops(1, r.K, r.v_pad, r.sizes["d"])
    for t in r.rec.tracked:
        plen = len(t.spec.prompt)
        for n, x in enumerate(t.times):
            if n >= 1 and lo <= x <= hi:    # token n came from a decode step
                flops += r.reference.token_flops(r.sizes, plen + n) + head
    return 100.0 * flops / ((hi - lo) * r.peak["bf16_flops_per_s"]) if flops else None
