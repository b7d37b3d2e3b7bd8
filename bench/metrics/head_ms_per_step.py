"""DS head: device time of the Pallas DS kernels (``dss_topk*`` ops)
inside the decode program, per decode step. Nothing to read where the
decode step resolved to a path without a Pallas DS kernel."""
from metrics.common import DECODE


def read(r):
    if r.trace is None:
        return None
    n = len(r.trace.modules.get(DECODE, []))
    t = r.trace.ds_kernel_s.get(DECODE, 0.0)
    return 1e3 * t / n if n and t > 0 else None
