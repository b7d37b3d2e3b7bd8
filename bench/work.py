"""Operations and bytes of the served work, from shapes alone.

The counts are the model's, not an implementation's: whichever kernel
runs, a decode token of a dense model costs its matrix products, its
attention over the positions it really has, and the DS head's gate plus
one expert's ``v_pad`` rows. Each architecture's per-token backbone count
lives beside its reference (``reference/<name>.py`` ``token_flops``) and
is built from the pieces here, as do the bytes of one slot's cache or
state that a decode token moves (``token_bytes``).
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def matmul_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def attention_flops(heads: int, head_dim: int, q_len: int, ctx: int) -> int:
    """Scores and weighted sum for ``q_len`` queries over ``ctx`` keys."""
    return 2 * matmul_flops(q_len, head_dim, ctx) * heads


def dense_token_flops(d: int, H: int, KV: int, hd: int, ff: int, L: int,
                      ctx: int) -> int:
    """One token through a GQA + SwiGLU decoder stack with ``ctx``
    positions to attend to (itself included)."""
    proj = matmul_flops(1, d, H * hd) + 2 * matmul_flops(1, d, KV * hd) \
        + matmul_flops(1, H * hd, d)
    mlp = 3 * matmul_flops(1, d, ff)
    return L * (proj + mlp + attention_flops(H, hd, 1, ctx))


def head_flops(B: int, K: int, v_pad: int, d: int) -> int:
    """The DS head for ``B`` tokens: the (K, d) gate, then one expert's
    ``v_pad`` rows per token."""
    return matmul_flops(B, d, K) + matmul_flops(B, d, v_pad)


def head_bytes(B: int, K: int, v_pad: int, d: int, n_hit: int,
               wbytes: int = 2, hbytes: int = 2) -> int:
    """Least bytes the head must move: the rows of each expert that got
    at least one token, the gate, and the hidden states."""
    return n_hit * v_pad * d * wbytes + K * d * wbytes + B * d * hbytes


def backbone_bytes(reference, s: dict) -> int:
    """Bytes of the weights that one decode step reads once whatever its
    batch: every array of ``reference.init`` but the embedding table, of
    which a step reads one row a token (counted apart)."""
    import jax

    shapes = jax.eval_shape(lambda k: reference.init(k, s), jax.random.PRNGKey(0))
    return sum(x.size * x.dtype.itemsize for name, tree in shapes.items()
               if name != "embed" for x in jax.tree.leaves(tree))


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time in seconds, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
