"""Open loop: requests arrive on a schedule, whether or not the earlier
ones have finished.

The gaps between arrivals follow a gamma distribution of mean ``1 / rate``
seconds and coefficient of variation ``cv`` (1 is Poisson; above 1,
bursts and lulls). They come in blocks of ``block``: the gaps at the
quantiles (i + 0.5) / block of that gamma, scaled so that their mean is
exactly ``1 / rate``, each block in an order of its own drawn from the
seed. Prompt and output lengths come in the same blocks
(``common.make_specs``), and in each block the share ``sampled.share`` of
the requests, at seeded places, samples at ``sampled.temperature`` from
its ``sampled.top_k`` best candidates; the rest are greedy. Every seed
then offers, block by block, the same gaps, sizes and sampling, and runs
differ by arrangement only.

``generate`` yields ``(due_s, Spec)`` in order of ``due_s``, in seconds
from the open of the measured window: first ``warm_s`` seconds of the
same process before it (due times below 0, from blocks of their own),
then the window's, without end.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator

import numpy as np

from generators.common import Spec, make_specs, rng_for

LOOP = "open"
WARM_SALT = 1 << 20       # blocks of the warm stretch; the window's count from 0


def gaps(params: dict, n: int) -> np.ndarray:
    """The ``n`` gaps of one block, in seconds, in ascending order."""
    from scipy.stats import gamma

    shape = 1.0 / float(params["cv"]) ** 2
    g = gamma.ppf((np.arange(n) + 0.5) / n, shape)
    return g / g.mean() / float(params["rate"])


def _blocks(params: dict, seed: int, vocab: int, salt0: int):
    """Endless (gap to the next arrival, spec) pairs, block by block."""
    block = int(params["block"])
    g = gaps(params, block)
    smp = params["sampled"]
    n_sampled = int(round(float(smp["share"]) * block))
    for b in itertools.count():
        salt = salt0 + b
        rng = rng_for(seed, salt, 0x0A)
        order = rng.permutation(g)
        sampled = rng.permutation(np.arange(block) < n_sampled)
        streams = rng.integers(0, 2 ** 31, block)
        for gap, spec, s, st in zip(order, make_specs(params, seed, vocab, block, salt),
                                    sampled, streams):
            if s:
                spec = dataclasses.replace(spec, temperature=float(smp["temperature"]),
                                           top_k=int(smp["top_k"]), seed=int(st))
            yield float(gap), spec


def generate(params: dict, seed: int, vocab: int,
             seconds: float) -> Iterator[tuple[float, Spec]]:
    """The warm stretch's arrivals, then the window's, without end."""
    del seconds  # the harness stops taking arrivals when the window closes
    t = -float(params["warm_s"])
    for gap, spec in _blocks(params, seed, vocab, WARM_SALT):
        if t >= 0.0:
            break
        yield t, spec
        t += gap
    t = 0.0
    for gap, spec in _blocks(params, seed, vocab, 0):
        yield t, spec
        t += gap
