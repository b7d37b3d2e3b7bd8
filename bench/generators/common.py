"""Pieces every traffic generator shares: the request spec, seeded length
draws, and prompt tokens.

Lengths are drawn as fixed quantiles of a clipped log-normal, so every
seed gets the same multiset of sizes; the seed only orders them and
picks the token ids. Runs on different seeds then do the same work.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Optional

import numpy as np


@dataclass(frozen=True)
class Spec:
    """One request as the generator offers it: greedy unless it sets a
    ``temperature`` (then sampled from the head's ``top_k`` candidates
    with the sampling stream ``seed``)."""

    prompt: np.ndarray          # (S,) int32 token ids
    max_new_tokens: int
    temperature: float = 0.0
    top_k: Optional[int] = None
    seed: int = 0


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator keyed by the run seed (any size) and a salt."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), *salt])


def length_quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a log-normal with
    the given ``median`` and ``sigma``, clipped to ``[min, max]``."""
    q = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(p) for p in q])
    x = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def make_specs(params: dict, seed: int, vocab: int, block: int,
               salt: int) -> list[Spec]:
    """One block of ``block`` requests: the fixed multisets of prompt and
    output lengths, each shuffled by the seed, with uniform prompt ids."""
    rng = rng_for(seed, salt)
    p_len = rng.permutation(length_quantiles(params["prompt"], block))
    o_len = rng.permutation(length_quantiles(params["output"], block))
    return [Spec(rng.integers(0, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(p_len, o_len)]
