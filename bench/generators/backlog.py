"""Closed backlog: a queue that never empties.

The harness tops the server up so that every slot is busy and one request
waits; a finished request is replaced at once. Requests come in blocks of
``block``, each block the same multiset of lengths in a seeded order, so
any window consumes the same mix.
"""
from __future__ import annotations

import itertools
from typing import Iterator

from generators.common import Spec, make_specs


def generate(params: dict, seed: int, vocab: int, seconds: float) -> Iterator[Spec]:
    """An endless stream of requests."""
    del seconds  # the backlog is endless; the harness stops pulling
    block = int(params.get("block", params["slots"]))
    for b in itertools.count():
        yield from make_specs(params, seed, vocab, block, salt=b)
