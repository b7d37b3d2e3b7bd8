"""Tests of the benchmark itself, run on the CPU at tiny sizes:
``python -m pytest bench/tests`` from the root of the checkout."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def tiny_sample(monkeypatch):
    """Tiny cells serve few tokens: compare what they have."""
    import harness

    monkeypatch.setattr(harness, "SAMPLE_TOKENS", 40)
    monkeypatch.setattr(harness, "SAMPLE_MIN_TOKENS", 8)
