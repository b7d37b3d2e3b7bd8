"""``correct`` must come out false when the timed path is broken, and
when the lower-precision control stands in for it. A tiny cell on the
CPU: the command's chip check is skipped and the rest of a run is driven
through ``run.run_cell`` with the fault planted underneath."""
from __future__ import annotations

import pytest

BIG_SEED = 2 ** 31 + 4242


CELLS = {"qwen2-1.5b.decode_backlog": "qwen2_cell", "mamba2-130m.chat_bursty": "mamba2_cell"}


def _run(seconds=1.5, seed=BIG_SEED, control=False, name="qwen2-1.5b.decode_backlog"):
    import harness
    import run
    import tiny
    from work import peaks

    cell = getattr(tiny, CELLS[name])()
    cell.name = name
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    return run.run_cell(cell, seed, seconds, False, bench, peaks("TPU v5 lite"),
                        harness.device_info(), control=control)


def _patch_decode(monkeypatch, wrap):
    """Wrap the session's jitted decode step as it is built."""
    from repro.train import serve

    orig = serve.ServeSession._build_decode_fn

    def build(self):
        orig(self)
        self._decode_fn = wrap(self._decode_fn)

    monkeypatch.setattr(serve.ServeSession, "_build_decode_fn", build)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_state_left_unchanged_fails(monkeypatch, name):
    """A decode step that returns its cache (state) as it came in. The
    step is handed its cache to reuse (donated), so the fault keeps a
    copy of it to return."""
    import jax
    import jax.numpy as jnp

    def wrap(fn):
        def step(p, t, c, tok, pos):
            kept = jax.tree.map(jnp.copy, c)
            vals, ids, _, stats = fn(p, t, c, tok, pos)
            return vals, ids, kept, stats
        return step

    _patch_decode(monkeypatch, wrap)
    res = _run(name=name)
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] is None or \
        res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_half_the_batch_left_out_fails(monkeypatch, name):
    """The decode step computes the first half of the slots and hands the
    second half the first half's answers."""
    def wrap(fn):
        def step(p, t, c, tok, pos):
            vals, ids, c, stats = fn(p, t, c, tok, pos)
            h = vals.shape[0] // 2
            return (vals.at[h:2 * h].set(vals[:h]), ids.at[h:2 * h].set(ids[:h]),
                    c, stats)
        return step

    _patch_decode(monkeypatch, wrap)
    assert _run(name=name)["correct"] is False


@pytest.mark.parametrize("name", sorted(CELLS))
def test_token_altered_where_produced_fails(monkeypatch, name):
    """Greedy sampling returns the runner-up on every fifth token."""
    from repro.train import serve

    orig = serve.ServeSession._sample

    def sample(self, vals, ids, sp, n_emitted):
        t = orig(self, vals, ids, sp, n_emitted)
        return int(ids[1]) if sp.temperature <= 0 and n_emitted % 5 == 4 else t

    monkeypatch.setattr(serve.ServeSession, "_sample", sample)
    assert _run(name=name)["correct"] is False


@pytest.mark.parametrize("name", sorted(CELLS))
def test_float8_control_is_not_correct(name):
    """The reference from float8 operands, put in the program's place,
    goes through the run's own comparison and limits and comes out not
    correct, reading a wider gap than the program on the same seed."""
    prog = _run(name=name)
    ctrl = _run(control=True, name=name)
    assert prog["correct"] is True, prog["checks"]
    assert ctrl["correct"] is False
    gap, limit = ctrl["checks"]["logit_gap"]["value"], ctrl["checks"]["logit_gap"]["limit"]
    assert gap is None or (gap > limit and gap > 3 * prog["checks"]["logit_gap"]["value"])
