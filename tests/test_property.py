"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs.base import DSSoftmaxConfig
from repro.core import dssoftmax as ds
from repro.core.dispatch import dispatch_indices
from repro.core import gating, pruning
from repro.distributed.hlo_analysis import type_bytes


@settings(max_examples=25, deadline=None)
@given(
    n_tokens=st.integers(1, 64),
    n_experts=st.integers(1, 8),
    capacity=st.integers(1, 32),
    seed=st.integers(0, 2 ** 16),
)
def test_dispatch_indices_invariants(n_tokens, n_experts, capacity, seed):
    rng = np.random.RandomState(seed)
    e = jnp.asarray(rng.randint(0, n_experts, size=n_tokens).astype(np.int32))
    slot, valid = dispatch_indices(e, n_experts, capacity)
    slot, valid, e = np.asarray(slot), np.asarray(valid), np.asarray(e)
    # (expert, slot) pairs unique among valid assignments
    pairs = {(int(e[i]), int(slot[i])) for i in range(n_tokens) if valid[i]}
    assert len(pairs) == valid.sum()
    # slots within capacity; per-expert valid count == min(count, capacity)
    assert np.all(slot[valid] < capacity)
    for ex in range(n_experts):
        cnt = int((e == ex).sum())
        assert int(valid[e == ex].sum()) == min(cnt, capacity)


def test_dispatch_never_admits_out_of_range_experts():
    """A sharded shard routes tokens it does not own to the sentinel id K.
    They are never valid, never counted, and never reach a capacity slot
    (an out-of-bounds index is undefined on TPU, so none may be relied on)."""
    from repro.core.dispatch import dispatch_load

    K, C = 4, 1
    e = jnp.asarray([4, 0, 0, 4, 2, 4], jnp.int32)
    slot, valid = dispatch_indices(e, K, C)
    assert np.asarray(valid).tolist() == [False, True, False, False, True, False]
    assert np.asarray(slot)[[1, 4]].tolist() == [0, 0]
    disp, over = dispatch_load(e, K, valid)
    assert np.asarray(disp).tolist() == [2, 0, 1, 0]
    assert np.asarray(over).tolist() == [1, 0, 0, 0]
    h = jnp.arange(6, dtype=jnp.float32)[:, None] + 1.0
    buf, g_buf, _, _ = ds._group_tokens(h, jnp.ones((6,)), e, K, C)
    assert np.asarray(buf)[:, 0, 0].tolist() == [2.0, 0.0, 5.0, 0.0]
    assert np.asarray(g_buf)[:, 0].tolist() == [1.0, 0.0, 1.0, 0.0]


@settings(max_examples=20, deadline=None)
@given(
    k=st.integers(1, 8),
    d=st.integers(2, 24),
    b=st.integers(1, 16),
    seed=st.integers(0, 2 ** 16),
)
def test_sparse_gate_properties(k, d, b, seed):
    rng = np.random.RandomState(seed)
    u = jnp.asarray(rng.normal(size=(k, d)).astype(np.float32))
    h = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    idx, g, G = gating.top1_gate(u, h)
    assert np.all(np.asarray(g) >= 1.0 / k - 1e-6)  # max of a k-simplex point
    assert np.all(np.asarray(g) <= 1.0 + 1e-6)
    Gs = gating.sparse_gate_matrix(G)
    assert np.all(np.asarray((Gs > 0).sum(-1)) == 1)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(4, 64),
    k=st.integers(1, 6),
    gamma=st.floats(0.0, 2.0),
    seed=st.integers(0, 2 ** 16),
)
def test_prune_never_kills_classes_entirely(n, k, gamma, seed):
    rng = np.random.RandomState(seed)
    w = jnp.asarray(rng.normal(scale=0.3, size=(k, n, 8)).astype(np.float32))
    mask = jnp.ones((k, n), bool)
    new = pruning.prune_step(w, mask, jnp.asarray(0.0), gamma=gamma, threshold=1.0)
    assert np.all(np.asarray(new).sum(axis=0) >= 1), "keep-one-copy violated"


@settings(max_examples=6, deadline=None)
@given(
    b=st.integers(1, 24),
    n_experts=st.integers(2, 4),
    k=st.integers(1, 5),
    seed=st.integers(0, 2 ** 16),
)
def test_registered_kernels_match_oracle(b, n_experts, k, seed):
    """Every single-device kernel registered in the policy registry
    (including the Pallas paths under interpret mode) agrees with the jnp
    oracle on random shapes — ids exactly, values to accumulation-order
    ulps. The ``*_ep`` specs are expert-parallel: ``serve_topk`` refuses
    them by design (``test_serve_sharded.py`` covers them on a mesh)."""
    from repro.kernels.registry import get_spec, kernel_names

    rng = np.random.RandomState(seed)
    cfg = DSSoftmaxConfig(num_experts=n_experts)
    params, state = ds.init(jax.random.PRNGKey(seed % 100), 16, 96, cfg)
    mask = jnp.asarray(rng.rand(n_experts, 96) < 0.7)
    mask = mask.at[:, 0].set(True)  # keep at least one class everywhere
    table = ds.pack_experts(params, ds.DSState(mask=mask))
    h = jnp.asarray(rng.normal(size=(b, 16)).astype(np.float32))
    v_ref, i_ref = ds.serve_topk(params["gate"], table, h, k=k, kernel="jnp")
    for name in (n for n in kernel_names() if not get_spec(n).sharded):
        v, i = ds.serve_topk(params["gate"], table, h, k=k, kernel=name)
        np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref),
                                   rtol=1e-3, atol=1e-4, err_msg=name)
        # ids exact except where different f32 accumulation orders swap a
        # rank-adjacent near-tie (values at that rank must still agree).
        mm = np.asarray(i) != np.asarray(i_ref)
        if mm.any():
            dv = np.abs(np.asarray(v)[mm] - np.asarray(v_ref)[mm])
            assert (dv <= 1e-4 * (1.0 + np.abs(np.asarray(v_ref)[mm]))).all(), name


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16), b=st.integers(1, 8))
def test_serve_topk_values_sorted_and_valid(seed, b):
    rng = np.random.RandomState(seed)
    cfg = DSSoftmaxConfig(num_experts=3)
    params, state = ds.init(jax.random.PRNGKey(seed % 100), 8, 40, cfg)
    table = ds.pack_experts(params, state)
    h = jnp.asarray(rng.normal(size=(b, 8)).astype(np.float32))
    vals, ids = ds.serve_topk(params["gate"], table, h, k=5)
    v = np.asarray(vals)
    assert np.all(np.diff(v, axis=1) <= 1e-6)  # descending
    assert np.all((np.asarray(ids) >= 0) & (np.asarray(ids) < 40))


@settings(max_examples=30, deadline=None)
@given(
    dt=st.sampled_from(["f32", "bf16", "s32", "pred", "u8", "f16"]),
    dims=st.lists(st.integers(1, 64), min_size=0, max_size=3),
)
def test_hlo_type_bytes(dt, dims):
    n = int(np.prod(dims)) if dims else 1
    per = {"f32": 4, "bf16": 2, "s32": 4, "pred": 1, "u8": 1, "f16": 2}[dt]
    s = f"{dt}[{','.join(map(str, dims))}]"
    assert type_bytes(s) == n * per
