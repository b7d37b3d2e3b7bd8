"""Per-kernel allclose tests vs the pure-jnp oracles (interpret=True),
sweeping shapes and dtypes per the assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dss_topk, flash_attention, gate_top1, lasso_prune, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("K,V,d,B,k", [(2, 256, 32, 4, 3), (4, 512, 64, 8, 5), (8, 1024, 128, 16, 8)])
def test_dss_topk_matches_ref(K, V, d, B, k, dtype):
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (K, V, d)).astype(dtype)
    ids = jnp.where(
        jax.random.uniform(jax.random.PRNGKey(1), (K, V)) < 0.8,
        jax.random.randint(jax.random.PRNGKey(2), (K, V), 0, 10 * V), -1,
    ).astype(jnp.int32)
    h = jax.random.normal(jax.random.PRNGKey(3), (B, d)).astype(dtype)
    eidx = jax.random.randint(jax.random.PRNGKey(4), (B,), 0, K)
    g = jax.random.uniform(jax.random.PRNGKey(5), (B,), minval=0.1, maxval=1.0)
    v1, i1 = dss_topk(w, ids, h, eidx, g, k, interpret=True)
    v2, i2 = ref.dss_topk_ref(w, ids, h, eidx, g, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=2e-2, atol=1e-4)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("K,d,B", [(2, 16, 4), (8, 64, 32), (64, 256, 128)])
def test_gate_top1_matches_ref(K, d, B):
    u = jax.random.normal(jax.random.PRNGKey(5), (K, d))
    h = jax.random.normal(jax.random.PRNGKey(6), (B, d))
    i1, g1 = gate_top1(u, h, interpret=True)
    i2, g2 = ref.gate_top1_ref(u, h)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("K,N,d", [(2, 128, 16), (4, 1024, 64)])
def test_lasso_prune_matches_ref(K, N, d, dtype):
    w = (jax.random.normal(jax.random.PRNGKey(7), (K, N, d)) * 0.2).astype(dtype)
    mask = jax.random.uniform(jax.random.PRNGKey(8), (K, N)) < 0.9
    n1, m1 = lasso_prune(w, mask, 0.5, interpret=True)
    n2, m2 = ref.lasso_prune_ref(w, mask, 0.5)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=2e-2, atol=1e-4)
    assert np.array_equal(np.asarray(m1), np.asarray(m2))


@pytest.mark.parametrize("S,dh,bq,bk", [(64, 16, 16, 16), (128, 32, 32, 64), (256, 64, 128, 128)])
def test_flash_attention_matches_ref(S, dh, bq, bk):
    q = jax.random.normal(jax.random.PRNGKey(9), (2, 2, S, dh))
    k = jax.random.normal(jax.random.PRNGKey(10), (2, 2, S, dh))
    v = jax.random.normal(jax.random.PRNGKey(11), (2, 2, S, dh))
    o1 = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
    o2 = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-4, atol=1e-5)


def test_dss_topk_kernel_equals_serve_topk_path():
    """The pallas path plugs into core.serve_topk and agrees with jnp path."""
    from repro.configs.base import DSSoftmaxConfig
    from repro.core import dssoftmax as ds

    cfg = DSSoftmaxConfig(num_experts=4)
    params, state = ds.init(jax.random.PRNGKey(0), 32, 256, cfg)
    table = ds.pack_experts(params, state)
    h = jax.random.normal(jax.random.PRNGKey(1), (8, 32))
    v1, i1 = ds.serve_topk(params["gate"], table, h, k=5, kernel="jnp")
    v2, i2 = ds.serve_topk(params["gate"], table, h, k=5, kernel="pallas")
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# Expert-grouped streaming serving kernel (dss_topk_grouped)
# ---------------------------------------------------------------------------

def _grouped_fixture(dtype, K=4, d=32, n_classes=900, keep=0.5):
    from repro.configs.base import DSSoftmaxConfig
    from repro.core import dssoftmax as ds

    cfg = DSSoftmaxConfig(num_experts=K)
    params, state = ds.init(jax.random.PRNGKey(0), d, n_classes, cfg, dtype=dtype)
    mask = jax.random.uniform(jax.random.PRNGKey(2), (K, n_classes)) < keep
    state = ds.DSState(mask=mask)
    return params, ds.pack_experts(params, state)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B", [16, 256])
@pytest.mark.parametrize("k", [1, 8, 64])
def test_dss_topk_grouped_matches_jnp_oracle(B, k, dtype):
    """Ids exactly equal; values equal up to f32 accumulation-order ulps
    (the oracle is a batched matvec, the kernel an MXU block matmul — both
    accumulate in fp32 over the same d axis)."""
    from repro.core import dssoftmax as ds

    params, table = _grouped_fixture(dtype)
    h = jax.random.normal(jax.random.PRNGKey(1), (B, 32)).astype(dtype)
    v1, i1 = ds.serve_topk(params["gate"], table, h, k=k, kernel="jnp")
    v2, i2 = ds.serve_topk(params["gate"], table, h, k=k, kernel="pallas_grouped")
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6, atol=2e-6)


@pytest.mark.parametrize("kern", ["grouped", "pallas_grouped"])
@pytest.mark.parametrize("cf", [1.0, 0.25])
@pytest.mark.parametrize("B", [16, 256])
def test_dss_topk_grouped_capacity_overflow_exact(B, cf, kern):
    """Small capacity factors force real overflow (verified below) — the
    chunked fallback must keep ALL overflowed tokens exact vs the oracle,
    even when the overflow far exceeds one fixup chunk (cf=0.25 overflows
    most of the batch)."""
    from repro.core import dssoftmax as ds
    from repro.core.dispatch import dispatch_indices
    from repro.core.gating import top1_gate

    K = 4
    params, table = _grouped_fixture(jnp.float32, K=K)
    h = jax.random.normal(jax.random.PRNGKey(1), (B, 32))
    eidx, _, _ = top1_gate(params["gate"], h)
    capacity = int(max(1, round(B / K * cf)))
    _, valid = dispatch_indices(eidx, K, capacity)
    n_over = int(np.sum(~np.asarray(valid)))
    assert n_over > 0, "fixture must actually overflow"
    v1, i1 = ds.serve_topk(params["gate"], table, h, k=8, kernel="jnp")
    v2, i2 = ds.serve_topk(params["gate"], table, h, k=8, kernel=kern,
                           capacity_factor=cf)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6, atol=2e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dss_topk_grouped_kernel_writes_only_bk_outputs(dtype):
    """The kernel's HBM outputs are the grouped (K, C, k) values/ids —
    O(B·k) total, one row per dispatched slot. No (B, n_blocks, k)
    candidate buffer exists (the top-k carry lives in VMEM scratch)."""
    from repro.kernels import ops as kops

    K, v_pad, d, C, k = 4, 512, 32, 16, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (K, v_pad, d)).astype(dtype)
    ids = jnp.where(
        jax.random.uniform(jax.random.PRNGKey(1), (K, v_pad)) < 0.8,
        jax.random.randint(jax.random.PRNGKey(2), (K, v_pad), 0, 10 * v_pad), -1,
    ).astype(jnp.int32)
    buf = jax.random.normal(jax.random.PRNGKey(3), (K, C, d)).astype(dtype)
    g_buf = jax.random.uniform(jax.random.PRNGKey(4), (K, C))
    vals, idxs = kops.dss_topk_grouped(w, ids, buf, g_buf, k)
    assert vals.shape == (K, C, k) and vals.dtype == jnp.float32
    assert idxs.shape == (K, C, k) and idxs.dtype == jnp.int32
    # oracle over the same grouped buffers
    z = jnp.einsum("kcd,kvd->kcv", buf, w, preferred_element_type=jnp.float32)
    z = z * g_buf[..., None]
    z = jnp.where(ids[:, None, :] >= 0, z, -1e9)
    v_ref, pos = jax.lax.top_k(z, k)
    i_ref = jnp.take_along_axis(jnp.broadcast_to(ids[:, None, :], z.shape), pos, axis=2)
    assert np.array_equal(np.asarray(idxs), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(v_ref), rtol=1e-6, atol=2e-6)


@pytest.mark.parametrize("kern", ["grouped", "pallas_grouped"])
def test_dss_topk_grouped_overflow_last_token_exact(kern):
    """Regression: when the LAST token overflows and shares a fixup chunk
    with sentinel padding, the clamped sentinel scatter used to clobber its
    corrected result with the stale slot value (observed as one request
    receiving another request's top-k in ServeSession decode)."""
    from repro.core import dssoftmax as ds
    from repro.core.gating import top1_gate

    K, d = 4, 32
    params, table = _grouped_fixture(jnp.float32, K=K, d=d)
    # Steer every token to expert 0: capacity=2 at B=8/cf=1 → 6 overflow
    # tokens, and the fixup chunks contain sentinels clamping to row B-1.
    params = dict(params)
    params["gate"] = jnp.zeros_like(params["gate"]).at[0].set(1.0)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (8, d))) + 0.1
    eidx, _, _ = top1_gate(params["gate"], h)
    assert np.all(np.asarray(eidx) == 0)
    v1, i1 = ds.serve_topk(params["gate"], table, h, k=8, kernel="jnp")
    v2, i2 = ds.serve_topk(params["gate"], table, h, k=8, kernel=kern,
                           capacity_factor=1.0)
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6, atol=2e-6)


def test_dss_topk_grouped_non_multiple_v_pad_exact():
    """Regression: v_pad that no block size divides (e.g. explicit
    serve_pad=192) must be padded inside the kernel wrapper, not floored —
    flooring n_vb would silently skip the trailing packed rows."""
    from repro.configs.base import DSSoftmaxConfig
    from repro.core import dssoftmax as ds

    cfg = DSSoftmaxConfig(num_experts=4)
    params, state = ds.init(jax.random.PRNGKey(0), 32, 180, cfg)
    table = ds.pack_experts(params, state, pad=192)
    assert table.v_pad == 192  # not a multiple of the 128-row block
    h = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    v1, i1 = ds.serve_topk(params["gate"], table, h, k=8, kernel="jnp")
    v2, i2 = ds.serve_topk(params["gate"], table, h, k=8, kernel="pallas_grouped")
    assert np.array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6, atol=2e-6)


def test_serve_topk_rejects_unknown_kernel():
    from repro.core import dssoftmax as ds

    params, table = _grouped_fixture(jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    with pytest.raises(ValueError, match="unknown serve kernel"):
        ds.serve_topk(params["gate"], table, h, k=4, kernel="palas_grouped")


# ---------------------------------------------------------------------------
# Kernel-policy registry: per-call-site auto selection
# ---------------------------------------------------------------------------

def test_registry_has_all_serve_paths():
    from repro.kernels.registry import get_spec, kernel_names

    base = {"jnp", "grouped", "pallas", "pallas_grouped", "pallas_fused"}
    # every base path + its expert-parallel shard_map twin
    assert set(kernel_names()) == base | {f"{n}_ep" for n in base}
    # Pallas paths are native only on TPU; XLA paths run everywhere.
    for name in kernel_names():
        spec = get_spec(name)
        assert spec.supports("tpu")
        assert spec.supports("cpu") == (not spec.pallas)
        assert spec.sharded == name.endswith("_ep")
        if spec.sharded:
            assert spec.local_name == name[:-3]
        # fused / quantized capability flags carry to the _ep twins
        assert spec.fused == ("fused" in name)
        assert spec.quantized_ok == ("pallas" != (spec.local_name or name))


@pytest.mark.parametrize("B,expected", [
    (1, "jnp"), (8, "jnp"),         # decode-scale: B ≲ K → per-token path
    (512, "grouped"), (2048, "grouped"),  # prefill-scale: B ≫ K → grouped
])
def test_auto_policy_cpu_batch_size_selection(B, expected):
    """On CPU the feasible paths are jnp/grouped; the bytes-moved model
    puts the crossover near B ≈ K/2 (ROADMAP open item closed)."""
    from repro.kernels.registry import AutoPolicy, KernelContext

    ctx = KernelContext(B=B, d=128, K=32, v_pad=1024, k=8, backend="cpu")
    assert AutoPolicy().resolve(ctx) == expected


@pytest.mark.parametrize("B,expected", [
    (8, "pallas"),                  # small decode batch: per-token streaming
    (2048, "pallas_grouped"),       # production batch: expert-grouped
])
def test_auto_policy_tpu_prefers_fused_paths(B, expected):
    """On TPU the Pallas paths dominate their XLA twins (no gather/logit
    spill), and the per-token/grouped crossover tracks B vs K."""
    from repro.kernels.registry import AutoPolicy, KernelContext

    ctx = KernelContext(B=B, d=128, K=32, v_pad=1024, k=8, backend="tpu")
    assert AutoPolicy().resolve(ctx) == expected


def test_auto_policy_prefill_vs_decode_same_engine():
    """Acceptance: the SAME policy object resolves a B=2048 prefill and a
    B=8 decode against the same packed table to different kernels, each
    agreeing exactly with the jnp oracle."""
    from repro.configs.base import DSSoftmaxConfig
    from repro.core import dssoftmax as ds
    from repro.kernels.registry import AutoPolicy

    K, d = 32, 32
    cfg = DSSoftmaxConfig(num_experts=K)
    params, state = ds.init(jax.random.PRNGKey(0), d, 512, cfg)
    mask = jax.random.uniform(jax.random.PRNGKey(2), (K, 512)) < 0.5
    table = ds.pack_experts(params, ds.DSState(mask=mask))

    policy = AutoPolicy(history=[])
    for B in (2048, 8):
        h = jax.random.normal(jax.random.PRNGKey(1), (B, d))
        v_ref, i_ref = ds.serve_topk(params["gate"], table, h, k=8, kernel="jnp")
        v, i = ds.serve_topk(params["gate"], table, h, k=8, kernel=policy)
        assert np.array_equal(np.asarray(i), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref),
                                   rtol=1e-6, atol=2e-6)
    assert policy.history == [(2048, "grouped"), (8, "jnp")]


def test_all_registered_kernels_agree_with_oracle():
    """Every KernelSpec's compute path matches the jnp oracle (Pallas
    paths under interpret=True on this CPU container; sharded *_ep specs
    through serve_topk_sharded on a host mesh over whatever devices this
    process has — the 8-fake-device CI job gives them a real split)."""
    from repro.core import dssoftmax as ds
    from repro.kernels.registry import get_spec, kernel_names
    from repro.launch.mesh import make_host_mesh

    params, table = _grouped_fixture(jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    v_ref, i_ref = ds.serve_topk(params["gate"], table, h, k=8, kernel="jnp")
    mesh = make_host_mesh()
    stab = ds.shard_table(table, mesh)
    for name in kernel_names():
        if get_spec(name).sharded:
            v, i = ds.serve_topk_sharded(
                params["gate"], stab, h, k=8, mesh=mesh,
                kernel=get_spec(name).local_name)
        else:
            v, i = ds.serve_topk(params["gate"], table, h, k=8, kernel=name)
        assert np.array_equal(np.asarray(i), np.asarray(i_ref)), name
        # 'pallas' folds g into h before the matmul (g·h)·W vs g·(h·W):
        # same ids, values equal to accumulation-order tolerance.
        np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref),
                                   rtol=1e-3, atol=1e-4, err_msg=name)


def test_fixed_policy_validates_name():
    from repro.kernels.registry import FixedPolicy

    assert FixedPolicy("grouped").name == "grouped"
    with pytest.raises(ValueError, match="unknown serve kernel"):
        FixedPolicy("goruped")


# ---------------------------------------------------------------------------
# Sharded specs: feasibility, ICI-bytes term, calibration
# ---------------------------------------------------------------------------

def test_sharded_specs_feasibility_tracks_ep():
    """Base specs only at single-device call sites, *_ep specs only at
    sharded ones — a policy can never hand serve_topk a sharded name or
    serve_topk_sharded a path that ignores the mesh."""
    from repro.kernels.registry import KernelContext, get_spec

    flat = KernelContext(B=64, d=128, K=32, v_pad=1024, backend="cpu")
    shard = KernelContext(B=64, d=128, K=32, v_pad=1024, backend="cpu",
                          ep=8, ndata=2)
    for name in ("jnp", "grouped"):
        assert get_spec(name).feasible(flat)
        assert not get_spec(name).feasible(shard)
        assert get_spec(f"{name}_ep").feasible(shard)
        assert not get_spec(f"{name}_ep").feasible(flat)


def test_sharded_spec_costs_local_hbm_plus_ici():
    """The *_ep HBM model is the base path at the per-device shapes (K/ep
    experts, B/ndata rows) and the ICI term is exactly the O(B·k) merge —
    (ep-1) carries of fp32 vals + int32 ids per local row."""
    from repro.kernels.registry import KernelContext, get_spec

    ctx = KernelContext(B=64, d=128, K=32, v_pad=1024, k=8, backend="cpu",
                        ep=8, ndata=2)
    local = ctx.local()
    assert (local.B, local.K, local.ep, local.ndata) == (32, 4, 1, 1)
    for name in ("jnp", "grouped"):
        base, sh = get_spec(name), get_spec(f"{name}_ep")
        assert sh.bytes_moved(ctx) == base.bytes_moved(local)
        assert sh.ici_bytes(ctx) == (8 - 1) * 32 * 8 * 8
        assert base.ici_bytes(ctx) == 0
    # grouped_ep reads 1/ep of the table per device: far below the flat
    # grouped path at the same global shapes
    assert get_spec("grouped_ep").bytes_moved(ctx) < get_spec("grouped").bytes_moved(
        KernelContext(B=64, d=128, K=32, v_pad=1024, k=8, backend="cpu"))


def test_auto_policy_resolves_sharded_call_sites():
    """ep > 1 call sites resolve to *_ep specs; the B-vs-K crossover logic
    carries over to the per-device shapes."""
    from repro.kernels.registry import AutoPolicy, KernelContext

    big = KernelContext(B=2048, d=128, K=32, v_pad=1024, backend="cpu",
                        ep=8, ndata=1)
    # B=1 decode: one local row vs K/ep=4 local experts → per-token wins
    small = KernelContext(B=1, d=128, K=32, v_pad=1024, backend="cpu",
                          ep=8, ndata=1)
    assert AutoPolicy().resolve(big) == "grouped_ep"
    assert AutoPolicy().resolve(small) == "jnp_ep"


def test_auto_policy_calibration_overrides_bytes_tie():
    """Measured µs/byte flips a selection the bytes model alone would
    make: if the grouped path's measured read rate is far worse than the
    per-token path's, a near-crossover call site goes per-token."""
    from repro.kernels.registry import AutoPolicy, KernelContext, get_spec

    ctx = KernelContext(B=64, d=128, K=32, v_pad=1024, backend="cpu")
    assert AutoPolicy().resolve(ctx) == "grouped"  # bytes model: grouped wins
    ratio = get_spec("jnp").bytes_moved(ctx) / get_spec("grouped").bytes_moved(ctx)
    calib = {("cpu", "jnp", 4): 1.0, ("cpu", "grouped", 4): 2.0 * ratio}
    assert AutoPolicy(calibration=calib).resolve(ctx) == "jnp"
    # incomplete calibration (one path missing) falls back to modeled bytes
    assert AutoPolicy(calibration={("cpu", "jnp", 4): 1.0}).resolve(ctx) == "grouped"
    # calibration measured at a DIFFERENT wbytes never prices this call
    # site (int8 and fp32 sweeps must not mix) → modeled-bytes fallback
    calib1 = {("cpu", "jnp", 1): 1.0, ("cpu", "grouped", 1): 2.0 * ratio}
    assert AutoPolicy(calibration=calib1).resolve(ctx) == "grouped"


def test_load_bench_calibration_roundtrip(tmp_path):
    """load_bench_calibration: median µs/byte per (backend, path, wbytes)
    from a sweep file; rows without a wbytes field key as the fp32
    default 4; absent/empty files mean 'stay on modeled bytes'."""
    import json

    from repro.kernels.registry import load_bench_calibration

    p = tmp_path / "BENCH_serve_topk.json"
    rows = [
        {"path": "jnp", "us": 100.0, "bytes_model": 1000},
        {"path": "jnp", "us": 300.0, "bytes_model": 1000},
        {"path": "jnp", "us": 200.0, "bytes_model": 1000},
        {"path": "grouped", "us": 50.0, "bytes_model": 1000},
        {"path": "pallas", "us": None, "bytes_model": 1000},  # skipped row
        # an int8 sweep of the same path lands under its own wbytes key
        {"path": "grouped", "us": 30.0, "bytes_model": 1000, "wbytes": 1},
    ]
    p.write_text(json.dumps({"config": {"backend": "cpu"}, "rows": rows}))
    calib = load_bench_calibration(str(p))
    assert calib[("cpu", "jnp", 4)] == pytest.approx(0.2)  # median of the three
    assert calib[("cpu", "grouped", 4)] == pytest.approx(0.05)
    assert calib[("cpu", "grouped", 1)] == pytest.approx(0.03)
    assert ("cpu", "pallas", 4) not in calib
    assert load_bench_calibration(str(tmp_path / "missing.json")) is None


def test_serve_topk_rejects_sharded_kernel_without_mesh():
    from repro.core import dssoftmax as ds

    params, table = _grouped_fixture(jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    with pytest.raises(ValueError, match="serve_topk_sharded"):
        ds.serve_topk(params["gate"], table, h, k=4, kernel="grouped_ep")


def test_pack_experts_rejects_truncating_pad():
    """pad smaller than the largest expert used to silently truncate
    surviving rows at idx[:v_pad]; it must raise instead."""
    from repro.configs.base import DSSoftmaxConfig
    from repro.core import dssoftmax as ds

    cfg = DSSoftmaxConfig(num_experts=2)
    params, state = ds.init(jax.random.PRNGKey(0), 8, 64, cfg)  # all 64 survive
    with pytest.raises(ValueError, match="truncate"):
        ds.pack_experts(params, state, pad=32)


def test_pack_experts_error_names_offending_experts():
    """The error must say WHICH experts exceed pad and by how many rows
    (not just the max), so an operator can size serve_pad from the
    message alone."""
    from repro.configs.base import DSSoftmaxConfig
    from repro.core import dssoftmax as ds

    cfg = DSSoftmaxConfig(num_experts=4)
    params, state = ds.init(jax.random.PRNGKey(0), 8, 64, cfg)
    # expert 1 keeps 40 rows, expert 3 keeps 33, others keep 8
    mask = np.zeros((4, 64), bool)
    mask[0, :8] = mask[2, :8] = True
    mask[1, :40] = True
    mask[3, :33] = True
    state = ds.DSState(mask=jnp.asarray(mask))
    with pytest.raises(ValueError) as ei:
        ds.pack_experts(params, state, pad=32)
    msg = str(ei.value)
    assert "expert 1: 40 rows" in msg
    assert "expert 3: 33 rows" in msg
    assert "2/4 experts" in msg
    assert "expert 0" not in msg and "expert 2" not in msg


def test_dss_topk_grouped_all_pruned_expert():
    """An expert whose packed rows are all padding must yield NEG_INF values
    and id -1 (matching lax.top_k over a fully masked row)."""
    from repro.kernels import ops as kops

    K, v_pad, d, C, k = 2, 128, 16, 8, 4
    w = jax.random.normal(jax.random.PRNGKey(0), (K, v_pad, d))
    ids = jnp.stack([
        jnp.arange(v_pad, dtype=jnp.int32),
        jnp.full((v_pad,), -1, jnp.int32),  # expert 1: everything pruned
    ])
    buf = jax.random.normal(jax.random.PRNGKey(1), (K, C, d))
    g_buf = jnp.ones((K, C))
    vals, idxs = kops.dss_topk_grouped(w, ids, buf, g_buf, k)
    assert np.all(np.asarray(vals[1]) == -1e9)
    assert np.all(np.asarray(idxs[1]) == -1)
