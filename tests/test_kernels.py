"""Pallas kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.objectives import HINGE, LOGISTIC, RIDGE
from repro.kernels import ops, ref

OBJS = [LOGISTIC, RIDGE, HINGE]


def _data(obj, d, n, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.standard_normal((d, n)), dtype)
    y = jnp.asarray(rng.choice([-1.0, 1.0], n) if obj.classification
                    else rng.standard_normal(n), dtype)
    a = jnp.zeros(n, dtype)
    v0 = jnp.asarray(rng.standard_normal(d) * 0.1, jnp.float32)
    return X, y, a, v0


@pytest.mark.parametrize("obj", OBJS, ids=lambda o: o.name)
@pytest.mark.parametrize("d,n,B", [
    (8, 32, 8),          # minimal tile
    (37, 64, 16),        # d needs padding
    (100, 96, 16),       # padding + several buckets
    (128, 64, 32),       # aligned, wide bucket
    (13, 40, 8),         # both d and n awkward; B | n
])
def test_sdca_bucket_kernel_matches_oracle(obj, d, n, B):
    X, y, a, v0 = _data(obj, d, n, seed=d * 1000 + n)
    lam_n, sig = 0.1 * n, 2.0
    a_k, dv_k = ops.sdca_bucket_subepoch(obj, X, y, a, v0, lam_n, sig,
                                         bucket=B, interpret=True)
    a_r, v_r = ref.sdca_subepoch_ref(obj, X, y, a, v0, lam_n, sig)
    dv_r = (v_r - v0) / sig
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r),
                               rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(dv_k), np.asarray(dv_r),
                               rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("obj", OBJS, ids=lambda o: o.name)
def test_sdca_kernel_sequential_semantics(obj):
    """Kernel must process buckets IN ORDER: running it over [b0, b1] must
    equal running b0 then b1 with the carried v."""
    d, n, B = 16, 32, 16
    X, y, a, v0 = _data(obj, d, n, seed=9)
    lam_n, sig = 3.2, 1.0
    a_all, dv_all = ops.sdca_bucket_subepoch(obj, X, y, a, v0, lam_n, sig,
                                             bucket=B, interpret=True)
    a1, dv1 = ops.sdca_bucket_subepoch(obj, X[:, :B], y[:B], a[:B], v0,
                                       lam_n, sig, bucket=B,
                                       interpret=True)
    v_mid = v0 + sig * dv1
    a2, dv2 = ops.sdca_bucket_subepoch(obj, X[:, B:], y[B:], a[B:], v_mid,
                                       lam_n, sig, bucket=B,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(a_all),
                               np.concatenate([a1, a2]),
                               rtol=3e-4, atol=3e-5)
    np.testing.assert_allclose(np.asarray(dv_all),
                               np.asarray(dv1 + dv2), rtol=3e-4,
                               atol=3e-5)


@pytest.mark.parametrize("T,D,bt", [
    (64, 128, 16), (128, 128, 128), (256, 256, 64), (32, 8, 8),
])
def test_rglru_kernel_matches_oracle(T, D, bt):
    rng = np.random.default_rng(T + D)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    ga = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    gx = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    a_log = -jnp.abs(jnp.asarray(rng.standard_normal(D), jnp.float32)) * .1
    h0 = jnp.asarray(rng.standard_normal(D) * 0.1, jnp.float32)
    hk = ops.rglru_scan(x, a_log, ga, gx, h0, block_t=bt, interpret=True)
    hr = ref.rglru_ref(x, a_log, ga, gx, h0)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_kernel_dtypes(dtype):
    T, D = 64, 128
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, D)), dtype)
    ga = jnp.asarray(rng.standard_normal((T, D)), dtype)
    gx = jnp.asarray(rng.standard_normal((T, D)), dtype)
    a_log = -jnp.abs(jnp.asarray(rng.standard_normal(D), jnp.float32)) * .1
    h0 = jnp.zeros(D, jnp.float32)
    hk = ops.rglru_scan(x, a_log, ga, gx, h0, block_t=32, interpret=True)
    hr = ref.rglru_ref(x.astype(jnp.float32), a_log,
                       ga.astype(jnp.float32), gx.astype(jnp.float32), h0)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(hk, np.float32),
                               np.asarray(hr), rtol=tol, atol=tol)


def test_kernel_rejects_bad_tile():
    from repro.kernels import sdca_bucket
    with pytest.raises(ValueError, match="multiples of 8"):
        sdca_bucket.sdca_bucket_kernel(
            LOGISTIC, jnp.zeros((2, 9, 8)), jnp.zeros((2, 8)),
            jnp.zeros((2, 8)), jnp.zeros((9, 1)), jnp.zeros(2), True)
    # the error names the offending data source
    with pytest.raises(ValueError, match="tile cache"):
        sdca_bucket.sdca_bucket_kernel(
            LOGISTIC, jnp.zeros((2, 9, 8)), jnp.zeros((2, 8)),
            jnp.zeros((2, 8)), jnp.zeros((9, 1)), jnp.zeros(2), True,
            "tile cache")


# ---------------------------------------------------------------------------
# Sparse SDCA bucket kernel (kernels/sdca_sparse_bucket.py): the contract
# is BITWISE equality with the XLA gather/scatter scan, not allclose.
# ---------------------------------------------------------------------------

from repro.core import sdca as core_sdca
from repro.data.formats import zero_duplicates


def _sparse_data(obj, n, d, nnz, seed, v_scale=0.1):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, d, (n, nnz)).astype(np.int32)
    val = (rng.standard_normal((n, nnz)) / np.sqrt(max(nnz, 1))
           ).astype(np.float32)
    val = zero_duplicates(idx, val)          # CSR invariant (S11)
    y = np.asarray(rng.choice([-1.0, 1.0], n) if obj.classification
                   else rng.standard_normal(n), np.float32)
    a = np.zeros(n, np.float32)
    v0 = (rng.standard_normal(d) * v_scale).astype(np.float32)
    return (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(y),
            jnp.asarray(a), jnp.asarray(v0))


def _aliased(idx, val, v0, hot, neg_zero):
    """Rows that all hold the `hot` features first (feature 0 real)
    and end with a zero-valued feature-0 padding entry: every bucket's
    alias pass and write-back meet the same few features in every row.
    `neg_zero` puts -0.0 into v at the hot features and at a quarter of
    the touched ones."""
    idx, val, v0 = np.array(idx), np.array(val), np.array(v0)
    idx[:, :len(hot)] = hot
    val[:, 0] = np.where(val[:, 0] == 0, 0.5, val[:, 0])
    idx[:, -1], val[:, -1] = 0, 0.0
    val = zero_duplicates(idx, val)
    if neg_zero:
        touched = np.unique(idx)
        v0[touched[::4]] = -0.0
        v0[list(hot)] = -0.0
    return jnp.asarray(idx), jnp.asarray(val), jnp.asarray(v0)


def _assert_bits(got, want):
    """Equal bit patterns: unlike assert_array_equal, -0.0 != +0.0."""
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


def _run_both(obj, idx, val, y, a, v0, lam_n, sig, B):
    a_ref, dv_ref = core_sdca.sparse_local_subepoch(
        obj, idx, val, y, a, v0, jnp.float32(lam_n), jnp.float32(sig))
    a_k, dv_k = ops.sdca_sparse_bucket_subepoch(
        obj, idx, val, y, a, v0, jnp.float32(lam_n), jnp.float32(sig),
        bucket=B, interpret=True)
    return (np.asarray(a_ref), np.asarray(dv_ref),
            np.asarray(a_k), np.asarray(dv_k))


@pytest.mark.parametrize("obj", OBJS, ids=lambda o: o.name)
@pytest.mark.parametrize("n,d,nnz,B,hot,neg_zero", [
    # minimal tile
    pytest.param(32, 64, 8, 8, (), False, id="32-64-8-8"),
    # wider rows, several buckets
    pytest.param(64, 128, 16, 8, (), False, id="64-128-16-8"),
    # tiny d: heavy feature sharing inside buckets
    pytest.param(64, 32, 8, 16, (), False, id="64-32-8-16"),
    # nearly collision-free rows
    pytest.param(48, 1000, 8, 8, (), False, id="48-1000-8-8"),
    # criteo's width, fully unrolled loops: four features in every row
    pytest.param(32, 300, 40, 8, (0, 1, 2, 3), False, id="hot-40"),
    # ... with -0.0 in v where the rows touch it
    pytest.param(32, 300, 40, 8, (0, 1, 2, 3), True, id="hot-40-negzero"),
    # wider than UNROLL_NNZ: the blocked loop form
    pytest.param(32, 300, 136, 8, (0, 1, 2, 3), True, id="hot-136-negzero"),
])
def test_sdca_sparse_kernel_bitwise_vs_scan(obj, n, d, nnz, B, hot,
                                            neg_zero):
    idx, val, y, a, v0 = _sparse_data(obj, n, d, nnz, seed=n * 7 + d)
    if hot:
        idx, val, v0 = _aliased(idx, val, v0, hot, neg_zero)
    a_ref, dv_ref, a_k, dv_k = _run_both(
        obj, idx, val, y, a, v0, 0.1 * n, 2.0, B)
    _assert_bits(a_k, a_ref)
    _assert_bits(dv_k, dv_ref)
    assert np.abs(dv_k).max() > 0          # actually moved


@pytest.mark.parametrize("obj", OBJS, ids=lambda o: o.name)
def test_sdca_sparse_kernel_sequential_semantics(obj):
    """Buckets must be processed IN ORDER: one call over [b0, b1] must
    equal b0 then b1 with the carried v — bitwise."""
    n, d, nnz, B = 32, 64, 8, 16
    idx, val, y, a, v0 = _sparse_data(obj, n, d, nnz, seed=5)
    lam_n, sig = jnp.float32(3.2), jnp.float32(1.0)
    a_all, dv_all = ops.sdca_sparse_bucket_subepoch(
        obj, idx, val, y, a, v0, lam_n, sig, bucket=B, interpret=True)
    a1, dv1 = ops.sdca_sparse_bucket_subepoch(
        obj, idx[:B], val[:B], y[:B], a[:B], v0, lam_n, sig,
        bucket=B, interpret=True)
    v_mid = v0 + sig * dv1
    a2, _ = ops.sdca_sparse_bucket_subepoch(
        obj, idx[B:], val[B:], y[B:], a[B:], v_mid, lam_n, sig,
        bucket=B, interpret=True)
    np.testing.assert_array_equal(np.asarray(a_all),
                                  np.concatenate([a1, a2]))
    assert np.abs(np.asarray(dv_all)).max() > 0


def test_sdca_sparse_kernel_padding_rows_inert():
    """Cache-style padding rows (idx=0, val=0, y=+1) leave v untouched
    and the real rows' results bitwise-unchanged."""
    n, d, nnz, B = 24, 64, 8, 8
    idx, val, y, a, v0 = _sparse_data(LOGISTIC, n, d, nnz, seed=11)
    pad = 8
    idx_p = jnp.concatenate([idx, jnp.zeros((pad, nnz), jnp.int32)])
    val_p = jnp.concatenate([val, jnp.zeros((pad, nnz), jnp.float32)])
    y_p = jnp.concatenate([y, jnp.ones(pad, jnp.float32)])
    a_p = jnp.concatenate([a, jnp.zeros(pad, jnp.float32)])
    lam_n, sig = jnp.float32(0.1 * n), jnp.float32(2.0)
    a1, dv1 = ops.sdca_sparse_bucket_subepoch(
        LOGISTIC, idx, val, y, a, v0, lam_n, sig, bucket=B,
        interpret=True)
    a2, dv2 = ops.sdca_sparse_bucket_subepoch(
        LOGISTIC, idx_p, val_p, y_p, a_p, v0, lam_n, sig, bucket=B,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(a2)[:n], np.asarray(a1))
    np.testing.assert_array_equal(np.asarray(dv2), np.asarray(dv1))


def test_sdca_sparse_kernel_rejects_misalignment_actionably():
    ok = dict(bucket=8, interpret=True)
    idx, val, y, a, v0 = _sparse_data(LOGISTIC, 16, 32, 8, seed=0)
    lam_n = sig = jnp.float32(1.0)
    # nnz not a multiple of 8: names the alignment AND both fixes
    with pytest.raises(ValueError, match="multiples of 8"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, idx[:, :7], val[:, :7], y, a, v0, lam_n, sig, **ok)
    with pytest.raises(ValueError, match="nnz_multiple"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, idx[:, :7], val[:, :7], y, a, v0, lam_n, sig, **ok)
    # the offending source is reported (cache vs ad-hoc arrays)
    with pytest.raises(ValueError, match="ad-hoc arrays"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, idx[:, :7], val[:, :7], y, a, v0, lam_n, sig, **ok)
    with pytest.raises(ValueError, match="tile cache"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, idx[:, :7], val[:, :7], y, a, v0, lam_n, sig,
            bucket=8, interpret=True, source="tile cache")
    # bucket not a multiple of 8
    with pytest.raises(ValueError, match="multiples of 8"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, idx, val, y, a, v0, lam_n, sig, bucket=4,
            interpret=True)
    # bucket must divide the chunk
    with pytest.raises(ValueError, match="divide"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, idx[:12], val[:12], y[:12], a[:12], v0, lam_n,
            sig, **ok)


def test_sdca_sparse_kernel_vmem_budget_guard():
    from repro.kernels.sdca_sparse_bucket import V_VMEM_BUDGET_BYTES
    d_big = V_VMEM_BUDGET_BYTES // 4 + 8
    idx, val, y, a, _ = _sparse_data(LOGISTIC, 8, 32, 8, seed=1)
    with pytest.raises(ValueError, match="xla"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, idx, val, y, a, jnp.zeros(d_big, jnp.float32),
            jnp.float32(1.0), jnp.float32(1.0), bucket=8, interpret=True)


def test_sdca_sparse_kernel_total_vmem_budget_guard():
    """Wide tiles whose footprint blows the TOTAL VMEM budget get the
    same actionable ValueError narrow workloads do, not an opaque Mosaic
    OOM (v alone is tiny here: B=512, nnz=2048 puts the double-buffered
    idx/val tiles at 16 MiB)."""
    from repro.kernels.sdca_sparse_bucket import (
        TOTAL_VMEM_BUDGET_BYTES, vmem_bytes_estimate)
    B, nnz, d = 512, 2048, 64
    assert vmem_bytes_estimate(B, nnz, 64) > TOTAL_VMEM_BUDGET_BYTES
    idx = jnp.zeros((B, nnz), jnp.int32)
    val = jnp.zeros((B, nnz), jnp.float32)
    y = jnp.ones(B, jnp.float32)
    a = jnp.zeros(B, jnp.float32)
    with pytest.raises(ValueError, match="total budget"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, idx, val, y, a, jnp.zeros(d, jnp.float32),
            jnp.float32(1.0), jnp.float32(1.0), bucket=B, interpret=True)
    with pytest.raises(ValueError, match="xla"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, idx, val, y, a, jnp.zeros(d, jnp.float32),
            jnp.float32(1.0), jnp.float32(1.0), bucket=B, interpret=True)


def test_sdca_dense_kernel_bucket_cap_and_vmem_guard():
    """The dense kernel enforces its documented B <= 512 cap and a
    total-VMEM budget (tile + resident v + Gram) with actionable
    errors instead of an opaque Mosaic OOM."""
    from repro.kernels.sdca_bucket import (MAX_BUCKET,
                                           TOTAL_VMEM_BUDGET_BYTES,
                                           vmem_bytes_estimate)
    one = jnp.float32(1.0)
    B = MAX_BUCKET + 8
    with pytest.raises(ValueError, match=str(MAX_BUCKET)):
        ops.sdca_bucket_subepoch(
            LOGISTIC, jnp.zeros((8, B)), jnp.ones(B), jnp.zeros(B),
            jnp.zeros(8), one, one, bucket=B, interpret=True)
    # tall tiles: d_pad * B over the total budget even at B = 512
    d = 4096
    assert vmem_bytes_estimate(MAX_BUCKET, d) > TOTAL_VMEM_BUDGET_BYTES
    with pytest.raises(ValueError, match="xla"):
        ops.sdca_bucket_subepoch(
            LOGISTIC, jnp.zeros((d, MAX_BUCKET)), jnp.ones(MAX_BUCKET),
            jnp.zeros(MAX_BUCKET), jnp.zeros(d), one, one,
            bucket=MAX_BUCKET, interpret=True)


def test_sdca_sparse_kernel_rejects_duplicate_nonzeros():
    """Concrete ad-hoc rows repeating a feature id with NONZERO values
    break the bitwise-vs-XLA contract silently — they must be rejected
    with a pointer at formats.zero_duplicates.  Zero-valued duplicates
    (padding, sanitized rows) stay accepted."""
    idx, val, y, a, v0 = _sparse_data(LOGISTIC, 8, 32, 8, seed=2)
    bad_idx = np.asarray(idx).copy()
    bad_val = np.asarray(val).copy()
    bad_idx[3, 1] = bad_idx[3, 0]            # duplicate feature id...
    bad_val[3, 0] = 0.5
    bad_val[3, 1] = 0.25                     # ...both values nonzero
    with pytest.raises(ValueError, match="zero_duplicates"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, jnp.asarray(bad_idx), jnp.asarray(bad_val), y, a,
            v0, jnp.float32(1.0), jnp.float32(1.0), bucket=8,
            interpret=True)
    # a zero-valued duplicate BETWEEN two nonzero duplicates of the
    # same id must not mask the violation (value order A, 0, A after
    # the stable sort defeats a naive adjacent-pair check)
    tri_idx = np.asarray(idx).copy()
    tri_val = np.asarray(val).copy()
    tri_idx[5, :3] = 7
    tri_val[5, :3] = [1.0, 0.0, 2.0]
    with pytest.raises(ValueError, match="zero_duplicates"):
        ops.sdca_sparse_bucket_subepoch(
            LOGISTIC, jnp.asarray(tri_idx), jnp.asarray(tri_val), y, a,
            v0, jnp.float32(1.0), jnp.float32(1.0), bucket=8,
            interpret=True)
    # sanitizing the same rows makes them acceptable again
    ok_val = zero_duplicates(bad_idx, bad_val)
    ops.sdca_sparse_bucket_subepoch(
        LOGISTIC, jnp.asarray(bad_idx), jnp.asarray(ok_val), y, a, v0,
        jnp.float32(1.0), jnp.float32(1.0), bucket=8, interpret=True)


def test_sdca_sparse_kernel_bitwise_property():
    """Hypothesis sweep: bitwise equality with the scan across random
    shapes, objectives, scalings, and warm dual starts."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(st.sampled_from(OBJS),
           st.sampled_from([8, 16]),            # bucket
           st.integers(1, 3),                   # buckets per sub-epoch
           st.sampled_from([8, 16]),            # nnz
           st.integers(10, 200),                # d
           st.integers(0, 2 ** 16),             # data seed
           st.floats(0.05, 50.0),               # lam*n
           st.sampled_from([1.0, 2.0, 8.0]))    # sigma'
    @settings(max_examples=40, deadline=None)
    def bitwise(obj, B, nb, nnz, d, seed, lam_n, sig):
        n = B * nb
        idx, val, y, a, v0 = _sparse_data(obj, n, d, nnz, seed=seed)
        if obj.classification:    # feasible warm start: a*y in [0, 1)
            rng = np.random.default_rng(seed + 1)
            a = jnp.asarray(
                rng.uniform(0, 0.5, n).astype(np.float32) * np.asarray(y))
        a_ref, dv_ref, a_k, dv_k = _run_both(
            obj, idx, val, y, a, v0, lam_n, sig, B)
        np.testing.assert_array_equal(a_k, a_ref)
        np.testing.assert_array_equal(dv_k, dv_ref)

    bitwise()


# ---------------------------------------------------------------------------
# Feature-sharded sparse kernel (DESIGN.md S12): the same bitwise contract,
# lane by lane, with the engine's exchange emulated in-process.
# ---------------------------------------------------------------------------

from repro.kernels import sdca_sparse_bucket


@pytest.mark.parametrize("obj", OBJS, ids=lambda o: o.name)
@pytest.mark.parametrize("n,d,nnz,B", [
    (32, 64, 8, 8),       # aligned d
    (32, 250, 8, 16),     # d needs sublane padding inside the slice
])
def test_sdca_sparse_sharded_single_lane_bitwise(obj, n, d, nnz, B):
    """model_lanes=1: the one slice IS the whole v, so the sharded
    driver must reproduce the scan (and replicated kernel) bitwise."""
    idx, val, y, a, v0 = _sparse_data(obj, n, d, nnz, seed=n + d)
    lam_n, sig = jnp.float32(0.1 * n), jnp.float32(2.0)
    a_ref, dv_ref = core_sdca.sparse_local_subepoch(
        obj, idx, val, y, a, v0, lam_n, sig)
    a_s, dv_s = ops.sdca_sparse_sharded_subepoch(
        obj, idx, val, y, a, v0, lam_n, sig, bucket=B, interpret=True)
    np.testing.assert_array_equal(np.asarray(a_s), np.asarray(a_ref))
    np.testing.assert_array_equal(np.asarray(dv_s), np.asarray(dv_ref))
    assert np.abs(np.asarray(dv_s)).max() > 0


@pytest.mark.parametrize("obj", OBJS, ids=lambda o: o.name)
@pytest.mark.parametrize("M,d,hot", [
    # d=50: uneven slices + padding
    pytest.param(2, 50, (), id="2"),
    pytest.param(4, 50, (), id="4"),
    # every row touches features on both sides of the slice boundary
    # at 2048, and feature 0 both real and padded
    pytest.param(2, 2100, (0, 1, 2047, 2048, 2099), id="2-boundary"),
])
def test_sdca_sparse_sharded_multilane_emulated_exchange(obj, M, d, hot):
    """Drive the per-bucket kernel pair lane by lane with the engine's
    all-gather/owner-select exchange emulated in jnp: the M lanes'
    disjoint dv slices, concatenated, must equal the serial scan's dv
    bitwise, and every lane must agree on the duals."""
    n, nnz, B = 32, 8, 16
    idx, val, y, a, v0 = _sparse_data(obj, n, d, nnz, seed=3 + M)
    if hot:
        idx, val, v0 = _aliased(idx, val, v0, hot, neg_zero=True)
    lam_n, sig = jnp.float32(0.1 * n), jnp.float32(2.0)
    a_ref, dv_ref = core_sdca.sparse_local_subepoch(
        obj, idx, val, y, a, v0, lam_n, sig)

    d_loc = ops.sparse_slice_width(d, M)
    d_pad = d_loc * M
    v_pad = jnp.zeros((d_pad,), jnp.float32).at[:d].set(v0)
    v_locs = [v_pad[k * d_loc:(k + 1) * d_loc].reshape(-1, 128)
              for k in range(M)]
    v0_locs = list(v_locs)
    scal = jnp.stack([lam_n, sig])
    q = core_sdca.row_sq_norms(val.astype(jnp.float32))
    a_rows = []
    for b in range(n // B):
        sl = slice(b * B, (b + 1) * B)
        idx_t, val_t = idx[sl], val[sl]
        y_t, a_t, q_t = y[sl], a[sl], q[sl]
        parts = jnp.stack([
            sdca_sparse_bucket.sdca_sparse_gather_bucket(
                idx_t, v_locs[k], jnp.int32(k * d_loc), True)
            for k in range(M)])                       # (M, B, nnz)
        owner = (idx_t // jnp.int32(d_loc)).astype(jnp.int32)
        W = jnp.take_along_axis(parts, owner[None], axis=0)[0]
        a_lanes = []
        for k in range(M):
            a_new, v_locs[k] = (
                sdca_sparse_bucket.sdca_sparse_sharded_bucket(
                    obj, idx_t, val_t, y_t, a_t, q_t, W, v_locs[k],
                    scal, jnp.int32(k * d_loc), True))
            a_lanes.append(np.asarray(a_new))
        for other in a_lanes[1:]:       # redundant recursion agrees
            np.testing.assert_array_equal(other, a_lanes[0])
        a_rows.append(a_lanes[0])
    dv = jnp.concatenate(
        [(v_locs[k] - v0_locs[k]).reshape(-1) for k in range(M)])[:d] / sig
    _assert_bits(np.concatenate(a_rows), a_ref)
    _assert_bits(dv, dv_ref)
    assert np.abs(np.asarray(dv)).max() > 0
    if hot:     # both lanes own touched features
        assert all(np.abs(np.asarray(dv)[k * d_loc:(k + 1) * d_loc]).max()
                   > 0 for k in range(M))


def test_sdca_sparse_sharded_kernel_guards():
    """The sharded kernel pair enforces alignment and both VMEM budgets
    with actionable errors, mirroring the replicated kernel's guards."""
    from repro.kernels.sdca_sparse_bucket import (
        TOTAL_VMEM_BUDGET_BYTES, V_VMEM_BUDGET_BYTES,
        vmem_bytes_estimate_sharded)
    B, nnz = 8, 8
    idx_t = jnp.zeros((B, nnz), jnp.int32)
    lo = jnp.int32(0)
    # slice rows over the resident budget even after sharding
    rows_big = V_VMEM_BUDGET_BYTES // (4 * 128) + 8
    with pytest.raises(ValueError, match="even feature-sharded"):
        sdca_sparse_bucket.sdca_sparse_gather_bucket(
            idx_t, jnp.zeros((rows_big, 128), jnp.float32), lo, True)
    # slice not tile-aligned (the ops wrapper always aligns; direct
    # callers get told who is responsible)
    with pytest.raises(ValueError, match="multiple of 8"):
        sdca_sparse_bucket.sdca_sparse_gather_bucket(
            idx_t, jnp.zeros((12, 128), jnp.float32), lo, True)
    # wide (B, nnz) tiles blow the total budget
    Bw, nnzw = 512, 2048
    assert (vmem_bytes_estimate_sharded(Bw, nnzw, 1024)
            > TOTAL_VMEM_BUDGET_BYTES)
    with pytest.raises(ValueError, match="total budget"):
        sdca_sparse_bucket.sdca_sparse_sharded_bucket(
            LOGISTIC, jnp.zeros((Bw, nnzw), jnp.int32),
            jnp.zeros((Bw, nnzw), jnp.float32), jnp.ones(Bw),
            jnp.zeros(Bw), jnp.zeros(Bw),
            jnp.zeros((Bw, nnzw), jnp.float32),
            jnp.zeros((8, 128), jnp.float32),
            jnp.stack([jnp.float32(1.0), jnp.float32(1.0)]), lo, True)


# ---------------------------------------------------------------------------
# Flash attention kernel (kernels/flash_attention.py)
# ---------------------------------------------------------------------------

from repro.models.attention import blocked_attention


@pytest.mark.parametrize("kind", ["causal", "full", "local"])
@pytest.mark.parametrize("B,Sq,Sk,H,Hkv,hd,hd_v", [
    (2, 64, 64, 4, 2, 32, 32),      # GQA
    (1, 64, 64, 4, 1, 32, 16),      # MQA, hd_v != hd (MLA-like)
    (1, 32, 64, 2, 2, 32, 32),      # Sq != Sk
    (2, 48, 48, 2, 2, 16, 16),      # non-multiple of block (pads)
])
def test_flash_attention_matches_blocked(kind, B, Sq, Sk, H, Hkv, hd,
                                         hd_v):
    if kind == "causal" and Sq != Sk:
        pytest.skip("causal needs aligned positions")
    rng = np.random.default_rng(Sq + Sk + H)
    window = 24
    q = jnp.asarray(rng.standard_normal((B, Sq, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Sk, Hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Sk, Hkv, hd_v)), jnp.float32)
    ref_out = blocked_attention(q, k, v, q_positions=jnp.arange(Sq),
                                kind=kind, window=window, chunk=16)
    out = ops.flash_attention(q, k, v, kind=kind, window=window,
                              bq=16, bk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    rng = np.random.default_rng(7)
    B, S, H, hd = 1, 64, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
    ref_out = blocked_attention(q, k, v, q_positions=jnp.arange(S),
                                kind="causal", chunk=16)
    out = ops.flash_attention(q, k, v, kind="causal", bq=16, bk=16,
                              interpret=True)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_causal_tile_skip_correct():
    """The skipped tiles must not change results vs a full sweep: compare
    block sizes that do / don't align with the diagonal."""
    rng = np.random.default_rng(9)
    B, S, H, hd = 1, 96, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)
    o1 = ops.flash_attention(q, k, v, kind="causal", bq=16, bk=16,
                             interpret=True)
    o2 = ops.flash_attention(q, k, v, kind="causal", bq=32, bk=48,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-4, atol=2e-4)
