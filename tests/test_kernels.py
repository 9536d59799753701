"""Per-kernel allclose sweeps: Pallas (interpret=True on CPU) vs pure-jnp
ref.py oracles across shape/dtype grids."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.similarity import normalize

pytestmark = pytest.mark.pallas


def rand_emb(rng, n, d, dtype):
    return jnp.asarray(normalize(rng.standard_normal((n, d))), dtype)


# ----------------------------------------------------------------------------
# sim_hist
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,d", [(64, 64, 16), (128, 64, 32), (256, 128, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sim_hist_matches_ref(m, n, d, dtype):
    from repro.kernels.sim_hist.kernel import sim_hist_pallas
    from repro.kernels.sim_hist.ref import sim_hist_ref

    rng = np.random.default_rng(0)
    e1, e2 = rand_emb(rng, m, d, dtype), rand_emb(rng, n, d, dtype)
    n_bins = 256
    got = sim_hist_pallas(e1, e2, n_bins=n_bins, bm=min(64, m), bn=min(64, n),
                          interpret=True)
    want = sim_hist_ref(e1, e2, n_bins=n_bins)
    assert int(got.sum()) == m * n
    # bf16 rounding can move boundary scores one bin; compare CDFs loosely
    np.testing.assert_allclose(
        np.cumsum(np.asarray(got)), np.cumsum(np.asarray(want)),
        atol=max(2, 0.01 * m * n),
    )
    if dtype == jnp.float32:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("exponent", [0.5, 1.0, 2.0])
def test_sim_hist_exponent(exponent):
    from repro.kernels.sim_hist.kernel import sim_hist_pallas
    from repro.kernels.sim_hist.ref import sim_hist_ref

    rng = np.random.default_rng(1)
    e1, e2 = rand_emb(rng, 64, 16, jnp.float32), rand_emb(rng, 64, 16, jnp.float32)
    got = sim_hist_pallas(e1, e2, n_bins=128, exponent=exponent, bm=64, bn=64,
                          interpret=True)
    want = sim_hist_ref(e1, e2, n_bins=128, exponent=exponent)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sim_hist_ops_padding():
    from repro.kernels.sim_hist import sim_hist

    rng = np.random.default_rng(2)
    e1 = normalize(rng.standard_normal((100, 16)))   # not a block multiple
    e2 = normalize(rng.standard_normal((70, 16)))
    counts, edges = sim_hist(e1, e2, n_bins=256)
    assert counts.sum() == 100 * 70
    assert (counts >= 0).all()


# ----------------------------------------------------------------------------
# sim_topk
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,d,k", [(64, 128, 16, 4), (128, 256, 32, 8), (64, 64, 8, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sim_topk_matches_ref(m, n, d, k, dtype):
    from repro.kernels.sim_topk.kernel import sim_topk_pallas
    from repro.kernels.sim_topk.ref import sim_topk_ref

    rng = np.random.default_rng(3)
    e1, e2 = rand_emb(rng, m, d, dtype), rand_emb(rng, n, d, dtype)
    vals, idx = sim_topk_pallas(e1, e2, k=k, bm=min(64, m), bn=min(64, n),
                                interpret=True)
    rvals, ridx = sim_topk_ref(e1, e2, k=k)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), atol=tol)
    # indices may differ on exact ties; values must match, and where values
    # are distinct the indices must agree
    distinct = np.abs(np.diff(np.asarray(rvals), axis=1)) > 1e-5
    same = np.asarray(idx)[:, :-1][distinct] == np.asarray(ridx)[:, :-1][distinct]
    assert same.mean() > 0.99


def test_sim_topk_ops_padding_and_validity():
    from repro.kernels.sim_topk import sim_topk

    rng = np.random.default_rng(4)
    e1 = normalize(rng.standard_normal((50, 8)))
    e2 = normalize(rng.standard_normal((37, 8)))
    vals, idx, valid = sim_topk(e1, e2, k=5)
    assert vals.shape == (50, 5) and idx.shape == (50, 5)
    assert (idx[valid] < 37).all()


# ----------------------------------------------------------------------------
# sim_sweep (fused histogram + top-k + per-block count tiles)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,d,k", [(64, 64, 16, 4), (128, 64, 32, 8),
                                     (100, 70, 16, 8)])
def test_sim_sweep_bit_identical_to_two_kernel_path(m, n, d, k):
    """The fused sweep must reproduce the sequential sim_hist + sim_topk
    outputs bit-for-bit at fp32, and its count tiles must column-sum to the
    global histogram exactly."""
    from repro.kernels.sim_hist import sim_hist
    from repro.kernels.sim_sweep import sim_sweep
    from repro.kernels.sim_topk import sim_topk

    rng = np.random.default_rng(10)
    e1 = normalize(rng.standard_normal((m, d)))
    e2 = normalize(rng.standard_normal((n, d)))
    sw = sim_sweep(e1, e2, n_bins=256, k=k)
    counts, edges = sim_hist(e1, e2, n_bins=256)
    vals, idx, valid = sim_topk(e1, e2, k=k)
    np.testing.assert_array_equal(sw.counts, counts)
    np.testing.assert_array_equal(sw.vals, vals)
    np.testing.assert_array_equal(sw.idx, idx)
    np.testing.assert_array_equal(sw.valid, valid)
    np.testing.assert_array_equal(sw.block_counts.sum(axis=0), sw.counts)
    assert int(sw.counts.sum()) == m * n


def test_sim_sweep_scale_matches_sim_hist():
    """The per-row scale operand (k-way chain-prefix weights) must bin
    identically to sim_hist's."""
    from repro.kernels.sim_hist import sim_hist
    from repro.kernels.sim_sweep import sim_sweep

    rng = np.random.default_rng(11)
    e1 = normalize(rng.standard_normal((96, 16)))
    e2 = normalize(rng.standard_normal((80, 16)))
    scale = rng.random(96).astype(np.float32)
    sw = sim_sweep(e1, e2, n_bins=128, exponent=0.5, scale=scale)
    counts, _ = sim_hist(e1, e2, n_bins=128, exponent=0.5, scale=scale)
    np.testing.assert_array_equal(sw.counts, counts)


def test_sim_sweep_matches_ref():
    from repro.kernels.sim_sweep.kernel import sim_sweep_pallas
    from repro.kernels.sim_sweep.ref import sim_sweep_ref

    rng = np.random.default_rng(12)
    e1 = rand_emb(rng, 128, 16, jnp.float32)
    e2 = rand_emb(rng, 64, 16, jnp.float32)
    bc, vals, idx, rs = sim_sweep_pallas(e1, e2, n_bins=256, k=4, bm=64,
                                         bn=64, interpret=True)
    rbc, rvals, ridx, rrs = sim_sweep_ref(e1, e2, n_bins=256, k=4, bm=64)
    np.testing.assert_array_equal(np.asarray(bc), np.asarray(rbc))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), atol=1e-6)
    np.testing.assert_allclose(np.asarray(rs), np.asarray(rrs), rtol=1e-6)
    distinct = np.abs(np.diff(np.asarray(rvals), axis=1)) > 1e-5
    same = np.asarray(idx)[:, :-1][distinct] == np.asarray(ridx)[:, :-1][distinct]
    assert same.mean() > 0.99


@pytest.mark.parametrize("precision", ["bf16", "int8"])
def test_sim_sweep_low_precision_within_tolerance(precision):
    """bf16/int8 fast paths: exact total mass, CDF within the documented
    per-precision tolerance of the fp32 histogram."""
    from repro.configs.joinml_embedder import EMBEDDING_PRECISIONS
    from repro.kernels.sim_sweep import sim_sweep

    rng = np.random.default_rng(13)
    e1 = normalize(rng.standard_normal((100, 32)))
    e2 = normalize(rng.standard_normal((90, 32)))
    ref = sim_sweep(e1, e2, n_bins=256, k=8)
    low = sim_sweep(e1, e2, n_bins=256, k=8, precision=precision)
    assert int(low.counts.sum()) == 100 * 90
    dev = np.abs(
        np.cumsum(ref.counts) - np.cumsum(low.counts)
    ) / ref.counts.sum()
    assert dev.max() <= EMBEDDING_PRECISIONS[precision].max_cdf_shift
    # top-k of the lowp scores still finds (nearly) the same neighbours
    hit = np.mean([
        len(set(a) & set(b)) / len(a)
        for a, b in zip(low.idx.tolist(), ref.idx.tolist())
    ])
    assert hit > 0.9


def test_quantize_rows_int8_roundtrip():
    from repro.core.similarity import dequantize_rows_int8, quantize_rows_int8

    rng = np.random.default_rng(14)
    e = normalize(rng.standard_normal((50, 32)))
    e[7] = 0.0  # padding-style all-zero row
    q, rs = quantize_rows_int8(e)
    assert q.dtype == np.int8 and rs.shape == (50, 1)
    back = dequantize_rows_int8(q, rs)
    assert np.abs(back - e).max() <= (np.abs(e).max(axis=1) / 127).max() * 0.51
    assert (q[7] == 0).all() and rs[7] == 0.0


# ----------------------------------------------------------------------------
# flash_attention
# ----------------------------------------------------------------------------

@pytest.mark.parametrize(
    "b,hq,hkv,s,d", [(1, 4, 4, 128, 32), (2, 8, 2, 64, 16), (1, 4, 1, 128, 64)]
)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, hq, hkv, s, d, causal, dtype):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import flash_attention_ref

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, bq=32, bkv=32,
                                 interpret=True)
    want = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_flash_attention_window():
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import flash_attention_ref

    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.standard_normal((1, 2, 128, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 128, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 128, 16)), jnp.float32)
    got = flash_attention_pallas(q, k, v, causal=True, window=32, bq=32, bkv=32,
                                 interpret=True)
    want = flash_attention_ref(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


# ----------------------------------------------------------------------------
# rwkv6_scan
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,t,hd", [(1, 2, 64, 16), (2, 4, 128, 32)])
@pytest.mark.parametrize("ct", [16, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan_matches_ref(b, h, t, hd, ct, dtype):
    from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_pallas
    from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref

    rng = np.random.default_rng(7)
    r = jnp.asarray(rng.standard_normal((b, h, t, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((b, h, t, hd)) * 0.3, dtype)
    v = jnp.asarray(rng.standard_normal((b, h, t, hd)), dtype)
    w = jnp.asarray(rng.uniform(0.7, 0.999, (b, h, t, hd)), dtype)
    u = jnp.asarray(rng.standard_normal((h, hd)) * 0.1, jnp.float32)
    got = rwkv6_scan_pallas(r, k, v, w, u, ct=ct, interpret=True)
    want = rwkv6_scan_ref(r, k, v, w, u)
    tol = 1e-4 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


# ----------------------------------------------------------------------------
# rglru_scan
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,r", [(1, 64, 128), (2, 256, 64), (1, 128, 512)])
@pytest.mark.parametrize("ct", [32, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_matches_ref(b, t, r, ct, dtype):
    from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
    from repro.kernels.rglru_scan.ref import rglru_scan_ref

    rng = np.random.default_rng(8)
    a = jnp.asarray(rng.uniform(0.6, 0.999, (b, t, r)), dtype)
    g = jnp.asarray(rng.standard_normal((b, t, r)) * 0.2, dtype)
    ct_ = min(ct, t)
    br = min(512, r)
    got = rglru_scan_pallas(a, g, ct=ct_, br=br, interpret=True)
    want = rglru_scan_ref(a, g)
    tol = 1e-4 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol, rtol=tol)


def test_rglru_scan_long_decay_stability():
    """Long-horizon stability: with a close to 1 the doubling scan must not
    diverge from the serial reference."""
    from repro.kernels.rglru_scan.kernel import rglru_scan_pallas
    from repro.kernels.rglru_scan.ref import rglru_scan_ref

    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.uniform(0.995, 0.9999, (1, 512, 128)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((1, 512, 128)) * 0.05, jnp.float32)
    got = rglru_scan_pallas(a, g, ct=128, br=128, interpret=True)
    want = rglru_scan_ref(a, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3, rtol=1e-3)
