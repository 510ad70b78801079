"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes (assignment requirement)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bitmap_filter as bf
from repro.kernels import ivf_scan as ivf
from repro.kernels import ops, pq_adc, ref, topk_merge as tkm


@pytest.mark.parametrize("nq,n,d", [(8, 512, 16), (8, 1024, 128),
                                    (16, 512, 64), (32, 2048, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ivf_scan_kernel_matches_ref(nq, n, d, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(nq, d)), dtype)
    v = jnp.asarray(rng.normal(size=(n, d)), dtype)
    out = ivf.ivf_scan(q, v, interpret=True)
    want = ref.ivf_scan_ref(q, v)
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol * d)


@pytest.mark.parametrize("n,m", [(512, 4), (1024, 8), (512, 16)])
def test_pq_adc_kernel_matches_ref(n, m):
    rng = np.random.default_rng(1)
    codes = jnp.asarray(rng.integers(0, 256, (n, m)), jnp.int32)
    lut = jnp.asarray(rng.normal(size=(m, 256)) ** 2, jnp.float32)
    out = pq_adc.pq_adc(codes, lut, interpret=True)
    want = ref.pq_adc_ref(codes, lut)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("n,c", [(1024, 1), (2048, 3), (1024, 6)])
def test_bitmap_filter_kernel_matches_ref(n, c):
    rng = np.random.default_rng(2)
    cols = jnp.asarray(rng.uniform(0, 1, (n, c)), jnp.float32)
    bounds = np.sort(rng.uniform(0, 1, (c, 2)), axis=1)
    out = bf.bitmap_filter(cols, jnp.asarray(bounds, jnp.float32),
                           interpret=True)
    want = ref.bitmap_filter_ref(cols, jnp.asarray(bounds, jnp.float32))
    np.testing.assert_array_equal(np.asarray(out).astype(bool),
                                  np.asarray(want))


@pytest.mark.parametrize("nq,s,kk,k", [(4, 3, 8, 5), (8, 5, 16, 10),
                                       (2, 2, 4, 8)])
def test_batched_topk_merge_matches_ref(nq, s, kk, k):
    rng = np.random.default_rng(9)
    d = np.sort(rng.normal(size=(nq, s, kk)) ** 2, axis=2)
    ids = rng.integers(0, 10**6, (nq, s, kk))
    # duplicate scores across shards so the (score, id) tie-break matters,
    # and pad one shard tail with the sentinel slot encoding
    d[:, 1, :] = d[:, 0, :]
    sent = np.iinfo(np.int32).max
    d[:, -1, kk // 2:] = np.inf
    ids[:, -1, kk // 2:] = sent
    d = jnp.asarray(d, jnp.float32)
    ids = jnp.asarray(ids, jnp.int32)
    od, oi = tkm.batched_topk_merge(d, ids, k, interpret=True)
    wd, wi = ref.batched_topk_merge_ref(d, ids, k)
    np.testing.assert_allclose(np.asarray(od), np.asarray(wd), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(oi), np.asarray(wi))


def test_ops_backends_agree():
    """ops.py with use_pallas=True must equal the ref backend, including
    padding edge cases (non-multiple shapes)."""
    rng = np.random.default_rng(4)
    q = rng.normal(size=(3, 24)).astype(np.float32)      # nq not /8, d odd-ish
    x = rng.normal(size=(700, 24)).astype(np.float32)    # n not /512
    a = ops.l2_distances(q, x, use_pallas=True)
    b = ops.l2_distances(q, x, use_pallas=False)
    np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)

    cols = rng.uniform(0, 1, (1000, 2)).astype(np.float32)
    bounds = np.sort(rng.uniform(0, 1, (2, 2)), axis=1).astype(np.float32)
    np.testing.assert_array_equal(
        ops.range_bitmap(cols, bounds, use_pallas=True),
        ops.range_bitmap(cols, bounds, use_pallas=False))

    codes = rng.integers(0, 256, (700, 8)).astype(np.uint8)
    books = rng.normal(size=(8, 256, 3)).astype(np.float32)
    qv = rng.normal(size=24).astype(np.float32)
    np.testing.assert_allclose(
        ops.pq_adc_distances(qv, codes, books, use_pallas=True),
        ops.pq_adc_distances(qv, codes, books, use_pallas=False),
        rtol=1e-4, atol=1e-4)
