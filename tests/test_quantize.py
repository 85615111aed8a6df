"""Vector quantization (A4): PQ / SQ correctness and quantized search."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as qz
from repro.core.types import QuantConfig, SearchConfig
from repro.data.vectors import recall_at_k


def test_kmeans_reduces_distortion():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(500, 8)).astype(np.float32))
    c1 = qz.kmeans(x, 16, iters=1)
    c10 = qz.kmeans(x, 16, iters=10)

    def distortion(c):
        d = (jnp.sum(x * x, 1)[:, None] + jnp.sum(c * c, 1)[None]
             - 2 * x @ c.T)
        return float(jnp.mean(jnp.min(d, axis=1)))
    assert distortion(c10) <= distortion(c1) + 1e-5


def test_pq_adc_equals_reconstructed_distance():
    """ADC(q, code) must equal ||q - reconstruct(code)||^2 exactly."""
    rng = np.random.default_rng(1)
    m, ds = 4, 8
    x = jnp.asarray(rng.normal(size=(300, m * ds)).astype(np.float32))
    st = qz.pq_train(x, QuantConfig(kind="pq", pq_m=m, kmeans_iters=5))
    codes = qz.pq_encode(st.codebooks, x)
    q = x[:3]
    lut = qz.pq_query_tables(st.codebooks, q, "l2").reshape(3, m, 256)
    ids = jnp.arange(10, dtype=jnp.int32)[None].repeat(3, 0)
    from repro.kernels.ref import pq_adc_ref
    adc = np.asarray(pq_adc_ref(lut, codes, ids))
    # reconstruct and compare
    books = np.asarray(st.codebooks)
    cc = np.asarray(codes[:10]).astype(int)
    recon = np.stack([
        np.concatenate([books[j, cc[i, j]] for j in range(m)])
        for i in range(10)])
    for qi in range(3):
        exact = ((np.asarray(q[qi])[None] - recon) ** 2).sum(1)
        np.testing.assert_allclose(adc[qi], exact, rtol=1e-4, atol=1e-4)


def test_pq_search_recall_with_rerank(deep_ds):
    from repro.core.index import KBest
    from repro.core.types import BuildConfig, IndexConfig
    cfg = IndexConfig(
        dim=deep_ds.base.shape[1], metric=deep_ds.metric,
        build=BuildConfig(M=24, knn_k=32, builder="brute", refine_iters=1,
                          refine_cands=64),
        search=SearchConfig(L=64, k=10, early_term=False),
        quant=QuantConfig(kind="pq", pq_m=8, kmeans_iters=5))
    idx = KBest(cfg).add(deep_ds.base)
    d, i = idx.search(deep_ds.queries, k=10)
    assert recall_at_k(np.asarray(i), deep_ds.gt_ids, 10) >= 0.8


def test_sq_search_recall(deep_ds):
    from repro.core.index import KBest
    from repro.core.types import BuildConfig, IndexConfig
    cfg = IndexConfig(
        dim=deep_ds.base.shape[1], metric=deep_ds.metric,
        build=BuildConfig(M=24, knn_k=32, builder="brute", refine_iters=1,
                          refine_cands=64),
        search=SearchConfig(L=64, k=10, early_term=False),
        quant=QuantConfig(kind="sq"))
    idx = KBest(cfg).add(deep_ds.base)
    d, i = idx.search(deep_ds.queries, k=10)
    assert recall_at_k(np.asarray(i), deep_ds.gt_ids, 10) >= 0.9


def test_sq_kernel_impl_routes_through_kernel_and_matches(monkeypatch):
    """sq_make_dist_fn used to IGNORE impl — dist_impl="kernel" SQ runs
    were the ref path mislabeled under a ("sq", "kernel") cache key. The
    kernel impl must now actually call the fused sq_gather_dist kernel and
    agree with the ref path."""
    import repro.kernels.ops as kops

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(200, 32)).astype(np.float32))
    st = qz.sq_train(x)
    codes = qz.sq_encode(st, x)
    q = x[:4]
    ids = jnp.asarray(rng.integers(-1, 200, size=(4, 9)).astype(np.int32))

    called = {}
    real = kops.sq_gather_dist

    def spy(*a, **kw):
        called["kernel"] = True
        return real(*a, **kw)

    monkeypatch.setattr(kops, "sq_gather_dist", spy)
    for metric in ("l2", "ip"):
        called.clear()
        out_r = qz.sq_make_dist_fn(metric, impl="ref")((codes, st), q, ids)
        assert "kernel" not in called
        out_k = qz.sq_make_dist_fn(metric, impl="kernel")((codes, st), q,
                                                           ids)
        assert called.get("kernel"), "impl='kernel' must hit the kernel path"
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   rtol=3e-5, atol=3e-4)


def test_pq_ip_tables():
    """IP LUTs: sum over subspaces == -<q, reconstruction>."""
    rng = np.random.default_rng(2)
    m, ds = 4, 4
    x = jnp.asarray(rng.normal(size=(300, m * ds)).astype(np.float32))
    st = qz.pq_train(x, QuantConfig(kind="pq", pq_m=m, kmeans_iters=5))
    codes = qz.pq_encode(st.codebooks, x)
    lut = qz.pq_query_tables(st.codebooks, x[:2], "ip").reshape(2, m, 256)
    from repro.kernels.ref import pq_adc_ref
    ids = jnp.arange(5, dtype=jnp.int32)[None].repeat(2, 0)
    adc = np.asarray(pq_adc_ref(lut, codes, ids))
    books = np.asarray(st.codebooks)
    cc = np.asarray(codes[:5]).astype(int)
    recon = np.stack([np.concatenate([books[j, cc[i, j]] for j in range(m)])
                      for i in range(5)])
    for qi in range(2):
        exact = -(recon @ np.asarray(x[qi]))
        np.testing.assert_allclose(adc[qi], exact, rtol=1e-4, atol=1e-4)
