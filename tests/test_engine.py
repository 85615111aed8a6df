"""Batch-serving engine (repro.serve) + search-path correctness regressions.

Covers the serving tentpole (shape-bucketed compile cache, padded-lane
bit-identity, coalescing scheduler with true served-count accounting) and
the search-path bugfixes that shipped with and after it:
  1. duplicate entry seeds corrupting the visited bitmap (scatter-add carry)
  2. partial-batch recall denominators (served-count accounting)
  3. graph-quantized n_dist excluding exact re-rank distances (cross-family
     comparability with the IVF path)
  4. `search(q, k=K)` with K > SearchConfig.L asserting instead of widening
  5. n_dist excluding the entry-seed distances computed at traversal init
     (undercounted by n_entries across every graph family)
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import search as search_mod
from repro.core.index import KBest
from repro.core.types import (BuildConfig, IVFConfig, IndexConfig,
                              QuantConfig, SearchConfig)
from repro.data.vectors import make_dataset, recall_at_k
from repro.serve import Request, SearchEngine, bucket_for, serve_loop


@pytest.fixture(scope="module")
def tiny_ds():
    # small enough that L=256 >= n: the queue holds every discovered node,
    # so "queue" mode is exact and any bitmap corruption shows up as a diff
    return make_dataset("deep_like", n=200, n_queries=16, k=10)


@pytest.fixture(scope="module")
def tiny_index(tiny_ds):
    cfg = IndexConfig(
        dim=tiny_ds.base.shape[1], metric=tiny_ds.metric,
        build=BuildConfig(M=8, knn_k=16, builder="brute", refine_iters=0,
                          reorder="none"),
        search=SearchConfig(L=64, k=10, early_term=False))
    return KBest(cfg).add(tiny_ds.base)


# ---------------------------------------------------------------- tentpole
def test_compile_cache_one_trace_per_bucket(deep_ds, deep_index):
    eng = SearchEngine(deep_index, min_bucket=8, max_bucket=32)
    for q in (5, 6, 7):                       # three sizes, one bucket (8)
        eng.search(deep_ds.queries[:q])
    assert eng.n_traces == 1, "same bucket must compile exactly once"
    assert eng.cache_misses == 1 and eng.cache_hits == 2
    eng.search(deep_ds.queries[:12])          # bucket 16 -> one more trace
    assert eng.n_traces == 2
    eng.search(deep_ds.queries[:13])          # bucket 16 again -> cached
    assert eng.n_traces == 2
    # a different k is a different SearchConfig => its own cache entry
    eng.search(deep_ds.queries[:5], k=5)
    assert eng.n_traces == 3


def test_padded_results_bit_identical(deep_ds, deep_index):
    eng = SearchEngine(deep_index, min_bucket=16, max_bucket=32)
    for q in (3, 11, 16):
        d_pad, i_pad = eng.search(deep_ds.queries[:q])
        d_ref, i_ref = deep_index.search(deep_ds.queries[:q])
        assert d_pad.shape == (q, 10)
        np.testing.assert_array_equal(i_pad, np.asarray(i_ref))
        np.testing.assert_array_equal(d_pad, np.asarray(d_ref))


def test_search_padded_invalid_lanes_cost_nothing(deep_ds, deep_index):
    qp = np.zeros((8, deep_ds.queries.shape[1]), np.float32)
    qp[:3] = deep_ds.queries[:3]
    mask = np.zeros(8, bool)
    mask[:3] = True
    d, i, st = deep_index.search_padded(qp, mask, with_stats=True)
    assert np.all(np.isinf(np.asarray(d)[3:]))
    assert np.all(np.asarray(i)[3:] == -1)
    assert np.all(np.asarray(st.n_dist)[3:] == 0)
    assert np.all(np.asarray(st.n_hops)[3:] == 0)
    assert np.all(np.asarray(st.n_dist)[:3] > 0)


def test_search_padded_ivf_lanes_masked(tiny_ds):
    ivf = KBest(IndexConfig(
        dim=tiny_ds.base.shape[1], metric=tiny_ds.metric, index_type="ivf",
        ivf=IVFConfig(kmeans_iters=4, list_pad=32),
        quant=QuantConfig(kind="pq", pq_m=16, kmeans_iters=4),
        search=SearchConfig(L=64, k=10, nprobe=4))).add(tiny_ds.base)
    qp = np.zeros((8, tiny_ds.queries.shape[1]), np.float32)
    qp[:5] = tiny_ds.queries[:5]
    mask = np.zeros(8, bool)
    mask[:5] = True
    d, i, st = ivf.search_padded(qp, mask, with_stats=True)
    assert np.all(np.isinf(np.asarray(d)[5:]))
    assert np.all(np.asarray(i)[5:] == -1)
    assert np.all(np.asarray(st.n_dist)[5:] == 0)
    d_ref, i_ref = ivf.search(tiny_ds.queries[:5])
    np.testing.assert_array_equal(np.asarray(i)[:5], np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(d)[:5], np.asarray(d_ref))


def test_warmup_precompiles(deep_ds, deep_index):
    eng = SearchEngine(deep_index, min_bucket=8, max_bucket=32)
    fresh = eng.warmup()                       # whole ladder: 8, 16, 32
    assert fresh == 3
    before = eng.n_traces
    for q in (2, 9, 17, 30):
        eng.search(deep_ds.queries[:q])
    assert eng.n_traces == before, "warmed buckets must never re-trace"


def test_oversized_batch_splits(deep_ds, deep_index):
    eng = SearchEngine(deep_index, min_bucket=8, max_bucket=16)
    d, i = eng.search(deep_ds.queries[:40])    # 16 + 16 + 8
    assert d.shape == (40, 10)
    d_ref, i_ref = deep_index.search(deep_ds.queries[:40])
    np.testing.assert_array_equal(i, np.asarray(i_ref))


def test_serve_loop_mixed_families_and_k(tiny_ds, tiny_index):
    ivf = KBest(IndexConfig(
        dim=tiny_ds.base.shape[1], metric=tiny_ds.metric, index_type="ivf",
        ivf=IVFConfig(kmeans_iters=4, list_pad=32),
        quant=QuantConfig(kind="pq", pq_m=16, kmeans_iters=4),
        search=SearchConfig(L=64, k=10, nprobe=4))).add(tiny_ds.base)
    engines = {"graph": SearchEngine(tiny_index, max_bucket=16, name="graph"),
               "ivf": SearchEngine(ivf, max_bucket=16, name="ivf")}
    reqs = [
        Request(queries=tiny_ds.queries[:5], engine="graph", k=3,
                gt_ids=tiny_ds.gt_ids[:5]),
        Request(queries=tiny_ds.queries[5:12], engine="ivf", k=10,
                gt_ids=tiny_ds.gt_ids[5:12]),
        Request(queries=tiny_ds.queries[12:16], engine="graph", k=3,
                gt_ids=tiny_ds.gt_ids[12:16]),
    ]
    rep = serve_loop(engines, reqs)
    assert rep.n_served == 16
    assert [r.ids.shape for r in rep.results] == [(5, 3), (7, 10), (4, 3)]
    by_id = {r.request_id: r for r in rep.results}
    assert set(by_id) == {0, 1, 2}
    assert rep.recall_at_k is not None and rep.recall_at_k > 0.5


def test_serve_loop_coalesces_consecutive_compatible(tiny_ds, tiny_index):
    eng = SearchEngine(tiny_index, min_bucket=8, max_bucket=32)
    reqs = [Request(queries=tiny_ds.queries[s:s + 4]) for s in (0, 4, 8)]
    rep = serve_loop(eng, reqs)
    assert rep.n_dispatches == 1, "3x4 compatible rows pack into one bucket"
    assert rep.n_requests == 3 and rep.n_served == 12
    # sliced-back results match per-request direct searches
    for r, s in zip(rep.results, (0, 4, 8)):
        d_ref, i_ref = tiny_index.search(tiny_ds.queries[s:s + 4])
        np.testing.assert_array_equal(r.ids, np.asarray(i_ref))


def test_bucket_for():
    assert bucket_for(1) == 8               # min_bucket clamp
    assert bucket_for(8) == 8
    assert bucket_for(9) == 16
    assert bucket_for(100) == 128
    assert bucket_for(1000, max_bucket=256) == 256


# ------------------------------------------------- bugfix 1: bitmap seeds
def test_bitmap_set_tolerates_duplicates_and_resets():
    bm = jnp.zeros((2,), jnp.uint32)
    out = search_mod._bitmap_set(bm, jnp.array([5, 5, 5], jnp.int32))
    assert int(out[0]) == 1 << 5, "duplicate ids must set the bit ONCE"
    # setting an already-set bit again must not carry either
    out2 = search_mod._bitmap_set(out, jnp.array([5, 6], jnp.int32))
    assert int(out2[0]) == (1 << 5) | (1 << 6)
    # invalid ids are ignored
    out3 = search_mod._bitmap_set(out2, jnp.array([-1, -1], jnp.int32))
    assert int(out3[0]) == (1 << 5) | (1 << 6) and int(out3[1]) == 0


def test_bitmap_parity_with_colliding_entry_seeds(tiny_ds, tiny_index):
    # deliberately colliding seeds: the medoid duplicated plus adjacent
    # pairs — pre-fix, the scatter-add carry marks UNVISITED neighbors as
    # visited, silently dropping them from the candidate set
    e = tiny_index.entry
    n = tiny_index.db.shape[0]
    seeds = jnp.array([e, e, (e + 7) % n, (e + 7) % n, e], jnp.int32)
    dist_fn, data = tiny_index._get_dist_fn("full", "ref")
    out = {}
    for mode in ("queue", "bitmap"):
        cfg = SearchConfig(L=256, k=10, early_term=False, visited_mode=mode)
        d, ids, _ = search_mod.search(
            tiny_index.graph, jnp.asarray(tiny_ds.queries), seeds, data,
            dist_fn=dist_fn, cfg=cfg, n_total=n)
        out[mode] = np.asarray(ids)
    np.testing.assert_array_equal(out["bitmap"], out["queue"])


def test_entry_ids_distinct():
    idx = KBest.__new__(KBest)               # only _entry_ids is exercised
    for entry in (0, 3, 97):
        idx.entry = entry
        for n in (2, 3, 5, 8, 9, 100, 4001):
            for e in (1, 2, 8, 16):
                ids = np.asarray(idx._entry_ids(e, n))
                assert ids[0] == entry % n
                assert len(set(ids.tolist())) == len(ids), (n, e, ids)
                assert ids.min() >= 0 and ids.max() < n


# --------------------------------------- bugfix 2: partial-batch accounting
def test_partial_batch_true_served_count(tiny_ds, tiny_index):
    eng = SearchEngine(tiny_index, min_bucket=8, max_bucket=8)
    # 14 queries in batches of 8 => 8 + 6 (partial): the old denominator
    # ceil-batches * batch_size would claim 16 served
    reqs = [Request(queries=tiny_ds.queries[s:min(s + 8, 14)],
                    gt_ids=tiny_ds.gt_ids[s:min(s + 8, 14)])
            for s in range(0, 14, 8)]
    rep = serve_loop(eng, reqs, coalesce=False)
    assert rep.n_served == 14
    assert rep.engine_stats[eng.name].n_queries == 14
    assert len(range(0, 14, 8)) * 8 == 16     # the buggy denominator
    # recall over the true count must match a straight evaluation
    d, i = tiny_index.search(tiny_ds.queries[:14])
    direct = recall_at_k(np.asarray(i), tiny_ds.gt_ids[:14], 10)
    assert rep.recall_at_k == pytest.approx(direct, abs=1e-9)


# ------------------------------------ bugfix 3: n_dist includes the re-rank
def test_graph_quantized_ndist_counts_rerank(deep_ds):
    cfg = IndexConfig(
        dim=deep_ds.base.shape[1], metric=deep_ds.metric,
        build=BuildConfig(M=24, knn_k=32, builder="brute", refine_iters=0,
                          reorder="none"),
        search=SearchConfig(L=64, k=10, early_term=False),
        quant=QuantConfig(kind="sq", rerank=20))
    idx = KBest(cfg).add(deep_ds.base)
    q = deep_ds.queries[:8]
    _, _, st20 = idx.search(q, with_stats=True)
    # deepening the exact re-rank by 20 must add exactly 20 distances/query
    # (all candidates valid on this corpus) — pre-fix both reported the
    # same n_dist because the re-rank was invisible to the stats
    idx.config = dataclasses.replace(
        cfg, quant=dataclasses.replace(cfg.quant, rerank=40))
    _, _, st40 = idx.search(q, with_stats=True)
    np.testing.assert_array_equal(
        np.asarray(st40.n_dist) - np.asarray(st20.n_dist),
        np.full(8, 20, np.int32))


def test_ivf_and_graph_ndist_same_units(tiny_ds, tiny_index):
    # both families must count approx-pass evaluations + exact re-ranks;
    # IVF n_dist >= its re-rank depth and graph-SQ n_dist >= its re-rank
    ivf = KBest(IndexConfig(
        dim=tiny_ds.base.shape[1], metric=tiny_ds.metric, index_type="ivf",
        ivf=IVFConfig(kmeans_iters=4, list_pad=32),
        quant=QuantConfig(kind="pq", pq_m=16, kmeans_iters=4, rerank=12),
        search=SearchConfig(L=64, k=10, nprobe=4))).add(tiny_ds.base)
    _, _, st = ivf.search(tiny_ds.queries[:8], with_stats=True)
    assert np.all(np.asarray(st.n_dist) >= 12)


# --------------------------------- bugfix 5: n_dist counts the entry seeds
def test_ndist_counts_entry_seeds_all_graph_families(tiny_ds):
    """Seed-inclusive n_dist accounting, pinned exactly.

    On a corpus small enough that L >= n, bitmap mode computes every
    reachable node's distance exactly once, so n_dist must equal the
    BFS-reachable count FROM THE SEED SET (seeds included — the init
    dist_fn call computes them) plus, for quantized families, the exact
    re-rank depth. Pre-fix, ndist started at 0 after the seed distances
    were already computed, undercounting every family by n_entries —
    equivalently, n_dist changed when n_entries changed, which this pins
    against across full/PQ/PQ4/SQ."""
    import collections

    base, n, k = tiny_ds.base, tiny_ds.base.shape[0], 10
    quants = {
        "full": QuantConfig(),
        "pq": QuantConfig(kind="pq", pq_m=16, kmeans_iters=4),
        "pq4": QuantConfig(kind="pq4", pq_m=16, kmeans_iters=4),
        "sq": QuantConfig(kind="sq"),
    }
    for name, q in quants.items():
        cfg = IndexConfig(
            dim=base.shape[1], metric=tiny_ds.metric,
            build=BuildConfig(M=8, knn_k=16, builder="brute",
                              refine_iters=0, reorder="none"),
            search=SearchConfig(L=256, k=k, early_term=False,
                                visited_mode="bitmap"),
            quant=q)
        idx = KBest(cfg).add(base)
        graph = np.asarray(idx.graph)
        per_entries = []
        for e in (1, 8):
            seeds = np.asarray(idx._entry_ids(e, n)).tolist()
            seen, dq = set(seeds), collections.deque(seeds)
            while dq:
                for v in graph[dq.popleft()]:
                    if v >= 0 and int(v) not in seen:
                        seen.add(int(v))
                        dq.append(int(v))
            s = dataclasses.replace(cfg.search, n_entries=e)
            _, _, st = idx.search(tiny_ds.queries[:6], search_cfg=s,
                                  with_stats=True)
            expect = len(seen) + (0 if name == "full" else 4 * k)
            np.testing.assert_array_equal(
                np.asarray(st.n_dist), np.full(6, expect, np.int32),
                err_msg=f"family={name} n_entries={e}")
            per_entries.append(np.asarray(st.n_dist))
        # exhaustive traversal covers the same reachable set regardless of
        # seed count — only seed-EXCLUDING accounting makes these differ
        np.testing.assert_array_equal(per_entries[0], per_entries[1])


def test_ivf_ndist_identity_scanned_plus_rerank(tiny_ds):
    """IVF has no entry seeds; its n_dist stays the exact identity
    scanned codes + valid re-ranked candidates (cross-family units)."""
    import jax.numpy as jnp
    from repro.core import ivf as ivf_mod

    cfg = IndexConfig(
        dim=tiny_ds.base.shape[1], metric=tiny_ds.metric, index_type="ivf",
        ivf=IVFConfig(kmeans_iters=4, list_pad=32),
        quant=QuantConfig(kind="pq", pq_m=16, kmeans_iters=4),
        search=SearchConfig(L=64, k=10, nprobe=4))
    idx = KBest(cfg).add(tiny_ds.base)
    q = idx._prep_queries(tiny_ds.queries[:6])
    metric = "ip" if cfg.metric == "cosine" else cfg.metric
    _, _, st = idx.search(tiny_ds.queries[:6], with_stats=True)
    wide_L = max(64, 4 * 10)                     # _widen's queue width
    _, cand, probes = ivf_mod.search_ivf(idx.ivf, q, 4, wide_L, metric)
    expect = (np.asarray(ivf_mod.scanned_counts(idx.ivf, probes))
              + np.asarray((cand >= 0).sum(axis=1)))
    np.testing.assert_array_equal(np.asarray(st.n_dist),
                                  expect.astype(np.int32))


# ------------------------------------------------- bugfix 4: k > L widening
def test_k_greater_than_L_widens(tiny_ds, tiny_index):
    assert tiny_index.config.search.L == 64
    d, i = tiny_index.search(tiny_ds.queries[:4], k=128)   # k > L: no crash
    assert d.shape == (4, 128) and i.shape == (4, 128)
    dd = np.asarray(d)
    assert np.all(np.diff(dd, axis=1) >= 0), "results stay sorted"
    # the widened queue really returns k results on a reachable corpus
    assert np.all(np.asarray(i)[:, :64] >= 0)


def test_k_greater_than_L_through_engine(tiny_ds, tiny_index):
    eng = SearchEngine(tiny_index, min_bucket=8, max_bucket=8)
    d, i = eng.search(tiny_ds.queries[:4], k=96)
    assert d.shape == (4, 96)
    d_ref, i_ref = tiny_index.search(tiny_ds.queries[:4], k=96)
    np.testing.assert_array_equal(i, np.asarray(i_ref))
