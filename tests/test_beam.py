"""Beam-parallel traversal (DESIGN.md §2): W=1 bit-parity against a port of
the seed (single-expansion, full-argsort) traversal, sorted-merge vs argsort
oracle equivalence, pick_top_w / dedupe properties, per-expansion ET
ordering, padded-lane and batch_B guarantees, and the serving cache key."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as qz
from repro.core import queue as qmod
from repro.core import search as search_mod
from repro.core.index import KBest, _widen, _widen_bin
from repro.core.types import (BuildConfig, IVFConfig, IndexConfig,
                              QuantConfig, SearchConfig)
from repro.data.vectors import make_dataset

# Every registered quant kind (derived from THE registry so a new kind
# lands in the beam parity sweep automatically — kbest-lint flags
# hand-enumerated kind lists).
QUANTS = tuple(dict.fromkeys(
    kw["kind"] for kw in qz.quant_variants().values()))


# --------------------------------------------------------------------------
# The seed traversal, ported verbatim (pre-beam semantics): one expansion
# per iteration, masked-argmin pick, full stable-argsort merge. This is the
# parity anchor — every W=1 search must reproduce it bit-for-bit.
# --------------------------------------------------------------------------
def _seed_merge_insert(q, new_dists, new_ids):
    L = q.dists.shape[0]
    in_queue = jnp.any(new_ids[:, None] == q.ids[None, :], axis=1)
    m = new_ids.shape[0]
    dup_prior = jnp.any(
        (new_ids[:, None] == new_ids[None, :])
        & (jnp.arange(m)[None, :] < jnp.arange(m)[:, None]), axis=1)
    bad = in_queue | dup_prior | (new_ids < 0)
    nd = jnp.where(bad, jnp.inf, new_dists)
    ni = jnp.where(bad, -1, new_ids)
    cat_d = jnp.concatenate([q.dists, nd])
    cat_i = jnp.concatenate([q.ids, ni])
    cat_v = jnp.concatenate([q.visited, jnp.zeros_like(ni, dtype=bool)])
    order = jnp.argsort(cat_d, stable=True)
    sd, si, sv = cat_d[order], cat_i[order], cat_v[order]
    out = qmod.Queue(dists=sd[:L], ids=si[:L], visited=sv[:L])
    best_new = jnp.min(nd)
    better = jnp.sum(cat_d < best_new) + jnp.sum(q.dists == best_new)
    best_rank = jnp.where(jnp.isinf(best_new), L,
                          jnp.minimum(better, L)).astype(jnp.int32)
    return out, best_rank


def _seed_pick(q):
    masked = jnp.where(q.visited, jnp.inf, q.dists)
    idx = jnp.argmin(masked).astype(jnp.int32)
    return idx, jnp.isfinite(masked[idx])


def _seed_search(graph, queries, entry_ids, dist_fn, cfg, n_total,
                 valid_mask=None):
    Q = queries.shape[0]
    L, k = cfg.L, cfg.k
    t_pos = jnp.int32(int(cfg.et_t_frac * L))
    W = (n_total + 31) // 32 if cfg.visited_mode == "bitmap" else 1

    e_ids = jnp.broadcast_to(entry_ids[None, :], (Q, entry_ids.shape[0]))
    e_dists = dist_fn(queries, e_ids)
    q0 = qmod.init_queue(L)
    q0 = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (Q,) + x.shape), q0)
    queue = jax.vmap(lambda qq, nd, ni: _seed_merge_insert(qq, nd, ni)[0])(
        qmod.Queue(q0[0], q0[1], q0[2]), e_dists, e_ids)
    bitmap = jnp.zeros((Q, W), dtype=jnp.uint32)
    if cfg.visited_mode == "bitmap":
        bitmap = jax.vmap(search_mod._bitmap_set)(bitmap, e_ids)
    active0 = (jnp.ones((Q,), bool) if valid_mask is None
               else valid_mask.astype(bool))
    n_seed = jnp.where(active0, jnp.sum(e_ids >= 0, axis=1), 0).astype(jnp.int32)
    carry = (queue.dists, queue.ids, queue.visited, bitmap,
             jnp.zeros((Q,), jnp.int32), jnp.zeros((Q,), bool), active0,
             jnp.zeros((Q,), jnp.int32), n_seed, jnp.int32(0))

    def cond(c):
        return jnp.any(c[6]) & (c[9] < cfg.hops_bound)

    def body(c):
        (cd, ci, cv, bitmap, et_ctr, fired, active, hops, ndist, it) = c
        queue = qmod.Queue(cd, ci, cv)
        idx, has = jax.vmap(_seed_pick)(queue)
        expand = active & has
        v = jnp.where(expand, queue.ids[jnp.arange(Q), idx], -1)
        vis = jax.vmap(lambda qq, i, do: qq.visited.at[i].set(
            jnp.where(do, True, qq.visited[i])))(queue, idx, expand)
        queue = queue._replace(visited=vis)
        nbrs = jnp.where(v[:, None] >= 0, graph[jnp.maximum(v, 0)], -1)
        m = nbrs.shape[1]
        dup = jnp.any((nbrs[:, :, None] == nbrs[:, None, :])
                      & (jnp.arange(m)[None, None, :]
                         < jnp.arange(m)[None, :, None]), axis=2)
        nbrs = jnp.where(dup | (nbrs < 0), -1, nbrs)
        if cfg.visited_mode == "bitmap":
            seen = jax.vmap(search_mod._bitmap_test)(bitmap, nbrs)
            nbrs = jnp.where(seen, -1, nbrs)
            bitmap = jax.vmap(search_mod._bitmap_set_raw)(bitmap, nbrs)
        n_new = jnp.sum(nbrs >= 0, axis=1).astype(jnp.int32)
        # semantically a no-op (merge_insert discards in-queue dups anyway,
        # and n_new is counted above, as the seed counted it): masking
        # before the distance call keeps this port's XLA program fused the
        # same way as the refactored loop, so dists compare BIT-exact
        # instead of to within codegen reassociation ulps
        in_q = jnp.any(nbrs[:, :, None] == queue.ids[:, None, :], axis=2)
        nbrs = jnp.where(in_q & (nbrs >= 0), -1, nbrs)
        nd = dist_fn(queries, nbrs)
        nd = jnp.where(nbrs >= 0, nd, jnp.inf)
        merged, best_rank = jax.vmap(_seed_merge_insert)(queue, nd, nbrs)
        queue = jax.tree.map(
            lambda new, old: jnp.where(
                expand.reshape((Q,) + (1,) * (new.ndim - 1)), new, old),
            merged, queue)
        beyond = best_rank > t_pos
        et_ctr = jnp.where(expand, jnp.where(beyond, et_ctr + 1, 0), et_ctr)
        fired = fired | (cfg.early_term & expand & (et_ctr >= cfg.et_patience))
        hops = hops + expand.astype(jnp.int32)
        ndist = ndist + jnp.where(expand, n_new, 0)
        active = active & has & ~fired & (hops < cfg.hops_bound)
        return (queue.dists, queue.ids, queue.visited, bitmap, et_ctr,
                fired, active, hops, ndist, it + 1)

    out = jax.lax.while_loop(cond, body, carry)
    final = qmod.Queue(out[0], out[1], out[2])
    dists_k, ids_k = jax.vmap(lambda q: qmod.topk(q, k))(final)
    return dists_k, ids_k, search_mod.SearchStats(out[7], out[8], out[5],
                                                  out[9])


# --------------------------------------------------------------------------
# Fixtures: one small dataset, one index per quant family
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def ds():
    return make_dataset("deep_like", n=800, n_queries=16, k=10)


def _index(ds, quant):
    cfg = IndexConfig(
        dim=ds.base.shape[1], metric=ds.metric,
        build=BuildConfig(M=10, knn_k=16, builder="brute", refine_iters=1,
                          refine_cands=24, reorder="mst"),
        quant=QuantConfig(kind=quant, pq_m=16, kmeans_iters=3),
        search=SearchConfig(L=24, k=8, early_term=True, et_patience=8))
    return KBest(cfg).add(ds.base)


@pytest.fixture(scope="module")
def indexes(ds):
    return {q: _index(ds, q) for q in QUANTS}


def _traversal_operands(idx, scfg, queries):
    """The (graph, queries-operand, entry_ids, (dist_fn, data), cfg) a
    KBest search hands to core.search for its quant family (white-box,
    mirrors _search_impl so the seed port can be driven identically)."""
    from repro.core import quantize as qz
    ds_q = jnp.asarray(queries)
    cfg = idx.config
    metric = "ip" if cfg.metric == "cosine" else cfg.metric
    quant = cfg.quant.kind
    if quant == "none":
        return idx.graph, ds_q, idx._entry_ids(scfg.n_entries,
                                               idx.db.shape[0]), \
            idx._get_dist_fn("full", "ref"), scfg
    if quant == "pq":
        op = qz.pq_query_tables(idx.pq.codebooks, ds_q, metric)
    elif quant == "pq4":
        op = qz.pq4_query_tables(idx.pq.codebooks, ds_q, metric)
    elif quant == "bin":
        op = qz.bin_query_codes(idx.bin, ds_q)
    else:
        op = ds_q
    widen = _widen_bin if quant == "bin" else _widen
    return idx.graph, op, idx._entry_ids(scfg.n_entries, idx.db.shape[0]), \
        idx._get_dist_fn(quant if quant != "none" else "full", "ref"), \
        widen(scfg)


@pytest.mark.parametrize("visited_mode", ["queue", "bitmap"])
@pytest.mark.parametrize("quant", QUANTS)
def test_w1_bit_parity_vs_seed(indexes, ds, quant, visited_mode):
    """beam_width=1 must reproduce the seed traversal bit-for-bit — dists,
    ids and every stat — for every quant family and visited mode."""
    idx = indexes[quant]
    scfg = dataclasses.replace(idx.config.search, visited_mode=visited_mode)
    graph, op, entries, (dist_fn, data), cfg = _traversal_operands(
        idx, scfg, ds.queries)
    n = idx.db.shape[0]
    d0, i0, st0 = _seed_search(graph, op, entries,
                               lambda q, i: dist_fn(data, q, i), cfg, n)
    d1, i1, st1 = search_mod.search(graph, op, entries, data,
                                    dist_fn=dist_fn, cfg=cfg, n_total=n)
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    for a, b in zip(st0, st1):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if quant == "none":
        assert np.array_equal(np.asarray(d0), np.asarray(d1))
    else:
        # the seed PORT is a separately-compiled XLA program, and XLA may
        # reassociate the fused ADC-sum reduction differently across
        # programs — the traversal itself is bit-faithful (ids, every stat,
        # and the full-precision dists above are exact; the true pre-PR
        # binary matched bit-for-bit at refactor time), so quantized dists
        # get a last-ulp budget, not a semantic tolerance
        f0, f1 = np.asarray(d0), np.asarray(d1)
        assert np.array_equal(np.isfinite(f0), np.isfinite(f1))
        m = np.isfinite(f0)
        np.testing.assert_array_max_ulp(f0[m], f1[m], maxulp=4)


@pytest.mark.parametrize("quant", QUANTS)
def test_w1_bit_parity_facade_with_and_without_stats(indexes, ds, quant):
    """KBest-level W=1 explicit beam config == default config, stats or
    not (the whole pipeline incl. re-rank is beam-invariant at W=1)."""
    idx = indexes[quant]
    s1 = dataclasses.replace(idx.config.search, beam_width=1)
    d0, i0 = idx.search(ds.queries, search_cfg=idx.config.search)
    d1, i1, st = idx.search(ds.queries, search_cfg=s1, with_stats=True)
    assert np.array_equal(np.asarray(d0), np.asarray(d1))
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    assert np.asarray(st.n_dist).min() > 0


@pytest.mark.parametrize("quant", ["pq", "pq4"])
def test_ivf_beam_invariant(ds, quant):
    """IVF has no traversal loop: any beam_width must give identical
    results and stats (the beam knob only shapes the graph family)."""
    cfg = IndexConfig(
        dim=ds.base.shape[1], metric=ds.metric, index_type="ivf",
        ivf=IVFConfig(nlist=12, kmeans_iters=3, list_pad=16),
        quant=QuantConfig(kind=quant, pq_m=16, kmeans_iters=3),
        search=SearchConfig(L=24, k=8, nprobe=4))
    idx = KBest(cfg).add(ds.base)
    d1, i1, s1 = idx.search(ds.queries, with_stats=True)
    s4 = dataclasses.replace(cfg.search, beam_width=4)
    d4, i4, st4 = idx.search(ds.queries, search_cfg=s4, with_stats=True)
    assert np.array_equal(np.asarray(d1), np.asarray(d4))
    assert np.array_equal(np.asarray(i1), np.asarray(i4))
    for a, b in zip(s1, st4):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# Beam semantics
# --------------------------------------------------------------------------
@pytest.mark.parametrize("quant", QUANTS)
def test_beam_cuts_iterations(indexes, ds, quant):
    """The tentpole claim at test scale: W=4 needs >= 1.5x fewer lockstep
    iterations than W=1 with recall intact (benchmarks/traverse.py sweeps
    the full curve)."""
    idx = indexes[quant]
    base = dataclasses.replace(idx.config.search, early_term=False)
    _, i1, s1 = idx.search(ds.queries, search_cfg=base, with_stats=True)
    s = dataclasses.replace(base, beam_width=4)
    _, i4, s4 = idx.search(ds.queries, search_cfg=s, with_stats=True)
    assert int(s1.iters) >= 1.5 * int(s4.iters), (int(s1.iters),
                                                  int(s4.iters))
    from repro.data.vectors import recall_at_k
    r1 = recall_at_k(np.asarray(i1), ds.gt_ids, 8)
    r4 = recall_at_k(np.asarray(i4), ds.gt_ids, 8)
    assert r4 >= r1 - 0.02, (r1, r4)


def test_beam_kernel_matches_ref(indexes, ds):
    """W>1 with dist_impl=kernel routes through fused_expand; results and
    distance counts must match the ref path exactly."""
    for quant in QUANTS:
        idx = indexes[quant]
        s = dataclasses.replace(idx.config.search, beam_width=3,
                                early_term=False)
        d0, i0, st0 = idx.search(ds.queries[:6], search_cfg=s,
                                 with_stats=True)
        sk = dataclasses.replace(s, dist_impl="kernel")
        d1, i1, st1 = idx.search(ds.queries[:6], search_cfg=sk,
                                 with_stats=True)
        assert np.array_equal(np.asarray(i0), np.asarray(i1)), quant
        assert np.array_equal(np.asarray(st0.n_dist),
                              np.asarray(st1.n_dist)), quant


def test_et_fires_no_later_under_beam(indexes, ds):
    """Eq. 3 in beam order: ET fires no later than W=1 on the lockstep
    clock. Per lane, a beam lane that fires does so within ceil(hops/W)+1
    iterations, which must not exceed the W=1 lane's firing iteration
    (== its hops); the batch critical path shrinks with it. (Expansion
    COUNTS may grow — the beam deliberately trades cheap extra expansions
    for fewer iterations, and a lane may even exhaust its queue before the
    patience threshold — so the clock, not the hop count, is the
    no-later guarantee.)"""
    idx = indexes["none"]
    base = dataclasses.replace(idx.config.search, early_term=True,
                               et_patience=6, L=24)
    _, _, s1 = idx.search(ds.queries, search_cfg=base, with_stats=True)
    fired1 = np.asarray(s1.early_terminated)
    assert fired1.any(), "workload must ET-fire"
    for W in (2, 4):
        s = dataclasses.replace(base, beam_width=W)
        _, _, sw = idx.search(ds.queries, search_cfg=s, with_stats=True)
        assert int(sw.iters) <= int(s1.iters)
        firedw = np.asarray(sw.early_terminated)
        assert firedw.any(), "beam must not disable ET"
        both = fired1 & firedw
        it1 = np.asarray(s1.n_hops)[both]            # 1 hop == 1 iteration
        itw = -(-np.asarray(sw.n_hops)[both] // W) + 1
        assert np.all(itw <= it1), (W, itw, it1)


def test_padded_lanes_free_under_beam(indexes, ds):
    """search_padded under W=4: invalid lanes add zero distance
    computations and valid lanes are bit-identical to the unpadded call."""
    idx = indexes["none"]
    s = dataclasses.replace(idx.config.search, beam_width=4)
    Qv = 10
    qp = np.zeros((16, ds.base.shape[1]), np.float32)
    qp[:Qv] = ds.queries[:Qv]
    vm = np.zeros((16,), bool)
    vm[:Qv] = True
    dp, ip_, stp = idx.search_padded(qp, vm, search_cfg=s, with_stats=True)
    d, i, st = idx.search(ds.queries[:Qv], search_cfg=s, with_stats=True)
    assert np.array_equal(np.asarray(dp)[:Qv], np.asarray(d))
    assert np.array_equal(np.asarray(ip_)[:Qv], np.asarray(i))
    assert np.all(np.asarray(stp.n_dist)[Qv:] == 0)
    assert np.all(np.asarray(stp.n_hops)[Qv:] == 0)
    assert np.array_equal(np.asarray(stp.n_dist)[:Qv], np.asarray(st.n_dist))


def test_batch_B_chunking_identical(indexes, ds):
    """SearchConfig.batch_B chunks the W·M distance calls without changing
    the search: identical candidate sets/order and identical work counts.
    (Distance BITS may drift a few ulp — XLA vectorizes the per-candidate
    reduction differently at different call shapes, exactly as real
    hardware tiles would — so dists compare at ulp, ids and stats exactly.)"""
    idx = indexes["none"]
    for W in (1, 4):
        s = dataclasses.replace(idx.config.search, beam_width=W)
        d0, i0, st0 = idx.search(ds.queries, search_cfg=s, with_stats=True)
        for B in (4, 7, 64):
            sb = dataclasses.replace(s, batch_B=B)
            d1, i1, st1 = idx.search(ds.queries, search_cfg=sb,
                                     with_stats=True)
            np.testing.assert_array_max_ulp(np.asarray(d0), np.asarray(d1),
                                            maxulp=4)
            assert np.array_equal(np.asarray(i0), np.asarray(i1)), (W, B)
            assert np.array_equal(np.asarray(st0.n_dist),
                                  np.asarray(st1.n_dist)), (W, B)
        # kernel impl honors batch_B by falling back to chunked dist calls
        sbk = dataclasses.replace(s, batch_B=8, dist_impl="kernel")
        d2, i2 = idx.search(ds.queries, search_cfg=sbk)
        assert np.array_equal(np.asarray(i0), np.asarray(i2)), W


def test_beam_width_validation():
    with pytest.raises(AssertionError):
        SearchConfig(L=8, k=4, beam_width=0)
    with pytest.raises(AssertionError):
        SearchConfig(L=8, k=4, beam_width=9)   # > L
    with pytest.raises(AssertionError):
        SearchConfig(L=8, k=4, batch_B=-1)


def test_sharded_beam_parity(ds):
    """1-shard ShardedKBest at W=4 stays bit-identical to plain KBest."""
    from repro.core.sharded import ShardedKBest
    cfg = IndexConfig(
        dim=ds.base.shape[1], metric=ds.metric,
        build=BuildConfig(M=10, knn_k=16, builder="brute", refine_iters=1,
                          refine_cands=24),
        search=SearchConfig(L=24, k=8, beam_width=4, early_term=False))
    a = KBest(cfg).add(ds.base)
    b = ShardedKBest(cfg, n_shards=1).add(ds.base)
    da, ia, sa = a.search(ds.queries, with_stats=True)
    db_, ib, sb = b.search(ds.queries, with_stats=True)
    assert np.array_equal(np.asarray(da), np.asarray(db_))
    assert np.array_equal(np.asarray(ia), np.asarray(ib))
    assert np.array_equal(np.asarray(sa.n_dist), np.asarray(sb.n_dist))


def test_engine_cache_keys_on_beam_width(indexes, ds):
    """A changed beam_width is a different XLA program: new trace; the same
    beam_width re-serves from cache without retracing."""
    from repro.serve.engine import SearchEngine
    eng = SearchEngine(indexes["none"], min_bucket=8, max_bucket=16)
    s2 = dataclasses.replace(indexes["none"].config.search, beam_width=2)
    s4 = dataclasses.replace(indexes["none"].config.search, beam_width=4)
    eng.search(ds.queries[:5], search_cfg=s2)
    t = eng.n_traces
    eng.search(ds.queries[:5], search_cfg=s2)
    assert eng.n_traces == t, "same beam_width must not retrace"
    eng.search(ds.queries[:5], search_cfg=s4)
    assert eng.n_traces == t + 1, "new beam_width must be a new program"


# --------------------------------------------------------------------------
# Queue primitives: each property is a plain checker, driven BOTH by a
# seeded sweep (always runs — this container has no hypothesis) and by
# hypothesis when available (CI installs it; same profile as test_property).
# --------------------------------------------------------------------------
try:
    from hypothesis import given, settings, strategies as st
    settings.register_profile("beam", max_examples=25, deadline=None)
    settings.load_profile("beam")
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _random_queue(r, L):
    n_filled = int(r.integers(0, L + 1))
    dists = np.full(L, np.inf, np.float32)
    ids = np.full(L, -1, np.int64)
    dists[:n_filled] = r.normal(size=n_filled).astype(np.float32)
    ids[:n_filled] = r.choice(10_000, size=n_filled, replace=False)
    vis = np.ones(L, bool)
    vis[:n_filled] = r.random(n_filled) < 0.5
    order = np.argsort(dists, kind="stable")
    return qmod.Queue(jnp.asarray(dists[order], jnp.float32),
                      jnp.asarray(ids[order], jnp.int32),
                      jnp.asarray(vis[order]))


def _check_sorted_merge_equals_argsort(L, M, seed):
    """merge_insert (sort-block + two-run merge) must equal the historical
    full-argsort implementation bit-for-bit — queue arrays, best_rank."""
    r = np.random.default_rng(seed)
    q = _random_queue(r, L)
    nd = jnp.asarray(r.normal(size=M).astype(np.float32))
    # id range overlapping the queue's so in-queue dups get exercised
    ni = jnp.asarray(r.integers(-1, 40, size=M).astype(np.int32))
    out, br, _ = qmod.merge_insert(q, nd, ni)
    exp, br_exp = _seed_merge_insert(q, nd, ni)
    assert np.array_equal(np.asarray(out.dists), np.asarray(exp.dists))
    assert np.array_equal(np.asarray(out.ids), np.asarray(exp.ids))
    assert np.array_equal(np.asarray(out.visited), np.asarray(exp.visited))
    assert int(br) == int(br_exp)
    # queue stays sorted ascending — the invariant pick_top_w exploits
    od = np.asarray(out.dists)
    assert np.all(od[:-1] <= od[1:])


def _check_merge_insert_beam_matches(L, W, seed):
    """The beam merge's queue equals merge_insert's for the same flat
    block, and rank[0] of a W=1 beam equals merge_insert's best_rank."""
    r = np.random.default_rng(seed)
    q = _random_queue(r, L)
    M = int(r.integers(1, 6)) * W
    nd = jnp.asarray(r.normal(size=M).astype(np.float32))
    ni = jnp.asarray(r.integers(-1, 40, size=M).astype(np.int32))
    out, br, _ = qmod.merge_insert(q, nd, ni)
    outw, ranks = qmod.merge_insert_beam(q, nd, ni, W)
    assert np.array_equal(np.asarray(out.dists), np.asarray(outw.dists))
    assert np.array_equal(np.asarray(out.ids), np.asarray(outw.ids))
    if W == 1:
        assert int(ranks[0]) == int(br)
    # every per-expansion rank is sane and >= the global best rank
    assert np.all((np.asarray(ranks) >= int(br)) & (np.asarray(ranks) <= L))


def _check_dedupe_ids(M, seed):
    """dedupe_ids keeps exactly the FIRST occurrence of every valid id."""
    r = np.random.default_rng(seed)
    ids = r.integers(-1, 8, size=M).astype(np.int32)
    out = np.asarray(qmod.dedupe_ids(jnp.asarray(ids)))
    seen = set()
    for j in range(M):
        if ids[j] >= 0 and ids[j] not in seen:
            assert out[j] == ids[j]
            seen.add(ids[j])
        else:
            assert out[j] == -1


def _check_pick_top_w(L, w, seed):
    """pick_top_w returns the first w unvisited finite slots in queue
    order, and pick_unvisited (w=1) matches the seed's masked argmin."""
    r = np.random.default_rng(seed)
    q = _random_queue(r, L)
    idxs, has = qmod.pick_top_w(q, w)
    dists = np.asarray(q.dists)
    vis = np.asarray(q.visited)
    expected = [i for i in range(L)
                if not vis[i] and np.isfinite(dists[i])][:w]
    assert int(np.asarray(has).sum()) == len(expected)
    assert np.asarray(idxs)[:len(expected)].tolist() == expected
    # seed equivalence at w=1
    idx1, has1 = qmod.pick_unvisited(q)
    sidx, shas = _seed_pick(q)
    assert bool(has1) == bool(shas)
    if bool(shas):
        assert int(idx1) == int(sidx)


# ---- seeded sweeps (always run) ----
def test_sorted_merge_equals_argsort_oracle_seeded():
    r = np.random.default_rng(0)
    for _ in range(20):
        _check_sorted_merge_equals_argsort(int(r.integers(2, 24)),
                                           int(r.integers(1, 16)),
                                           int(r.integers(0, 2 ** 30)))


def test_merge_insert_beam_matches_merge_insert_seeded():
    r = np.random.default_rng(1)
    for _ in range(12):
        _check_merge_insert_beam_matches(int(r.integers(2, 24)),
                                         int(r.integers(1, 5)),
                                         int(r.integers(0, 2 ** 30)))


def test_dedupe_ids_seeded():
    r = np.random.default_rng(2)
    for _ in range(20):
        _check_dedupe_ids(int(r.integers(1, 21)), int(r.integers(0, 2 ** 30)))


def test_pick_top_w_seeded():
    r = np.random.default_rng(3)
    for _ in range(20):
        _check_pick_top_w(int(r.integers(2, 24)), int(r.integers(1, 7)),
                          int(r.integers(0, 2 ** 30)))


# ---- hypothesis drivers (CI) ----
if HAVE_HYPOTHESIS:
    @given(st.integers(2, 24), st.integers(1, 16), st.integers(0, 2 ** 30))
    def test_sorted_merge_equals_argsort_oracle(L, M, seed):
        _check_sorted_merge_equals_argsort(L, M, seed)

    @given(st.integers(2, 24), st.integers(1, 4), st.integers(0, 2 ** 30))
    def test_merge_insert_beam_matches_merge_insert(L, W, seed):
        _check_merge_insert_beam_matches(L, W, seed)

    @given(st.integers(1, 20), st.integers(0, 2 ** 30))
    def test_dedupe_ids_property(M, seed):
        _check_dedupe_ids(M, seed)

    @given(st.integers(2, 24), st.integers(1, 6), st.integers(0, 2 ** 30))
    def test_pick_top_w_first_unvisited(L, w, seed):
        _check_pick_top_w(L, w, seed)


@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("visited_mode", ["queue", "bitmap"])
@pytest.mark.parametrize("quant", ["none", "pq"])
def test_lane_results_independent_of_batch(indexes, ds, quant, visited_mode,
                                           W):
    """Lockstep lanes never interact: searching a batch whole or in two
    uneven parts gives every lane the same dists, ids and per-lane stats
    (the build's chunked searches and the engine's padded buckets rely on
    it)."""
    idx = indexes[quant]
    scfg = dataclasses.replace(idx.config.search, visited_mode=visited_mode,
                               beam_width=W, early_term=True)
    graph, op, entries, (dist_fn, data), cfg = _traversal_operands(
        idx, scfg, ds.queries)
    nq = op.shape[0]
    vm = jnp.asarray(np.arange(nq) % 5 != 3)            # some padded lanes

    def run(sl):
        d, i, st = search_mod.search(
            graph, op[sl], entries, data, dist_fn=dist_fn, cfg=cfg,
            n_total=idx.db.shape[0], valid_mask=vm[sl])
        return [d, i, st.n_hops, st.n_dist, st.early_terminated]

    whole = run(slice(None))
    cut = nq // 3
    parts = [np.concatenate([np.asarray(a), np.asarray(b)])
             for a, b in zip(run(slice(0, cut)), run(slice(cut, None)))]
    for a, b in zip(whole, parts):
        assert np.array_equal(np.asarray(a), b)
