"""Index refinement (paper §3.2, A1): edge selection + 2-hop iteration.

Pipeline: kNN graph -> edge-selection rule -> F rounds of {expand candidates
to 2-hop neighborhood, re-select}. Three selection rules, all expressed as
one greedy sweep with a rule-specific acceptance predicate:

  alpha (Vamana/NSG): accept c iff for every already-selected s,
        d(node, c) < alpha * d(s, c)            (hnsw == alpha with a=1.0)
  ssg:  accept c iff for every selected s, the angle at `node` between
        (c - node) and (s - node) is >= theta.

The greedy sweep is vectorized over all nodes simultaneously (node-lanes);
per candidate step it needs d(c, s) for the <=M selected vectors, i.e. an
(n, M, d) batched distance — again the paper's Q-to-B workload. All heavy
steps are chunked over nodes to bound the gather footprint.

Used by: `core/index.py: KBest.add` (graph family) — `refine_graph` is the
pipeline stage between `core/build.py`'s kNN construction and
`core/reorder.py`'s relabeling, driven by the `BuildConfig` knobs
(select_rule / alpha / ssg_angle_deg / refine_iters / refine_cands /
search_passes); the per-dataset values live in `configs/kbest.py`.
"""
from __future__ import annotations

import functools
import logging
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.build import _merge_topk

log = logging.getLogger(__name__)

# Rows per device call in every chunked refinement step. Each row's result
# is independent of its chunk, so this only trades memory for dispatches:
# the build-time searches are launch-bound at small chunks (512-row chunks
# spent 635 s in the search pass of a 1M-row build on one TPU v5e).
NODE_CHUNK = 4096


@functools.partial(jax.jit, static_argnames=("M", "rule", "metric"))
def select_edges(db: jnp.ndarray, rows: jnp.ndarray, cand_ids: jnp.ndarray,
                 cand_dists: jnp.ndarray, *, M: int, rule: str, metric: str,
                 alpha: float = 1.2, cos_theta: float = 0.5) -> jnp.ndarray:
    """Greedy rule-based pruning of sorted candidates to <=M edges per node.

    rows: (nc,) node ids this chunk refines; cand_ids/cand_dists: (nc, C)
    sorted ascending by distance, -1/inf padded. Returns (nc, M) int32.
    """
    nc, C = cand_ids.shape
    node_vecs = db[rows]                            # (nc, d)

    def step(j, state):
        sel_ids, sel_cnt, sel_vecs = state          # (nc, M), (nc,), (nc, M, d)
        cid = cand_ids[:, j]
        cdist = cand_dists[:, j]
        cvec = db[jnp.maximum(cid, 0)]              # (nc, d)

        slot_mask = jnp.arange(M)[None, :] < sel_cnt[:, None]
        if rule == "ssg":
            u = cvec - node_vecs
            v = sel_vecs - node_vecs[:, None, :]
            num = jnp.einsum("nmd,nd->nm", v, u)
            den = jnp.linalg.norm(v, axis=-1) * jnp.linalg.norm(u, axis=-1)[:, None]
            cos = num / jnp.maximum(den, 1e-12)
            violate = jnp.any(slot_mask & (cos > cos_theta), axis=1)
        else:  # alpha / hnsw; diversity geometry in L2 of the raw vectors
            diff = sel_vecs - cvec[:, None, :]
            d_sc = jnp.sum(diff * diff, axis=-1)
            d_pc = jnp.sum((cvec - node_vecs) ** 2, axis=-1)
            violate = jnp.any(
                slot_mask & (d_pc[:, None] >= (alpha * alpha) * d_sc), axis=1)

        accept = (cid >= 0) & jnp.isfinite(cdist) & ~violate & (sel_cnt < M)
        pos = jnp.minimum(sel_cnt, M - 1)
        hit = accept[:, None] & (jnp.arange(M)[None, :] == pos[:, None])
        sel_ids = jnp.where(hit, cid[:, None], sel_ids)
        sel_vecs = jnp.where(hit[:, :, None], cvec[:, None, :], sel_vecs)
        sel_cnt = sel_cnt + accept.astype(jnp.int32)
        return sel_ids, sel_cnt, sel_vecs

    init = (jnp.full((nc, M), -1, jnp.int32), jnp.zeros((nc,), jnp.int32),
            jnp.zeros((nc, M, db.shape[1]), db.dtype))
    sel_ids, sel_cnt, _ = jax.lax.fori_loop(0, C, step, init)
    # guarantee out-degree >= 1 (keep the closest candidate)
    empty = sel_cnt == 0
    sel_ids = sel_ids.at[:, 0].set(jnp.where(empty, cand_ids[:, 0], sel_ids[:, 0]))
    return sel_ids


def _chunk_dists(db: jnp.ndarray, rows: jnp.ndarray, ids: jnp.ndarray,
                 metric: str) -> jnp.ndarray:
    """d(db[rows[i]], db[ids[i, j]]) with -1 masked to inf. (nc, C)."""
    vecs = db[jnp.maximum(ids, 0)]
    base = db[rows]
    if metric == "l2":
        diff = vecs - base[:, None, :]
        out = jnp.sum(diff * diff, axis=-1)
    else:
        out = -jnp.einsum("ncd,nd->nc", vecs, base)
    return jnp.where(ids >= 0, out, jnp.inf)


@functools.partial(jax.jit, static_argnames=("C", "metric"))
def expand_two_hop(db: jnp.ndarray, graph: jnp.ndarray, rows: jnp.ndarray,
                   *, C: int, metric: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Candidates = 1-hop ∪ 2-hop neighbors of `rows`, deduped, top-C."""
    g1 = graph[rows]                                            # (nc, M)
    n, M = graph.shape
    g2 = jnp.where(g1[:, :, None] >= 0,
                   graph[jnp.maximum(g1, 0)], -1).reshape(g1.shape[0], M * M)
    cands = jnp.concatenate([g1, g2], axis=1)
    cands = jnp.where(cands == rows[:, None], -1, cands)
    dists = _chunk_dists(db, rows, cands, metric)
    nc = cands.shape[0]
    ids, dists = _merge_topk(cands, dists, jnp.full((nc, 1), -1, jnp.int32),
                             jnp.full((nc, 1), jnp.inf, jnp.float32), C)
    return ids, dists


def _reverse_proposals(graph: np.ndarray, cap: int) -> np.ndarray:
    """(n, cap) int32 of reverse-edge proposers per node (-1 padded)."""
    n, M = graph.shape
    out = np.full((n, cap), -1, dtype=np.int32)
    us = np.repeat(np.arange(n, dtype=np.int32), M)
    vs = graph.reshape(-1)
    ok = vs >= 0
    us, vs = us[ok], vs[ok]
    # group the edges by target, keeping edge order within a group: the
    # first `cap` proposers of v in (u, slot) order fill out[v]
    order = np.argsort(vs, kind="stable")
    us, vs = us[order], vs[order]
    rank = np.arange(vs.size) - np.searchsorted(vs, vs, side="left")
    keep = rank < cap
    out[vs[keep], rank[keep]] = us[keep]
    return out


def reverse_merge_select(db: jnp.ndarray, graph: np.ndarray, *, M: int,
                         rule: str, metric: str, alpha: float,
                         cos_theta: float, node_chunk: int = NODE_CHUNK,
                         rev_cap: int = None) -> np.ndarray:
    """Vamana-style reverse-edge pass WITH re-pruning.

    Every edge u->v proposes v->u; instead of dropping proposals when v is
    full (which starves hub nodes and leaves the graph fragmented), each
    node re-runs the edge-selection rule over {current edges} ∪ {proposals}.
    The diversity rule then trades near-duplicate intra-cluster edges for
    long-range connectivity — this is what stitches cluster islands into one
    searchable component.
    """
    n = graph.shape[0]
    rev_cap = rev_cap or 2 * M
    rev = _reverse_proposals(np.asarray(graph), rev_cap)
    g = jnp.asarray(graph)
    rv = jnp.asarray(rev)
    rows_all = jnp.arange(n, dtype=jnp.int32)
    outs = []
    for s in range(0, n, node_chunk):
        e = min(s + node_chunk, n)
        rows = rows_all[s:e]
        pool = jnp.concatenate([g[s:e], rv[s:e]], axis=1)
        pool = jnp.where(pool == rows[:, None], -1, pool)
        d = _chunk_dists(db, rows, pool, metric)
        ci, cd = _merge_topk(pool, d, jnp.full((e - s, 1), -1, jnp.int32),
                             jnp.full((e - s, 1), jnp.inf, jnp.float32),
                             pool.shape[1])
        outs.append(select_edges(db, rows, ci, cd, M=M, rule=rule,
                                 metric=metric, alpha=alpha,
                                 cos_theta=cos_theta))
    return np.asarray(jnp.concatenate(outs, axis=0))


def add_reverse_edges(graph: np.ndarray, max_degree: int) -> np.ndarray:
    """Fill -1 slots with reverse edges (host-side build step).

    Standard Vamana/NSG post-pass: every edge u->v proposes v->u; accepted
    while v has spare capacity. Keeps the graph closer to strongly-connected.
    """
    graph = np.asarray(graph)
    n, M = graph.shape
    assert max_degree <= M
    # plain Python lists: this loop is sequential by nature (a row may grow
    # while later rows are scanned), and list indexing is several times
    # faster than numpy scalar indexing at 1M rows
    g = graph.tolist()
    deg = (graph >= 0).sum(axis=1).tolist()
    existing = [set(x for x in r if x >= 0) for r in g]
    for u in range(n):
        for v in g[u]:
            if v < 0:
                continue
            if deg[v] < max_degree and u not in existing[v]:
                g[v][deg[v]] = u
                existing[v].add(u)
                deg[v] += 1
    return np.asarray(g, dtype=graph.dtype)


def search_candidates(db: jnp.ndarray, graph: jnp.ndarray, rows: jnp.ndarray,
                      entry: int, metric: str, search_L: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Search-based candidate generation (paper: "refine each vertex's
    neighborhood based on search results from the initial kNN graph").

    Runs the batched KBest traversal with db[rows] as queries over the
    current graph from the global entry point, returning the final candidate
    queue per node. This is what creates cross-cluster backbone edges
    (NSG/Vamana-style) that pure kNN + 2-hop expansion cannot: remote nodes
    acquire edges toward the entry's basin, and the reverse-edge pass then
    makes the link bidirectional.
    """
    from repro.core import search as search_mod
    from repro.core.types import SearchConfig

    cfg = SearchConfig(L=search_L, k=search_L, early_term=False,
                       visited_mode="queue", n_entries=1)
    dist_fn = search_mod.make_dist_fn(metric, "ref")
    dists, ids, _ = search_mod.search(
        graph, db[rows], jnp.array([entry], jnp.int32), db, dist_fn=dist_fn,
        cfg=cfg, n_total=db.shape[0])
    ids = jnp.where(ids == rows[:, None], -1, ids)   # drop self
    dists = jnp.where(ids >= 0, dists, jnp.inf)
    return ids.astype(jnp.int32), dists


def refine_graph(db: jnp.ndarray, knn_ids: jnp.ndarray, knn_dists: jnp.ndarray,
                 *, M: int, rule: str, metric: str, alpha: float,
                 ssg_angle_deg: float, iters: int, cand_cap: int,
                 entry: int = 0, search_L: int = 48, search_passes: int = 1,
                 node_chunk: int = NODE_CHUNK) -> np.ndarray:
    """Full A1 pipeline. Returns the final (n, M) int32 padded CSR graph."""
    cos_theta = float(np.cos(np.deg2rad(ssg_angle_deg)))
    n = db.shape[0]
    all_rows = jnp.arange(n, dtype=jnp.int32)
    t0 = [time.perf_counter()]

    def done(step, out):
        """Log one sub-step's wall time (build set-up telemetry)."""
        jax.block_until_ready(out)
        t = time.perf_counter()
        log.info("refine %s: %.1f s", step, t - t0[0])
        t0[0] = t
        return out

    def _select(cids, cdists):
        outs = []
        for s in range(0, n, node_chunk):
            e = min(s + node_chunk, n)
            outs.append(select_edges(
                db, all_rows[s:e], cids[s:e], cdists[s:e], M=M, rule=rule,
                metric=metric, alpha=alpha, cos_theta=cos_theta))
        return jnp.concatenate(outs, axis=0)

    if rule == "none":
        graph = knn_ids[:, :M]
    else:
        graph = done("select", _select(knn_ids, knn_dists))

    # --- phase 2 of A1: search-based neighborhood refinement ----------------
    # The graph searched during refinement is {current graph} ∪ {R random
    # long edges per node}. Vamana gets global percolation by *initializing*
    # with a random R-regular graph; augmenting the search graph with random
    # edges gives the same property (build-time searches can cross cluster
    # islands, so far-but-useful candidates enter the pools) without
    # polluting the final edge set.
    sel_rule = rule if rule != "none" else "alpha"
    rng = np.random.default_rng(0)
    n_rand = max(4, M // 4)
    for _ in range(0 if rule == "none" else search_passes):
        rand_edges = jnp.asarray(
            rng.integers(0, n, size=(n, n_rand), dtype=np.int32))
        search_graph = jnp.concatenate([jnp.asarray(graph), rand_edges], axis=1)
        cid_chunks, cd_chunks = [], []
        for s in range(0, n, node_chunk):
            e = min(s + node_chunk, n)
            sc_ids, sc_d = search_candidates(
                db, search_graph, all_rows[s:e], entry, metric, search_L)
            # pool: search results ∪ current edges ∪ original kNN
            pool_ids = jnp.concatenate(
                [sc_ids, graph[s:e], knn_ids[s:e]], axis=1)
            pool_d = jnp.concatenate(
                [sc_d, _chunk_dists(db, all_rows[s:e], graph[s:e], metric),
                 knn_dists[s:e]], axis=1)
            ci, cd = _merge_topk(
                pool_ids, pool_d,
                jnp.full((e - s, 1), -1, jnp.int32),
                jnp.full((e - s, 1), jnp.inf, jnp.float32), cand_cap)
            cid_chunks.append(ci)
            cd_chunks.append(cd)
        graph = done("search pass", _select(jnp.concatenate(cid_chunks, 0),
                                            jnp.concatenate(cd_chunks, 0)))
        # Vamana-style reverse pass with re-pruning: stitches islands.
        graph = done("reverse merge", jnp.asarray(reverse_merge_select(
            db, np.asarray(graph), M=M, rule=sel_rule, metric=metric,
            alpha=alpha, cos_theta=cos_theta, node_chunk=node_chunk)))

    # --- phase 3 of A1: iterative 2-hop expansion ---------------------------
    for _ in range(iters):
        cid_chunks, cd_chunks = [], []
        for s in range(0, n, node_chunk):
            e = min(s + node_chunk, n)
            ci, cd = expand_two_hop(db, graph, all_rows[s:e], C=cand_cap,
                                    metric=metric)
            cid_chunks.append(ci)
            cd_chunks.append(cd)
        cids = jnp.concatenate(cid_chunks, axis=0)
        cdists = jnp.concatenate(cd_chunks, axis=0)
        graph = done("2-hop", _select(cids, cdists))

    graph = done("reverse edges", add_reverse_edges(np.asarray(graph), M))
    return done("connectivity", connectivity_repair(db, graph, entry, metric))


def _flood(g: np.ndarray, starts: np.ndarray, seen: np.ndarray) -> np.ndarray:
    """Mark everything reachable from `starts` in `seen` (in place) by a
    level-synchronous BFS over the padded graph; returns `seen`."""
    frontier = np.unique(np.asarray(starts, np.int64))
    frontier = frontier[~seen[frontier]]
    seen[frontier] = True
    while frontier.size:
        nb = g[frontier].ravel()
        nb = np.unique(nb[nb >= 0])
        frontier = nb[~seen[nb]]
        seen[frontier] = True
    return seen


def _nearest_seen(db: jnp.ndarray, rows: np.ndarray, seen: np.ndarray,
                  metric: str, block: int = 256
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """For each of `rows`, its nearest row among `seen` under the metric:
    (ids, dists). Device-side, `block` rows against the corpus at a time."""
    from repro.core.distance import pairwise
    seen_d = jnp.asarray(seen)
    ids, dists = [], []
    for s in range(0, len(rows), block):
        d = pairwise(db[jnp.asarray(rows[s:s + block])], db, metric)
        d = jnp.where(seen_d[None, :], d, jnp.inf)
        ids.append(np.asarray(jnp.argmin(d, axis=1)))
        dists.append(np.asarray(jnp.min(d, axis=1)))
    return np.concatenate(ids), np.concatenate(dists)


def connectivity_repair(db: jnp.ndarray, graph: np.ndarray, entry: int,
                        metric: str) -> np.ndarray:
    """NSG-style spanning pass: guarantee every node is reachable from the
    entry by linking each unreachable region from its nearest reachable
    node (replacing that node's worst edge if it has no spare slot).

    Every unreachable node's nearest reachable node is found in one
    blocked device pass; the nodes are then linked in ascending order of
    that distance, each link followed by a flood fill, so a node that a
    previous link already made reachable costs nothing. The work is linear
    in the corpus however many regions are cut off (the former one-region-
    per-iteration loop rescanned every reachable x unreachable pair per
    region, which at 1M rows did not finish in 25 minutes)."""
    g = np.asarray(graph).copy()
    n, M = g.shape
    seen = _flood(g, np.array([entry]), np.zeros(n, dtype=bool))
    un = np.nonzero(~seen)[0]
    if un.size == 0:
        return g
    near, dist = _nearest_seen(db, un, seen, metric)
    for i in np.argsort(dist, kind="stable"):
        u = un[i]
        if seen[u]:
            continue
        r = near[i]
        spare = np.nonzero(g[r] < 0)[0]
        slot = spare[0] if len(spare) else M - 1   # replace worst (last) edge
        g[r, slot] = u
        _flood(g, np.array([u]), seen)
    log.info("connectivity repair: %d of %d nodes were unreachable", un.size,
             n)
    return g
