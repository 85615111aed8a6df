"""KBestIndex — the user-facing API (paper §4, Table 2).

    index = KBest(config)          # parameter preparation
    index.add(x)                   # index construction (build pipeline)
    d, i = index.search(q, k)      # query processing
    index.save(path) / KBest.load(path)

One facade, two index families (config.index_type):

"graph" build pipeline (DESIGN.md §3): kNN graph (brute / NN-descent) ->
edge selection -> F rounds of 2-hop refinement (A1) -> reverse-edge fill ->
graph reordering (A2) -> optional PQ/SQ training+encoding (A4) -> medoid
entry point. Search runs the batched traversal of core.search with early
termination (A3); quantized searches re-rank the top candidates with exact
distances (standard ADC + re-rank).

"ivf" build pipeline (DESIGN.md §4): k-means coarse quantizer -> residual
PQ training+encoding (A4, shared codebook knobs) -> padded dense inverted
lists. Search probes the nprobe nearest clusters, runs the fused ADC scan
with per-list partial top-L (kernels/ivf_scan), then re-ranks exactly via
the same gather path as the graph index.

Either family scales past one device through the sharded composition
(core/sharded.py: ShardedKBest, DESIGN.md §12): IndexConfig.n_shards > 1
builds one single-shard KBest per contiguous row range and merges
shard-local results; plain KBest always owns the whole corpus.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import logging
import time
import warnings
from pathlib import Path
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import build as build_mod
from repro.core import ivf as ivf_mod
from repro.core import persist
from repro.core import quantize as qz
from repro.core import reorder as reorder_mod
from repro.core import search as search_mod
from repro.core.distance import normalize, pairwise
from repro.core.refine import refine_graph
from repro.core.types import IndexConfig, SearchConfig

log = logging.getLogger(__name__)


class KBest:
    def __init__(self, config: IndexConfig):
        self.config = config
        self.db: Optional[jnp.ndarray] = None        # (n, d) f32 (normalized if cosine)
        self.graph: Optional[jnp.ndarray] = None     # (n, M) i32
        self.entry: int = 0
        self.order: Optional[jnp.ndarray] = None     # (n,) i32 new->old id map
        # quantization state
        self.pq: Optional[qz.PQState] = None
        self.pq_codes: Optional[jnp.ndarray] = None
        self.sq: Optional[qz.SQState] = None
        self.sq_codes: Optional[jnp.ndarray] = None
        self.bin: Optional[qz.BinState] = None
        self.bin_codes: Optional[jnp.ndarray] = None
        self.ivf: Optional[ivf_mod.IVFState] = None
        # wall seconds of each build stage of the last add() (set-up cost)
        self.build_s: dict = {}

    @contextlib.contextmanager
    def _stage(self, name: str):
        t0 = time.perf_counter()
        yield
        self.build_s[name] = time.perf_counter() - t0
        log.info("build %s: %.1f s", name, self.build_s[name])

    # ------------------------------------------------------------------ add
    def add(self, x: np.ndarray) -> "KBest":
        cfg = self.config
        assert cfg.n_shards == 1, \
            "config.n_shards > 1 is the sharded composition — build it " \
            "with repro.core.sharded.ShardedKBest, not KBest"
        b = cfg.build
        x = jnp.asarray(x, dtype=jnp.float32)
        assert x.ndim == 2 and x.shape[1] == cfg.dim, x.shape
        if cfg.metric == "cosine":
            x = normalize(x)
        metric = "ip" if cfg.metric == "cosine" else cfg.metric

        self.build_s = {}
        if cfg.index_type == "ivf":
            self.db = x
            with self._stage("ivf"):     # coarse k-means, PQ, list layout
                self.ivf = ivf_mod.build_ivf(x, cfg.ivf, cfg.quant)
                jax.block_until_ready(self.ivf.list_codes)
            return self

        with self._stage("knn"):
            knn_ids, knn_dists = build_mod.build_knn(
                x, b.knn_k, metric, builder=b.builder,
                rounds=b.nn_descent_rounds, sample=b.nn_descent_sample,
                seed=b.seed)
            jax.block_until_ready(knn_ids)

        with self._stage("refine"):
            entry = build_mod.medoid(x, metric)
            graph = refine_graph(
                x, knn_ids, knn_dists, M=b.M, rule=b.select_rule,
                metric=metric, alpha=b.alpha, ssg_angle_deg=b.ssg_angle_deg,
                iters=b.refine_iters, cand_cap=b.refine_cands, entry=entry,
                search_L=b.search_L, search_passes=b.search_passes)

        with self._stage("reorder"):
            if b.reorder != "none":
                weights = np.asarray(_edge_weights(x, graph, metric))
                if b.reorder == "mst":
                    order = reorder_mod.mst_reorder(np.asarray(graph),
                                                    weights, entry)
                elif b.reorder == "cm":
                    order = reorder_mod.cuthill_mckee(np.asarray(graph),
                                                      entry)
                else:
                    raise ValueError(b.reorder)
                db2, g2, new_of_old = reorder_mod.apply_order(
                    order, np.asarray(x), np.asarray(graph))
                x, graph = jnp.asarray(db2), jnp.asarray(g2)
                entry = int(new_of_old[entry])
                self.order = jnp.asarray(order, jnp.int32)

        self.db, self.graph, self.entry = x, jnp.asarray(graph), entry
        with self._stage("quant"):
            self._train_quant(x)
        return self

    def _train_quant(self, x: jnp.ndarray) -> None:
        """Train + encode the configured quantizer over the stored db (also
        used to attach a different quantizer to an already-built graph,
        e.g. the quantization ablation)."""
        q = self.config.quant
        if q.kind == "pq":
            self.pq = qz.pq_train(x, q)
            self.pq_codes = qz.pq_encode(self.pq.codebooks, x)
        elif q.kind == "pq4":
            self.pq = qz.pq_train(x, q)                 # (m, 16, ds) books
            self.pq_codes = qz.pq4_encode(self.pq.codebooks, x)  # packed
        elif q.kind == "sq":
            self.sq = qz.sq_train(x)
            self.sq_codes = qz.sq_encode(self.sq, x)
        elif q.kind == "bin":
            self.bin = qz.bin_train(x, q)
            self.bin_codes = qz.bin_encode(self.bin, x)   # (n, ceil(d/32)) u32
        jax.block_until_ready((self.pq_codes, self.sq_codes, self.bin_codes))

    # --------------------------------------------------------------- search
    def search(self, queries: np.ndarray, k: Optional[int] = None,
               search_cfg: Optional[SearchConfig] = None,
               with_stats: bool = False):
        """Top-k search. queries: (Q, d). Returns (dists, ids[, stats])."""
        assert self.db is not None, "call add() first"
        scfg = self._resolve_cfg(k, search_cfg)
        dists, ids, stats = self._search_impl(
            self._prep_queries(queries), scfg, valid_mask=None,
            with_stats=with_stats)
        if with_stats:
            return dists, ids, stats
        return dists, ids

    def search_padded(self, queries: np.ndarray, valid_mask: np.ndarray,
                      k: Optional[int] = None,
                      search_cfg: Optional[SearchConfig] = None,
                      with_stats: bool = False):
        """Shape-stable search over a padded batch (the serving entry point).

        queries: (B, d) where only rows with valid_mask[i] are real requests;
        padded rows come back as (+inf, -1) with zeroed stats, and valid
        rows are bit-identical to an unpadded `search` of the same queries,
        so a serving engine can pad every incoming batch to a fixed set of
        shape buckets and never re-trace. For the graph index padded rows
        start inactive in the lockstep traversal (free idle lanes,
        core.search's masking); the IVF scan is dense per-lane work with no
        loop to idle, so its padded lanes still compute (then get masked) —
        bucketing amortizes that to at most one bucket step of slack.
        """
        assert self.db is not None, "call add() first"
        scfg = self._resolve_cfg(k, search_cfg)
        vm = jnp.asarray(valid_mask, dtype=bool)
        dists, ids, stats = self._search_impl(
            self._prep_queries(queries), scfg, valid_mask=vm,
            with_stats=with_stats)
        dists, ids, stats = mask_padded_lanes(vm, dists, ids, stats)
        if with_stats:
            return dists, ids, stats
        return dists, ids

    def _resolve_cfg(self, k: Optional[int],
                     search_cfg: Optional[SearchConfig]) -> SearchConfig:
        return resolve_search_cfg(self.config, k, search_cfg)

    def _prep_queries(self, queries) -> jnp.ndarray:
        return prep_queries(self.config, queries)

    def _search_impl(self, q: jnp.ndarray, scfg: SearchConfig,
                     valid_mask: Optional[jnp.ndarray],
                     with_stats: bool):
        """Shared body of search/search_padded. Pure jax ops on concrete
        configs over the index's arrays, so the serving engine can jit it
        with the index itself as an argument (KBest is a pytree), one trace
        per (shape bucket, config) key. Returns (dists, ids, stats|None)."""
        cfg = self.config
        metric = "ip" if cfg.metric == "cosine" else cfg.metric
        n = self.db.shape[0]

        if cfg.index_type == "ivf":
            Q = q.shape[0]
            wide = _widen_bin(scfg) if cfg.quant.kind == "bin" else _widen(scfg)
            _, cand, probes = ivf_mod.search_ivf(
                self.ivf, q, scfg.nprobe, wide.L, metric,
                impl=scfg.dist_impl,
                lut_u8=cfg.quant.kind == "pq4" and cfg.quant.pq4_lut_u8)
            # default: re-rank the WHOLE candidate queue — the ADC scan is
            # far cheaper per candidate than graph traversal, so the exact
            # pass (L distances/query) is where IVF recall is won back.
            # bin instead reranks its explicit rescore_factor*k overfetch
            # (DESIGN.md §14), so recall is monotone in the factor.
            if cfg.quant.kind == "bin" and cfg.quant.rerank == 0:
                rr = scfg.rescore_factor * scfg.k
            else:
                rr = cfg.quant.rerank if cfg.quant.rerank > 0 else cand.shape[1]
            dists, ids, n_exact = self._rerank(q, cand, metric, scfg.k,
                                               rr, impl=scfg.dist_impl)
            if not with_stats:
                return dists, ids, None
            # scanned PQ codes + the exact re-rank distances, so the
            # benchmark's dists_per_query column is comparable across
            # index families
            stats = search_mod.SearchStats(
                n_hops=jnp.full((Q,), min(scfg.nprobe, self.ivf.nlist),
                                jnp.int32),
                n_dist=ivf_mod.scanned_counts(self.ivf, probes) + n_exact,
                early_terminated=jnp.zeros((Q,), bool),
                iters=jnp.int32(0))
            return dists, ids, stats

        entry_ids = self._entry_ids(scfg.n_entries, n)
        quant = cfg.quant.kind

        if quant in ("pq", "pq4"):
            with jax.named_scope("kbest.lut"):
                if quant == "pq":
                    tables = qz.pq_query_tables(self.pq.codebooks, q, metric)
                else:
                    tables = qz.pq4_query_tables(
                        self.pq.codebooks, q, metric,
                        lut_u8=cfg.quant.pq4_lut_u8)
            wide = _widen(scfg)
            dist_fn, data = self._get_dist_fn(quant, scfg.dist_impl)
            dists, ids, stats = search_mod.search(
                self.graph, tables, entry_ids, data, dist_fn=dist_fn,
                cfg=wide, n_total=n, valid_mask=valid_mask,
                expand_fn=self._get_expand_fn(quant, wide))
            dists, ids, n_exact = self._rerank(q, ids, metric, scfg.k,
                                               cfg.quant.rerank,
                                               impl=scfg.dist_impl)
        elif quant == "sq":
            wide = _widen(scfg)
            dist_fn, data = self._get_dist_fn("sq", scfg.dist_impl)
            dists, ids, stats = search_mod.search(
                self.graph, q, entry_ids, data, dist_fn=dist_fn, cfg=wide,
                n_total=n, valid_mask=valid_mask,
                expand_fn=self._get_expand_fn("sq", wide))
            dists, ids, n_exact = self._rerank(q, ids, metric, scfg.k,
                                               cfg.quant.rerank,
                                               impl=scfg.dist_impl)
        elif quant == "bin":
            # two-stage rescore (DESIGN.md §14): traverse under packed
            # Hamming with the queue widened to hold rescore_factor*k
            # candidates, then exact re-rank that overfetch
            with jax.named_scope("kbest.lut"):
                qcodes = qz.bin_query_codes(self.bin, q)
            wide = _widen_bin(scfg)
            dist_fn, data = self._get_dist_fn("bin", scfg.dist_impl)
            dists, ids, stats = search_mod.search(
                self.graph, qcodes, entry_ids, data, dist_fn=dist_fn,
                cfg=wide, n_total=n, valid_mask=valid_mask,
                expand_fn=self._get_expand_fn("bin", wide))
            rr = cfg.quant.rerank if cfg.quant.rerank > 0 \
                else scfg.rescore_factor * scfg.k
            dists, ids, n_exact = self._rerank(q, ids, metric, scfg.k, rr,
                                               impl=scfg.dist_impl)
        else:
            n_exact = None
            dist_fn, data = self._get_dist_fn("full", scfg.dist_impl)
            dists, ids, stats = search_mod.search(
                self.graph, q, entry_ids, data, dist_fn=dist_fn, cfg=scfg,
                n_total=n, valid_mask=valid_mask,
                expand_fn=self._get_expand_fn("full", scfg))

        if n_exact is not None:
            # the quantized first pass counts ADC lookups in n_dist; the
            # exact re-rank distances must be counted too, or the graph-PQ/SQ
            # rows undercount vs. the IVF path (which adds its re-rank) and
            # the cross-family dists_per_query comparison silently breaks
            stats = stats._replace(n_dist=stats.n_dist + n_exact)

        # translate internal (post-reorder) ids back to the user's add() ids
        if self.order is not None:
            ids = jnp.where(ids >= 0, self.order[jnp.maximum(ids, 0)], -1)

        return dists, ids, (stats if with_stats else None)

    def _entry_ids(self, n_entries: int, n: int) -> jnp.ndarray:
        """Medoid + evenly-spaced deterministic seeds: cheap cluster coverage
        for the lockstep search (the paper uses a random-or-fixed entry;
        multiple entries are the batched equivalent of per-thread random
        entries).

        The offsets are strictly increasing integers in [1, n-1] (linspace
        step >= 1 because e <= n), so all e ids are DISTINCT — the old
        strided form `entry + i*(n//e) mod n` could wrap duplicates onto the
        medoid for small n, which both wastes queue slots and hands
        duplicate ids to the bitmap seeding (see _bitmap_set's disjointness
        contract)."""
        e = max(1, min(n_entries, n))
        if e == 1:
            return jnp.array([self.entry % n], jnp.int32)
        off = np.round(np.linspace(1, n - 1, e - 1)).astype(np.int64)
        ids = (self.entry + np.concatenate([[0], off])) % n
        return jnp.asarray(ids, jnp.int32)

    def _metric(self) -> str:
        return "ip" if self.config.metric == "cosine" else self.config.metric

    def _get_dist_fn(self, kind: str, impl: str):
        """(dist_fn, data) for the traversal: a cached pure function of the
        static knobs, and the array operand it reads (the corpus or the
        codes) — passed to core.search as an argument, never captured."""
        if kind == "full":
            return search_mod.make_dist_fn(self._metric(), impl), self.db
        if kind == "pq":
            return qz.pq_make_dist_fn(self.pq.m, impl), self.pq_codes
        if kind == "pq4":
            return qz.pq4_make_dist_fn(self.pq.m, impl), self.pq_codes
        if kind == "sq":
            return (qz.sq_make_dist_fn(self._metric(), impl),
                    (self.sq_codes, self.sq))
        if kind == "bin":
            return qz.bin_make_dist_fn(impl), self.bin_codes
        raise ValueError(kind)

    def _get_expand_fn(self, kind: str, scfg: SearchConfig):
        """Fused gather+distance+sort backend for the beam traversal (the
        kernels' fused_expand family), or None for the dist_fn + host-sort
        path. Engaged only for kernel-impl beam searches: W=1 keeps the
        seed gather-then-merge kernel path (the bit-parity anchor), and a
        set batch_B means chunked dist_fn calls (core.search honors the
        knob by falling back). It reads the same data operand as
        _get_dist_fn(kind, ...)."""
        if scfg.dist_impl != "kernel" or scfg.beam_width <= 1 \
                or scfg.batch_B != 0:
            return None
        L, W = scfg.L, scfg.beam_width
        if kind == "full":
            return search_mod.make_expand_fn(self._metric(), L=L, n_beam=W)
        if kind in ("pq", "pq4"):
            return _pq_expand_fn(kind, self.pq.m, L, W)
        if kind == "sq":
            return _sq_expand_fn(self._metric(), L, W)
        if kind == "bin":
            return _bin_expand_fn(L, W)
        raise ValueError(kind)

    def _rerank(self, q, ids, metric, k, rerank, impl: str = "ref"):
        """Exact re-rank of the quantized/IVF search's top candidates, via
        the gather-then-distance path (Pallas gather_dist when impl is
        "kernel", jnp gather otherwise). Returns (dists (Q, k), ids (Q, k),
        n_exact (Q,) i32 — the exact distances actually computed, for the
        cross-family n_dist accounting)."""
        r = rerank if rerank > 0 else min(4 * k, ids.shape[1])
        r = min(max(r, k), ids.shape[1])   # never fewer candidates than k
        with jax.named_scope("kbest.rerank"):
            cand = ids[:, :r]
            if impl == "kernel":
                from repro.kernels import ops as kops
                d = kops.gather_dist(q, self.db, cand, metric=metric)
            else:
                vecs = self.db[jnp.maximum(cand, 0)]
                from repro.core.distance import batched_one_to_many
                d = batched_one_to_many(q, vecs, metric)
            d = jnp.where(cand >= 0, d, jnp.inf)
            neg, pos = jax.lax.top_k(-d, k)
            n_exact = jnp.sum(cand >= 0, axis=1).astype(jnp.int32)
            return -neg, jnp.take_along_axis(cand, pos, axis=1), n_exact

    # ------------------------------------------------------------ save/load
    def save(self, path: str, _label: str = "index") -> None:
        """Crash-safe save (DESIGN.md §17): the .npz is written atomically
        (tmp + fsync + rename), then the JSON sidecar — carrying a crc32
        per array — commits the save atomically after it. A crash at any
        point leaves either the previous save or a pair load() rejects;
        `_label` namespaces the kill points (sharded saves pass shard{s})."""
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        arrs = {"db": np.asarray(self.db)}
        if self.graph is not None:
            arrs["graph"] = np.asarray(self.graph)
        if self.ivf is not None:
            arrs["ivf_centroids"] = np.asarray(self.ivf.centroids)
            arrs["ivf_list_ids"] = np.asarray(self.ivf.list_ids)
            arrs["ivf_list_codes"] = np.asarray(self.ivf.list_codes)
            if self.ivf.pq is not None:
                arrs["ivf_codebooks"] = np.asarray(self.ivf.pq.codebooks)
            if self.ivf.bin is not None:
                arrs["ivf_bin_rot"] = np.asarray(self.ivf.bin.rot)
        if self.order is not None:
            arrs["order"] = np.asarray(self.order)
        if self.pq is not None:
            arrs["pq_codebooks"] = np.asarray(self.pq.codebooks)
            arrs["pq_codes"] = np.asarray(self.pq_codes)
        if self.sq is not None:
            arrs["sq_scale"] = np.asarray(self.sq.scale)
            arrs["sq_zero"] = np.asarray(self.sq.zero)
            arrs["sq_codes"] = np.asarray(self.sq_codes)
        if self.bin is not None:
            arrs["bin_rot"] = np.asarray(self.bin.rot)
            arrs["bin_codes"] = np.asarray(self.bin_codes)
        sums = persist.save_arrays(_npz_path(p), arrs, f"{_label}.arrays")
        meta = {"entry": self.entry,
                "config": _config_to_dict(self.config),
                "format": 2,
                "checksums": sums}
        # append ".json" to the FULL name: with_suffix(".json") used to map
        # both save("a.graph") and save("a.ivf") onto "a.json", so two
        # indexes sharing a stem clobbered each other's metadata
        persist.atomic_write(_meta_path(p), json.dumps(meta).encode(),
                             f"{_label}.meta")

    @classmethod
    def load(cls, path: str) -> "KBest":
        """Load with validation (DESIGN.md §17): any unreadable/torn sidecar
        or npz, and any array whose crc32 disagrees with the sidecar's,
        raises persist.IndexCorruptError — never a silently wrong index.
        Sidecars from pre-checksum saves (no "checksums" key) still load."""
        p = Path(path)
        mp = _meta_path(p)
        if not mp.exists() and p.with_suffix(".json").exists():
            mp = p.with_suffix(".json")     # pre-fix saves (load-compat)
        try:
            meta = json.loads(mp.read_text())
        except FileNotFoundError:
            raise
        except Exception as e:              # torn/garbage sidecar bytes
            raise persist.IndexCorruptError(
                f"unreadable index sidecar at {mp}: {e!r}") from e
        cfg = _config_from_dict(meta["config"])
        idx = cls(cfg)
        z = persist.load_arrays(_npz_path(p), meta.get("checksums"))
        idx.db = jnp.asarray(z["db"])
        if "graph" in z:
            idx.graph = jnp.asarray(z["graph"])
        if "ivf_centroids" in z:
            pq_state = None
            if "ivf_codebooks" in z:
                books = jnp.asarray(z["ivf_codebooks"])
                pq_state = qz.PQState(books, books.shape[0],
                                      books.shape[2])
            bin_state = qz.BinState(jnp.asarray(z["ivf_bin_rot"])) \
                if "ivf_bin_rot" in z else None
            idx.ivf = ivf_mod.IVFState(
                centroids=jnp.asarray(z["ivf_centroids"]),
                list_ids=jnp.asarray(z["ivf_list_ids"]),
                list_codes=jnp.asarray(z["ivf_list_codes"]),
                pq=pq_state,
                residual=cfg.ivf.residual,
                packed=cfg.quant.kind == "pq4",
                bin=bin_state)
        if "pq_codebooks" in z:
            books = jnp.asarray(z["pq_codebooks"])
            idx.pq = qz.PQState(books, books.shape[0], books.shape[2])
            idx.pq_codes = jnp.asarray(z["pq_codes"])
        if "sq_scale" in z:
            idx.sq = qz.SQState(jnp.asarray(z["sq_scale"]),
                                jnp.asarray(z["sq_zero"]))
            idx.sq_codes = jnp.asarray(z["sq_codes"])
        if "bin_rot" in z:
            idx.bin = qz.BinState(jnp.asarray(z["bin_rot"]))
            idx.bin_codes = jnp.asarray(z["bin_codes"])
        if "order" in z:
            idx.order = jnp.asarray(z["order"], jnp.int32)
        idx.entry = int(meta["entry"])
        return idx


# KBest is a pytree: its arrays (corpus, graph, id map, codes, quantizer and
# IVF states) are the leaves, config and entry point static metadata. The
# serving engine jits `index.search_padded` with the index as an ARGUMENT,
# so the arrays are held once on the device and every compiled program
# reads them as operands — never as embedded constants.
_KBEST_LEAVES = ("db", "graph", "order", "pq", "pq_codes", "sq", "sq_codes",
                 "bin", "bin_codes", "ivf")


def _kbest_flatten(idx: KBest):
    return (tuple(getattr(idx, a) for a in _KBEST_LEAVES),
            (idx.config, idx.entry))


def _kbest_unflatten(aux, leaves) -> KBest:
    idx = KBest.__new__(KBest)
    idx.config, idx.entry = aux
    for a, v in zip(_KBEST_LEAVES, leaves):
        setattr(idx, a, v)
    return idx


jax.tree_util.register_pytree_node(KBest, _kbest_flatten, _kbest_unflatten)


@functools.lru_cache(maxsize=None)
def _pq_expand_fn(kind: str, m: int, L: int, W: int):
    from repro.kernels import ops as kops
    K = 16 if kind == "pq4" else 256
    fe = kops.fused_expand_pq4 if kind == "pq4" else kops.fused_expand_pq

    def fn(codes, tables, nbr_ids):
        lut = tables.reshape(tables.shape[0], m, K)
        return fe(lut, codes, nbr_ids, L=L, n_beam=W)
    return fn


@functools.lru_cache(maxsize=None)
def _sq_expand_fn(metric: str, L: int, W: int):
    from repro.kernels import ops as kops

    def fn(data, queries, nbr_ids):
        codes, sq = data
        return kops.fused_expand_sq(
            queries, codes, sq.scale.reshape(1, -1), sq.zero.reshape(1, -1),
            nbr_ids, metric=metric, L=L, n_beam=W)
    return fn


@functools.lru_cache(maxsize=None)
def _bin_expand_fn(L: int, W: int):
    from repro.kernels import ops as kops

    def fn(codes, qcodes, nbr_ids):
        return kops.fused_expand_bin(qcodes, codes, nbr_ids, L=L, n_beam=W)
    return fn


def resolve_search_cfg(config: IndexConfig, k: Optional[int],
                       search_cfg: Optional[SearchConfig]) -> SearchConfig:
    """Fold a per-call k override into a concrete SearchConfig (shared by
    KBest, ShardedKBest and the serving engine's cache keying)."""
    scfg = search_cfg or config.search
    if k is not None and k != scfg.k:
        # k > L would trip SearchConfig's k <= L invariant; a caller
        # asking for more results than the queue holds means "widen the
        # queue to fit", not "crash".
        scfg = dataclasses.replace(scfg, k=k, L=max(scfg.L, k))
    return scfg


def prep_queries(config: IndexConfig, queries) -> jnp.ndarray:
    """Query-side add()-time preprocessing: f32 cast + cosine normalize."""
    q = jnp.asarray(queries, dtype=jnp.float32)
    if config.metric == "cosine":
        q = normalize(q)
    return q


def mask_padded_lanes(vm: jnp.ndarray, dists: jnp.ndarray, ids: jnp.ndarray,
                      stats):
    """The search_padded output contract, in one place for every facade
    (KBest and ShardedKBest must stay bit-compatible for the serving
    engine): invalid lanes come back as (+inf, -1) with zeroed stats.
    `stats` may be None (with_stats=False) and passes through."""
    dists = jnp.where(vm[:, None], dists, jnp.inf)
    ids = jnp.where(vm[:, None], ids, -1)
    if stats is not None:
        stats = search_mod.SearchStats(
            n_hops=jnp.where(vm, stats.n_hops, 0),
            n_dist=jnp.where(vm, stats.n_dist, 0),
            early_terminated=stats.early_terminated & vm,
            iters=stats.iters)
    return dists, ids, stats


def _widen(scfg: SearchConfig) -> SearchConfig:
    """Quantized first-pass searches return their whole (wide) queue so the
    exact re-rank has at least 4k candidates to work with."""
    want = max(scfg.L, 4 * scfg.k)
    return dataclasses.replace(scfg, L=want, k=want)


def _widen_bin(scfg: SearchConfig) -> SearchConfig:
    """bin first pass (DESIGN.md §14): the Hamming queue must hold the
    rescore_factor*k overfetch the exact rescore picks from. L stays at
    max(L, rescore_factor*k): while rescore_factor*k <= L the traversal is
    IDENTICAL across factors and a deeper factor just rescores a longer
    prefix of the same Hamming ranking, so recall is deterministically
    non-decreasing in rescore_factor; past L/k the queue itself widens."""
    want = max(scfg.L, scfg.rescore_factor * scfg.k)
    return dataclasses.replace(scfg, L=want, k=want)


def _edge_weights(db: jnp.ndarray, graph: jnp.ndarray, metric: str) -> jnp.ndarray:
    from repro.core.refine import _chunk_dists
    n = graph.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    out = []
    for s in range(0, n, 1024):
        e = min(s + 1024, n)
        out.append(_chunk_dists(db, rows[s:e], graph[s:e], metric))
    w = jnp.concatenate(out, axis=0)
    return jnp.where(jnp.isfinite(w), w, 0.0)


def _meta_path(p: Path) -> Path:
    """Metadata sidecar: the FULL array-file name + ".json" (so "a.graph"
    and "a.ivf" get distinct sidecars, unlike with_suffix)."""
    return p.with_name(p.name + ".json")


def _npz_path(p: Path) -> Path:
    """The array file np.savez would have produced for `p` (".npz" appended
    unless already present) — save and load must agree on it."""
    return p if p.suffix == ".npz" else Path(str(p) + ".npz")


def _known_fields(cls, d: dict) -> dict:
    """Drop keys a (possibly older) checkout's dataclass doesn't know, so
    metadata written by newer versions (e.g. pq4-era QuantConfig fields)
    still loads instead of raising TypeError. The drop is warned about,
    not silent: a forward-compat load that loses knobs (and their tuned
    values) should be observable in logs."""
    names = {f.name for f in dataclasses.fields(cls)}
    dropped = sorted(set(d) - names)
    if dropped:
        warnings.warn(
            f"index metadata has {cls.__name__} keys {dropped} unknown to "
            f"this version — loading without them (their saved values are "
            f"discarded)", stacklevel=2)
    return {k: v for k, v in d.items() if k in names}


def _config_to_dict(cfg: IndexConfig) -> dict:
    return dataclasses.asdict(cfg)


def _config_from_dict(d: dict) -> IndexConfig:
    from repro.core.types import BuildConfig, IVFConfig, QuantConfig
    return IndexConfig(
        dim=d["dim"], metric=d["metric"],
        index_type=d.get("index_type", "graph"),
        n_shards=d.get("n_shards", 1),
        build=BuildConfig(**_known_fields(BuildConfig, d["build"])),
        search=SearchConfig(**_known_fields(SearchConfig, d["search"])),
        quant=QuantConfig(**_known_fields(QuantConfig, d["quant"])),
        ivf=IVFConfig(**_known_fields(IVFConfig, d.get("ivf", {}))),
    )
