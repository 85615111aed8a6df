"""SearchEngine — shape-bucketed, compile-cached serving facade (DESIGN.md §11).

The paper's deployment scenario is a service absorbing tens of millions of
queries per day. For a JIT-compiled search stack the dominant avoidable cost
under variable-size traffic is re-tracing: every new batch shape (and every
`SearchConfig` tweak) is a fresh XLA compile, orders of magnitude slower
than the search itself. The engine removes that cost structurally:

  1. **Shape buckets.** Incoming batches are padded up to the next
     power-of-two bucket (clamped to [min_bucket, max_bucket]); batches
     larger than max_bucket are split. A handful of buckets covers any
     traffic mix, so the set of compiled programs is small and bounded.
  2. **Padded lanes are (nearly) free.** Padding rides
     `KBest.search_padded`: graph-index padded rows enter the lockstep
     traversal inactive (core.search's `active` mask — the same mechanism
     that idles early-terminated queries), so they cost no distance
     computations; IVF padded lanes still run the dense ADC scan (no loop
     to idle) but are bounded by one bucket step of slack. Valid rows are
     bit-identical to an unpadded `index.search` either way.
  3. **Compile cache.** Compiled callables are cached on
     (bucket, SearchConfig, index_type, quant_kind, n_shards) — the last
     component is the mesh shape of a sharded index (`ShardedKBest` serves
     through the same facade; its P unrolled shard searches + merge are one
     XLA program per bucket). `n_traces` counts
     actual traces (a Python side effect at trace time), which is both the
     serving telemetry and the regression guard: serving many batch sizes
     under one bucket must trace exactly once.
  4. **Telemetry.** Each dispatch records wall latency, per-query
     distance counts, and early-termination fires; `stats()` folds them
     into an `EngineStats` snapshot (p50/p95/p99, dists/query, ET fire
     rate, and recall when ground truth is supplied).
  5. **Spans and counters** (`serve.spans`). Each bucket dispatch is
     timed in spans `kbest.pad`, `kbest.enqueue` (the jit call: argument
     flattening, host-to-device copy, launch), `kbest.device_wait`
     (`block_until_ready`) and `kbest.fetch` (one batched device-to-host
     copy of results and counters); `serve_loop` wraps each `search` call
     in a `kbest.dispatch` span labelled with its requests. The compiled
     program sums its lanes' SearchStats into one small int32 vector, so
     the counters (rows true and padded, distances, early terminations,
     lockstep iterations, hops) cost no extra transfer. Each bucket's
     program is named `kbest_search_b<bucket>`, and its stages carry
     `kbest.*` named scopes (`compiled_text` shows them in the HLO).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.index import KBest
from repro.core.types import SearchConfig
from repro.serve.spans import Recorder, SpanStats

# the order of the per-dispatch counter vector the compiled program returns:
# SearchStats summed over the live lanes (iters is the batch's own count)
DEVICE_COUNTERS = ("n_dist", "early_terminated", "iters", "hops")


def bucket_for(q: int, min_bucket: int = 8, max_bucket: int = 256) -> int:
    """Smallest power-of-two >= q, clamped to [min_bucket, max_bucket]."""
    assert q >= 1, q
    b = 1 << (q - 1).bit_length()
    return max(min_bucket, min(b, max_bucket))


def bucket_ladder(min_bucket: int = 8, max_bucket: int = 256) -> Tuple[int, ...]:
    """All buckets the engine can emit, ascending."""
    out = []
    b = max(1, min_bucket)
    while b < max_bucket:
        out.append(b)
        b <<= 1
    out.append(max_bucket)
    return tuple(out)


def percentiles(values) -> Tuple[float, float, float]:
    """(p50, p95, p99) with the empty-history case guarded: percentile
    telemetry is read before traffic arrives and after drains where every
    request was rejected/shed, and np.percentile([]) raises."""
    arr = np.asarray(list(values), np.float64)
    if arr.size == 0:
        return 0.0, 0.0, 0.0
    return (float(np.percentile(arr, 50)), float(np.percentile(arr, 95)),
            float(np.percentile(arr, 99)))


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """Telemetry snapshot over every call since construction/reset."""

    n_requests: int            # compiled calls served (post-coalescing)
    n_queries: int             # TRUE query count (padding excluded)
    n_traces: int              # XLA traces of the underlying search
    cache_hits: int
    cache_misses: int
    lat_p50_ms: float          # per-call wall latency percentiles
    lat_p95_ms: float
    lat_p99_ms: float
    mean_lat_ms: float
    dists_per_query: float     # mean over valid lanes (cross-family units)
    et_fire_rate: float        # fraction of valid lanes that early-terminated
    recall_at_k: Optional[float]   # only when gt_ids were supplied
    # ---- overload telemetry (DESIGN.md §17; fed by serve_loop) ----
    n_rejected: int = 0        # deadline-infeasible at admission
    n_shed: int = 0            # dropped at a full bounded queue
    n_failed: int = 0          # dispatch raised; failed its own result
    deadline_miss_rate: float = 0.0   # served-late / deadline-carrying
    degrade_occupancy: Tuple[Tuple[int, int], ...] = ()  # (level, dispatches)
    # ---- spans and counters (serve.spans), summed over dispatches ----
    host_p50_ms: float = 0.0   # median host time of a bucket dispatch:
                               # kbest.pad through kbest.fetch, less
                               # kbest.device_wait
    rows_padded: int = 0       # padding lanes added to reach the bucket
                               # (the live lanes are n_queries)
    iters: int = 0             # lockstep iterations (0 for IVF)
    hops: int = 0              # nodes expanded by the live lanes
    lane_slots: int = 0        # iters x live lanes x beam width: the
                               # expansions the lockstep loop had room for
    spans: Dict[str, SpanStats] = dataclasses.field(default_factory=dict)

    def summary(self) -> str:
        rec = ("-" if self.recall_at_k is None
               else f"{self.recall_at_k:.3f}")
        return (f"requests={self.n_requests} queries={self.n_queries} "
                f"traces={self.n_traces} "
                f"cache={self.cache_hits}h/{self.cache_misses}m | "
                f"lat p50={self.lat_p50_ms:.2f} p95={self.lat_p95_ms:.2f} "
                f"p99={self.lat_p99_ms:.2f} ms | "
                f"dists/q={self.dists_per_query:.0f} "
                f"et_rate={self.et_fire_rate:.2f} recall={rec} | "
                f"rej={self.n_rejected} shed={self.n_shed} "
                f"fail={self.n_failed} "
                f"miss={self.deadline_miss_rate:.2f}")


class SearchEngine:
    """Serving facade over one built index — KBest (graph or IVF) or a
    ShardedKBest mesh (anything exposing config / db / _resolve_cfg /
    search_padded)."""

    def __init__(self, index: KBest, *, min_bucket: int = 8,
                 max_bucket: int = 256, name: str = "default"):
        assert index.db is not None, "serve a BUILT index (call add() first)"
        assert min_bucket >= 1 and max_bucket >= min_bucket
        # non-power-of-two bounds would make bucket_ladder (warmup) and
        # bucket_for (dispatch) disagree, so warmed traffic could re-trace
        assert min_bucket & (min_bucket - 1) == 0, min_bucket
        assert max_bucket & (max_bucket - 1) == 0, max_bucket
        self.index = index
        self.name = name
        self.min_bucket = min_bucket
        self.max_bucket = max_bucket
        self._cache: Dict[tuple, callable] = {}
        # telemetry accumulators
        self.n_traces = 0
        self.cache_hits = 0
        self.cache_misses = 0
        self._lat_ms: list = []
        self._host_ms: list = []
        self.recorder = Recorder()
        self._gt_hits = 0.0
        self._gt_queries = 0
        # overload telemetry (DESIGN.md §17), fed by serve_loop's note_*
        self._n_rejected = 0
        self._n_shed = 0
        self._n_failed = 0
        self._n_deadline = 0
        self._n_deadline_missed = 0
        self._degrade_occ: Dict[int, int] = {}

    # ------------------------------------------------------------- compile
    def _cache_key(self, bucket: int, scfg: SearchConfig) -> tuple:
        # n_shards is the flat mesh shape of a sharded index (DESIGN.md
        # §12): a re-sharded index is a different XLA program (P unrolled
        # shard searches + merge), so it must be a different cache entry.
        # scfg is the WHOLE frozen SearchConfig, so traversal-shape knobs —
        # beam_width (W expansions/iteration unroll the ET scan W times,
        # DESIGN.md §2), batch_B (chunked distance calls), visited_mode —
        # key distinct programs by construction (tests/test_beam.py pins
        # the beam_width case).
        cfg = self.index.config
        return (bucket, scfg, cfg.index_type, cfg.quant.kind, cfg.n_shards)

    def _compiled(self, bucket: int, scfg: SearchConfig):
        key = self._cache_key(bucket, scfg)
        fn = self._cache.get(key)
        if fn is None:
            self.cache_misses += 1

            def run(index, q, mask):
                # Python side effect: executes once per XLA trace, never on
                # cached executions — this IS the trace counter.
                self.n_traces += 1
                dists, ids, st = index.search_padded(
                    q, mask, search_cfg=scfg, with_stats=True)
                # padded lanes come back with zeroed stats, so these sums
                # are over the live lanes
                counters = jnp.stack([
                    jnp.sum(st.n_dist), jnp.sum(st.early_terminated),
                    st.iters, jnp.sum(st.n_hops)]).astype(jnp.int32)
                return dists, ids, counters

            # a stable program name per bucket: trace ops then read
            # jit_kbest_search_b256(...)/%fusion.52
            run.__name__ = run.__qualname__ = f"kbest_search_b{bucket}"
            # the index is an ARGUMENT (KBest/ShardedKBest are pytrees):
            # its arrays stay where they are on the device and the program
            # reads them as operands instead of embedding them as constants
            fn = functools.partial(jax.jit(run), self.index)
            self._cache[key] = fn
        else:
            self.cache_hits += 1
        return fn

    def warmup(self, batch_sizes: Optional[Sequence[int]] = None,
               k: Optional[int] = None,
               search_cfg: Optional[SearchConfig] = None) -> int:
        """Precompile the buckets covering `batch_sizes` (default: the whole
        ladder) for one SearchConfig. Returns the number of fresh traces."""
        scfg = self.index._resolve_cfg(k, search_cfg)
        if batch_sizes is None:
            buckets = bucket_ladder(self.min_bucket, self.max_bucket)
        else:
            buckets = sorted({bucket_for(b, self.min_bucket, self.max_bucket)
                              for b in batch_sizes})
        before = self.n_traces
        d = self.index.db.shape[1]
        for b in buckets:
            q = np.zeros((b, d), np.float32)
            mask = np.zeros((b,), bool)
            mask[0] = True     # one live lane: exercise the real loop body
            out = self._compiled(b, scfg)(q, mask)
            jax.block_until_ready(out)
        return self.n_traces - before

    def compiled_text(self, bucket: int, k: Optional[int] = None,
                      search_cfg: Optional[SearchConfig] = None) -> str:
        """The optimized HLO of a warmed bucket's program. Its
        `metadata={op_name=...}` carries the `kbest.*` scopes, which the
        device ops of a profiler trace do not: this text maps op to scope.
        Neither traces nor compiles again, and leaves the telemetry as it
        is; a bucket not warmed raises KeyError."""
        scfg = self.index._resolve_cfg(k, search_cfg)
        fn = self._cache[self._cache_key(bucket, scfg)]
        d = self.index.db.shape[1]
        lowered = fn.func.lower(self.index, np.zeros((bucket, d), np.float32),
                                np.zeros((bucket,), bool))
        return lowered.compile().as_text()

    # -------------------------------------------------------------- search
    def search(self, queries: np.ndarray, k: Optional[int] = None,
               search_cfg: Optional[SearchConfig] = None,
               gt_ids: Optional[np.ndarray] = None
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Serve one request batch. queries: (Q, d), any Q >= 1.

        Pads to the shape bucket, dispatches through the compile cache and
        returns exactly (Q, k) results. Batches beyond max_bucket are split
        into max_bucket chunks. When gt_ids (Q, >=k) is given, recall@k is
        folded into the engine telemetry with the TRUE served count as the
        denominator.
        """
        queries = np.asarray(queries, np.float32)
        assert queries.ndim == 2, queries.shape
        Q = queries.shape[0]
        scfg = self.index._resolve_cfg(k, search_cfg)
        if Q > self.max_bucket:
            parts = [self.search(queries[s:s + self.max_bucket],
                                 search_cfg=scfg,
                                 gt_ids=None if gt_ids is None
                                 else gt_ids[s:s + self.max_bucket])
                     for s in range(0, Q, self.max_bucket)]
            return (np.concatenate([p[0] for p in parts]),
                    np.concatenate([p[1] for p in parts]))

        bucket = bucket_for(Q, self.min_bucket, self.max_bucket)
        rec = self.recorder
        with rec.span("kbest.pad") as pad:
            qp = np.zeros((bucket, queries.shape[1]), np.float32)
            qp[:Q] = queries
            mask = np.zeros((bucket,), bool)
            mask[:Q] = True
        fn = self._compiled(bucket, scfg)
        with rec.span("kbest.enqueue") as enqueue:
            out = fn(qp, mask)
        with rec.span("kbest.device_wait") as wait:
            jax.block_until_ready(out)
        with rec.span("kbest.fetch") as fetch:
            # one batched copy: results and the counter vector
            dists, ids, counters = jax.device_get(out)
            dists, ids = dists[:Q], ids[:Q]
            got = dict(zip(DEVICE_COUNTERS, counters.tolist()))
            for name, v in got.items():
                rec.count(name, v)
            rec.count("rows_true", Q)
            rec.count("rows_padded", bucket - Q)
            rec.count("lane_slots", got["iters"] * Q * scfg.beam_width)
        # the dispatch latency: the jit call through block_until_ready; the
        # host's part: padding through the fetch, less the device wait
        self._lat_ms.append((wait.end_ns - enqueue.start_ns) * 1e-6)
        self._host_ms.append((fetch.end_ns - pad.start_ns) * 1e-6 - wait.ms)

        if gt_ids is not None:
            from repro.data.vectors import recall_at_k
            self._gt_hits += recall_at_k(ids, np.asarray(gt_ids)[:Q],
                                         scfg.k) * Q
            self._gt_queries += Q
        return dists, ids

    # ----------------------------------------------------------- telemetry
    def note_rejected(self, n: int = 1) -> None:
        self._n_rejected += n

    def note_shed(self, n: int = 1) -> None:
        self._n_shed += n

    def note_failed(self, n: int = 1) -> None:
        self._n_failed += n

    def note_deadline(self, missed: bool) -> None:
        """One served deadline-carrying request: hit or miss."""
        self._n_deadline += 1
        self._n_deadline_missed += int(missed)

    def note_degrade(self, level: int) -> None:
        """One dispatch served at this degrade-ladder level."""
        self._degrade_occ[level] = self._degrade_occ.get(level, 0) + 1

    def stats(self) -> EngineStats:
        p50, p95, p99 = percentiles(self._lat_ms)
        lat = np.asarray(self._lat_ms, np.float64)
        c = self.recorder.counters
        n_queries = c.get("rows_true", 0)
        nq = max(n_queries, 1)
        return EngineStats(
            n_requests=lat.size,
            n_queries=n_queries,
            n_traces=self.n_traces,
            cache_hits=self.cache_hits,
            cache_misses=self.cache_misses,
            lat_p50_ms=p50,
            lat_p95_ms=p95,
            lat_p99_ms=p99,
            mean_lat_ms=float(lat.mean()) if lat.size else 0.0,
            dists_per_query=c.get("n_dist", 0) / nq,
            et_fire_rate=c.get("early_terminated", 0) / nq,
            recall_at_k=(self._gt_hits / self._gt_queries
                         if self._gt_queries else None),
            n_rejected=self._n_rejected,
            n_shed=self._n_shed,
            n_failed=self._n_failed,
            deadline_miss_rate=(self._n_deadline_missed / self._n_deadline
                                if self._n_deadline else 0.0),
            degrade_occupancy=tuple(sorted(self._degrade_occ.items())),
            host_p50_ms=percentiles(self._host_ms)[0],
            rows_padded=c.get("rows_padded", 0),
            iters=c.get("iters", 0),
            hops=c.get("hops", 0),
            lane_slots=c.get("lane_slots", 0),
            spans=self.recorder.spans(),
        )

    def reset_stats(self) -> None:
        """Clear telemetry; the compile cache (and n_traces) is kept —
        traces are a property of the cache, not of a measurement window."""
        self._lat_ms = []
        self._host_ms = []
        self.recorder.reset()
        self._gt_hits = 0.0
        self._gt_queries = 0
        self._n_rejected = 0
        self._n_shed = 0
        self._n_failed = 0
        self._n_deadline = 0
        self._n_deadline_missed = 0
        self._degrade_occ = {}
