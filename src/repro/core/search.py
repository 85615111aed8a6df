"""Batched beam-parallel best-first traversal (paper Algorithm 1 + Eq. 3).

Hardware adaptation (DESIGN.md §2): the paper runs one search per CPU
thread; a TPU has no independent scalar threads, so we run a *batch* of Q
queries in SIMD lockstep inside one `jax.lax.while_loop`, with a per-query
`active` mask. Each iteration expands the top-W unvisited candidates per
active query (`SearchConfig.beam_width`, the beam) and computes distances to
all W·M gathered neighbors in a single (Q, W·M) batched operation — the
paper's 1-to-B SIMD batching (H1) lifted to 2-D (Q-to-B) so it saturates the
MXU/VPU, with the beam multiplying B so the gather pipeline always has W·M
rows in flight to hide latency behind (H2). A wider beam cuts the lockstep
trip count ~W× and amortizes per-iteration queue maintenance; W=1 is the
classic best-first traversal and stays bit-identical to it. Queries that
satisfy the early-termination test (Eq. 3) or exhaust their queue are masked
off and become idle lanes (measured as `lockstep_overhead` in the
benchmarks).

Queue maintenance per iteration is a sort of the W·M-entry candidate block
(small) plus a stable merge of two sorted runs (`queue.merge_sorted_runs`,
one stable sort of the L + W·M concatenation: no scatters). Early
termination under a beam is defined per expansion in beam order: expansion
w's best surviving candidate gets its insertion rank in the merged order,
and Eq. 3's patience counter consumes the W ranks sequentially (w = 0
first), exactly as if the expansions had happened one per iteration — so
W=1 semantics are the original ones by construction, and a beam can only
reach the patience threshold at the same expansion count or earlier.

The per-step distance batch: the paper sizes B to the L1 cache (Eq. 1). On
TPU the analogous constraint is VMEM tile sizing inside the Pallas kernels
(repro/kernels); `SearchConfig.batch_B` optionally chunks the W·M candidate
axis into batch_B-sized distance calls (0 = one fused call). The
kernel path under a beam routes through the `fused_expand` family
(kernels/gather_dist.py and twins): the gather + distance kernel followed
by the block sort, emitting the sorted candidate block directly.

Named scopes, for a device trace (each bucket's compiled HLO maps an op to
its scope): `kbest.seed` (entry distances, queue init), `kbest.expand`
(neighbour gather, dedupe, visited and in-queue filters, distances) and
`kbest.queue` (pick and mark, merge of the candidate block, Eq. 3, the
final top-k); the callers add `kbest.lut` and `kbest.rerank`.

Visited-set semantics: "bitmap" mode implements Algorithm 1 exactly (a
packed per-query bitmap of distance-computed nodes, O(n/8) bytes/query);
"queue" mode (default) dedupes only against the candidate queue, which may
recompute distances of long-evicted nodes but never changes recall — the
classic memory/compute trade for huge n.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import queue as qmod
from repro.core.types import SearchConfig

# dist_fn(data, queries (Q, d), nbr_ids (Q, B)) -> (Q, B) float32 distances.
# `data` is the index's array operand (the corpus, or the codes of a
# quantized family) — a traced argument of `search`, never a closed-over
# constant: dist_fn itself is a pure, cached function of static knobs only.
DistFn = Callable[[object, jnp.ndarray, jnp.ndarray], jnp.ndarray]

# expand_fn(data, queries (Q, d), nbr_ids (Q, W*M)) ->
#   (sorted_dists (Q, T), sorted_ids (Q, T), per-beam bests (Q, W),
#    earlier-expansion tie counts (Q, W))
# — the fused gather+distance+sort step (the kernels' fused_expand family).
# T is min(L, W*M): only the L best new candidates can survive the merge.
ExpandFn = Callable[[object, jnp.ndarray, jnp.ndarray],
                    Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                          jnp.ndarray]]


class SearchStats(NamedTuple):
    n_hops: jnp.ndarray        # (Q,) i32 nodes expanded
    n_dist: jnp.ndarray        # (Q,) i32 distances computed
    early_terminated: jnp.ndarray  # (Q,) bool
    iters: jnp.ndarray         # () i32 lockstep iterations of the batch


class _Carry(NamedTuple):
    dists: jnp.ndarray    # (Q, L)
    ids: jnp.ndarray      # (Q, L)
    visited: jnp.ndarray  # (Q, L)
    bitmap: jnp.ndarray   # (Q, nwords) u32 (nwords=1 dummy in queue mode)
    et_ctr: jnp.ndarray   # (Q,) i32
    et_fired: jnp.ndarray  # (Q,) bool
    active: jnp.ndarray   # (Q,) bool
    hops: jnp.ndarray     # (Q,) i32
    ndist: jnp.ndarray    # (Q,) i32
    it: jnp.ndarray       # () i32


def _dedupe_row(ids: jnp.ndarray) -> jnp.ndarray:
    """Mask (to -1) ids duplicating an earlier position in the row (the
    shared lower-triangle helper, kept under its historical name for the
    traversal's callers/tests)."""
    return qmod.dedupe_ids(ids)


def _bitmap_test(bitmap: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """(nwords,) u32 bitmap, (M,) ids -> (M,) bool seen (invalid -> False)."""
    safe = jnp.maximum(ids, 0)
    words = bitmap[safe >> 5]
    bit = (words >> (safe.astype(jnp.uint32) & 31)) & 1
    return (bit == 1) & (ids >= 0)


def _bitmap_set_raw(bitmap: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Set bits via scatter-add. PRECONDITION: ids are deduped within the
    batch AND none of their bits are already set — the add only equals a
    bitwise-or while the added bits are disjoint; a duplicate id (or an
    already-set bit) carries into the adjacent bit and corrupts the visited
    set. The traversal loop satisfies this by construction (dedupe_ids +
    _bitmap_test masking); every other caller must use _bitmap_set."""
    valid = ids >= 0
    safe = jnp.maximum(ids, 0)
    word_idx = jnp.where(valid, safe >> 5, bitmap.shape[0] - 1)
    val = jnp.where(valid, jnp.uint32(1) << (safe.astype(jnp.uint32) & 31), jnp.uint32(0))
    return bitmap.at[word_idx].add(val)


def _bitmap_set(bitmap: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Set bits for valid ids: safe for ANY input — dedupes within the
    batch and skips already-set bits before the scatter-add, so colliding
    entry seeds (e.g. strided seeds wrapping onto the medoid) cannot carry
    into adjacent bits."""
    ids = qmod.dedupe_ids(ids)
    ids = jnp.where(_bitmap_test(bitmap, ids), -1, ids)
    return _bitmap_set_raw(bitmap, ids)


def _dist_chunked(dist_fn: DistFn, data, queries: jnp.ndarray,
                  ids: jnp.ndarray, batch_B: int) -> jnp.ndarray:
    """Distance computation over the candidate axis, optionally chunked into
    batch_B-sized calls (SearchConfig.batch_B; 0 = one fused (Q, W·M) call).
    Chunking never changes the candidate set or ordering (pinned by tests);
    distance bits may drift a few ulp across chunk shapes, as any
    hardware retiling would."""
    C = ids.shape[1]
    if batch_B <= 0 or batch_B >= C:
        return dist_fn(data, queries, ids)
    return jnp.concatenate(
        [dist_fn(data, queries, ids[:, s:s + batch_B])
         for s in range(0, C, batch_B)], axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "n_total", "dist_fn", "expand_fn"),
)
def search(
    graph: jnp.ndarray,            # (n, M) i32, -1 padded
    queries: jnp.ndarray,          # (Q, d) f32
    entry_ids: jnp.ndarray,        # (E,) i32 entry points
    data,                          # dist_fn / expand_fn array operand
    *,
    dist_fn: DistFn,
    cfg: SearchConfig,
    n_total: int,
    valid_mask: Optional[jnp.ndarray] = None,  # (Q,) bool; None => all valid
    expand_fn: Optional[ExpandFn] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, SearchStats]:
    """Batched ANN search. Returns (dists (Q, k), ids (Q, k), stats).

    `valid_mask` marks real queries in a shape-padded batch: invalid lanes
    start inactive, so they are the same free lockstep-idle lanes as
    early-terminated queries and add no distance computations. Their rows
    still hold the (garbage) seed entries — callers mask outputs (the
    serving engine's `search_padded` does).

    `data` (the corpus or codes) is a traced argument, so a compiled
    search takes the index as an operand instead of embedding it.

    `expand_fn`, when given, replaces dist_fn + host-side block sort with
    the fused gather+distance+sort kernel for the per-iteration candidate
    block (the seed distances still go through dist_fn). It is only engaged
    when `cfg.batch_B == 0` — a chunked distance path is a dist_fn property,
    so batch_B falls back to dist_fn and the knob is always honored.
    """
    Q = queries.shape[0]
    L, k, M = cfg.L, cfg.k, graph.shape[1]
    W = cfg.beam_width
    t_pos = jnp.int32(int(cfg.et_t_frac * L))
    nwords = (n_total + 31) // 32 if cfg.visited_mode == "bitmap" else 1
    use_fused = expand_fn is not None and cfg.batch_B == 0

    # ---- init: seed the queue with the entry points -----------------------
    with jax.named_scope("kbest.seed"):
        e_ids = jnp.broadcast_to(entry_ids[None, :],
                                 (Q, entry_ids.shape[0]))
        e_dists = dist_fn(data, queries, e_ids)
        q0 = qmod.init_queue(L)
        q0 = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (Q,) + x.shape), q0)

        def _seed(qq, nd, ni):
            out, _, _ = qmod.merge_insert(qq, nd, ni)
            return out

        queue = jax.vmap(_seed)(qmod.Queue(q0[0], q0[1], q0[2]), e_dists,
                                e_ids)
        bitmap = jnp.zeros((Q, nwords), dtype=jnp.uint32)
        if cfg.visited_mode == "bitmap":
            bitmap = jax.vmap(_bitmap_set)(bitmap, e_ids)

        # seed distances count: the init dist_fn call above already
        # computed one distance per valid entry seed — starting ndist at 0
        # undercounted every family's n_dist by n_entries (benchmarks,
        # EngineStats dists/query). Gated on the lane being valid: padded
        # lanes must keep the documented "invalid lanes add no distance
        # computations" invariant.
        active0 = (jnp.ones((Q,), bool) if valid_mask is None
                   else valid_mask.astype(bool))
        n_seed = jnp.where(active0,
                           jnp.sum(e_ids >= 0, axis=1), 0).astype(jnp.int32)

        carry = _Carry(
            dists=queue.dists, ids=queue.ids, visited=queue.visited,
            bitmap=bitmap,
            et_ctr=jnp.zeros((Q,), jnp.int32),
            et_fired=jnp.zeros((Q,), bool),
            active=active0,
            hops=jnp.zeros((Q,), jnp.int32),
            ndist=n_seed,
            it=jnp.int32(0),
        )

    def cond(c: _Carry):
        return jnp.any(c.active) & (c.it < cfg.hops_bound)

    def body(c: _Carry) -> _Carry:
        queue = qmod.Queue(c.dists, c.ids, c.visited)
        with jax.named_scope("kbest.queue"):
            # the W closest unvisited candidates per lane, by the sorted
            # invariant (first W unvisited slots in queue order)
            idxs, has = jax.vmap(lambda qq: qmod.pick_top_w(qq, W))(queue)
            exp_w = c.active[:, None] & has                    # (Q, W)
            any_has = jnp.any(has, axis=1)
            exp_any = c.active & any_has
            v = jnp.where(exp_w, queue.ids[jnp.arange(Q)[:, None], idxs], -1)
            queue = jax.vmap(qmod.mark_visited_many)(queue, idxs, exp_w)

        with jax.named_scope("kbest.expand"):
            # gather all W neighbor lists at once; -1 rows for idle
            # slots/lanes. The flat (W*M,) row is deduped against EARLIER
            # flat positions, so overlap between beam expansions collapses
            # exactly like duplicates within one adjacency row (beam order
            # = flat order).
            nbrs = jnp.where(v[..., None] >= 0, graph[jnp.maximum(v, 0)], -1)
            flat = jax.vmap(qmod.dedupe_ids)(nbrs.reshape(Q, W * M))

            bitmap = c.bitmap
            if cfg.visited_mode == "bitmap":
                seen = jax.vmap(_bitmap_test)(bitmap, flat)
                flat = jnp.where(seen, -1, flat)
                # flat ids are deduped (above) and seen-masked: raw
                # scatter safe
                bitmap = jax.vmap(_bitmap_set_raw)(bitmap, flat)

            n_new = jnp.sum(flat >= 0, axis=1).astype(jnp.int32)

            # candidates already in the queue are masked BEFORE the
            # distance computation (merge_insert would discard them anyway,
            # so results are unchanged; the fused kernel path requires it,
            # and n_new above keeps the historical "computed distances"
            # accounting)
            flat = jnp.where(jax.vmap(qmod.in_queue_mask)(queue, flat), -1,
                             flat)

            # --- the 1-to-B (here Q-to-W·B) batched distance step (H1 +
            # H2) ---
            if use_fused:
                sd, si, bests, ties = expand_fn(data, queries, flat)
            else:
                nd = _dist_chunked(dist_fn, data, queries, flat,
                                   cfg.batch_B)
                nd = jnp.where(flat >= 0, nd, jnp.inf)

        with jax.named_scope("kbest.queue"):
            if use_fused:
                merged = jax.vmap(qmod.merge_sorted_runs)(queue, sd, si)
                ranks = jax.vmap(qmod.block_ranks)(queue, sd, bests, ties)
            else:
                # flat is fully deduped/in-queue-masked above, so the merge
                # can skip _dedupe_new's O((WM)² + WM·L) re-derivation per
                # step
                merged, ranks = jax.vmap(
                    lambda qq, d, i: qmod.merge_expand(qq, d, i, W))(
                        queue, nd, flat)
            queue = jax.tree.map(
                lambda new, old: jnp.where(
                    exp_any.reshape((Q,) + (1,) * (new.ndim - 1)), new, old),
                merged, queue)

            # --- early termination, Eq. 3: per expansion, in beam order ---
            beyond = ranks > t_pos                             # (Q, W)
            et_ctr, fired = c.et_ctr, c.et_fired
            for w in range(W):
                ex = exp_w[:, w]
                et_ctr = jnp.where(ex, jnp.where(beyond[:, w], et_ctr + 1,
                                                 0), et_ctr)
                fired = fired | (cfg.early_term & ex
                                 & (et_ctr >= cfg.et_patience))

            hops = c.hops + jnp.sum(exp_w, axis=1).astype(jnp.int32)
            ndist = c.ndist + jnp.where(exp_any, n_new, 0)
            active = c.active & any_has & ~fired & (hops < cfg.hops_bound)
        return _Carry(queue.dists, queue.ids, queue.visited, bitmap,
                      et_ctr, fired, active, hops, ndist, c.it + 1)

    out = jax.lax.while_loop(cond, body, carry)
    final = qmod.Queue(out.dists, out.ids, out.visited)
    with jax.named_scope("kbest.queue"):
        dists_k, ids_k = jax.vmap(lambda q: qmod.topk(q, k))(final)
    stats = SearchStats(out.hops, out.ndist, out.et_fired, out.it)
    return dists_k, ids_k, stats


@functools.lru_cache(maxsize=None)
def make_dist_fn(metric: str, impl: str = "ref") -> DistFn:
    """Gather-then-distance backend over a database operand (n, d).

    impl="ref" is the jnp oracle; impl="kernel" routes through the Pallas
    gather_dist kernel (interpret-mode on CPU). Cached, so equal knobs give
    the identical function (it is a static argument of `search`).
    """
    if impl == "kernel":
        from repro.kernels import ops as kops

        def fn(db, queries, nbr_ids):
            return kops.gather_dist(queries, db, nbr_ids, metric=metric)
        return fn

    from repro.core.distance import batched_one_to_many

    def fn(db, queries, nbr_ids):
        vecs = db[jnp.maximum(nbr_ids, 0)]
        return batched_one_to_many(queries, vecs, metric)
    return fn


@functools.lru_cache(maxsize=None)
def make_expand_fn(metric: str, *, L: int, n_beam: int) -> ExpandFn:
    """Fused gather+distance+sort backend for the beam traversal's per-
    iteration candidate block (full-precision rows): the Pallas kernel
    gathers the W·M neighbor rows with scalar-prefetch double-buffered DMA
    (H2) and computes distances on-chip (H1); the per-query sorted
    top-min(L, W·M) block plus per-expansion bests (the Eq. 3 operand)
    follow in XLA — see kernels/gather_dist.py."""
    from repro.kernels import ops as kops

    def fn(db, queries, nbr_ids):
        return kops.fused_expand(queries, db, nbr_ids, metric=metric,
                                 L=L, n_beam=n_beam)
    return fn
