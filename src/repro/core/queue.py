"""Fixed-size sorted candidate queue (the priority queue of Algorithm 1).

JAX needs static shapes, so the queue is a struct-of-arrays of length L kept
sorted ascending by distance:

  dists   f32 (L,)  +inf in empty slots
  ids     i32 (L,)  -1   in empty slots
  visited bool (L,) True in empty slots (so they are never expanded)

The queue's single write operation is a *merge*: fold a block of candidate
(dist, id) pairs into the sorted run. Since DESIGN.md §2's beam traversal
the merge is structured as sort-the-new-block + a stable merge of TWO SORTED
RUNS (`merge_sorted_runs`): the new block (W·M entries) is sorted once —
O(WM log WM) on the small block, or after the fused_expand kernel — and
merged against the already-sorted queue by one stable sort of the
concatenation (existing entries win ties). That is what `merge_insert`
produced historically; the hypothesis suite pins the equivalence against
an argsort oracle.

No per-iteration queue operation scatters, because the TPU serializes
scatters. On one TPU v5e, searching a random 30-regular graph over 1M
rows, a rank-arithmetic merge (five scatters per lane) and scattered
pick/mark steps took 26.1 ms per lockstep iteration at 4096 lanes and
L=48, against 4.4 ms for the sort and compare forms below, and 8.0
against 0.28 ms at 256 lanes and L=384, with the same ids.

`merge_insert` reports the insertion rank of the best surviving new
candidate — the signal Eq. 3 (early termination) needs; the beam variant
(`merge_insert_beam`) reports one rank per beam expansion, evaluated against
the same merged order (DESIGN.md §2's per-lane ET semantics).

Everything is written for a single query and lifted with jax.vmap by the
search loop.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

INF = jnp.float32(jnp.inf)


class Queue(NamedTuple):
    dists: jnp.ndarray    # (L,) f32, ascending
    ids: jnp.ndarray      # (L,) i32
    visited: jnp.ndarray  # (L,) bool


def init_queue(L: int) -> Queue:
    return Queue(
        dists=jnp.full((L,), INF, dtype=jnp.float32),
        ids=jnp.full((L,), -1, dtype=jnp.int32),
        visited=jnp.ones((L,), dtype=bool),
    )


# --------------------------------------------------------------------------
# Dedupe helpers — ONE copy of the O(M²) lower-triangle logic.
#
# Historically three call sites each re-derived this comparison (the search
# loop's row dedupe, the queue's new-block dedupe, and the bitmap path's
# seen-mask combination); they now all route through dup_prior_mask /
# dedupe_ids, property-tested in tests/test_beam.py.
# --------------------------------------------------------------------------
def dup_prior_mask(ids: jnp.ndarray) -> jnp.ndarray:
    """(M,) ids -> (M,) bool: True where ids[i] equals ids[j] for some
    j < i (strict lower triangle). Negative ids never match anything —
    callers decide separately how to treat invalid slots."""
    m = ids.shape[0]
    tri = jnp.arange(m)[None, :] < jnp.arange(m)[:, None]
    return jnp.any((ids[:, None] == ids[None, :]) & tri & (ids >= 0)[:, None],
                   axis=1)


def dedupe_ids(ids: jnp.ndarray) -> jnp.ndarray:
    """Mask (to -1) ids duplicating an earlier position, and invalid ids."""
    return jnp.where(dup_prior_mask(ids) | (ids < 0), -1, ids)


def in_queue_mask(q: Queue, ids: jnp.ndarray) -> jnp.ndarray:
    """(M,) ids -> (M,) bool: id already present in the queue."""
    return jnp.any(ids[:, None] == q.ids[None, :], axis=1) & (ids >= 0)


def _dedupe_new(q: Queue, new_dists: jnp.ndarray, new_ids: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Invalidate new entries that duplicate the queue or earlier new entries."""
    bad = in_queue_mask(q, new_ids) | dup_prior_mask(new_ids) | (new_ids < 0)
    return jnp.where(bad, INF, new_dists), jnp.where(bad, -1, new_ids)


# --------------------------------------------------------------------------
# Sorted-run merge (DESIGN.md §2)
# --------------------------------------------------------------------------
def sort_block(dists: jnp.ndarray, ids: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stable ascending sort of a candidate block by distance (ties keep
    block order) — the same sort fused_expand applies after its kernel."""
    order = jnp.argsort(dists, stable=True)
    return dists[order], ids[order]


def merge_sorted_runs(q: Queue, sd: jnp.ndarray, si: jnp.ndarray) -> Queue:
    """Merge the sorted queue with an ascending-sorted candidate block.

    One stable key-value sort of the concatenation (queue first), cut back
    to L: ties place queue entries first and keep block order, so the
    result is the stable two-run merge (entry i of the queue at
    i + |{block < queue[i]}|, entry j of the block at
    j + |{queue <= block[j]}|) — no-op merges preserve visited flags
    exactly. A sort, not rank arithmetic plus scatters: see the module
    docstring.

    PRECONDITION: `si` is already deduped against the queue and within
    itself (masked entries carry dist=+inf, id=-1) — `merge_insert` /
    `merge_insert_beam` establish this; the fused-expand kernel path does it
    before the kernel computes distances.
    """
    L = q.dists.shape[0]
    md, mi, mv = jax.lax.sort(
        (jnp.concatenate([q.dists, sd]), jnp.concatenate([q.ids, si]),
         # new entries enter unvisited; only queue entries carry True flags
         jnp.concatenate([q.visited, jnp.zeros(sd.shape, bool)])),
        num_keys=1, is_stable=True)
    return Queue(dists=md[:L], ids=mi[:L], visited=mv[:L])


def block_ranks(q: Queue, all_dists: jnp.ndarray, bests: jnp.ndarray,
                ties_prior: jnp.ndarray = None) -> jnp.ndarray:
    """Insertion rank of each `bests[w]` in the merged (queue + block)
    order: #entries strictly better + existing-entry ties (stable-sort
    placement), capped at L; +inf (nothing inserted) ranks L.

    `ties_prior (W,)` counts block entries from EARLIER beam expansions
    whose distance exactly ties bests[w]: the stable merge places those
    before w's best, and the as-if-sequential Eq. 3 semantics would have
    inserted them first, so they count toward w's rank (exact ties across
    expansions are real on quantized workloads, e.g. u8-LUT ADC sums).
    None => zeros — correct for W=1, where no earlier expansion exists.

    `all_dists` may be the full deduped block or its sorted top-L prefix —
    ranks at or beyond L saturate identically either way (the prefix holds
    the L best, so any undercount only affects ranks that cap at L).
    """
    L = q.dists.shape[0]
    better = (jnp.sum(q.dists[None, :] < bests[:, None], axis=1)
              + jnp.sum(all_dists[None, :] < bests[:, None], axis=1)
              + jnp.sum(q.dists[None, :] == bests[:, None], axis=1))
    if ties_prior is not None:
        better = better + ties_prior
    return jnp.where(jnp.isinf(bests), L,
                     jnp.minimum(better, L)).astype(jnp.int32)


def beam_tie_counts(block: jnp.ndarray, bests: jnp.ndarray) -> jnp.ndarray:
    """(W, M) block dists, (W,) per-expansion bests -> (W,) counts of
    earlier-expansion entries exactly tying bests[w] (block_ranks'
    ties_prior operand; the fused kernels compute the same in-kernel)."""
    W = block.shape[0]
    eq = jnp.sum(block[None, :, :] == bests[:, None, None], axis=2)  # (W, W')
    tri = jnp.arange(W)[None, :] < jnp.arange(W)[:, None]
    return jnp.sum(jnp.where(tri, eq, 0), axis=1).astype(jnp.int32)


def merge_insert(q: Queue, new_dists: jnp.ndarray, new_ids: jnp.ndarray
                 ) -> Tuple[Queue, jnp.ndarray, jnp.ndarray]:
    """Merge (new_dists, new_ids) into the queue.

    Returns (queue', best_rank, n_inserted) where best_rank is the rank (0-
    based position in the merged order) of the best *new* candidate, or L if
    nothing was inserted — the Eq. 3 insertion position p for this step.
    """
    L = q.dists.shape[0]
    nd, ni = _dedupe_new(q, new_dists, new_ids)
    sd, si = sort_block(nd, ni)
    out = merge_sorted_runs(q, sd, si)
    best_rank = block_ranks(q, nd, jnp.min(nd)[None])[0]
    n_inserted = jnp.sum((nd < q.dists[L - 1]) & (ni >= 0)).astype(jnp.int32)
    return out, best_rank, n_inserted


def merge_expand(q: Queue, new_dists: jnp.ndarray, new_ids: jnp.ndarray,
                 n_beam: int) -> Tuple[Queue, jnp.ndarray]:
    """Beam merge of a PRE-DEDUPED candidate block: (W·M,) candidates from
    W expansions, flat in beam order (expansion w owns slots
    [w·M, (w+1)·M)), with duplicates / in-queue / invalid entries already
    masked to (dist=+inf, id=-1) — the search loop establishes exactly this
    before the distance step, so re-deriving the O((WM)² + WM·L) dedupe
    masks here would burn the per-iteration fixed cost the beam exists to
    amortize. External callers use merge_insert_beam, which dedupes first.

    Returns (queue', best_ranks (W,)) — best_ranks[w] is the merged-order
    rank of expansion w's best surviving candidate (or L), all evaluated
    against the same post-merge order; the search loop consumes them in
    beam order for Eq. 3 (DESIGN.md §2).
    """
    block = new_dists.reshape(n_beam, -1)
    bests = jnp.min(block, axis=1)
    sd, si = sort_block(new_dists, new_ids)
    out = merge_sorted_runs(q, sd, si)
    return out, block_ranks(q, new_dists, bests,
                            beam_tie_counts(block, bests))


def merge_insert_beam(q: Queue, new_dists: jnp.ndarray, new_ids: jnp.ndarray,
                      n_beam: int) -> Tuple[Queue, jnp.ndarray]:
    """Safe-for-any-input beam merge: _dedupe_new, then merge_expand. With
    n_beam=1 this is exactly merge_insert."""
    nd, ni = _dedupe_new(q, new_dists, new_ids)
    return merge_expand(q, nd, ni, n_beam)


# --------------------------------------------------------------------------
# Expansion picking
# --------------------------------------------------------------------------
def pick_top_w(q: Queue, w: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Slot indices of the w closest unvisited entries, plus existence mask.

    Exploits the sorted-ascending invariant: the w closest unvisited
    candidates are simply the FIRST w unvisited finite slots in queue order
    (no masked argmin over L needed — the scan is a cumulative count).
    Returns (idxs (w,) clamped into [0, L), has (w,) bool); has[j] is False
    (and idxs[j] meaningless) when fewer than j+1 unvisited entries exist.
    """
    L = q.dists.shape[0]
    unv = (~q.visited) & jnp.isfinite(q.dists)
    rank = jnp.cumsum(unv.astype(jnp.int32)) - 1       # rank among unvisited
    # output position j takes the one slot whose unvisited rank is j: a
    # (w, L) compare, not a scatter (the TPU serializes scatters)
    hit = unv[None, :] & (rank[None, :] == jnp.arange(w)[:, None])
    has = jnp.any(hit, axis=1)
    idxs = jnp.sum(jnp.where(hit, jnp.arange(L, dtype=jnp.int32)[None, :], 0),
                   axis=1)
    return jnp.where(has, idxs, L - 1).astype(jnp.int32), has


def pick_unvisited(q: Queue) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Index of the closest unvisited entry and whether one exists —
    pick_top_w with a beam of one (kept for the W=1 callers/tests)."""
    idxs, has = pick_top_w(q, 1)
    return idxs[0], has[0]


def mark_visited(q: Queue, idx: jnp.ndarray, do: jnp.ndarray) -> Queue:
    vis = q.visited.at[idx].set(jnp.where(do, True, q.visited[idx]))
    return q._replace(visited=vis)


def mark_visited_many(q: Queue, idxs: jnp.ndarray, do: jnp.ndarray) -> Queue:
    """Mark several slots at once. idxs may contain clamped duplicates for
    do=False lanes (pick_top_w's sentinel), so the marks are OR-ed: a
    (w, L) compare, not a scatter (the TPU serializes scatters)."""
    L = q.visited.shape[0]
    hit = jnp.any((idxs[:, None] == jnp.arange(L)[None, :]) & do[:, None],
                  axis=0)
    return q._replace(visited=q.visited | hit)


def topk(q: Queue, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Final result extraction (queue is sorted): first k entries."""
    return q.dists[:k], q.ids[:k]
