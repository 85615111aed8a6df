"""kNN-graph construction (paper §3.2, phase 1 of index refinement).

Two builders:
  * brute_force_knn — tiled exact kNN; the (chunk, n) distance tiles are the
    Q-to-B batched-distance workload that the batch_dist Pallas kernel
    implements on the MXU (DESIGN.md H1).
  * nn_descent — jit-friendly fixed-round NN-descent (paper uses RNNDescent;
    same family: iterate "my neighbors' neighbors are candidates").
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.distance import pairwise


def _merge_topk(ids_a, dists_a, ids_b, dists_b, k) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Row-wise merge of two candidate sets with id-dedupe, keep k best."""
    ids = jnp.concatenate([ids_a, ids_b], axis=-1)
    dists = jnp.concatenate([dists_a, dists_b], axis=-1)
    # sort by id, kill duplicates (neighboring equal ids), re-sort by dist
    order = jnp.argsort(ids, axis=-1, stable=True)
    ids_s = jnp.take_along_axis(ids, order, axis=-1)
    dists_s = jnp.take_along_axis(dists, order, axis=-1)
    dup = jnp.concatenate(
        [jnp.zeros_like(ids_s[..., :1], dtype=bool), ids_s[..., 1:] == ids_s[..., :-1]],
        axis=-1)
    dists_s = jnp.where(dup | (ids_s < 0), jnp.inf, dists_s)
    order2 = jnp.argsort(dists_s, axis=-1, stable=True)[..., :k]
    return (jnp.take_along_axis(ids_s, order2, axis=-1),
            jnp.take_along_axis(dists_s, order2, axis=-1))


@functools.partial(jax.jit, static_argnames=("k", "metric", "chunk"))
def brute_force_knn(db: jnp.ndarray, k: int, metric: str, chunk: int = 256
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact kNN. Returns (ids (n, k), dists (n, k)), self excluded."""
    n, d = db.shape
    n_chunks = -(-n // chunk)
    n_pad = n_chunks * chunk
    dbp = jnp.pad(db, ((0, n_pad - n), (0, 0)))

    def body(i):
        qs = jax.lax.dynamic_slice(dbp, (i * chunk, 0), (chunk, d))
        dm = pairwise(qs, db, metric)                       # (chunk, n)
        rows = i * chunk + jnp.arange(chunk)
        dm = jnp.where(jnp.arange(n)[None, :] == rows[:, None], jnp.inf, dm)
        neg, idx = jax.lax.top_k(-dm, k)
        return idx.astype(jnp.int32), -neg

    ids, dists = jax.lax.map(body, jnp.arange(n_chunks))
    return ids.reshape(n_pad, k)[:n], dists.reshape(n_pad, k)[:n]


def _row_dists(db: jnp.ndarray, rows: jnp.ndarray, ids: jnp.ndarray,
               metric: str) -> jnp.ndarray:
    """d(db[rows[i]], db[ids[i, j]]) with -1 masked to inf. (nc, C)."""
    vecs = db[jnp.maximum(ids, 0)]                          # (nc, C, d)
    base = db[rows]
    if metric == "l2":
        diff = vecs - base[:, None, :]
        out = jnp.sum(diff * diff, axis=-1)
    else:
        out = -jnp.einsum("ncd,nd->nc", vecs, base)
    return jnp.where(ids >= 0, out, jnp.inf)


def map_rows(f, n: int, chunk: int):
    """Apply f(rows (c,)) -> per-row outputs over all n rows, `chunk` rows
    at a time (lax.map), so the gathered (chunk, C, d) working set is
    bounded independently of n. A tail chunk repeats the last row and its
    outputs are dropped; with n <= chunk it is one call over arange(n)."""
    c = min(chunk, n)
    n_chunks = -(-n // c)
    rows = jnp.minimum(jnp.arange(n_chunks * c, dtype=jnp.int32), n - 1)
    out = jax.lax.map(f, rows.reshape(n_chunks, c))
    return jax.tree.map(lambda a: a.reshape((n_chunks * c,) + a.shape[2:])[:n],
                        out)


# Bytes of gathered neighbor vectors one NN-descent chunk may hold: bounds
# a round's working set independently of n (a 1M x 384-candidate x 96-d
# round gathered at once is ~147 GB, far beyond one chip's 16 GB HBM).
GATHER_BUDGET = 1 << 30


@functools.partial(jax.jit, static_argnames=("k", "metric", "rounds", "sample"))
def nn_descent(db: jnp.ndarray, k: int, metric: str, rounds: int = 6,
               sample: int = 12, seed: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Approximate kNN graph by fixed-round NN-descent.

    Candidates per round: current neighbors ∪ (first `sample` neighbors of
    each neighbor). Distances are the (rows, C, d) batched gather-einsum —
    the same Q-to-B workload as search, so the MXU path applies at build
    time. Each round reads the previous round's whole graph and updates
    rows chunk by chunk (GATHER_BUDGET), so the result does not depend on
    the chunking.
    """
    n, d = db.shape
    chunk = max(1, GATHER_BUDGET // (4 * d * k * max(sample, 1)))
    key = jax.random.PRNGKey(seed)
    ids = jax.random.randint(key, (n, k), 0, n, dtype=jnp.int32)
    # avoid trivial self edges
    ids = jnp.where(ids == jnp.arange(n, dtype=jnp.int32)[:, None], (ids + 1) % n, ids)

    def init_rows(rows):
        r_ids = ids[rows]
        r_d = _row_dists(db, rows, r_ids, metric)
        return _merge_topk(r_ids, r_d, r_ids, r_d, k)  # dedupe the random init

    ids, dists = map_rows(init_rows, n, chunk)

    def round_fn(carry, _):
        ids, dists = carry

        def rows_fn(rows):
            r_ids = ids[rows]
            nbr2 = ids[jnp.maximum(r_ids, 0)][:, :, :sample].reshape(
                rows.shape[0], -1)                          # (c, k*sample)
            nbr2 = jnp.where(nbr2 == rows[:, None], -1, nbr2)
            d2 = _row_dists(db, rows, nbr2, metric)
            return _merge_topk(r_ids, dists[rows], nbr2, d2, k)

        return map_rows(rows_fn, n, chunk), None

    (ids, dists), _ = jax.lax.scan(round_fn, (ids, dists), None, length=rounds)
    return ids, dists


def build_knn(db: jnp.ndarray, k: int, metric: str, builder: str = "auto",
              rounds: int = 6, sample: int = 12, seed: int = 0,
              brute_threshold: int = 20_000) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n = db.shape[0]
    if builder == "auto":
        builder = "brute" if n <= brute_threshold else "nn_descent"
    if builder == "brute":
        return brute_force_knn(db, k, metric)
    return nn_descent(db, k, metric, rounds=rounds, sample=sample, seed=seed)


def medoid(db: jnp.ndarray, metric: str = "l2", sample: int = 4096, seed: int = 0) -> int:
    """Entry point: the vector closest to the dataset mean (cheap medoid)."""
    mean = jnp.mean(db, axis=0, keepdims=True)
    d = pairwise(mean, db, "l2")[0]
    return int(jnp.argmin(d))
