"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic specification; tests sweep shapes/dtypes and
assert_allclose kernel-vs-oracle.
"""
from __future__ import annotations

import jax.numpy as jnp


def batch_dist_ref(q: jnp.ndarray, x: jnp.ndarray, metric: str) -> jnp.ndarray:
    """(Q, d), (B, d) -> (Q, B) distance matrix."""
    if metric == "l2":
        qq = jnp.sum(q.astype(jnp.float32) ** 2, axis=-1, keepdims=True)
        xx = jnp.sum(x.astype(jnp.float32) ** 2, axis=-1)[None, :]
        qx = q.astype(jnp.float32) @ x.astype(jnp.float32).T
        return jnp.maximum(qq + xx - 2.0 * qx, 0.0)
    return -(q.astype(jnp.float32) @ x.astype(jnp.float32).T)


def gather_dist_ref(q: jnp.ndarray, db: jnp.ndarray, ids: jnp.ndarray,
                    metric: str) -> jnp.ndarray:
    """(Q, d) queries, (n, d) db, (Q, M) ids -> (Q, M) distances.

    Invalid ids (< 0) produce +inf.
    """
    vecs = db[jnp.maximum(ids, 0)].astype(jnp.float32)        # (Q, M, d)
    qf = q.astype(jnp.float32)
    if metric == "l2":
        diff = vecs - qf[:, None, :]
        out = jnp.sum(diff * diff, axis=-1)
    else:
        out = -jnp.einsum("qmd,qd->qm", vecs, qf)
    return jnp.where(ids >= 0, out, jnp.inf)


def sq_gather_dist_ref(q: jnp.ndarray, codes: jnp.ndarray,
                       scale: jnp.ndarray, zero: jnp.ndarray,
                       ids: jnp.ndarray, metric: str) -> jnp.ndarray:
    """(Q, d) queries, (n, d) u8 codes, (1, d) scale/zero, (Q, M) ids ->
    (Q, M) distances against the affine-dequantized rows
    (code * scale + zero). Invalid ids (< 0) produce +inf.
    """
    vecs = (codes[jnp.maximum(ids, 0)].astype(jnp.float32)
            * scale.reshape(-1)[None, None, :]
            + zero.reshape(-1)[None, None, :])
    qf = q.astype(jnp.float32)
    if metric == "l2":
        diff = vecs - qf[:, None, :]
        out = jnp.sum(diff * diff, axis=-1)
    else:
        out = -jnp.einsum("qmd,qd->qm", vecs, qf)
    return jnp.where(ids >= 0, out, jnp.inf)


def pq_adc_ref(lut: jnp.ndarray, codes: jnp.ndarray, ids: jnp.ndarray
               ) -> jnp.ndarray:
    """(Q, m, K) luts, (n, m) uint8 codes, (Q, B) ids -> (Q, B) ADC dists.

    dist[q, b] = sum_j lut[q, j, codes[ids[q, b], j]]; invalid ids -> +inf.
    """
    c = codes[jnp.maximum(ids, 0)].astype(jnp.int32)          # (Q, B, m)
    g = jnp.take_along_axis(lut[:, None, :, :], c[..., None], axis=-1)[..., 0]
    out = jnp.sum(g, axis=-1)
    return jnp.where(ids >= 0, out, jnp.inf)


def _unpack_nibbles_ref(packed: jnp.ndarray) -> jnp.ndarray:
    """(..., m//2) packed bytes -> (..., m) i32 codes (low nibble first)."""
    p = packed.astype(jnp.int32)
    lo = p & 0x0F
    hi = (p >> 4) & 0x0F
    return jnp.stack([lo, hi], axis=-1).reshape(
        packed.shape[:-1] + (2 * packed.shape[-1],))


def pq4_adc_ref(lut: jnp.ndarray, packed: jnp.ndarray, ids: jnp.ndarray
                ) -> jnp.ndarray:
    """(Q, m, 16) luts, (n, m//2) u8 nibble-packed codes, (Q, B) ids ->
    (Q, B) ADC dists; invalid ids -> +inf. Unpack-then-pq_adc semantics."""
    c = _unpack_nibbles_ref(packed[jnp.maximum(ids, 0)])      # (Q, B, m)
    g = jnp.take_along_axis(lut[:, None, :, :], c[..., None], axis=-1)[..., 0]
    out = jnp.sum(g, axis=-1)
    return jnp.where(ids >= 0, out, jnp.inf)


def sorted_block_ref(d: jnp.ndarray, ids: jnp.ndarray, L: int, n_beam: int):
    """Shared epilogue of the fused_expand family: mask invalid ids to
    +inf, stable-sort ascending (ties keep flat beam order), truncate to
    T = min(L, C), and report each beam expansion's best (minimum) distance
    plus its earlier-expansion exact-tie count (queue.block_ranks'
    ties_prior operand — Eq. 3 must rank a best behind same-iteration
    earlier-expansion entries that tie it).

    d (Q, C), ids (Q, C) with C divisible by n_beam ->
    (dists (Q, T) ascending, ids (Q, T) with -1 beyond the finite prefix,
    bests (Q, n_beam), ties (Q, n_beam) i32).
    """
    Q, C = d.shape
    T = min(L, C)
    d = jnp.where(ids >= 0, d, jnp.inf)
    order = jnp.argsort(d, axis=1, stable=True)
    sd = jnp.take_along_axis(d, order, axis=1)[:, :T]
    si = jnp.take_along_axis(ids, order, axis=1)[:, :T]
    si = jnp.where(jnp.isfinite(sd), si, -1)
    block = d.reshape(Q, n_beam, -1)
    bests = jnp.min(block, axis=2)
    eq = jnp.sum(block[:, None, :, :] == bests[:, :, None, None], axis=3)
    tri = (jnp.arange(n_beam)[None, :] < jnp.arange(n_beam)[:, None])[None]
    ties = jnp.sum(jnp.where(tri, eq, 0), axis=2).astype(jnp.int32)
    return sd, si, bests, ties


def fused_expand_ref(q: jnp.ndarray, db: jnp.ndarray, ids: jnp.ndarray,
                     metric: str, L: int, n_beam: int = 1):
    """(Q, d), (n, d), (Q, C) -> sorted top-min(L, C) candidate block +
    per-expansion bests; gather_dist then the sorted-block epilogue."""
    return sorted_block_ref(gather_dist_ref(q, db, ids, metric), ids,
                            L, n_beam)


def fused_expand_sq_ref(q: jnp.ndarray, codes: jnp.ndarray,
                        scale: jnp.ndarray, zero: jnp.ndarray,
                        ids: jnp.ndarray, metric: str, L: int,
                        n_beam: int = 1):
    """SQ twin: sq_gather_dist_ref then the sorted-block epilogue."""
    d = sq_gather_dist_ref(q, codes, scale, zero, ids, metric)
    return sorted_block_ref(d, ids, L, n_beam)


def fused_expand_pq_ref(lut: jnp.ndarray, codes: jnp.ndarray,
                        ids: jnp.ndarray, L: int, n_beam: int = 1):
    """PQ-ADC twin: pq_adc_ref then the sorted-block epilogue."""
    return sorted_block_ref(pq_adc_ref(lut, codes, ids), ids, L, n_beam)


def fused_expand_pq4_ref(lut: jnp.ndarray, packed: jnp.ndarray,
                         ids: jnp.ndarray, L: int, n_beam: int = 1):
    """PQ4 twin: pq4_adc_ref then the sorted-block epilogue."""
    return sorted_block_ref(pq4_adc_ref(lut, packed, ids), ids, L, n_beam)


def pq4_ivf_scan_ref(luts: jnp.ndarray, list_codes: jnp.ndarray,
                     list_ids: jnp.ndarray, probe_ids: jnp.ndarray, L: int):
    """pq4 twin of ivf_scan_ref: (nlist, max_len, m//2) packed list codes
    are unpacked to (nlist, max_len, m) and scanned identically."""
    return ivf_scan_ref(luts, _unpack_nibbles_ref(list_codes), list_ids,
                        probe_ids, L)


def bin_dist_ref(qcodes: jnp.ndarray, codes: jnp.ndarray, ids: jnp.ndarray
                 ) -> jnp.ndarray:
    """(Q, nw) u32 packed query signs, (n, nw) u32 packed db signs, (Q, B)
    ids -> (Q, B) f32 Hamming distances (XOR + popcount); invalid ids ->
    +inf. Tail bits past d are zero on both sides, so they never count."""
    import jax

    c = codes[jnp.maximum(ids, 0)]                    # (Q, B, nw)
    x = jnp.bitwise_xor(c, qcodes[:, None, :])
    out = jnp.sum(jax.lax.population_count(x), axis=-1).astype(jnp.float32)
    return jnp.where(ids >= 0, out, jnp.inf)


def fused_expand_bin_ref(qcodes: jnp.ndarray, codes: jnp.ndarray,
                         ids: jnp.ndarray, L: int, n_beam: int = 1):
    """bin twin: bin_dist_ref then the sorted-block epilogue."""
    return sorted_block_ref(bin_dist_ref(qcodes, codes, ids), ids, L, n_beam)


def bin_ivf_scan_ref(qcodes: jnp.ndarray, list_codes: jnp.ndarray,
                     list_ids: jnp.ndarray, probe_ids: jnp.ndarray, L: int):
    """bin twin of ivf_scan_ref: (Q, nw) u32 packed queries against
    (nlist, max_len, nw) u32 packed list codes; XOR+popcount Hamming,
    padding (-1) masked to +inf, per-list top-L."""
    import jax

    codes = list_codes[probe_ids]                     # (Q, P, max_len, nw)
    ids = list_ids[probe_ids]                         # (Q, P, max_len)
    x = jnp.bitwise_xor(codes, qcodes[:, None, None, :])
    d = jnp.sum(jax.lax.population_count(x), axis=-1).astype(jnp.float32)
    d = jnp.where(ids >= 0, d, jnp.inf)
    neg, pos = jax.lax.top_k(-d, L)
    out_ids = jnp.take_along_axis(ids, pos, axis=-1)
    return -neg, jnp.where(jnp.isfinite(neg), out_ids, -1)


def ivf_scan_ref(luts: jnp.ndarray, list_codes: jnp.ndarray,
                 list_ids: jnp.ndarray, probe_ids: jnp.ndarray, L: int):
    """(Q, Pl, m, K) luts (Pl = P, or 1 for probe-independent tables),
    (nlist, max_len, m) codes, (nlist, max_len) ids, (Q, P) probes ->
    per-list top-L (dists (Q, P, L) ascending, ids (Q, P, L)).

    ADC over every code of every probed list, padding (-1) masked to +inf,
    then each list independently reduced to its L best — the semantic spec
    of ivf_scan's fused scan + partial reduction.
    """
    import jax

    codes = list_codes[probe_ids]                     # (Q, P, max_len, m)
    ids = list_ids[probe_ids]                         # (Q, P, max_len)
    # one subspace at a time: a (Q, P, max_len) table read per j, never a
    # (Q, P, max_len, m) f32 temporary (1.2 GB at a 256-query bucket, 24
    # probes and 3k-long lists)
    d = jnp.zeros(ids.shape, jnp.float32)
    for j in range(codes.shape[-1]):
        d = d + jnp.take_along_axis(luts[:, :, j, :],
                                    codes[..., j].astype(jnp.int32), axis=-1)
    d = jnp.where(ids >= 0, d, jnp.inf)
    neg, pos = jax.lax.top_k(-d, L)
    out_ids = jnp.take_along_axis(ids, pos, axis=-1)
    return -neg, jnp.where(jnp.isfinite(neg), out_ids, -1)
