"""Vector quantization (paper §3.2, A4): PQ and SQ as pluggable modules.

The paper exposes quantization behind a standalone interface so algorithms
can be swapped without touching the search core; we mirror that:

  Quantizer.train(db)      -> state (codebooks / scales)
  Quantizer.encode(db)     -> codes
  Quantizer.query_tables(q)-> per-query operand passed to search() as the
                              "queries" array (the search loop is agnostic)
  Quantizer.make_dist_fn() -> DistFn consuming (codes, tables, nbr_ids) —
                              the codes are an operand, never captured

PQ distance is ADC (asymmetric distance computation): per query build an
(m, K) lookup table of subspace distances; a database code (m,) then costs m
table reads. On TPU the LUT gather is computed either by take_along_axis
(ref) or the pq_adc Pallas kernel via one-hot contraction on the MXU
(DESIGN.md §2).

Two PQ code widths (DESIGN.md §13):
  kind="pq"  — 8-bit codes, K=256 centroids/sub-codebook, one byte/code.
  kind="pq4" — 4-bit fast-scan codes, K=16, TWO codes packed per byte
               (low nibble = even subspace 2j, high nibble = odd 2j+1).
               The (m, 16) LUT is 16x smaller, so it stays resident in
               VMEM/registers during the scan; optionally the LUT is
               requantized to u8 per query (pq4_requant_lut) as in x86
               fast-scan, trading a bounded distance error (<= m*step/2)
               for byte-wide table arithmetic.

One extreme-compression codec (DESIGN.md §14):
  kind="bin" — 1 bit/dimension: a seeded random orthonormal rotation
               (QR of a Gaussian) followed by sign quantization, packed
               into ceil(d/32) uint32 words per vector. The first-pass
               distance is Hamming (XOR + popcount) between packed query
               and database codes; the RaBitQ-style estimator error is
               absorbed by overfetching SearchConfig.rescore_factor * k
               candidates and re-ranking them exactly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import QUANT_KINDS, QuantConfig


# --------------------------------------------------------------------------
# k-means (shared by PQ training)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("k", "iters"))
def kmeans(x: jnp.ndarray, k: int, iters: int, seed: int = 0) -> jnp.ndarray:
    """Lloyd's algorithm; returns (k, d) centroids. Empty clusters keep
    their previous centroid (standard fix)."""
    n, d = x.shape
    key = jax.random.PRNGKey(seed)
    init_idx = jax.random.choice(key, n, (k,), replace=n < k)
    cents = x[init_idx]

    def step(cents, _):
        d2 = (jnp.sum(x * x, 1, keepdims=True) + jnp.sum(cents * cents, 1)[None]
              - 2.0 * x @ cents.T)
        assign = jnp.argmin(d2, axis=1)
        sums = jax.ops.segment_sum(x, assign, num_segments=k)
        cnts = jax.ops.segment_sum(jnp.ones((n,), x.dtype), assign, num_segments=k)
        new = jnp.where(cnts[:, None] > 0, sums / jnp.maximum(cnts[:, None], 1), cents)
        return new, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return cents


# --------------------------------------------------------------------------
# Product quantization
# --------------------------------------------------------------------------
@dataclasses.dataclass
class PQState:
    codebooks: jnp.ndarray  # (m, K, ds); K=256 for pq, 16 for pq4
    m: int
    ds: int

    @property
    def ksub(self) -> int:
        return self.codebooks.shape[1]


# The quantizer states are pytrees (arrays as leaves, sizes as static
# metadata), so a whole index can be handed to a jitted program as an
# argument (serve/engine.py) instead of being captured as constants.
jax.tree_util.register_dataclass(PQState, data_fields=["codebooks"],
                                 meta_fields=["m", "ds"])


def pq_train(db: jnp.ndarray, cfg: QuantConfig) -> PQState:
    """Train per-subspace codebooks; K follows cfg.kind (256 or 16)."""
    n, d = db.shape
    m = cfg.pq_m
    assert d % m == 0, f"dim {d} not divisible by pq_m {m}"
    ds = d // m
    K = cfg.ksub if cfg.kind in ("pq", "pq4") else 256
    subs = db.reshape(n, m, ds).transpose(1, 0, 2)  # (m, n, ds)
    books = jnp.stack([
        kmeans(subs[j], K, cfg.kmeans_iters, seed=cfg.seed + j)
        for j in range(m)
    ])
    return PQState(codebooks=books, m=m, ds=ds)


# Bytes of (rows, m, K) subspace distances one pq_encode chunk may hold:
# a 1M-row corpus scored against (16, 256) centroids at once is 16 GB.
ENCODE_BUDGET = 1 << 28


@jax.jit
def pq_encode(state_books: jnp.ndarray, db: jnp.ndarray) -> jnp.ndarray:
    """(n, d) -> (n, m) uint8 codes, ENCODE_BUDGET bytes of distances at a
    time."""
    from repro.core.build import map_rows
    m, K, ds = state_books.shape
    n = db.shape[0]
    bb = jnp.sum(state_books * state_books, -1)[None]

    def encode_rows(rows):
        subs = db[rows].reshape(rows.shape[0], m, ds)
        d2 = (jnp.sum(subs * subs, -1)[:, :, None] + bb
              - 2.0 * jnp.einsum("nmd,mkd->nmk", subs, state_books))
        return jnp.argmin(d2, axis=-1).astype(jnp.uint8)

    return map_rows(encode_rows, n, max(1, ENCODE_BUDGET // (4 * m * K)))


@functools.partial(jax.jit, static_argnames=("metric",))
def pq_query_tables(state_books: jnp.ndarray, queries: jnp.ndarray, metric: str
                    ) -> jnp.ndarray:
    """Per-query ADC lookup tables, flattened to (Q, m*256).

    l2: LUT[j, c] = ||q_j - C[j, c]||^2  (sums to ||q - x_hat||^2)
    ip: LUT[j, c] = -<q_j, C[j, c]>      (sums to -<q, x_hat>)
    """
    m, K, ds = state_books.shape
    Q = queries.shape[0]
    qs = queries.reshape(Q, m, ds)
    if metric == "l2":
        lut = (jnp.sum(qs * qs, -1)[:, :, None]
               + jnp.sum(state_books * state_books, -1)[None]
               - 2.0 * jnp.einsum("qmd,mkd->qmk", qs, state_books))
    else:
        lut = -jnp.einsum("qmd,mkd->qmk", qs, state_books)
    return lut.reshape(Q, m * K)


@functools.lru_cache(maxsize=None)
def pq_make_dist_fn(m: int, impl: str = "ref"):
    """DistFn over (n, m) PQ codes; `tables` (the search "queries") is
    (Q, m*256)."""
    K = 256

    if impl == "kernel":
        from repro.kernels import ops as kops

        def fn(codes, tables, nbr_ids):
            return kops.pq_adc(tables.reshape(tables.shape[0], m, K),
                               codes, nbr_ids)
        return fn

    def fn(codes, tables, nbr_ids):
        Q = tables.shape[0]
        lut = tables.reshape(Q, m, K)
        c = codes[jnp.maximum(nbr_ids, 0)]          # (Q, B, m) uint8
        g = jnp.take_along_axis(
            lut[:, None, :, :],                     # (Q, 1, m, K)
            c[..., None].astype(jnp.int32),         # (Q, B, m, 1)
            axis=-1)[..., 0]
        return jnp.sum(g, axis=-1)
    return fn


# --------------------------------------------------------------------------
# 4-bit fast-scan product quantization (DESIGN.md §13)
# --------------------------------------------------------------------------
def pq4_pack(codes: jnp.ndarray) -> jnp.ndarray:
    """(n, m) 4-bit codes (values < 16) -> (n, m//2) uint8, two per byte.

    Byte j holds subspace 2j in the LOW nibble and 2j+1 in the HIGH nibble,
    so a SIMD lane reading byte j serves two adjacent LUT rows.
    """
    n, m = codes.shape
    assert m % 2 == 0, m
    c = codes.astype(jnp.uint8)
    return (c[:, 0::2] | (c[:, 1::2] << 4)).astype(jnp.uint8)


def pq4_unpack(packed: jnp.ndarray) -> jnp.ndarray:
    """(..., m//2) packed bytes -> (..., m) int32 codes in [0, 16)."""
    lo = (packed & 0x0F).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    return jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1]
                                                + (2 * packed.shape[-1],))


def pq4_encode(state_books: jnp.ndarray, db: jnp.ndarray) -> jnp.ndarray:
    """(n, d) -> (n, m//2) uint8 nibble-packed codes (codebooks (m, 16, ds))."""
    assert state_books.shape[1] == 16, state_books.shape
    return pq4_pack(pq_encode(state_books, db))


def pq4_requant_lut(lut: jnp.ndarray) -> jnp.ndarray:
    """Fast-scan LUT requantization, applied per query.

    Each query's table is affinely mapped to u8 (step = (max-min)/255 over
    the whole (m, K) table) and mapped back, so every downstream consumer —
    ref gather, Pallas kernel, tests — sees exactly the distances a u8
    table walk would produce. The ADC sum error is bounded by m*step/2
    (each of the m reads is off by at most step/2); on real hardware the u8
    table is what lives in registers and this fold-back is free.

    lut: (Q, T) flattened tables. Returns same-shape f32.
    """
    lo = jnp.min(lut, axis=1, keepdims=True)
    hi = jnp.max(lut, axis=1, keepdims=True)
    step = jnp.maximum(hi - lo, 1e-12) / 255.0
    q = jnp.clip(jnp.round((lut - lo) / step), 0, 255)
    return q * step + lo


def pq4_query_tables(state_books: jnp.ndarray, queries: jnp.ndarray,
                     metric: str, lut_u8: bool = False) -> jnp.ndarray:
    """Per-query (m, 16) ADC tables, flattened to (Q, m*16).

    Same algebra as pq_query_tables (K=16); with lut_u8 the table goes
    through the fast-scan u8 requantization (pq4_requant_lut).
    """
    lut = pq_query_tables(state_books, queries, metric)
    return pq4_requant_lut(lut) if lut_u8 else lut


@functools.lru_cache(maxsize=None)
def pq4_make_dist_fn(m: int, impl: str = "ref"):
    """DistFn over (n, m//2) nibble-packed PQ4 codes; `tables` is
    (Q, m*16)."""
    K = 16

    if impl == "kernel":
        from repro.kernels import ops as kops

        def fn(packed, tables, nbr_ids):
            return kops.pq4_adc(tables.reshape(tables.shape[0], m, K),
                                packed, nbr_ids)
        return fn

    def fn(packed, tables, nbr_ids):
        Q = tables.shape[0]
        lut = tables.reshape(Q, m, K)
        c = pq4_unpack(packed[jnp.maximum(nbr_ids, 0)])   # (Q, B, m) i32
        g = jnp.take_along_axis(lut[:, None, :, :], c[..., None], axis=-1)[..., 0]
        return jnp.sum(g, axis=-1)
    return fn


# --------------------------------------------------------------------------
# Scalar quantization (int8 per-dimension affine)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class SQState:
    scale: jnp.ndarray   # (d,)
    zero: jnp.ndarray    # (d,)


jax.tree_util.register_dataclass(SQState, data_fields=["scale", "zero"],
                                 meta_fields=[])


def sq_train(db: jnp.ndarray) -> SQState:
    lo = jnp.min(db, axis=0)
    hi = jnp.max(db, axis=0)
    scale = jnp.maximum(hi - lo, 1e-12) / 255.0
    return SQState(scale=scale, zero=lo)


def sq_encode(state: SQState, db: jnp.ndarray) -> jnp.ndarray:
    return _sq_encode(state.scale, state.zero, db)


@jax.jit
def _sq_encode(scale: jnp.ndarray, zero: jnp.ndarray, db: jnp.ndarray
               ) -> jnp.ndarray:
    q = jnp.round((db - zero[None]) / scale[None])
    return jnp.clip(q, 0, 255).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def sq_make_dist_fn(metric: str, impl: str = "ref"):
    """DistFn with on-the-fly dequantization; its operand is the pair
    (codes (n, d) u8, SQState).

    impl="kernel" routes through the fused sq_gather_dist Pallas kernel
    (u8 rows gathered by scalar-prefetch, dequantized in-VMEM); impl="ref"
    is the jnp gather+dequant oracle. Historical bug: this function used to
    ignore `impl`, so dist_impl="kernel" SQ runs silently took — and were
    benchmarked as — the ref path under a ("sq", "kernel") cache key.
    """
    if impl == "kernel":
        from repro.kernels import ops as kops

        def fn(data, queries, nbr_ids):
            codes, state = data
            return kops.sq_gather_dist(queries, codes, state.scale,
                                       state.zero, nbr_ids, metric=metric)
        return fn

    from repro.core.distance import batched_one_to_many

    def fn(data, queries, nbr_ids):
        codes, state = data
        c = codes[jnp.maximum(nbr_ids, 0)].astype(jnp.float32)
        vecs = c * state.scale[None, None, :] + state.zero[None, None, :]
        return batched_one_to_many(queries, vecs, metric)
    return fn


# --------------------------------------------------------------------------
# 1-bit binary quantization (random-rotation sign codec, DESIGN.md §14)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class BinState:
    rot: jnp.ndarray    # (d, d) f32 orthonormal rotation (QR of a Gaussian)

    @property
    def dim(self) -> int:
        return self.rot.shape[0]

    @property
    def n_words(self) -> int:
        return -(-self.dim // 32)


jax.tree_util.register_dataclass(BinState, data_fields=["rot"],
                                 meta_fields=[])


def _random_rotation(d: int, seed: int) -> jnp.ndarray:
    """Orthonormal (d, d) rotation: QR of a seeded Gaussian, with the R
    diagonal sign-fixed so the factorization (and thus every code) is a
    deterministic function of the seed."""
    g = jax.random.normal(jax.random.PRNGKey(seed), (d, d), jnp.float32)
    q, r = jnp.linalg.qr(g)
    s = jnp.sign(jnp.diagonal(r))
    return q * jnp.where(s == 0, 1.0, s)[None, :]


def pack_signs(bits: jnp.ndarray) -> jnp.ndarray:
    """(n, d) sign bits ({0,1}, any int/bool dtype) -> (n, ceil(d/32))
    uint32. Bit b of word w holds dimension 32*w + b; tail dimensions of
    the last word (d not a multiple of 32) are zero on BOTH query and
    database codes, so they XOR to 0 and never contribute to Hamming."""
    n, d = bits.shape
    nw = -(-d // 32)
    b = bits.astype(jnp.uint32)
    if nw * 32 != d:
        b = jnp.pad(b, ((0, 0), (0, nw * 32 - d)))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    # disjoint bit positions: the uint32 sum is carry-free, i.e. an OR
    return jnp.sum(b.reshape(n, nw, 32) << shifts[None, None, :],
                   axis=-1, dtype=jnp.uint32)


def unpack_signs(packed: jnp.ndarray, d: int) -> jnp.ndarray:
    """(n, ceil(d/32)) uint32 -> (n, d) uint8 sign bits (pack_signs inverse)."""
    n, nw = packed.shape
    assert nw * 32 >= d, (nw, d)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(n, nw * 32)[:, :d].astype(jnp.uint8)


def bin_train(db: jnp.ndarray, cfg: QuantConfig) -> BinState:
    """"Training" is just drawing the rotation — data-independent, so the
    codec never needs retraining as the corpus changes."""
    return BinState(rot=_random_rotation(db.shape[1], cfg.seed))


@jax.jit
def _bin_encode(rot: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    return pack_signs((x @ rot >= 0).astype(jnp.uint32))


def bin_encode(state: BinState, db: jnp.ndarray) -> jnp.ndarray:
    """(n, d) f32 -> (n, ceil(d/32)) uint32 packed sign codes."""
    return _bin_encode(state.rot, db)


def bin_query_codes(state: BinState, queries: jnp.ndarray) -> jnp.ndarray:
    """Query-side operand passed to search() as the "queries" array: the
    SAME rotation+sign+pack as the database side (symmetric Hamming)."""
    return _bin_encode(state.rot, queries)


@functools.lru_cache(maxsize=None)
def bin_make_dist_fn(impl: str = "ref"):
    """DistFn over (n, nw) packed bin codes; `qcodes` (the search
    "queries") is (Q, nw) uint32. Distances are exact integer Hamming
    counts in f32."""
    if impl == "kernel":
        from repro.kernels import ops as kops

        def fn(codes, qcodes, nbr_ids):
            return kops.bin_dist(qcodes, codes, nbr_ids)
        return fn

    from repro.kernels.ref import bin_dist_ref

    def fn(codes, qcodes, nbr_ids):
        return bin_dist_ref(qcodes, codes, nbr_ids)
    return fn


# --------------------------------------------------------------------------
# Quant-kind registry (sweeps) and the code-size accounting they report
# --------------------------------------------------------------------------
def quant_variants(pq_m: int = 16) -> dict:
    """Named QuantConfig kwargs for every quantization variant — THE list
    sweeps enumerate (core/tune.py, benchmarks/ablation.py), so a new kind
    added here (and to types.QUANT_KINDS, which tests assert this registry
    covers) appears in every sweep automatically. pq_m must divide the
    dataset dim; "bin" and "sq" ignore it."""
    return {
        "full": dict(kind="none"),
        "pq8": dict(kind="pq", pq_m=pq_m),
        "pq4": dict(kind="pq4", pq_m=pq_m),
        "pq4+u8lut": dict(kind="pq4", pq_m=pq_m, pq4_lut_u8=True),
        "sq": dict(kind="sq"),
        "bin": dict(kind="bin"),
    }


# The IVF-capable subset of the registry: build_ivf has explicit codecs
# only for these — any other kind silently trains the default 8-bit PQ
# fine stage, so an "ivf-sq" sweep row would really measure ivf-pq.
# THE list the benchmarks derive their ivf-* rows from
# (benchmarks/qps_recall.py); kbest-lint asserts it stays a subset of
# types.QUANT_KINDS.
IVF_QUANT_KINDS = ("pq", "pq4", "bin")


def code_bytes_per_vector(idx) -> int:
    """Stored code bytes per database vector (the A4 memory axis), dtype-
    aware: pq/pq4/sq codes are uint8 (1 byte/element) but bin codes are
    uint32 words (4 bytes/element). Takes a KBest (duck-typed)."""
    for arr in (getattr(idx, "ivf", None) and idx.ivf.list_codes,
                getattr(idx, "bin_codes", None),
                getattr(idx, "pq_codes", None),
                getattr(idx, "sq_codes", None)):
        if arr is not None:
            return int(arr.shape[-1]) * arr.dtype.itemsize
    return 4 * int(idx.db.shape[-1])            # f32 full vectors
