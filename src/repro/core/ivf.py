"""IVF-PQ index: coarse k-means partitioning + residual product codes.

The partition-based sibling of the graph index (DESIGN.md §4), behind the
same KBest facade. Memory layout is TPU-first: inverted lists are PADDED
DENSE ARRAYS — `list_ids (nlist, max_len)` int32 with -1 padding and
`list_codes (nlist, max_len, m)` uint8 — not ragged CPU-style postings, so a
probed list is one contiguous DMA and the batched ADC scan (H1's 2-D lift)
runs without gather/scatter inside the kernel. `max_len` is padded to the
lane-width multiple (H3 alignment analogue, IVFConfig.list_pad). With
QuantConfig.kind="pq4" (DESIGN.md §13) the fine codes are 4-bit and
nibble-packed — `list_codes (nlist, max_len, m//2)`, half the bytes —
and the scan dispatches to the pq4_ivf_scan kernel. With kind="bin"
(DESIGN.md §14) the lists hold u32-packed sign codes —
`list_codes (nlist, max_len, ceil(d/32))` — and the scan is XOR+popcount
Hamming (bin_ivf_scan) with no LUT stage at all.

Search pipeline (mirrors the three-stage ScaNN/KScaNN shape):
  1. coarse probe: exact query-to-centroid distances, top-nprobe clusters
     (assignment space is L2; for ip/cosine the probe ranking still uses the
     index metric so high-|x| clusters are probed under ip);
  2. fused ADC scan of the probed lists with per-list partial top-L
     (kernels/ivf_scan, jnp reference in kernels/ref.py), then a global
     top-L merge across the nprobe partial lists;
  3. exact re-rank of the survivors from the full-precision vectors — done
     by the caller (KBest._rerank) via the gather_dist path.

Residual encoding (IVFConfig.residual): codes quantize r = x - c(x). For L2
the per-probe LUT is built from q - c_p, so summed ADC approximates
||q - c_p - r_hat||^2 = ||q - x_hat||^2 exactly in PQ's subspace sense. For
ip the LUT is built from q directly (⟨q, x_hat⟩ = ⟨q, c_p⟩ + ⟨q, r_hat⟩)
with the constant ⟨q, c_p⟩ folded into subspace 0 of the table, keeping the
kernel metric-agnostic: it only ever sums m table reads.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize as qz
from repro.core.distance import pairwise
from repro.core.types import IVFConfig, QuantConfig


@dataclasses.dataclass
class IVFState:
    """Built IVF-PQ index (all device arrays; see module docstring)."""

    centroids: jnp.ndarray    # (nlist, d) f32 coarse codebook
    list_ids: jnp.ndarray     # (nlist, max_len) i32, -1 padded
    list_codes: jnp.ndarray   # (nlist, max_len, m) u8 residual PQ codes,
                              # (nlist, max_len, m//2) nibble-packed pq4,
                              # or (nlist, max_len, ceil(d/32)) u32 bin
    pq: Optional[qz.PQState]  # fine codebooks (m, K, ds); K=256 pq / 16
                              # pq4; None for the bin codec
    residual: bool
    packed: bool = False      # True => pq4 nibble-packed list_codes
    bin: Optional[qz.BinState] = None  # set => 1-bit sign codec lists
                                       # (DESIGN.md §14)

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def max_len(self) -> int:
        return self.list_ids.shape[1]


# a pytree, like the quantizer states: arrays are leaves (jit arguments of
# the served program), the layout flags static metadata
jax.tree_util.register_dataclass(
    IVFState, data_fields=["centroids", "list_ids", "list_codes", "pq", "bin"],
    meta_fields=["residual", "packed"])


def auto_nlist(n: int) -> int:
    """sqrt(n) heuristic, clamped so tiny corpora still get >= 2 cells."""
    return max(2, min(n, int(round(float(np.sqrt(n))))))


# ---------------------------------------------------------------------- build
def build_ivf(x: jnp.ndarray, ivf_cfg: IVFConfig, quant_cfg: QuantConfig
              ) -> IVFState:
    """Train coarse + fine quantizers and lay out the padded lists.

    Assignment is L2 nearest-centroid regardless of metric (the standard
    IVF choice: residuals stay small, and for cosine the vectors are already
    unit-norm so L2 and angular assignment agree).
    """
    n, d = x.shape
    nlist = ivf_cfg.nlist if ivf_cfg.nlist > 0 else auto_nlist(n)
    nlist = min(nlist, n)
    cents = qz.kmeans(x, nlist, ivf_cfg.kmeans_iters, seed=ivf_cfg.seed)
    assign = jnp.argmin(pairwise(x, cents, "l2"), axis=1)

    if quant_cfg.kind == "bin":
        # 1-bit codec (DESIGN.md §14): signs of the ROTATED RAW vectors,
        # not residuals — Hamming between raw-sign codes is the quantity
        # the rescore bound speaks to, and a shared rotation means one
        # query encoding serves every probed list (no per-probe LUTs)
        pq, packed = None, False
        bin_state = qz.bin_train(x, quant_cfg)
        codes = qz.bin_encode(bin_state, x)             # (n, nw) u32
    else:
        bin_state = None
        vecs = x - cents[assign] if ivf_cfg.residual else x
        pq = qz.pq_train(vecs, quant_cfg)
        packed = quant_cfg.kind == "pq4"
        codes = qz.pq_encode(pq.codebooks, vecs)        # (n, m), values < K
        if packed:
            codes = qz.pq4_pack(codes)                  # (n, m//2)

    # host-side list layout: bucket ids by cluster, pad to a common max_len
    # (vectorized: stable sort by cluster, then scatter each point to its
    # rank within the cluster — no per-point Python loop)
    assign_h = np.asarray(assign)
    codes_h = np.asarray(codes)
    counts = np.bincount(assign_h, minlength=nlist)
    pad = ivf_cfg.list_pad
    max_len = int(-(-max(int(counts.max()), 1) // pad) * pad)
    order = np.argsort(assign_h, kind="stable")
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    slot = np.arange(n) - starts[assign_h[order]]       # rank within cluster
    list_ids = np.full((nlist, max_len), -1, np.int32)
    # dtype follows the codes (u8 for pq/pq4/sq, u32 words for bin)
    list_codes = np.zeros((nlist, max_len, codes_h.shape[1]), codes_h.dtype)
    list_ids[assign_h[order], slot] = order.astype(np.int32)
    list_codes[assign_h[order], slot] = codes_h[order]

    return IVFState(centroids=cents, list_ids=jnp.asarray(list_ids),
                    list_codes=jnp.asarray(list_codes), pq=pq,
                    residual=ivf_cfg.residual, packed=packed,
                    bin=bin_state)


# --------------------------------------------------------------------- search
def select_probes(state: IVFState, q: jnp.ndarray, nprobe: int, metric: str
                  ) -> jnp.ndarray:
    """(Q, d) -> (Q, P) nearest-centroid ids under the index metric."""
    P = min(nprobe, state.nlist)
    d = pairwise(q, state.centroids, metric)
    _, probes = jax.lax.top_k(-d, P)
    return probes.astype(jnp.int32)


def query_luts(state: IVFState, q: jnp.ndarray, probes: jnp.ndarray,
               metric: str, lut_u8: bool = False
               ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """ADC tables (Q, Pl, m, K) plus an optional per-probe bias (Q, P).

    Pl is P only when the table truly differs per probe (l2 residual);
    probe-independent tables stay Pl=1 so the scan kernel never
    materializes nprobe redundant copies. The ip-residual centroid term
    -<q, c_p> is a per-list constant, so it is returned as a separate bias
    added AFTER the per-list partial top-L (a constant shift cannot change
    within-list ranking) rather than folded into the table.
    See the module docstring for the residual/metric algebra.
    """
    Q, P = probes.shape
    books = state.pq.codebooks
    m, K, _ = books.shape
    requant = qz.pq4_requant_lut if lut_u8 else (lambda t: t)
    if metric == "l2" and state.residual:
        cents = state.centroids[probes]                 # (Q, P, d)
        qr = q[:, None, :] - cents
        lut = requant(qz.pq_query_tables(books, qr.reshape(Q * P, -1), "l2"))
        return lut.reshape(Q, P, m, K), None
    lut = requant(qz.pq_query_tables(books, q, metric)).reshape(Q, 1, m, K)
    if metric != "l2" and state.residual:
        bias = -jnp.einsum("qd,qpd->qp", q, state.centroids[probes])
        return lut, bias
    return lut, None


def scan_lists(state: IVFState, luts: jnp.ndarray, probes: jnp.ndarray,
               L: int, impl: str = "ref",
               bias: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused scan + per-list partial top-L, then the global top-L merge.
    Returns (dists (Q, L) ascending approx distances, ids (Q, L), -1 pad)."""
    Lp = min(L, state.max_len)
    if impl == "kernel":
        from repro.kernels import ops as kops
        scan = kops.pq4_ivf_scan if state.packed else kops.ivf_scan
        pd, pi = scan(luts, state.list_codes, state.list_ids, probes, L=Lp)
    else:
        from repro.kernels.ref import ivf_scan_ref, pq4_ivf_scan_ref
        scan = pq4_ivf_scan_ref if state.packed else ivf_scan_ref
        pd, pi = scan(luts, state.list_codes, state.list_ids, probes, Lp)
    if bias is not None:
        pd = pd + bias[:, :, None]      # +inf padding stays +inf
    Q = probes.shape[0]
    flat_d = pd.reshape(Q, -1)                          # (Q, P*Lp)
    flat_i = pi.reshape(Q, -1)
    k = min(L, flat_d.shape[1])
    neg, pos = jax.lax.top_k(-flat_d, k)
    ids = jnp.take_along_axis(flat_i, pos, axis=1)
    return -neg, jnp.where(jnp.isfinite(neg), ids, -1)


def scan_bin_lists(state: IVFState, qcodes: jnp.ndarray,
                   probes: jnp.ndarray, L: int, impl: str = "ref"
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """bin twin of scan_lists: XOR+popcount Hamming over the probed packed
    lists, per-list partial top-L, then the same global top-L merge.
    Returns (dists (Q, L) ascending Hamming, ids (Q, L), -1 pad)."""
    Lp = min(L, state.max_len)
    if impl == "kernel":
        from repro.kernels import ops as kops
        pd, pi = kops.bin_ivf_scan(qcodes, state.list_codes, state.list_ids,
                                   probes, L=Lp)
    else:
        from repro.kernels.ref import bin_ivf_scan_ref
        pd, pi = bin_ivf_scan_ref(qcodes, state.list_codes, state.list_ids,
                                  probes, Lp)
    Q = probes.shape[0]
    flat_d = pd.reshape(Q, -1)                          # (Q, P*Lp)
    flat_i = pi.reshape(Q, -1)
    k = min(L, flat_d.shape[1])
    neg, pos = jax.lax.top_k(-flat_d, k)
    ids = jnp.take_along_axis(flat_i, pos, axis=1)
    return -neg, jnp.where(jnp.isfinite(neg), ids, -1)


def search_ivf(state: IVFState, q: jnp.ndarray, nprobe: int, L: int,
               metric: str, impl: str = "ref", lut_u8: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stages 1+2 of the pipeline: probe, scan, merge.

    Returns (approx dists (Q, L), candidate ids (Q, L), probes (Q, P)) —
    the caller re-ranks the candidates with exact distances (stage 3) and
    can derive scan-cost stats from the probe set (see scanned_counts).

    Traversal-only SearchConfig knobs (`beam_width`, `batch_B`,
    `visited_mode`) do not reach this path: the IVF scan is already a
    dense multi-candidate expansion — every probed list is a "beam slot"
    of max_len candidates — so results are identical for any beam_width
    (pinned in tests/test_beam.py) and only (L, nprobe, dist_impl,
    quant) key its behavior. The stages carry the named scopes
    `kbest.probe`, `kbest.lut` and `kbest.scan`.
    """
    with jax.named_scope("kbest.probe"):
        probes = select_probes(state, q, nprobe, metric)
    if state.bin is not None:
        # bin codec: one packed query encoding serves every probed list
        # (no per-probe LUT machinery — DESIGN.md §14)
        with jax.named_scope("kbest.lut"):
            qcodes = qz.bin_query_codes(state.bin, q)
        with jax.named_scope("kbest.scan"):
            dists, ids = scan_bin_lists(state, qcodes, probes, L, impl)
        return dists, ids, probes
    with jax.named_scope("kbest.lut"):
        luts, bias = query_luts(state, q, probes, metric, lut_u8=lut_u8)
    with jax.named_scope("kbest.scan"):
        dists, ids = scan_lists(state, luts, probes, L, impl, bias=bias)
    return dists, ids, probes


def scanned_counts(state: IVFState, probes: jnp.ndarray) -> jnp.ndarray:
    """(Q, P) probes -> (Q,) valid codes scanned (stats only — O(index)
    work, so callers should gate it behind their with_stats flag)."""
    n_valid = jnp.sum(state.list_ids >= 0, axis=1)      # (nlist,)
    return jnp.sum(n_valid[probes], axis=1).astype(jnp.int32)
