"""Public jit'd wrappers over the Pallas kernels.

Handles: lane-width padding for the tiled batch_dist matmul (d -> multiple
of 128, the H3 alignment analogue), tile padding of Q/B, interpret-mode
auto-detection (the CPU backend runs kernels in interpret mode for
validation; a TPU compiles them through Mosaic), and clamping of the list
scans' per-list depth L to the padded list length. The row-gather kernels
take any d (their (1, 1, d) row blocks span the whole row), so the corpus
and codes reach them unpadded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import batch_dist as _bd
from repro.kernels import bin_hamming as _bh
from repro.kernels import gather_dist as _gd
from repro.kernels import ivf_scan as _iv
from repro.kernels import pq4_scan as _p4
from repro.kernels import pq_adc as _pq

LANE = 128


def _on_cpu() -> bool:
    return jax.devices()[0].platform == "cpu"


def _pad_dim(a: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def batch_dist(q: jnp.ndarray, x: jnp.ndarray, *, metric: str = "l2",
               tq: int = 128, tb: int = 128) -> jnp.ndarray:
    """(Q, d) x (B, d) -> (Q, B); any shapes, padding handled here."""
    Q, B = q.shape[0], x.shape[0]
    qp = _pad_dim(_pad_dim(q, 1, LANE), 0, tq)
    xp = _pad_dim(_pad_dim(x, 1, LANE), 0, tb)
    out = _bd.batch_dist(qp, xp, metric=metric, tq=tq, tb=tb,
                         interpret=_on_cpu())
    return out[:Q, :B]


def gather_dist(q: jnp.ndarray, db: jnp.ndarray, ids: jnp.ndarray, *,
                metric: str = "l2") -> jnp.ndarray:
    """(Q, d), (n, d), (Q, M) -> (Q, M); -1 ids produce +inf."""
    return _gd.gather_dist(q, db, ids, metric=metric, interpret=_on_cpu())


def pq_adc(lut: jnp.ndarray, codes: jnp.ndarray, ids: jnp.ndarray
           ) -> jnp.ndarray:
    """(Q, m, K), (n, m) u8, (Q, B) -> (Q, B); -1 ids produce +inf."""
    return _pq.pq_adc(lut, codes, ids, interpret=_on_cpu())


def pq4_adc(lut: jnp.ndarray, packed: jnp.ndarray, ids: jnp.ndarray
            ) -> jnp.ndarray:
    """(Q, m, 16), (n, m//2) u8 nibble-packed, (Q, B) -> (Q, B); -1 -> +inf."""
    return _p4.pq4_adc(lut, packed, ids, interpret=_on_cpu())


def bin_dist(qcodes: jnp.ndarray, codes: jnp.ndarray, ids: jnp.ndarray
             ) -> jnp.ndarray:
    """(Q, nw) u32 packed queries, (n, nw) u32 packed codes, (Q, B) ->
    (Q, B) exact Hamming; -1 ids produce +inf."""
    return _bh.bin_dist(qcodes, codes, ids, interpret=_on_cpu())


def sq_gather_dist(q: jnp.ndarray, codes: jnp.ndarray, scale: jnp.ndarray,
                   zero: jnp.ndarray, ids: jnp.ndarray, *,
                   metric: str = "l2") -> jnp.ndarray:
    """Fused SQ gather+dequant+distance: (Q, d), (n, d) u8, (d,), (d,),
    (Q, M) -> (Q, M); -1 ids produce +inf."""
    return _gd.sq_gather_dist(q, codes, scale.reshape(1, -1),
                              zero.reshape(1, -1), ids, metric=metric,
                              interpret=_on_cpu())


def fused_expand(q: jnp.ndarray, db: jnp.ndarray, ids: jnp.ndarray, *,
                 metric: str = "l2", L: int, n_beam: int = 1):
    """Fused beam-expansion step over full vectors (DESIGN.md §2):
    (Q, d), (n, d), (Q, C) ids -> (sorted dists (Q, T), ids (Q, T),
    per-expansion bests (Q, n_beam), earlier-expansion tie counts
    (Q, n_beam)) with T = min(L, C); -1 ids -> +inf."""
    return _gd.fused_expand(q, db, ids, metric=metric, L=L,
                            n_beam=n_beam, interpret=_on_cpu())


def fused_expand_sq(q: jnp.ndarray, codes: jnp.ndarray, scale: jnp.ndarray,
                    zero: jnp.ndarray, ids: jnp.ndarray, *,
                    metric: str = "l2", L: int, n_beam: int = 1):
    """SQ twin of fused_expand."""
    return _gd.fused_expand_sq(q, codes, scale.reshape(1, -1),
                               zero.reshape(1, -1), ids, metric=metric, L=L,
                               n_beam=n_beam, interpret=_on_cpu())


def fused_expand_pq(lut: jnp.ndarray, codes: jnp.ndarray, ids: jnp.ndarray,
                    *, L: int, n_beam: int = 1):
    """PQ-ADC twin of fused_expand: (Q, m, K) luts, (n, m) u8 codes."""
    return _pq.fused_expand_pq(lut, codes, ids, L=L, n_beam=n_beam,
                               interpret=_on_cpu())


def fused_expand_pq4(lut: jnp.ndarray, packed: jnp.ndarray,
                     ids: jnp.ndarray, *, L: int, n_beam: int = 1):
    """PQ4 twin: (Q, m, 16) luts, (n, m//2) nibble-packed u8 codes."""
    return _p4.fused_expand_pq4(lut, packed, ids, L=L, n_beam=n_beam,
                                interpret=_on_cpu())


def fused_expand_bin(qcodes: jnp.ndarray, codes: jnp.ndarray,
                     ids: jnp.ndarray, *, L: int, n_beam: int = 1):
    """bin twin: (Q, nw) u32 packed queries, (n, nw) u32 packed codes."""
    return _bh.fused_expand_bin(qcodes, codes, ids, L=L, n_beam=n_beam,
                                interpret=_on_cpu())


def ivf_scan(luts: jnp.ndarray, list_codes: jnp.ndarray,
             list_ids: jnp.ndarray, probe_ids: jnp.ndarray, *, L: int):
    """(Q, Pl, m, K) luts (Pl in {1, P}), padded lists, (Q, P) probes ->
    per-list top-L (dists, ids), each (Q, P, L'), L' = min(L, max_len)."""
    return _iv.ivf_scan(luts, list_codes, list_ids, probe_ids,
                        L=min(L, list_ids.shape[1]), interpret=_on_cpu())


def pq4_ivf_scan(luts: jnp.ndarray, list_codes: jnp.ndarray,
                 list_ids: jnp.ndarray, probe_ids: jnp.ndarray, *, L: int):
    """pq4 twin of ivf_scan: (Q, Pl, m, 16) luts, (nlist, max_len, m//2)
    nibble-packed list codes. Same L clamping."""
    return _p4.pq4_ivf_scan(luts, list_codes, list_ids, probe_ids,
                            L=min(L, list_ids.shape[1]), interpret=_on_cpu())


def bin_ivf_scan(qcodes: jnp.ndarray, list_codes: jnp.ndarray,
                 list_ids: jnp.ndarray, probe_ids: jnp.ndarray, *, L: int):
    """bin twin of ivf_scan: (Q, nw) u32 packed queries, (nlist, max_len,
    nw) u32 packed list codes. Same L clamping."""
    return _bh.bin_ivf_scan(qcodes, list_codes, list_ids, probe_ids,
                            L=min(L, list_ids.shape[1]), interpret=_on_cpu())
