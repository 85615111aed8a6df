"""pq4_scan — 4-bit fast-scan PQ ADC kernels (DESIGN.md §13).

x86 fast-scan (and its ARM port, the "ARM 4-bit PQ" line of work) shrinks
PQ sub-codebooks to 16 centroids so the whole (m, 16) lookup table fits in
SIMD registers and the LUT gather becomes an in-register byte shuffle. The
TPU analogue implemented here: the table is 16x smaller than 8-bit PQ's
(m, 256), so the one-hot select that replaces the gather runs over 16
sublanes instead of 256, and codes arrive nibble-packed (two per byte —
half the DMA bytes of pq_adc).

Three kernels share the nibble-unpack + one-hot idiom:

  pq4_adc          — graph-path gather ADC, grid (Q, C): the packed code
                     row of neighbor ids[q, b] is fetched by scalar
                     prefetch (H2, same mechanism as pq_adc/gather_dist)
                     and scored against query q's VMEM-resident LUT.
  fused_expand_pq4 — the same gather, then the traversal's sorted-block
                     epilogue in XLA.
  pq4_ivf_scan     — IVF list scan, grid (Q, P, max_len // TL): the pq4
                     twin of ivf_scan (same prefetch-driven list DMA, same
                     per-list top-L in XLA after the kernel), consuming
                     packed (nlist, max_len, m//2) list codes.

Nibble layout (core/quantize.py: pq4_pack): byte j = subspace 2j in the low
nibble, 2j+1 in the high nibble. The kernels never interleave: the
transposed table is split into its even and odd subspace columns, which
the low and high nibbles of byte j index directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_dist import put_lane
from repro.kernels.ivf_scan import list_tile, list_topk
from repro.kernels.ref import sorted_block_ref

K4 = 16  # centroids per 4-bit sub-codebook


def _split_lut(lut: jnp.ndarray):
    """(..., m, 16) tables -> transposed (..., 16, m//2) tables of the
    even (low-nibble) and odd (high-nibble) subspaces."""
    t = jnp.swapaxes(lut, -1, -2)
    return t[..., 0::2], t[..., 1::2]


# ------------------------------------------------------------ graph gather
def _adc_kernel(idx_ref, lo_ref, hi_ref, code_ref, o_ref):
    lut_lo = lo_ref[0].astype(jnp.float32)           # (16, m//2)
    lut_hi = hi_ref[0].astype(jnp.float32)
    packed = code_ref[0].astype(jnp.int32)           # (1, m//2)
    rows = jax.lax.broadcasted_iota(jnp.int32, lut_lo.shape, 0)
    picked = (jnp.where(rows == (packed & 0x0F), lut_lo, 0.0)
              + jnp.where(rows == ((packed >> 4) & 0x0F), lut_hi, 0.0))
    total = jnp.sum(jnp.sum(picked, axis=0, keepdims=True), axis=1,
                    keepdims=True)
    put_lane(o_ref, pl.program_id(1), total)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq4_adc(lut: jnp.ndarray, packed: jnp.ndarray, ids: jnp.ndarray, *,
            interpret: bool = False) -> jnp.ndarray:
    """(Q, m, 16) luts, (n, m//2) u8 packed codes, (Q, C) ids -> (Q, C)
    f32; ids < 0 come back +inf."""
    Q, m, K = lut.shape
    assert K == K4, K
    mh = packed.shape[1]
    assert mh * 2 == m, (mh, m)
    C = ids.shape[1]
    assert ids.shape[0] == Q
    lut_lo, lut_hi = _split_lut(lut)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            # LUT blocks depend only on q: DMA'd once per query row and
            # VMEM-resident across the C inner steps
            pl.BlockSpec((1, K4, mh), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, K4, mh), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, 1, mh),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _adc_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, lut_lo, lut_hi, packed[:, None, :])
    return jnp.where(ids >= 0, out[:, 0, :], jnp.inf)


@functools.partial(jax.jit, static_argnames=("L", "n_beam", "interpret"))
def fused_expand_pq4(lut: jnp.ndarray, packed: jnp.ndarray,
                     ids: jnp.ndarray, *, L: int, n_beam: int = 1,
                     interpret: bool = False):
    """PQ4 twin of fused_expand: (Q, m, 16) luts, (n, m//2) nibble-packed
    u8 codes -> the sorted candidate block (see gather_dist.fused_expand)."""
    Q, m, K = lut.shape
    C = ids.shape[1]
    mh = packed.shape[1]
    assert K == K4 and mh * 2 == m, (lut.shape, packed.shape)
    assert ids.shape[0] == Q and C % n_beam == 0, (ids.shape, n_beam)
    lut_lo, lut_hi = _split_lut(lut)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            pl.BlockSpec((1, K4, mh), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, K4, mh), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, 1, mh),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    dist = pl.pallas_call(
        _adc_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, lut_lo, lut_hi, packed[:, None, :])
    return sorted_block_ref(dist[:, 0, :], ids, L, n_beam)


# ---------------------------------------------------------------- IVF scan
def _scan_kernel(pids_ref, lo_ref, hi_ref, codes_ref, o_ref):
    lut_lo = lo_ref[0, 0].astype(jnp.float32)            # (16, m//2)
    lut_hi = hi_ref[0, 0].astype(jnp.float32)
    packed = codes_ref[0].astype(jnp.int32).T            # (m//2, TL)
    mh = packed.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (K4, packed.shape[1]), 0)
    acc = jnp.zeros((1, packed.shape[1]), jnp.float32)
    for j in range(mh):
        byte = packed[j:j + 1, :]
        picked = (jnp.where(rows == (byte & 0x0F), lut_lo[:, j:j + 1], 0.0)
                  + jnp.where(rows == ((byte >> 4) & 0x0F),
                              lut_hi[:, j:j + 1], 0.0))
        acc = acc + jnp.sum(picked, axis=0, keepdims=True)
    o_ref[0, 0] = acc


@functools.partial(jax.jit, static_argnames=("L", "interpret"))
def pq4_ivf_scan(luts: jnp.ndarray, list_codes: jnp.ndarray,
                 list_ids: jnp.ndarray, probe_ids: jnp.ndarray, *,
                 L: int, interpret: bool = False):
    """Scan probed inverted lists of nibble-packed codes (pq4 ivf_scan twin).

    luts:       (Q, Pl, m, 16) f32, Pl in {1, P} (see ivf_scan)
    list_codes: (nlist, max_len, m//2) uint8 packed codes
    list_ids:   (nlist, max_len) i32, -1 padding
    probe_ids:  (Q, P) i32
    Returns (dists (Q, P, L) ascending, ids (Q, P, L), -1 padding).
    """
    Q, Pl, m, K = luts.shape
    assert K == K4, K
    P = probe_ids.shape[1]
    nlist, max_len, mh = list_codes.shape
    assert mh * 2 == m, (mh, m)
    assert Pl in (1, P), (Pl, P)
    assert list_ids.shape == (nlist, max_len)
    assert L <= max_len, (L, max_len)
    TL = list_tile(max_len)
    lut_j = (lambda i, j, t, pids: (i, j, 0, 0)) if Pl == P else \
        (lambda i, j, t, pids: (i, 0, 0, 0))
    lut_lo, lut_hi = _split_lut(luts)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, P, max_len // TL),
        in_specs=[
            pl.BlockSpec((1, 1, K4, mh), lut_j),
            pl.BlockSpec((1, 1, K4, mh), lut_j),
            pl.BlockSpec((1, TL, mh), lambda i, j, t, pids: (pids[i, j], t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, TL),
                               lambda i, j, t, pids: (i, j, 0, t)),
    )
    d = pl.pallas_call(
        _scan_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, P, 1, max_len), jnp.float32),
        interpret=interpret,
    )(probe_ids, lut_lo, lut_hi, list_codes)
    return list_topk(d[:, :, 0, :], list_ids, probe_ids, L)
