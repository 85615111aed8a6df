"""bin_hamming — 1-bit XOR+popcount Hamming kernels (DESIGN.md §14).

The binary codec (core/quantize.py: kind="bin") stores one sign bit per
rotated dimension, packed 32 to a uint32 word — d=128 vectors become 4
words (16 bytes), 8x smaller than per-dimension 8-bit PQ codes and 32x
smaller than f32. Hamming distance between two packed codes is
popcount(XOR), an exact integer, so these kernels are bit-identical to
their jnp oracles (ref.py) and parity tests assert ==, not allclose.

Three kernels mirror the pq4 family one-for-one:

  bin_dist         — graph-path gather Hamming, grid (Q, C): the packed
                     code row of neighbor ids[q, b] streams by scalar
                     prefetch (H2) against the query's VMEM-resident
                     packed code — the per-row DMA is nw u32 words (16
                     bytes at d=128), the smallest gather in the system.
  fused_expand_bin — the same gather, then the traversal's sorted-block
                     epilogue in XLA — identical queue contract to
                     fused_expand_pq4.
  bin_ivf_scan     — IVF list scan, grid (Q, P, max_len // TL), per-list
                     top-L in XLA after the kernel.

Popcount is the SWAR bit-ladder (no LUT, no popcount intrinsic needed):
pairs, nibbles, bytes, then a *0x01010101 horizontal byte-sum — pure
shift/mask/add uint32 VPU ops, exact by construction. Counts leave the
u32 domain through i32 (Mosaic has no u32 -> f32 cast).
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


def _popcount(x: jnp.ndarray) -> jnp.ndarray:
    """Per-element bit count of a uint32 array (SWAR ladder, exact)."""
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (x * jnp.uint32(0x01010101)) >> 24          # byte-sum in the top byte


# ------------------------------------------------------------ graph gather
def _dist_kernel(idx_ref, q_ref, code_ref, o_ref):
    x = jnp.bitwise_xor(q_ref[0], code_ref[0])         # (1, nw) u32
    cnt = jnp.sum(_popcount(x).astype(jnp.int32), axis=1, keepdims=True)
    put_lane(o_ref, pl.program_id(1), cnt.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bin_dist(qcodes: jnp.ndarray, codes: jnp.ndarray, ids: jnp.ndarray, *,
             interpret: bool = False) -> jnp.ndarray:
    """(Q, nw) u32 packed queries, (n, nw) u32 packed codes, (Q, C) ids ->
    (Q, C) f32 exact Hamming distances; invalid ids -> +inf."""
    Q, nw = qcodes.shape
    assert codes.shape[1] == nw, (codes.shape, nw)
    C = ids.shape[1]
    assert ids.shape[0] == Q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            pl.BlockSpec((1, 1, nw), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, 1, nw),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _dist_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, qcodes[:, None, :], codes[:, None, :])
    return jnp.where(ids >= 0, out[:, 0, :], jnp.inf)


@functools.partial(jax.jit, static_argnames=("L", "n_beam", "interpret"))
def fused_expand_bin(qcodes: jnp.ndarray, codes: jnp.ndarray,
                     ids: jnp.ndarray, *, L: int, n_beam: int = 1,
                     interpret: bool = False):
    """bin twin of fused_expand_pq4: (Q, nw) u32 packed queries, (n, nw)
    u32 packed codes, (Q, C) ids -> the sorted candidate block (dists
    (Q, T) ascending, ids (Q, T), bests (Q, n_beam), tie counts
    (Q, n_beam)); T = min(L, C). ids < 0 come back (+inf, -1)."""
    Q, nw = qcodes.shape
    C = ids.shape[1]
    assert codes.shape[1] == nw, (codes.shape, nw)
    assert ids.shape[0] == Q and C % n_beam == 0, (ids.shape, n_beam)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            pl.BlockSpec((1, 1, nw), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, 1, nw),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    dist = pl.pallas_call(
        _dist_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, qcodes[:, None, :], codes[:, None, :])
    return sorted_block_ref(dist[:, 0, :], ids, L, n_beam)


# ---------------------------------------------------------------- IVF scan
def _scan_kernel(pids_ref, q_ref, codes_ref, o_ref):
    x = jnp.bitwise_xor(codes_ref[0], q_ref[0])         # (TL, nw) u32
    cnt = _popcount(x).astype(jnp.int32).T              # (nw, TL)
    o_ref[0, 0] = jnp.sum(cnt, axis=0, keepdims=True).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("L", "interpret"))
def bin_ivf_scan(qcodes: jnp.ndarray, list_codes: jnp.ndarray,
                 list_ids: jnp.ndarray, probe_ids: jnp.ndarray, *,
                 L: int, interpret: bool = False):
    """Scan probed inverted lists of packed sign codes (bin ivf_scan twin).

    qcodes:     (Q, nw) uint32 packed query signs
    list_codes: (nlist, max_len, nw) uint32 packed codes
    list_ids:   (nlist, max_len) i32, -1 padding
    probe_ids:  (Q, P) i32
    Returns (dists (Q, P, L) ascending, ids (Q, P, L), -1 padding).
    """
    Q, nw = qcodes.shape
    P = probe_ids.shape[1]
    nlist, max_len, nw2 = list_codes.shape
    assert nw2 == nw, (nw2, nw)
    assert list_ids.shape == (nlist, max_len)
    assert L <= max_len, (L, max_len)
    TL = list_tile(max_len)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, P, max_len // TL),
        in_specs=[
            pl.BlockSpec((1, 1, nw), lambda i, j, t, pids: (i, 0, 0)),
            pl.BlockSpec((1, TL, nw), lambda i, j, t, pids: (pids[i, j], t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, TL),
                               lambda i, j, t, pids: (i, j, 0, t)),
    )
    d = pl.pallas_call(
        _scan_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, P, 1, max_len), jnp.float32),
        interpret=interpret,
    )(probe_ids, qcodes[:, None, :], list_codes)
    return list_topk(d[:, :, 0, :], list_ids, probe_ids, L)
