"""ivf_scan — IVF-PQ padded-list scan kernel (DESIGN.md §4).

One grid step scans one tile of TL codes of one (query, probed-list) pair
and writes their ADC distances as a lane-dense (1, TL) row; the per-list
top-L then runs in XLA right after the kernel (`list_topk`: Mosaic has no
in-kernel sort or top_k).

The ADC gather is a one-hot select on the VPU, with the list rows on the
lanes: the (TL, m) code tile is transposed in-kernel to (m, TL), and for
each subspace j the code row broadcasts down the K sublanes of an iota,
selecting one entry of LUT column j per lane; the K-sublane sum adds it.
The LUT is held transposed, (K, m), so column j is a lane slice.

Prefetch (H2 analogue): the codes block of step (q, p, t) is tile t of the
list `probe_ids[q, p]` (scalar-prefetched), so the pipeline engine DMAs the
next tile while this one is scanned — the software-prefetch trick of the
paper's Fig. 5 applied to inverted lists.

Grid: (Q, P, max_len // TL). Blocks: LUT (1, 1, K, m) by (q, p) (or by q
when the table is probe-independent); codes (1, TL, m) by
(probe_ids[q, p], t); output (1, 1, 1, TL) by (q, p, t).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def list_tile(max_len: int) -> int:
    """Rows per scan step: the largest of 512/256/128 dividing max_len
    (lane-aligned output tiles), else the whole (short, unaligned) list."""
    return next((t for t in (512, 256, 128) if max_len % t == 0), max_len)


def list_topk(d: jnp.ndarray, list_ids: jnp.ndarray, probe_ids: jnp.ndarray,
              L: int):
    """(Q, P, max_len) scan distances -> per-list top-L (dists ascending,
    ids, -1 padding): list padding (-1 ids) is masked to +inf first."""
    ids = list_ids[probe_ids]                            # (Q, P, max_len)
    d = jnp.where(ids >= 0, d, jnp.inf)
    neg, pos = jax.lax.top_k(-d, L)
    out_ids = jnp.take_along_axis(ids, pos, axis=-1)
    return -neg, jnp.where(jnp.isfinite(neg), out_ids, -1)


def _kernel(pids_ref, lut_ref, codes_ref, o_ref):
    lut = lut_ref[0, 0].astype(jnp.float32)              # (K, m)
    codes = codes_ref[0].astype(jnp.int32).T             # (m, TL)
    K, m = lut.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (K, codes.shape[1]), 0)
    acc = jnp.zeros((1, codes.shape[1]), jnp.float32)
    for j in range(m):
        hit = rows == codes[j:j + 1, :]
        acc = acc + jnp.sum(jnp.where(hit, lut[:, j:j + 1], 0.0), axis=0,
                            keepdims=True)
    o_ref[0, 0] = acc


@functools.partial(jax.jit, static_argnames=("L", "interpret"))
def ivf_scan(luts: jnp.ndarray, list_codes: jnp.ndarray,
             list_ids: jnp.ndarray, probe_ids: jnp.ndarray, *,
             L: int, interpret: bool = False):
    """Scan probed inverted lists, returning per-list top-L candidates.

    luts:       (Q, Pl, m, K) f32 ADC tables; Pl is the probe count P, or 1
                when the table is probe-independent (non-residual, or ip
                with the centroid bias handled outside) — the kernel then
                re-reads the single block instead of materializing P copies
    list_codes: (nlist, max_len, m) uint8 PQ codes, padded rows arbitrary
    list_ids:   (nlist, max_len) i32 database ids, -1 padding
    probe_ids:  (Q, P) i32 probed cluster ids
    Returns (dists (Q, P, L) f32 ascending, ids (Q, P, L) i32, -1 padding).
    """
    Q, Pl, m, K = luts.shape
    P = probe_ids.shape[1]
    nlist, max_len = list_ids.shape
    assert Pl in (1, P), (Pl, P)
    assert probe_ids.shape == (Q, P) and list_codes.shape == (nlist, max_len, m)
    assert L <= max_len, (L, max_len)
    TL = list_tile(max_len)
    lut_j = (lambda i, j, t, pids: (i, j, 0, 0)) if Pl == P else \
        (lambda i, j, t, pids: (i, 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, P, max_len // TL),
        in_specs=[
            pl.BlockSpec((1, 1, K, m), lut_j),
            pl.BlockSpec((1, TL, m), lambda i, j, t, pids: (pids[i, j], t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, TL),
                               lambda i, j, t, pids: (i, j, 0, t)),
    )
    d = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, P, 1, max_len), jnp.float32),
        interpret=interpret,
    )(probe_ids, jnp.swapaxes(luts, 2, 3), list_codes)
    return list_topk(d[:, :, 0, :], list_ids, probe_ids, L)
