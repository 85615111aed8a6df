"""pq_adc — PQ asymmetric-distance gather kernels (paper A4).

ADC distance of a database code against a query lookup table:
    dist[q, b] = sum_j LUT[q, j, code[ids[q, b], j]]

x86 libraries implement the LUT gather with AVX shuffle bytes; the TPU has
no register shuffle. Here the table is held transposed, (K, m), so the
gather becomes a one-hot select on the VPU: the (1, m) code row broadcasts
down the K sublanes, `iota == code` picks one table entry per column, and
a sum over the (K, m) tile adds the m picked entries. Code rows are fetched
by the same scalar-prefetch gather as gather_dist (H2), as (1, 1, m) blocks
of a (n, 1, m) view, so code reads for step j+1 overlap step j's
arithmetic.

Grid: (Q, C); blocks: transposed LUT (1, K, m) by query, code row
(1, 1, m) by ids[q, b], output row (1, 1, C) by query (see gather_dist for
the layout rules). `fused_expand_pq` is the same gather followed by the
traversal's sorted-block epilogue in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather_dist import put_lane
from repro.kernels.ref import sorted_block_ref


def _kernel(idx_ref, lut_ref, code_ref, o_ref):
    lut = lut_ref[0].astype(jnp.float32)             # (K, m) transposed
    code = code_ref[0].astype(jnp.int32)             # (1, m)
    onehot = jax.lax.broadcasted_iota(jnp.int32, lut.shape, 0) == code
    picked = jnp.sum(jnp.where(onehot, lut, 0.0), axis=0, keepdims=True)
    put_lane(o_ref, pl.program_id(1), jnp.sum(picked, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq_adc(lut: jnp.ndarray, codes: jnp.ndarray, ids: jnp.ndarray, *,
           interpret: bool = False) -> jnp.ndarray:
    """(Q, m, K) luts, (n, m) uint8 codes, (Q, C) int32 ids -> (Q, C) f32;
    ids < 0 come back +inf."""
    Q, m, K = lut.shape
    C = ids.shape[1]
    assert ids.shape[0] == Q and codes.shape[1] == m
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            pl.BlockSpec((1, K, m), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, 1, m),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, jnp.swapaxes(lut, 1, 2), codes[:, None, :])
    return jnp.where(ids >= 0, out[:, 0, :], jnp.inf)


@functools.partial(jax.jit, static_argnames=("L", "n_beam", "interpret"))
def fused_expand_pq(lut: jnp.ndarray, codes: jnp.ndarray, ids: jnp.ndarray,
                    *, L: int, n_beam: int = 1, interpret: bool = False):
    """PQ-ADC twin of fused_expand: (Q, m, K) luts, (n, m) u8 codes ->
    the sorted candidate block (see gather_dist.fused_expand)."""
    Q, m, K = lut.shape
    C = ids.shape[1]
    assert ids.shape[0] == Q and C % n_beam == 0, (ids.shape, n_beam)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            pl.BlockSpec((1, K, m), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, 1, m),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    dist = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, jnp.swapaxes(lut, 1, 2), codes[:, None, :])
    return sorted_block_ref(dist[:, 0, :], ids, L, n_beam)
