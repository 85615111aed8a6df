"""gather_dist — scalar-prefetch gather + distance kernels (paper H2 on TPU).

The paper's software prefetch (`prfm PLDL1KEEP`) hides memory latency by
requesting neighbor vectors before the compute that needs them. TPUs have
no cache-prefetch instruction; the native equivalent is the Pallas
pipeline engine itself: when an input's BlockSpec index_map depends on a
*scalar-prefetch* operand, the engine reads the index array ahead of the
grid and issues the HBM->VMEM DMA for step (i, j+1)'s row while step
(i, j)'s compute runs — double buffering driven by the neighbor-id array,
i.e. "prefetch the adjacency targets of the node being expanded" (paper
Fig. 5) expressed structurally.

Grid: (Q, C). Step (i, j) gathers row ids[i, j] of the database and writes
its distance into lane j of query i's (1, C) output row, which stays
resident in VMEM across the C steps and is written back once per query.

Mosaic layout rules shape every operand here: a block's last two dims must
be (8, 128)-divisible or span the whole array, so single rows travel as
(1, 1, d) blocks of a (n, 1, d) view of the (n, d) array (a free reshape;
any d is legal, no lane padding), and per-query outputs are (1, 1, C) rows
of a (Q, 1, C) array rather than (1, 1) cells. Vector stores only: a
distance lands in its lane by a lane-masked select (`put_lane`).

The `fused_expand*` twins (here `fused_expand`, `fused_expand_sq`; the pq,
pq4 and bin ones beside their gather kernels) are no longer fused: Mosaic
has no in-kernel sort or top_k, so each is the plain gather kernel followed
by the traversal's sorted-block epilogue (`ref.sorted_block_ref`) in XLA.
That is the work the search loop's non-fused branch (`dist_fn` +
`queue.merge_expand`) already does, and their parity tests now check only
the gather. They are kept, under their old names, because the `use_fused`
branch of `core.search`, `KBest._get_expand_fn` and the cost model still
route through them; ROADMAP lists their removal.

Invalid ids (< 0, CSR padding) are clamped for the DMA in the index map and
masked to +inf after the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import sorted_block_ref


def put_lane(o_ref, j, val):
    """Write the (1, 1) value `val` into lane j of the resident (1, 1, C)
    output row (Mosaic cannot store a scalar to VMEM)."""
    row = o_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    o_ref[0] = jnp.where(lane == j, val, row)


def _row_dist(q, r, metric: str):
    """(1, d) f32 query and row -> (1, 1) distance."""
    if metric == "l2":
        diff = r - q
        return jnp.sum(diff * diff, axis=1, keepdims=True)
    return -jnp.sum(r * q, axis=1, keepdims=True)


def _make_kernel(metric: str):
    def kernel(idx_ref, q_ref, row_ref, o_ref):
        q = q_ref[0].astype(jnp.float32)                 # (1, d)
        r = row_ref[0].astype(jnp.float32)               # (1, d) gathered
        put_lane(o_ref, pl.program_id(1), _row_dist(q, r, metric))
    return kernel


def _make_sq_kernel(metric: str):
    def kernel(idx_ref, q_ref, row_ref, scale_ref, zero_ref, o_ref):
        # u8 codes dequantized in VMEM, never materialized as f32 rows in
        # HBM (the point of the SQ store); u8 -> i32 -> f32 is the cast
        # chain Mosaic lowers
        q = q_ref[0].astype(jnp.float32)
        c = row_ref[0].astype(jnp.int32).astype(jnp.float32)
        r = c * scale_ref[0] + zero_ref[0]
        put_lane(o_ref, pl.program_id(1), _row_dist(q, r, metric))
    return kernel


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def gather_dist(q: jnp.ndarray, db: jnp.ndarray, ids: jnp.ndarray, *,
                metric: str = "l2", interpret: bool = False) -> jnp.ndarray:
    """(Q, d) queries, (n, d) db, (Q, C) int32 ids -> (Q, C) f32 distances;
    ids < 0 come back +inf."""
    Q, d = q.shape
    C = ids.shape[1]
    assert ids.shape[0] == Q and db.shape[1] == d, (q.shape, db.shape)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j, idx: (i, 0, 0)),
            # the prefetch-driven gather: step (i, j)'s block is row
            # idx[i, j], DMA'd one step ahead by the pipeline engine
            pl.BlockSpec((1, 1, d),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _make_kernel(metric),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, q[:, None, :], db[:, None, :])
    return jnp.where(ids >= 0, out[:, 0, :], jnp.inf)


@functools.partial(jax.jit, static_argnames=("metric", "interpret"))
def sq_gather_dist(q: jnp.ndarray, codes: jnp.ndarray, scale: jnp.ndarray,
                   zero: jnp.ndarray, ids: jnp.ndarray, *,
                   metric: str = "l2", interpret: bool = False) -> jnp.ndarray:
    """Fused SQ gather + dequant + distance: the DMA'd rows are (1, d)
    uint8 — a quarter of the f32 traffic — and the affine dequant
    (code * scale + zero) runs in-kernel against the VMEM-resident scale
    and zero rows.

    q (Q, d) f32, codes (n, d) u8, scale/zero (1, d) f32, ids (Q, C) i32.
    """
    Q, d = q.shape
    C = ids.shape[1]
    assert ids.shape[0] == Q and codes.shape[1] == d
    assert scale.shape == (1, d) and zero.shape == (1, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, 1, d),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, j, idx: (0, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, j, idx: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _make_sq_kernel(metric),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, q[:, None, :], codes[:, None, :], scale[:, None, :],
      zero[:, None, :])
    return jnp.where(ids >= 0, out[:, 0, :], jnp.inf)


# --------------------------------------------- fused beam-expansion twins
@functools.partial(jax.jit,
                   static_argnames=("metric", "L", "n_beam", "interpret"))
def fused_expand(q: jnp.ndarray, db: jnp.ndarray, ids: jnp.ndarray, *,
                 metric: str = "l2", L: int, n_beam: int = 1,
                 interpret: bool = False):
    """(Q, d) queries, (n, d) db, (Q, C) ids -> the traversal's sorted
    candidate block (dists (Q, T) ascending, ids (Q, T), bests
    (Q, n_beam), earlier-expansion tie counts (Q, n_beam)); T = min(L, C).
    ids < 0 come back as (+inf, -1). Gather + distance in the kernel; the
    stable sort and per-expansion reductions in XLA after it."""
    Q, d = q.shape
    C = ids.shape[1]
    assert ids.shape[0] == Q and C % n_beam == 0, (ids.shape, n_beam)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, 1, d),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    dist = pl.pallas_call(
        _make_kernel(metric),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, q[:, None, :], db[:, None, :])
    return sorted_block_ref(dist[:, 0, :], ids, L, n_beam)


@functools.partial(jax.jit,
                   static_argnames=("metric", "L", "n_beam", "interpret"))
def fused_expand_sq(q: jnp.ndarray, codes: jnp.ndarray, scale: jnp.ndarray,
                    zero: jnp.ndarray, ids: jnp.ndarray, *,
                    metric: str = "l2", L: int, n_beam: int = 1,
                    interpret: bool = False):
    """SQ twin of fused_expand: u8 rows gathered and dequantized in VMEM,
    same sorted-block epilogue."""
    Q, d = q.shape
    C = ids.shape[1]
    assert ids.shape[0] == Q and codes.shape[1] == d
    assert scale.shape == (1, d) and zero.shape == (1, d)
    assert C % n_beam == 0, (C, n_beam)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q, C),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda i, j, idx: (i, 0, 0)),
            pl.BlockSpec((1, 1, d),
                         lambda i, j, idx: (jnp.maximum(idx[i, j], 0), 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, j, idx: (0, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i, j, idx: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda i, j, idx: (i, 0, 0)),
    )
    dist = pl.pallas_call(
        _make_sq_kernel(metric),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, C), jnp.float32),
        interpret=interpret,
    )(ids, q[:, None, :], codes[:, None, :], scale[:, None, :],
      zero[:, None, :])
    return sorted_block_ref(dist[:, 0, :], ids, L, n_beam)
