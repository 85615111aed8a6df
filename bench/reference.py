"""The plain reference: exact top-k by blocked brute force, on the device.

It shares nothing with the program: inner-product distances (`-<q, x>`,
smaller is nearer, the program's convention) are one float32 matrix product
at HIGHEST precision per block of queries, followed by `lax.top_k`. A
`dtype` below float32 gives the control: the same search with its operands
rounded to that type (products still accumulate in float32).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256


@functools.partial(jax.jit, static_argnames=("k", "dtype"))
def _topk_block(base, q, k, dtype):
    prec = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    s = jnp.dot(q.astype(dtype), base.astype(dtype).T, precision=prec,
                preferred_element_type=jnp.float32)
    v, i = jax.lax.top_k(s, k)
    return -v, i


@jax.jit
def _dists_block(base, q, ids):
    n = base.shape[0]
    ok = (ids >= 0) & (ids < n)
    rows = base[jnp.where(ok, ids, 0)]
    d = -jnp.einsum("qkd,qd->qk", rows, q,
                    precision=jax.lax.Precision.HIGHEST)
    return jnp.where(ok, d, jnp.inf)


def _blocks(n: int, size: int):
    """(start, rows) of each block; every block is padded to `size` rows
    so one compiled program serves them all."""
    return [(s, min(size, n - s)) for s in range(0, n, size)]


def _pad(x: np.ndarray, rows: int) -> np.ndarray:
    return np.pad(x, ((0, rows - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def exact_topk(base: jax.Array, queries: np.ndarray, k: int,
               dtype=jnp.float32) -> Tuple[np.ndarray, np.ndarray]:
    """(dists (Q, k), ids (Q, k)) of the k nearest rows of `base` under
    inner product, nearest first."""
    q = np.asarray(queries, np.float32)
    out_d, out_i = [], []
    for s, m in _blocks(q.shape[0], BLOCK):
        d, i = _topk_block(base, jnp.asarray(_pad(q[s:s + m], BLOCK)), k,
                           dtype)
        out_d.append(np.asarray(d)[:m])
        out_i.append(np.asarray(i)[:m])
    if not out_d:
        return np.zeros((0, k), np.float32), np.zeros((0, k), np.int32)
    return np.concatenate(out_d), np.concatenate(out_i)


def true_dists(base: jax.Array, queries: np.ndarray, ids: np.ndarray
               ) -> np.ndarray:
    """(Q, k) exact distances `-<q, base[id]>` of the given ids, at HIGHEST
    precision; an id outside the corpus reads +inf."""
    q = np.asarray(queries, np.float32)
    ids = np.asarray(ids, np.int32)
    out = []
    for s, m in _blocks(q.shape[0], 4 * BLOCK):
        d = _dists_block(base, jnp.asarray(_pad(q[s:s + m], 4 * BLOCK)),
                         jnp.asarray(_pad(ids[s:s + m], 4 * BLOCK)))
        out.append(np.asarray(d)[:m])
    if not out:
        return np.zeros(ids.shape, np.float32)
    return np.concatenate(out)
