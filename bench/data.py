"""The benchmark's own copy of the `deep_like` corpus generator.

DEEP (Babenko & Lempitsky, CVPR 2016) holds 96-d CNN descriptors,
L2-normalised and searched by inner product. No download is possible, so the
corpus is made with the distribution of the program's `repro.data.vectors`
`deep_like` analogue: a rank-32 Gaussian projected to 96 dimensions, plus
isotropic noise of scale 0.1, normalised to unit length. Queries come from
the same distribution (in-distribution, as DEEP's are).

The corpus is one fixed draw, from the configuration's `corpus_seed`: DEEP1M
is one dataset, and a corpus drawn anew per run changes the work (the IVF
scan pads every list to the longest, whose length moves with the draw). The
query pool is drawn from the run's seed. The whole draw is one jitted call
on the device, so a 1M-row corpus costs well under a second.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    seed = int(seed) % 2**64
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_corpus(seed: int, data: dict) -> Tuple[jax.Array, np.ndarray]:
    """(base (n, d) f32 on the device, query pool (queries, d) f32 on the
    host) for a configuration's `data` section."""
    n, nq, d = data["n"], data["queries"], data["dim"]
    rank, noise = data["rank"], data["noise"]

    def rows(proj, key, m):
        kb, kc = jax.random.split(key, 3)[1:]
        low = jax.random.normal(kb, (m, rank), jnp.float32)
        x = jnp.dot(low, proj, precision=jax.lax.Precision.HIGHEST)
        x = x + noise * jax.random.normal(kc, (m, d), jnp.float32)
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)

    @jax.jit
    def draw(corpus_key, query_key):
        ka = jax.random.split(corpus_key, 3)[0]
        proj = jax.random.normal(ka, (rank, d), jnp.float32)
        return rows(proj, corpus_key, n), rows(proj, query_key, nq)

    base, pool = draw(seed_key(data["corpus_seed"]), seed_key(seed))
    return base, np.asarray(pool)
