"""The one traffic generator: reads a mix's parameters and draws from seeds.

A mix (bench/traffic/<name>.json) is data. Two loops exist:

  closed  `{"loop": "closed", "clients": 1, "size": 256, "k": 10}`: each
          client sends its next request when the last one has returned.
  open    `{"loop": "open", "rate_per_s": r, "size_min": 1, "size_max": 16,
          "size_alpha": 1.5, "schedule_seed": 1, "k": 10}`: Poisson
          arrivals at rate r, request sizes s in [size_min, size_max] with
          P(s) proportional to s ** -size_alpha.

Either may add `"trace_seconds": t`: a traced run traces only the window's
first t seconds, up to a request boundary (bench/drive.py).

The open loop's gaps are the exponential distribution's quantiles at evenly
spaced probabilities and its sizes the size distribution's, each put in an
order drawn from the mix's `schedule_seed`: one fixed schedule, so that a
95th percentile over a few hundred requests does not move with the burst
pattern a seed happens to draw. The run's seed draws the queries: they
cycle through the pool, a fresh permutation each pass.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, stream])


class QueryStream:
    """Indices into the query pool: every query once per pass, each pass
    in a fresh order drawn from the seed."""

    def __init__(self, pool_size: int, seed: int):
        self.pool_size = pool_size
        self.seed = seed
        self.passes = 0
        self.order = np.zeros(0, np.int64)

    def take(self, m: int) -> np.ndarray:
        while self.order.size < m:
            self.order = np.concatenate([
                self.order,
                rng(self.seed, 1000 + self.passes).permutation(
                    self.pool_size)])
            self.passes += 1
        out, self.order = self.order[:m], self.order[m:]
        return out


def size_quantiles(mix: dict, count: int) -> np.ndarray:
    """`count` request sizes at evenly spaced probabilities of the mix's
    size distribution (sorted ascending)."""
    s = np.arange(mix["size_min"], mix["size_max"] + 1)
    p = s.astype(np.float64) ** -mix["size_alpha"]
    cdf = np.cumsum(p / p.sum())
    u = (np.arange(count) + 0.5) / count
    return s[np.minimum(np.searchsorted(cdf, u), s.size - 1)]


def open_schedule(mix: dict, seconds: float
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """(due times in seconds from the window's start, sizes) of every
    request due in a window of `seconds`."""
    seed = mix["schedule_seed"]
    count = max(1, int(round(mix["rate_per_s"] * seconds)))
    u = (np.arange(count) + 0.5) / count
    gaps = -np.log1p(-u) / mix["rate_per_s"]
    gaps = rng(seed, 1).permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    sizes = rng(seed, 2).permutation(size_quantiles(mix, count))
    keep = due < seconds
    return due[keep], sizes[keep]
