"""How `correct` is decided: every answer served in the window against the
plain reference (bench/reference.py), after the window has closed.

Three numbers are compared, each with a limit from the configuration file
(`limits`; PERF.md gives the readings each limit was set from):

  recall_at_10  share of the exact top-10 found, over every query of every
                request sent in the window (an unanswered query finds none).
                Its limit is the recall the deployment is sized for.
  dist_gap      the widest gap between a distance the program returned and
                the reference's float32 distance of the id it returned. An
                id altered where it is produced, or distances computed below
                the configuration's precision, widen it.
  bad_ids       answered rows holding an id outside the corpus or the same
                id twice, plus queries never answered. Exact: limit 0.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Dict, List

import numpy as np

from bench import reference


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float
    higher_is_better: bool

    @property
    def ok(self) -> bool:
        if self.higher_is_better:
            return self.value >= self.limit
        return self.value <= self.limit


def compare(base, pool: np.ndarray, served: List, k: int,
            limits: Dict[str, float]) -> List[Check]:
    """The checks for the answers in `served` (drive.Served records)."""
    n = base.shape[0]
    if not served:
        raise ValueError("no request was sent in the window")
    uniq = np.unique(np.concatenate([s.queries for s in served]))
    _, ref_ids = reference.exact_topk(base, pool[uniq], k)
    ref_of = np.full((pool.shape[0], k), -1, np.int64)
    ref_of[uniq] = ref_ids

    rows_q, rows_ids, rows_d = [], [], []
    hits = total = bad = 0
    for s in served:
        total += s.queries.size
        if s.status != "ok":
            bad += s.queries.size
            continue
        ids = s.ids[:, :k].astype(np.int64)
        valid = (ids >= 0) & (ids < n)
        srt = np.sort(ids, axis=1)
        dup = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
        bad += int(np.sum(~valid.all(axis=1) | dup))
        gt = ref_of[s.queries]
        hits += int(np.sum((ids[:, :, None] == gt[:, None, :]).any(axis=2)))
        rows_q.append(s.queries)
        rows_ids.append(ids)
        rows_d.append(s.dists[:, :k])
    gap = 0.0
    if rows_q:
        q = np.concatenate(rows_q)
        ids = np.concatenate(rows_ids)
        got = np.concatenate(rows_d).astype(np.float64)
        want = reference.true_dists(base, pool[q], ids).astype(np.float64)
        ok = np.isfinite(want)
        if ok.any():
            gap = float(np.max(np.abs(got[ok] - want[ok])))
    recall = hits / max(total * k, 1)
    return [Check("recall_at_10", recall, limits["recall_at_10"], True),
            Check("dist_gap", gap, limits["dist_gap"], False),
            Check("bad_ids", float(bad), limits["bad_ids"], False)]


def report(checks: List[Check]) -> dict:
    """Print each number beside its limit as the last lines of stderr, and
    return them for the result line's last key."""
    out = {}
    for c in checks:
        rel = ">=" if c.higher_is_better else "<="
        print(f"check {c.name} = {c.value!r} (limit {rel} {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
        out[c.name] = {"value": c.value, "limit": c.limit,
                       "must_be": "at_least" if c.higher_is_better
                       else "at_most"}
    return out
