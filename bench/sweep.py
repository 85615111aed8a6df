#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest request rate it sustains.

    python3 bench/sweep.py --workload deep1m_ivf_pq8.online --seed 7 \\
        --seconds 30 --rates 4 6 8 10 12

Builds the cell's index once, then serves the cell's mix at each rate for
`--seconds` on the wall clock. A rate is sustained when the queue does not
grow through the window: the median latency of the last quarter of the
requests is under twice that of the first quarter. One JSON line per rate
(rate, completed rate, p50/p95 latency, quarter medians, verdict); the
cell's fixed rate is then set to about 4/5 of the highest sustained one.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import data, drive, harness  # noqa: E402
from bench.spec import Spec  # noqa: E402


def verdict(win: drive.Window) -> dict:
    served = sorted(win.served, key=lambda s: s.due_s)
    lat = np.asarray([s.done_s - s.due_s for s in served]) * 1e3
    q = max(len(lat) // 4, 1)
    first, last = float(np.median(lat[:q])), float(np.median(lat[-q:]))
    rows = sum(s.queries.size for s in served)
    return {"requests": len(served), "rows": rows,
            "completed_per_s": len(served) / win.end_s,
            "p50_ms": float(np.median(lat)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_quarter_p50_ms": first, "last_quarter_p50_ms": last,
            "rows_per_dispatch": win.n_rows / max(win.n_dispatches, 1),
            "sustained": last < 2.0 * first}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--n", type=int)
    ap.add_argument("--pool", type=int)
    args = ap.parse_args()
    cell = harness.load(Spec(), args.workload, args.n, args.pool)
    if cell.mix["loop"] != "open":
        ap.error(f"{args.workload} is not an open-loop cell")
    harness.devices(cell.chips, args.rehearse)
    if not args.rehearse:
        harness.use_compile_cache()
    base, pool = data.make_corpus(args.seed, cell.config["data"])
    _, engine = harness.build(cell, base)
    for rate in args.rates:
        mix = dict(cell.mix, rate_per_s=rate)
        win = drive.run_window(engine, pool, mix, args.seed, args.seconds)
        print(json.dumps(dict(rate_per_s=rate, **verdict(win))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
