#!/usr/bin/env python3
"""The control: the reference, computed one precision below the
configuration's, put in the program's place. It must come out not correct.

    python3 bench/control.py --workload deep1m_ivf_pq8.bulk \\
        --seeds 11 12 13 --seconds 10

For each seed: the cell's data, then the cell's traffic for `--seconds`
through the same `serve_loop` and `SearchEngine` front end, except that each
dispatch is answered by the brute-force reference with its operands in
bfloat16, the precision below the configurations' float32. No index is
built. The
same comparison as a run's (bench/correct.py) then prints each number
beside its limit. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import correct, data, drive, harness, reference  # noqa: E402
from bench.spec import Spec  # noqa: E402


DTYPE = "bfloat16"


def control_engine(cell: harness.Cell, base):
    """A SearchEngine whose dispatches are answered by the reference in
    bfloat16 (the program's index is never built)."""
    import jax.numpy as jnp
    from repro.core.index import KBest
    from repro.serve import SearchEngine
    stub = KBest(harness.index_config(cell.config["index"]))
    stub.db = base
    engine = SearchEngine(stub, **cell.config["serve"])
    dt = jnp.dtype(DTYPE).type

    def search(queries, k=None, search_cfg=None, gt_ids=None):
        kk = k or (search_cfg.k if search_cfg else cell.mix["k"])
        return reference.exact_topk(base, queries, kk, dtype=dt)

    engine.search = search
    return engine


def run_control(cell: harness.Cell, seed: int, seconds: float,
                rehearse: bool = False) -> dict:
    harness.devices(cell.chips, rehearse)
    if not rehearse:
        harness.use_compile_cache()
    base, pool = data.make_corpus(seed, cell.config["data"])
    engine = control_engine(cell, base)
    engine.search(pool[:cell.mix.get("size", 1)], k=cell.mix["k"])  # compile
    win = drive.run_window(engine, pool, cell.mix, seed, seconds)
    checks = correct.compare(base, pool, win.served, cell.mix["k"],
                             cell.config["limits"])
    return {"seed": seed, "dtype": DTYPE, "requests": len(win.served),
            "correct": all(c.ok for c in checks),
            "checks": correct.report(checks)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--n", type=int)
    ap.add_argument("--pool", type=int)
    args = ap.parse_args()
    cell = harness.load(Spec(), args.workload, args.n, args.pool)
    for seed in args.seeds:
        t = time.perf_counter()
        out = run_control(cell, seed, args.seconds, args.rehearse)
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
