#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload deep1m_graph.bulk --seed 7 \\
        --seconds 30 --trace 0

Set-up (data from the seed, index build, warm-up of the cell's buckets) is
timed as `setup_s`; then the cell's traffic is served through
`repro.serve.serve_loop` for `--seconds` on the wall clock, every answer is
checked against the exact reference, and the last line of standard output
is one JSON object: `correct`, `attempted`, `failed`, `metrics` (the cell's
end-to-end metrics, or with `--trace 1` its per-layer metrics), `device`
(and with `--trace 1`, `breakdown`), then `checks`: each number compared,
with its limit. Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.

`--rehearse` runs on whatever JAX finds (the CPU, Pallas in interpret
mode), optionally at a smaller `--n` and `--pool`, and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402
from bench.spec import Spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever JAX finds; print no result")
    ap.add_argument("--n", type=int, help="corpus rows (rehearsal only)")
    ap.add_argument("--pool", type=int, help="query pool (rehearsal only)")
    args = ap.parse_args()
    if (args.n or args.pool) and not args.rehearse:
        ap.error("--n and --pool shrink a rehearsal only")
    logging.basicConfig(level=logging.INFO, stream=sys.stderr,
                        format="[log] %(message)s")
    for noisy in ("jax", "absl"):
        logging.getLogger(noisy).setLevel(logging.WARNING)
    spec = Spec()
    cell = harness.load(spec, args.workload, args.n, args.pool)
    try:
        out = harness.run(spec, cell, args.seed, args.seconds,
                          bool(args.trace), args.rehearse, T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    if args.rehearse:
        print(f"rehearsal (no result: not a chip run): correct="
              f"{out['correct']} metrics={json.dumps(out['metrics'])}",
              flush=True)
        return 0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
