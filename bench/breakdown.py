#!/usr/bin/env python3
"""One run of a cell as bench/run.py makes it, with the program's own
breakdown (bench/spans.py) added.

    python3 bench/breakdown.py --workload deep1m_graph.bulk --seed 7 \\
        --seconds 51 --trace 1

The run is `harness.run`, as in bench/run.py: the same set-up, window,
checks, metrics and result line. Besides, the engine's span aggregates
and counters (repro.serve.spans) are logged to stderr after the window,
traced or not; and with `--trace 1` the trace is also reduced by the
program's `kbest.*` spans and scopes before it is deleted: the result
line's `breakdown` gains `idle_by_program_span` and `device_by_scope`,
and stderr the device-to-host copies per `kbest.dispatch` span.

It stands in for lines that bench/harness.py lacks (PERF.md section 7):
for its run it replaces `drive.run_window` and `tracing.reduce_dir`,
which `harness.run` calls through their modules (bench/test_spans.py
checks that). Once harness.py makes these calls itself, this file goes.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import drive, harness, spans, tracing  # noqa: E402
from bench.spec import Spec  # noqa: E402


def run(spec: Spec, cell: harness.Cell, seed: int, seconds: float,
        trace: bool, rehearse: bool = False,
        t_start: float | None = None) -> dict:
    """`harness.run` with the engine's spans logged after the window and,
    traced, the trace reduced by bench/spans.py as well."""
    got = {"hlo": {}, "program": None}
    run_window, reduce_dir = drive.run_window, tracing.reduce_dir

    def window(engine, *args, **kw):
        win = run_window(engine, *args, **kw)
        spans.log_stats(engine.stats(), harness.log)
        if trace:
            got["hlo"] = spans.program_text(
                engine, harness.buckets(cell, engine), cell.mix["k"])
        return win

    def reduce(trace_dir):
        reduced = reduce_dir(trace_dir)
        got["program"] = spans.reduce_dir(trace_dir, got["hlo"], reduced)
        return reduced

    drive.run_window, tracing.reduce_dir = window, reduce
    try:
        out = harness.run(spec, cell, seed, seconds, trace, rehearse,
                          t_start)
    finally:
        drive.run_window, tracing.reduce_dir = run_window, reduce_dir
    p = got["program"]
    if p is not None and "breakdown" in out:
        out["breakdown"]["idle_by_program_span"] = p.idle
        out["breakdown"]["device_by_scope"] = p.device
        harness.log(f"device-to-host copies: {p.n_d2h} in "
                    f"{p.n_dispatches} kbest.dispatch spans")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever JAX finds (--n, --pool shrink it)")
    ap.add_argument("--n", type=int, help="corpus rows (rehearsal only)")
    ap.add_argument("--pool", type=int, help="query pool (rehearsal only)")
    args = ap.parse_args()
    if (args.n or args.pool) and not args.rehearse:
        ap.error("--n and --pool shrink a rehearsal only")
    spec = Spec()
    cell = harness.load(spec, args.workload, args.n, args.pool)
    try:
        out = run(spec, cell, args.seed, args.seconds, bool(args.trace),
                  args.rehearse, T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    # a rehearsal's line is marked, so that it is never read as a result
    print(("rehearsal (not a chip run): " if args.rehearse else "")
          + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
