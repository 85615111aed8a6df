"""Benchmark aggregator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Sections:
  [qps_recall]  paper Fig. 6 / Table 4 — QPS-recall curves, 4 datasets,
                4 graph build variants (baselines implemented in-framework)
                + the IVF-PQ family swept over nprobe
  [ablation]    paper Fig. 7 — Base -> +Index -> +EarlyTerm -> +SIMD ->
                +Prefetch
  [scaling]     paper §5.2 — corpus-size sweep + the ShardedKBest shard
                sweep (shards x family x quant, DESIGN.md §12)
  [serving]     beyond-paper — closed/open-loop QPS through the batch-
                serving engine (shape-bucketed compile cache, DESIGN.md §11)
  [traverse]    beyond-paper — beam-width sweep of the lockstep traversal
                (iterations / dists / recall vs W, DESIGN.md §2)
  [roofline]    beyond-paper — cost-model validation on live 5k runs:
                exact n_dist checks + predicted-vs-measured cost ordering
                + roofline table (DESIGN.md §16; writes BENCH_roofline.json)

Each section prints `name,us_per_call,derived` style CSV rows.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sizes (CI-friendly)")
    ap.add_argument("--sections", type=str, default="all")
    ap.add_argument("--bin-smoke", action="store_true",
                    help="run ONLY the bin CI lane (recall >= 0.85 at "
                         ">= 8x byte reduction vs per-dim pq8; writes "
                         "BENCH_bin_smoke.json — artifact-only)")
    ap.add_argument("--cost-smoke", action="store_true",
                    help="run ONLY the cost-model CI lane (exact n_dist "
                         "equality + Spearman >= 0.8 cost ordering at 5k; "
                         "writes BENCH_cost_smoke.json — artifact-only)")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="run ONLY the overload-serving CI lane (2x-"
                         "saturation ramp: policy p99 under SLO + goodput "
                         "over baseline + recall above ladder floor, plus "
                         "the crash-point save/load matrix; writes "
                         "BENCH_serving_smoke.json — artifact-only)")
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")
    if args.bin_smoke:
        from benchmarks import qps_recall
        qps_recall.bin_smoke()
        return
    if args.cost_smoke:
        from benchmarks import roofline
        roofline.main(smoke=True, out="BENCH_cost_smoke.json")
        return
    if args.serve_smoke:
        from benchmarks import serving
        serving.overload_main(smoke=True, out="BENCH_serving_smoke.json")
        return
    want = (args.sections.split(",") if args.sections != "all"
            else ["qps_recall", "ablation", "scaling", "serving",
                  "traverse", "roofline"])

    failures = []
    for name in want:
        print(f"\n{'='*72}\n[{name}]\n{'='*72}")
        t0 = time.time()
        try:
            if name == "qps_recall":
                from benchmarks import qps_recall
                qps_recall.main(quick=args.quick)
            elif name == "ablation":
                from benchmarks import ablation
                ablation.main(quick=args.quick)
            elif name == "scaling":
                from benchmarks import scaling
                scaling.main(quick=args.quick)
            elif name == "serving":
                from benchmarks import serving
                serving.main(smoke=args.quick)
            elif name == "traverse":
                from benchmarks import traverse
                # BENCH_traverse.json is the git-tracked 50k baseline —
                # quick (5k) runs must not clobber it
                traverse.main(quick=args.quick,
                              out=("BENCH_traverse_quick.json" if args.quick
                                   else "BENCH_traverse.json"))
            elif name == "roofline":
                from benchmarks import roofline
                # BENCH_roofline.json is the full-report output; --quick
                # keeps the same 5k size (the bench IS the validation)
                roofline.main(quick=args.quick)
            print(f"[{name}] done in {time.time()-t0:.1f}s")
        except Exception as e:
            traceback.print_exc()
            failures.append((name, repr(e)))
    if failures:
        print("\nBENCH FAILURES:", failures)
        sys.exit(1)
    print("\nall benchmark sections completed")


if __name__ == "__main__":
    main()
