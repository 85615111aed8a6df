"""QPS vs recall (paper Fig. 6 / Table 4) on the four dataset analogues.

Baselines are implemented in this framework (same harness, same traversal,
different GRAPH CONSTRUCTION — exactly the axis the paper varies):
  hnsw-style   : alpha rule with a=1.0 (HNSW's heuristic) + no 2-hop refine
  nsg-style    : alpha=1.2, search-based refinement, no 2-hop iterations
  vamana-style : alpha=1.2, 2 search passes (Vamana's two-pass build)
  kbest        : vamana-style + 2-hop iterative refinement (A1) + MST
                 reorder (A2); searched with tuned early termination (A3)

The IVF-PQ family (DESIGN.md §4) rides the same harness as a fifth variant:
its knob is nprobe (probed clusters) instead of L, and its cost driver is
scanned PQ codes (~m byte-reads each) instead of full-precision distances,
so its `dists_per_query` column counts scanned codes + re-ranked exacts.
The 4-bit fast-scan family (DESIGN.md §13) adds ivf-pq4 rows at half the
code bytes/vector, plus an ADC microbenchmark (adc_throughput) comparing
pq4's (m, 16) VMEM-resident-LUT scan against 8-bit PQ's (m, 256) gather —
`--pq4-smoke` runs a tiny config of exactly that and emits BENCH_pq4.json
so CI tracks the perf trajectory. The 1-bit sign codec (DESIGN.md §14)
adds ivf-bin rows (u32-packed XOR+popcount Hamming + exact rescore) —
`--bin-smoke` is its CI lane (recall >= 0.85 at >= 8x byte reduction vs
per-dimension pq8, BENCH_bin_smoke.json artifact) and `--bin-bench` the
50k acceptance lane behind the tracked BENCH_bin.json.

Wall-clock on this container is CPU-interpreted JAX, so absolute QPS is
meaningless; the table reports (a) per-query distance computations (the
hardware-independent cost driver: QPS ∝ 1/dists at fixed hardware) and
(b) measured relative QPS on CPU for the ablation's sanity.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import time

import jax.numpy as jnp
import numpy as np

from repro.core import quantize as qz
from repro.core.index import KBest
from repro.core.types import (BuildConfig, IVFConfig, IndexConfig,
                              QuantConfig, SearchConfig)
from repro.data.vectors import ALL_DATASETS, make_dataset, recall_at_k

# IVF sweep rows derive from THE registry (quantize.IVF_QUANT_KINDS) so a
# new IVF-capable kind appears here automatically; the per-kind kwargs are
# run_ivf overrides (bin: two-stage rescore needs the wider queue).
_IVF_KIND_KW = {kind: (dict(rescore_factor=16, L=192) if kind == "bin"
                       else {}) for kind in qz.IVF_QUANT_KINDS}
IVF_VARIANT_NAMES = tuple(f"ivf-{kind}" for kind in qz.IVF_QUANT_KINDS)

VARIANTS = {
    "hnsw-style": dict(select_rule="alpha", alpha=1.0, search_passes=0,
                       refine_iters=0, reorder="none"),
    "nsg-style": dict(select_rule="alpha", alpha=1.2, search_passes=1,
                      refine_iters=0, reorder="none"),
    "vamana-style": dict(select_rule="alpha", alpha=1.2, search_passes=2,
                         refine_iters=0, reorder="none"),
    "kbest": dict(select_rule="alpha", alpha=1.2, search_passes=2,
                  refine_iters=1, reorder="mst"),
}


# pq_m per dataset dim (must divide it); nprobe plays the role of L
IVF_PQ_M = {"glove_like": 20, "deep_like": 16, "t2i_like": 20,
            "bigann_like": 16}


def code_bytes_per_vector(idx: KBest) -> int:
    """Stored code bytes per database vector (the A4 memory axis).

    Delegates to the dtype-aware accounting in core/quantize.py — bin
    codes are uint32 WORDS, not bytes, so shape[-1] alone undercounts 4x."""
    from repro.core import quantize as qz
    return qz.code_bytes_per_vector(idx)


def run_ivf(ds, k: int, nprobes=(4, 8, 16, 32), quant_kind: str = "pq",
            pq_m: int = 0, rescore_factor: int = 8, L: int = 128) -> list:
    """The IVF rows: build once, sweep nprobe (the recall/cost knob).
    quant_kind "pq" (8-bit), "pq4" (4-bit fast-scan, half the bytes) or
    "bin" (1-bit sign codec, DESIGN.md §14 — rescore_factor*k exact
    rescore). pq_m=0 takes the per-dataset default; rescore_factor only
    matters for bin."""
    cfg = IndexConfig(
        dim=ds.base.shape[1], metric=ds.metric, index_type="ivf",
        ivf=IVFConfig(nlist=0, kmeans_iters=8),
        quant=QuantConfig(kind=quant_kind,
                          pq_m=pq_m or IVF_PQ_M[ds.name],
                          kmeans_iters=6),
        search=SearchConfig(L=L, k=k, nprobe=8,
                            rescore_factor=rescore_factor))
    idx = KBest(cfg).add(ds.base)
    rows = []
    for nprobe in nprobes:
        s = dataclasses.replace(cfg.search, nprobe=nprobe)
        idx.search(ds.queries[:8], search_cfg=s, with_stats=True)
        t0 = time.perf_counter()
        d, i, st = idx.search(ds.queries, search_cfg=s, with_stats=True)
        np.asarray(d)
        dt = time.perf_counter() - t0
        rows.append({
            "dataset": ds.name, "variant": f"ivf-{quant_kind}", "L": nprobe,
            "recall": recall_at_k(np.asarray(i), ds.gt_ids, k),
            "dists_per_query": float(np.asarray(st.n_dist).mean()),
            "hops_per_query": float(np.asarray(st.n_hops).mean()),
            "qps_cpu": ds.queries.shape[0] / dt,
            "code_bytes": code_bytes_per_vector(idx),
        })
    return rows


def adc_throughput(ds, n_codes: int = 4096, batch: int = 64,
                   reps: int = 5) -> dict:
    """ADC microbenchmark: pq4 (m, 16) LUT scan vs 8-bit pq (m, 256).

    Times the ref dist fn (XLA-compiled batched gather — the kernels'
    semantic twin; interpret-mode Pallas wall-clock is meaningless on CPU)
    over identical (Q, B) id batches and reports codes scored per second
    plus code bytes/vector. The hardware-independent claim pq4 makes is the
    memory one (half the code bytes, 16x smaller LUT); the measured CPU
    ratio is the sanity check that shrinking the gather axis helps.
    """
    import jax
    from repro.core import quantize as qz

    base = ds.base[:n_codes]
    q = ds.queries[:8]
    Q = q.shape[0]
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, base.shape[0], size=(Q, batch)),
                      jnp.int32)
    out = {}
    for kind in ("pq", "pq4"):
        m = IVF_PQ_M[ds.name]
        cfg = QuantConfig(kind=kind, pq_m=m, kmeans_iters=4)
        st = qz.pq_train(jnp.asarray(base), cfg)
        if kind == "pq4":
            codes = qz.pq4_encode(st.codebooks, jnp.asarray(base))
            tables = qz.pq4_query_tables(st.codebooks, jnp.asarray(q), ds.metric)
            fn = functools.partial(qz.pq4_make_dist_fn(m), codes)
        else:
            codes = qz.pq_encode(st.codebooks, jnp.asarray(base))
            tables = qz.pq_query_tables(st.codebooks, jnp.asarray(q), ds.metric)
            fn = functools.partial(qz.pq_make_dist_fn(m), codes)
        jfn = jax.jit(fn)
        jfn(tables, ids).block_until_ready()            # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jfn(tables, ids).block_until_ready()
        dt = (time.perf_counter() - t0) / reps
        out[kind] = {
            "codes_per_sec": Q * batch / dt,
            "code_bytes": int(codes.shape[-1]),
            "lut_bytes": int(tables.shape[-1]) * 4,
        }
    out["pq4_speedup"] = out["pq4"]["codes_per_sec"] / out["pq"]["codes_per_sec"]
    return out


def run(n: int = 4000, n_queries: int = 100, k: int = 10,
        Ls=(32, 64, 128, 192, 256), quick: bool = False):
    if quick:
        n, n_queries, Ls = 2000, 50, (32, 64, 128)
    rows = []
    for ds_name in ALL_DATASETS:
        ds = make_dataset(ds_name, n=n, n_queries=n_queries, k=k)
        nprobes = (4, 8, 16) if quick else (4, 8, 16, 32)
        # one ivf-<kind> row-set per IVF-capable registry kind; bin's flat
        # Hamming scan needs the wide-queue + deep-rescore overrides
        for kind in _IVF_KIND_KW:
            rows.extend(run_ivf(ds, k, nprobes=nprobes, quant_kind=kind,
                                **_IVF_KIND_KW[kind]))
        for variant, bkw in VARIANTS.items():
            cfg = IndexConfig(
                dim=ds.base.shape[1], metric=ds.metric,
                build=BuildConfig(M=32, knn_k=48, builder="brute", **bkw),
                search=SearchConfig(L=64, k=k, early_term=False))
            idx = KBest(cfg).add(ds.base)
            for L in Ls:
                # kbest searches with A3 early termination; patience scales
                # with L (the paper binary-searches tau_max per dataset —
                # L/4 is the tuner's typical landing zone, see core/tune.py)
                s = dataclasses.replace(
                    cfg.search, L=L,
                    early_term=(variant == "kbest"),
                    et_patience=max(16, L // 4))
                # warmup + timed
                idx.search(ds.queries[:8], search_cfg=s)
                t0 = time.perf_counter()
                d, i, st = idx.search(ds.queries, search_cfg=s,
                                      with_stats=True)
                np.asarray(d)
                dt = time.perf_counter() - t0
                rows.append({
                    "dataset": ds_name, "variant": variant, "L": L,
                    "recall": recall_at_k(np.asarray(i), ds.gt_ids, k),
                    "dists_per_query": float(np.asarray(st.n_dist).mean()),
                    "hops_per_query": float(np.asarray(st.n_hops).mean()),
                    "qps_cpu": n_queries / dt,
                })
    return rows


def qps_at_recall(rows, target=0.9):
    """Best hardware-independent throughput proxy (1/dists) meeting the
    recall target, per (dataset, variant) — the Table 4 analogue."""
    out = {}
    for r in rows:
        key = (r["dataset"], r["variant"])
        if r["recall"] >= target:
            score = 1.0 / r["dists_per_query"]
            if key not in out or score > out[key][0]:
                out[key] = (score, r)
    return out


def pq4_smoke(out: str = "BENCH_pq4.json", n: int = 2000,
              n_queries: int = 32) -> dict:
    """Tiny pq4 lane for CI: ivf-pq4 vs ivf-pq rows on one dataset + the
    ADC microbenchmark, written to `out` so the perf trajectory (ADC
    throughput, code bytes, recall) is recorded per commit."""
    ds = make_dataset("bigann_like", n=n, n_queries=n_queries, k=10)
    rows = (run_ivf(ds, 10, nprobes=(8, 16), quant_kind="pq")
            + run_ivf(ds, 10, nprobes=(8, 16), quant_kind="pq4"))
    adc = adc_throughput(ds)
    by_kind = {v: [r for r in rows if r["variant"] == v]
               for v in ("ivf-pq", "ivf-pq4")}
    # the memory claim is structural — fail the lane loudly if it drifts
    assert by_kind["ivf-pq4"][0]["code_bytes"] * 2 == \
        by_kind["ivf-pq"][0]["code_bytes"], "pq4 must be half of pq8 bytes"
    report = {
        "dataset": ds.name, "n": n, "rows": rows, "adc": adc,
        "best_recall": {v: max(r["recall"] for r in rs)
                        for v, rs in by_kind.items()},
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out}")
    print(f"  code bytes/vec: pq={adc['pq']['code_bytes']} "
          f"pq4={adc['pq4']['code_bytes']}")
    print(f"  ADC codes/s: pq={adc['pq']['codes_per_sec']:.3g} "
          f"pq4={adc['pq4']['codes_per_sec']:.3g} "
          f"(pq4 {adc['pq4_speedup']:.2f}x)")
    print(f"  best recall: {report['best_recall']}")
    return report


def bin_smoke(out: str = "BENCH_bin_smoke.json", n: int = 2000,
              n_queries: int = 32) -> dict:
    """Tiny bin lane for CI (DESIGN.md §14): IVF-bin + graph-bin rows vs an
    equal-per-dimension-resolution pq8 comparator (pq_m=d, i.e. one 8-bit
    code per dimension — the honest baseline for "8x smaller codes": the
    stock pq8 preset already compresses by grouping dims). Asserts the two
    structural claims CI tracks: best bin recall >= 0.85 and >= 8x byte
    reduction vs that pq8. Artifact-only (upload, don't commit)."""
    ds = make_dataset("deep_like", n=n, n_queries=n_queries, k=10)
    d = ds.base.shape[1]
    rows = run_ivf(ds, 10, nprobes=(16, 24), quant_kind="bin",
                   rescore_factor=16, L=192)
    # graph-bin row
    cfg = IndexConfig(
        dim=d, metric=ds.metric,
        build=BuildConfig(M=24, knn_k=32, builder="brute", refine_iters=0,
                          reorder="none"),
        quant=QuantConfig(kind="bin"),
        search=SearchConfig(L=192, k=10, rescore_factor=16,
                            early_term=False))
    gidx = KBest(cfg).add(ds.base)
    t0 = time.perf_counter()
    _, gi, gst = gidx.search(ds.queries, with_stats=True)
    dt = time.perf_counter() - t0
    bin_bytes = code_bytes_per_vector(gidx)
    rows.append({
        "dataset": ds.name, "variant": "graph-bin", "L": 192,
        "recall": recall_at_k(np.asarray(gi), ds.gt_ids, 10),
        "dists_per_query": float(np.asarray(gst.n_dist).mean()),
        "hops_per_query": float(np.asarray(gst.n_hops).mean()),
        "qps_cpu": ds.queries.shape[0] / dt,
        "code_bytes": bin_bytes,
    })
    # pq8 comparator at pq_m=d: one 8-bit code per dimension (d bytes)
    pq8_rows = run_ivf(ds, 10, nprobes=(16,), quant_kind="pq", pq_m=d)
    pq8_bytes = pq8_rows[0]["code_bytes"]
    best_bin = max(r["recall"] for r in rows)
    assert bin_bytes * 8 <= pq8_bytes, \
        f"bin must be >=8x smaller than per-dim pq8: {bin_bytes} vs {pq8_bytes}"
    assert best_bin >= 0.85, f"bin smoke recall floor: {best_bin:.3f} < 0.85"
    report = {
        "dataset": ds.name, "n": n, "rows": rows + pq8_rows,
        "bin_code_bytes": bin_bytes, "pq8_per_dim_code_bytes": pq8_bytes,
        "byte_reduction_vs_pq8": pq8_bytes / bin_bytes,
        "best_bin_recall": best_bin,
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out}")
    print(f"  code bytes/vec: bin={bin_bytes} pq8(m=d)={pq8_bytes} "
          f"({pq8_bytes / bin_bytes:.1f}x reduction)")
    print(f"  best bin recall@10: {best_bin:.3f}")
    return report


def bin_bench(out: str = "BENCH_bin.json", n: int = 50_000,
              n_queries: int = 50) -> dict:
    """The 50k bin acceptance lane (DESIGN.md §14): graph-bin and IVF-bin
    preset configs on the 50k deep_like analogue, recall floor 0.90 with
    rescore, at >= 8x smaller codes than per-dimension pq8 (d u8 codes).
    Writes the tracked BENCH_bin.json baseline."""
    from repro.configs import kbest as kcfg

    import dataclasses

    ds = make_dataset("deep_like", n=n, n_queries=n_queries, k=10)
    d = ds.base.shape[1]
    # graph-bin at 50k needs a much deeper queue than the <=10k preset
    # (DESIGN.md §14): L=640 / rf=64 measures 0.908 vs 0.818 at the
    # preset's L=320 / rf=32
    gcfg = kcfg.bin_index_config("deep_like")
    gcfg = dataclasses.replace(
        gcfg, search=dataclasses.replace(gcfg.search, L=640,
                                         rescore_factor=64,
                                         early_term=False))
    rows = []
    for name, cfg in (("ivf-bin", kcfg.ivf_bin_index_config("deep_like")),
                      ("graph-bin", gcfg)):
        t0 = time.perf_counter()
        idx = KBest(cfg).add(ds.base)
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        _, ids, st = idx.search(ds.queries, with_stats=True)
        dt = time.perf_counter() - t0
        rows.append({
            "dataset": ds.name, "variant": name, "n": n,
            "L": cfg.search.L, "nprobe": cfg.search.nprobe,
            "rescore_factor": cfg.search.rescore_factor,
            "recall": recall_at_k(np.asarray(ids), ds.gt_ids, 10),
            "dists_per_query": float(np.asarray(st.n_dist).mean()),
            "qps_cpu": n_queries / dt, "build_s": build_s,
            "code_bytes": code_bytes_per_vector(idx),
        })
        print(f"  {name}: recall@10={rows[-1]['recall']:.3f} "
              f"build_s={build_s:.0f}", flush=True)
    bin_bytes = rows[0]["code_bytes"]
    report = {
        "dataset": ds.name, "n": n, "rows": rows,
        "bin_code_bytes": bin_bytes,
        "pq8_per_dim_code_bytes": d,        # one u8 code per dimension
        "byte_reduction_vs_pq8": d / bin_bytes,
        "best_bin_recall": max(r["recall"] for r in rows),
    }
    for r in rows:
        assert r["recall"] >= 0.90, (r["variant"], r["recall"])
    assert bin_bytes * 8 <= d, (bin_bytes, d)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {out}")
    return report


def main(quick=False):
    rows = run(quick=quick)
    print("dataset,variant,L,recall,dists_per_query,qps_cpu,code_bytes")
    for r in rows:
        print(f"{r['dataset']},{r['variant']},{r['L']},{r['recall']:.3f},"
              f"{r['dists_per_query']:.0f},{r['qps_cpu']:.1f},"
              f"{r.get('code_bytes', '-')}")
    print("\n# Table-4 analogue: throughput proxy (1e3/dists) @ recall>=0.9")
    best = qps_at_recall(rows, 0.9)
    for ds in ALL_DATASETS:
        line = [f"{ds:12s}"]
        for v in list(VARIANTS) + list(IVF_VARIANT_NAMES):
            e = best.get((ds, v))
            line.append(f"{v}={1e3*e[0]:.2f}" if e else f"{v}=n/a")
        print("  ".join(line))
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--pq4-smoke", action="store_true",
                    help="tiny pq4-vs-pq8 lane; writes --out JSON")
    ap.add_argument("--bin-smoke", action="store_true",
                    help="tiny bin-vs-pq8 lane (recall>=0.85, >=8x bytes); "
                         "writes --out JSON")
    ap.add_argument("--bin-bench", action="store_true",
                    help="50k bin acceptance lane; writes BENCH_bin.json")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    if args.pq4_smoke:
        pq4_smoke(out=args.out or "BENCH_pq4.json")
    elif args.bin_smoke:
        bin_smoke(out=args.out or "BENCH_bin_smoke.json")
    elif args.bin_bench:
        bin_bench(out=args.out or "BENCH_bin.json")
    else:
        main(quick=args.quick)
