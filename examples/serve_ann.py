"""End-to-end ANNS serving driver (the paper's deployment scenario):
variable-size batched requests against a prebuilt index through the
batch-serving engine (repro.serve) — shape-bucketed compile cache, early
termination tuned to a recall target, quantized (SQ) first-pass + exact
re-rank, and per-request latency/recall telemetry.

    PYTHONPATH=src python examples/serve_ann.py
"""
import numpy as np

from repro.core.index import KBest
from repro.core.tune import tune_early_term
from repro.core.types import (BuildConfig, IndexConfig, QuantConfig,
                              SearchConfig)
from repro.data.vectors import make_dataset
from repro.launch.serve import device_label
from repro.serve import Request, SearchEngine, serve_loop


def main():
    ds = make_dataset("deep_like", n=4000, n_queries=200, k=10)
    config = IndexConfig(
        dim=ds.base.shape[1], metric=ds.metric,
        build=BuildConfig(M=32, knn_k=48, refine_iters=1, reorder="mst"),
        search=SearchConfig(L=64, k=10),
        quant=QuantConfig(kind="sq"),           # int8 store + exact re-rank
    )
    index = KBest(config).add(ds.base)

    # --- offline: tune early termination under a recall constraint -------
    held_q, held_gt = ds.queries[:50], ds.gt_ids[:50]
    tuned = tune_early_term(index, held_q, held_gt,
                            SearchConfig(L=64, k=10), recall_target=0.95)
    print(f"tuned early-term: t_frac={tuned.et_t_frac} "
          f"patience={tuned.et_patience}")

    # --- online: serve the remaining queries in variable-size batches ----
    engine = SearchEngine(index, min_bucket=8, max_bucket=32)
    engine.warmup([32], search_cfg=tuned)       # precompile the hot bucket
    batch_size = 32
    requests = [
        # the final batch is PARTIAL (150 % 32 != 0): recall and latency
        # denominators must use the true per-request counts, not
        # ceil-batches * batch_size — serve_loop accounts per served query
        Request(queries=ds.queries[s:s + batch_size],
                gt_ids=ds.gt_ids[s:s + batch_size], search_cfg=tuned)
        for s in range(50, 200, batch_size)
    ]
    report = serve_loop(engine, requests, coalesce=False)
    st = report.engine_stats[engine.name]
    per_q = st.mean_lat_ms * st.n_requests / max(st.n_queries, 1)
    print(f"served {report.n_served} queries | "
          f"recall@10={report.recall_at_k:.3f} | "
          f"mean latency {per_q:.2f} ms/q ({device_label()}) | "
          f"p95 {report.lat_p95_ms:.2f} ms/batch")
    print("engine:", st.summary())
    assert report.n_served == 150, report.n_served


if __name__ == "__main__":
    main()
