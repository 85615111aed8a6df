"""Serving launcher: KBest ANNS service or model serve steps.

    # ANNS service over a synthetic corpus
    PYTHONPATH=src python -m repro.launch.serve --mode ann --n 4000

    # same service over a sharded mesh (ShardedKBest, DESIGN.md §12):
    # every engine serves a --shards-way sharded index through the same
    # shape-bucketed compile cache (the cache key carries the mesh shape)
    PYTHONPATH=src python -m repro.launch.serve --mode ann --n 4000 --shards 2

    # beam-parallel traversal for the graph engine (DESIGN.md §2): W
    # expansions per lockstep iteration, same results floor, ~W x fewer
    # iterations per batch
    PYTHONPATH=src python -m repro.launch.serve --mode ann --beam 4

    # one decode step of a smoke LM with a KV cache (the decode_32k path)
    PYTHONPATH=src python -m repro.launch.serve --mode lm --arch gemma-2b
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def serve_ann(n: int, shards: int = 1, beam: int = 1):
    """Graph and IVF indexes served side by side through the batch-serving
    engine (repro.serve): mixed batch sizes and mixed k drain through one
    shape-bucketed compile cache per engine. shards > 1 builds each index
    as a ShardedKBest mesh (DESIGN.md §12) behind the same engines; beam > 1
    searches the graph engine with beam-parallel traversal (DESIGN.md §2 —
    the beam_width rides SearchConfig, so it is part of the cache key)."""
    from repro.core.index import KBest
    from repro.core.sharded import ShardedKBest
    from repro.core.types import (BuildConfig, IVFConfig, IndexConfig,
                                  QuantConfig, SearchConfig)
    from repro.data.vectors import make_dataset
    from repro.serve import Request, SearchEngine, serve_loop

    def build(cfg, base):
        if cfg.n_shards > 1:
            return ShardedKBest(cfg).add(base)
        return KBest(cfg).add(base)

    ds = make_dataset("deep_like", n=n, n_queries=100, k=10)
    dim = ds.base.shape[1]
    graph = build(IndexConfig(
        dim=dim, metric=ds.metric, n_shards=shards,
        build=BuildConfig(M=32, knn_k=48, refine_iters=1, reorder="mst"),
        search=SearchConfig(L=64, k=10, early_term=True,
                            beam_width=beam)), ds.base)
    ivf = build(IndexConfig(
        dim=dim, metric=ds.metric, index_type="ivf", n_shards=shards,
        ivf=IVFConfig(kmeans_iters=6),
        quant=QuantConfig(kind="pq", pq_m=16, kmeans_iters=6),
        search=SearchConfig(L=64, k=10, nprobe=8)), ds.base)

    engines = {"graph": SearchEngine(graph, max_bucket=16, name="graph"),
               "ivf": SearchEngine(ivf, max_bucket=16, name="ivf")}
    for e in engines.values():
        for kk in (5, 10):        # warm EVERY (bucket, k) the traffic emits,
            e.warmup(k=kk)        # or first-hit compiles pollute latencies

    rng = np.random.default_rng(0)
    requests, s = [], 0
    while s < len(ds.queries):
        b = int(rng.integers(4, 17))          # variable-size traffic
        e = min(s + b, len(ds.queries))
        requests.append(Request(
            queries=ds.queries[s:e], gt_ids=ds.gt_ids[s:e],
            engine=rng.choice(["graph", "ivf"]),
            k=int(rng.choice([5, 10]))))
        s = e

    t0 = time.perf_counter()
    report = serve_loop(engines, requests)
    dt = time.perf_counter() - t0
    print(f"{report.summary()} | wall {dt*1e3:.1f} ms ({device_label()})")
    for name, st in sorted(report.engine_stats.items()):
        print(f"  [{name}] {st.summary()}")
    for r in report.results:
        if r.status == "failed":
            print(f"  request {r.request_id} ({r.engine}) failed: {r.error}")
    return report


def device_label() -> str:
    """What the timings ran on, as JAX reports it (Pallas kernels run in
    interpret mode on a CPU platform)."""
    d = jax.devices()[0]
    mode = ", Pallas interpret mode" if d.platform == "cpu" else ""
    return f"{d.platform} {d.device_kind} x{len(jax.devices())}{mode}"


def serve_lm(arch: str):
    from repro import configs as reg
    from repro.models import transformer as T
    cfg = reg.get(arch).smoke_config()
    p = T.init_params(cfg, jax.random.PRNGKey(0))
    cache = T.init_cache(cfg, 2, 64, dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 1), 0, cfg.vocab)
    step = jax.jit(lambda p, c, t: T.decode_step(p, c, t, cfg))
    logits, cache = step(p, cache, toks)          # compile
    t0 = time.perf_counter()
    for _ in range(16):
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        logits, cache = step(p, cache, nxt)
    jax.block_until_ready(logits)
    dt = (time.perf_counter() - t0) / 16
    print(f"{arch}: {dt*1e3:.2f} ms/token (smoke config, {device_label()}), "
          f"cache len={int(cache['len'][0])}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("ann", "lm"), default="ann")
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--beam", type=int, default=1,
                    help="graph-engine beam width W (DESIGN.md §2)")
    ap.add_argument("--shards", type=int, default=1,
                    help="ShardedKBest mesh size for --mode ann (1 = plain)")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.mode == "ann":
        report = serve_ann(args.n, shards=args.shards, beam=args.beam)
        if report.n_failed:
            # a failed dispatch (e.g. a kernel the compiler refused) is
            # caught per request by serve_loop's error boundary — it must
            # still fail the launcher
            raise SystemExit(f"{report.n_failed} request(s) failed")
    else:
        serve_lm(args.arch)


if __name__ == "__main__":
    main()
