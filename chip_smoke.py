#!/usr/bin/env python3
"""On-chip smoke test: a 1M-vector DEEP-style index served on a TPU.

    python chip_smoke.py               # one chip: both index families
    python chip_smoke.py --chips 4     # the sharded shard_map path, 4 chips

One chip (the default). The corpus is `make_dataset("deep_like")` at
1,000,000 x 96 f32, unit-norm, inner product (the size of DEEP1M and of
ANN-Benchmarks' 1M sets), with 1,000 queries and exact top-10 ground truth,
all made from --seed. Both presets are built through `KBest.add` — the
graph index `configs.kbest.index_config("deep_like")` and the IVF-PQ8
index `ivf_index_config("deep_like")` — and the queries are served through
`SearchEngine` + `serve_loop` as mixed-size requests (1-256 queries, k in
{5, 10}), once on the presets' `dist_impl="ref"` path and once on the
Pallas `dist_impl="kernel"` path. It fails unless every request comes back
"ok", recall@10 >= 0.80 for each family and path, and the kernel path's ids
agree with the ref path's on >= 99% of (query, rank) entries.

Four chips (--chips 4). A 4-shard graph index over the same corpus
(`ShardedKBest`) is served by the `jax.shard_map` lowering
(core/sharded.py: build_sharded_search) with each shard's arrays on its own
chip, and compared with the one-device `ShardedKBest.search` of the same
shards: global ids must agree on >= 99% of entries, and each chip must hold
about a quarter of the index (device memory_stats).

Every phase prints its timings on the way (a single smoke run, not a
benchmark). The last stdout line is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}},
printed only when every phase passed; any failure exits non-zero. No TPU,
no result: the script never falls back to the CPU. `--rehearse` runs the
same phases on whatever JAX finds (the CPU, with Pallas in interpret mode)
at a --n of your choice, and prints no result line.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import logging
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# one bucket: every request is padded to 256 rows, so each (engine, path)
# compiles two programs (k = 5 and k = 10)
MIN_BUCKET, MAX_BUCKET = 256, 256
RECALL_FLOOR = 0.80
AGREEMENT_FLOOR = 0.99


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Timer:
    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = time.perf_counter() - self.t0
        if exc[0] is None:
            log(f"[time] {self.name}: {self.s:.1f} s")


def device_info(chips: int, rehearse: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    if not rehearse:
        check(info["platform"] == "tpu",
              f"no TPU found (JAX platform is {info['platform']!r})")
    check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)}")
    return info


def make_data(n: int, n_queries: int, seed: int):
    from repro.data.vectors import make_dataset
    with Timer(f"data: deep_like {n} x 96 + {n_queries} queries + exact "
               f"top-10"):
        ds = make_dataset("deep_like", n=n, n_queries=n_queries, k=10,
                          seed=seed)
    return ds


def make_requests(ds, seed: int):
    """Mixed-size traffic: 1-256 queries per request, k in {5, 10}."""
    import numpy as np
    rng = np.random.default_rng(seed)
    plan, s = [], 0
    while s < len(ds.queries):
        e = min(s + int(rng.integers(1, 257)), len(ds.queries))
        plan.append((s, e, int(rng.choice([5, 10]))))
        s = e
    return plan


def serve(name: str, index, ds, plan, impl: str):
    """One drain of the whole plan through a fresh engine; returns the
    per-request ids (request order) after checking every status."""
    import numpy as np
    from repro.data.vectors import recall_at_k
    from repro.serve import Request, SearchEngine, serve_loop
    from repro.serve.engine import bucket_ladder

    scfg = dataclasses.replace(index.config.search, dist_impl=impl)
    eng = SearchEngine(index, min_bucket=MIN_BUCKET, max_bucket=MAX_BUCKET,
                       name=f"{name}-{impl}")
    with Timer(f"compile {name}/{impl}") as tc:
        for k in (5, 10):
            eng.warmup(bucket_ladder(MIN_BUCKET, MAX_BUCKET), k=k,
                       search_cfg=scfg)
    n_compiles = eng.n_traces
    log(f"compile {name}/{impl}: {n_compiles} programs in {tc.s:.1f} s")
    requests = [Request(queries=ds.queries[s:e], gt_ids=ds.gt_ids[s:e],
                        k=k, search_cfg=scfg, engine=eng.name)
                for s, e, k in plan]
    with Timer(f"serve {name}/{impl}") as ts:
        report = serve_loop(eng, requests)
    bad = [(r.request_id, r.status, r.error) for r in report.results
           if r.status != "ok"]
    check(not bad, f"{name}/{impl}: requests not ok: {bad[:5]}")
    check(report.n_failed == 0 and report.n_rejected == 0
          and report.n_shed == 0, f"{name}/{impl}: {report.summary()}")
    check(report.n_served == len(ds.queries),
          f"{name}/{impl}: served {report.n_served} of {len(ds.queries)}")
    check(eng.n_traces == n_compiles,
          f"{name}/{impl}: {eng.n_traces - n_compiles} compiles while "
          f"serving warmed buckets")
    by_id = sorted(report.results, key=lambda r: r.request_id)
    ids = [np.asarray(r.ids) for r in by_id]
    rec = {}
    for k in (5, 10):
        sel = [i for i, (_, _, kk) in enumerate(plan) if kk == k]
        if sel:
            got = np.concatenate([ids[i] for i in sel])
            gt = np.concatenate([ds.gt_ids[plan[i][0]:plan[i][1]]
                                 for i in sel])
            rec[k] = recall_at_k(got, gt, k)
    log(f"serve {name}/{impl}: {report.summary()} | wall {ts.s:.2f} s | "
        f"recall@10={rec.get(10, float('nan')):.4f} (k=10 requests) "
        f"recall@5={rec.get(5, float('nan')):.4f} (k=5 requests)")
    check(10 in rec and rec[10] >= RECALL_FLOOR,
          f"{name}/{impl}: recall@10 {rec.get(10)} < {RECALL_FLOOR}")
    return ids, rec, n_compiles, tc.s


def agreement(a, b) -> float:
    import numpy as np
    a, b = np.concatenate([x.ravel() for x in a]), \
        np.concatenate([x.ravel() for x in b])
    return float(np.mean(a == b))


def one_chip(args) -> dict:
    from repro.configs import kbest
    from repro.core.index import KBest

    ds = make_data(args.n, args.queries, args.seed)
    plan = make_requests(ds, args.seed)
    log(f"traffic: {len(plan)} requests, sizes "
        f"{[e - s for s, e, _ in plan]}, k {[k for _, _, k in plan]}")
    out = {"n": args.n, "queries": args.queries, "build_s": {},
           "recall": {}, "agreement": {}, "compiles": {}}
    indexes = {"graph": kbest.index_config("deep_like"),
               "ivf": kbest.ivf_index_config("deep_like")}
    for name, cfg in indexes.items():
        with Timer(f"build {name}") as tb:
            idx = KBest(cfg).add(ds.base)
        stages = " ".join(f"{k}={v:.1f}s" for k, v in idx.build_s.items())
        log(f"build {name}: {tb.s:.1f} s ({stages})")
        out["build_s"][name] = dict(idx.build_s, total=tb.s)
        ids = {}
        for impl in ("ref", "kernel"):
            ids[impl], rec, nc, cs = serve(name, idx, ds, plan, impl)
            out["recall"][f"{name}/{impl}"] = rec
            out["compiles"][f"{name}/{impl}"] = {"programs": nc,
                                                 "seconds": cs}
        agree = agreement(ids["ref"], ids["kernel"])
        log(f"agreement {name}: kernel vs ref ids equal on "
            f"{agree * 100:.3f}% of (query, rank) entries")
        out["agreement"][name] = agree
        check(agree >= AGREEMENT_FLOOR,
              f"{name}: kernel/ref agreement {agree} < {AGREEMENT_FLOOR}")
        del idx
    return out


def build_sharded(cfg, base, devs):
    """ShardedKBest.add, with shard s built on devs[s] in its own thread
    (the shards are independent; device work overlaps), then gathered on
    devs[0] for the one-device reference search."""
    import jax
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor
    from repro.core.index import KBest
    from repro.core.sharded import ShardedKBest, shard_bounds

    idx = ShardedKBest(cfg)
    idx.offsets = shard_bounds(base.shape[0], cfg.n_shards)
    shard_cfg = dataclasses.replace(cfg, n_shards=1)

    def build(s):
        with jax.default_device(devs[s]):
            shard = KBest(shard_cfg).add(
                np.asarray(base[idx.offsets[s]:idx.offsets[s + 1]],
                           np.float32))
        log(f"build shard {s} on {devs[s]}: " + " ".join(
            f"{k}={v:.1f}s" for k, v in shard.build_s.items()))
        return shard

    with ThreadPoolExecutor(len(devs)) as ex:
        shards = list(ex.map(build, range(cfg.n_shards)))
    idx.shards = [jax.device_put(s, devs[0]) for s in shards]
    return idx


def four_chips(args) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import kbest
    from repro.core.sharded import build_sharded_search, make_sharded_arrays

    P = args.chips
    devs = jax.devices()[:P]
    ds = make_data(args.n, args.queries, args.seed)
    cfg = kbest.sharded_index_config("deep_like", P)
    # one entry point per shard: the shard_map lowering seeds each shard's
    # traversal from that shard's medoid alone
    scfg = dataclasses.replace(cfg.search, n_entries=1)
    with Timer(f"build {P}-shard graph, one shard per chip") as tb:
        idx = build_sharded(cfg, ds.base, devs)
    sizes = np.diff(idx.offsets)
    check(len(set(sizes.tolist())) == 1,
          f"shard_map layout needs equal shards, got {sizes}")
    n_local = int(sizes[0])

    with Timer("one-device ShardedKBest.search") as t1:
        _, ref_ids = idx.search(ds.queries, search_cfg=scfg)
        ref_ids = np.asarray(ref_ids)
    # host copies of the per-shard layout, then free the one-device index
    db = np.concatenate([np.asarray(s.db) for s in idx.shards])
    graph = np.concatenate([np.asarray(s.graph) for s in idx.shards])
    entries = np.asarray([s.entry for s in idx.shards], np.int32)
    orders = [np.asarray(s.order) if s.order is not None
              else np.arange(n_local) for s in idx.shards]
    offsets = idx.offsets
    del idx
    index_bytes = db.nbytes + graph.nbytes

    def in_use():
        stats = [d.memory_stats() for d in devs]
        if any(s is None for s in stats):
            check(args.rehearse, "device memory_stats not reported")
            return [0] * P
        return [s["bytes_in_use"] for s in stats]

    gc.collect()    # the per-chip build copies must be gone before `before`
    before = in_use()
    mesh = Mesh(np.asarray(devs), ("shards",))
    arrs = make_sharded_arrays(mesh, db, graph, entries,
                               jnp.asarray(ds.queries))
    jax.block_until_ready(arrs)
    held = [a - b for a, b in zip(in_use(), before)]
    # what each chip should hold: its shards of the index arrays as laid
    # out on the device (a TPU pads the minor dim of a 2-D array to 128
    # lanes, so (n, 96) f32 rows and (n, 24) i32 graph rows take more
    # than n * 4 * (96 + 24) B there)
    own = [sum(sh.data.on_device_size_in_bytes()
               for a in arrs[:3] for sh in a.addressable_shards
               if sh.device == d) for d in devs]
    log(f"index {index_bytes / 2**20:.1f} MiB ({index_bytes / P / 2**20:.1f}"
        f" MiB per chip unpadded); per-chip shard bytes on device "
        f"{[round(o / 2**20, 1) for o in own]} MiB; per-chip bytes placed "
        f"(memory_stats) {[round(h / 2**20, 1) for h in held]} MiB")
    fn = build_sharded_search(mesh, scfg, "ip", n_local)
    with Timer("compile shard_map search"):
        compiled = fn.lower(*arrs).compile()
    with Timer("shard_map search") as t4:
        _, g = compiled(*arrs)
        g = np.asarray(g)
    # shard_map ids index the concatenated shard layouts; map them to the
    # add() row ids the one-device search returns
    shard = np.where(g >= 0, g // n_local, 0)
    local = np.where(g >= 0, g % n_local, 0)
    orig = np.stack(orders) + offsets[:-1, None]        # (P, n_local)
    mapped = np.where(g >= 0, orig[shard, local], -1)
    agree = float(np.mean(mapped == ref_ids))
    log(f"agreement shard_map vs one-device: {agree * 100:.3f}% "
        f"(one-device {t1.s:.2f} s, {P}-chip {t4.s:.2f} s for "
        f"{len(ds.queries)} queries)")
    check(agree >= AGREEMENT_FLOOR,
          f"shard_map/one-device agreement {agree} < {AGREEMENT_FLOOR}")
    # the placement gates come last, so a run shows both results
    for s, (h, o) in enumerate(zip(held, own)):
        check(sum(own) >= index_bytes
              and 0.8 * sum(own) / P <= o <= 1.3 * sum(own) / P,
              f"chip {s} owns {o} B of a {index_bytes} B index "
              f"(expected ~1/{P})")
        check(args.rehearse or 0.8 * o <= h <= 1.3 * o,
              f"chip {s} holds {h} B, its shards take {o} B")
    return {"n": args.n, "chips": P, "build_s": tb.s, "agreement": agree,
            "held_bytes": held, "shard_bytes": own,
            "index_bytes": index_bytes}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--queries", type=int, default=1000)
    ap.add_argument("--out", default="",
                    help="also write the phase results as JSON here")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever JAX finds (e.g. CPU) and print "
                         "no result line")
    args = ap.parse_args()
    # build sub-stages log at INFO (core/index.py, core/refine.py): a run
    # cut by its time limit still shows how far it got
    logging.basicConfig(level=logging.INFO, stream=sys.stdout,
                        format="[log] %(message)s")
    for noisy in ("jax", "absl"):
        logging.getLogger(noisy).setLevel(logging.WARNING)
    t0 = time.perf_counter()
    try:
        info = device_info(args.chips, args.rehearse)
        from repro.launch.compile_cache import use_compile_cache
        log(f"compile cache: {use_compile_cache()}")
        result = four_chips(args) if args.chips > 1 else one_chip(args)
    except Exception as e:
        import traceback
        traceback.print_exc()
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"[time] total: {time.perf_counter() - t0:.1f} s")
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            dict(result, device=info), indent=1, default=float))
    if args.rehearse:
        log("rehearsal passed (no result line: not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
