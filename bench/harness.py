"""One run of one cell: set-up, the measured window, the checks, the metrics.

The steps are separate functions so that the knee sweep (bench/sweep.py)
and the control (bench/control.py) drive the same set-up and window.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Optional

import numpy as np

from bench import correct, data, drive, tracing
from bench.spec import ROOT, Spec


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict


def load(spec: Spec, name: str, n: Optional[int] = None,
         pool: Optional[int] = None) -> Cell:
    """The cell's configuration and mix; `n` and `pool` shrink the corpus
    and the query pool for a rehearsal."""
    w = spec.cell(name)
    cfg = spec.config(w["config"])
    if n is not None:
        cfg["data"]["n"] = n
    if pool is not None:
        cfg["data"]["queries"] = pool
    return Cell(name, w["chips"], cfg, spec.traffic(w["traffic"]))


def devices(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it; raises NoChip unless a TPU with
    enough chips is there (a rehearsal takes whatever JAX finds)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not rehearse and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX runs on {info['platform']!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return info


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: JAX_COMPILATION_CACHE_DIR when
    set, else the fixed directory `.jax_cache/` of this checkout. Every
    program is kept, so a second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def index_config(d: dict):
    """`IndexConfig` from the configuration file's `index` section: the
    fields it lists, every other field at the dataclass default."""
    from repro.core.types import IndexConfig
    kw = {}
    for f in dataclasses.fields(IndexConfig):
        if f.name in d:
            v = d[f.name]
            kw[f.name] = type(f.default_factory())(**v) \
                if isinstance(v, dict) else v
    return IndexConfig(**kw)


def buckets(cell: Cell, engine) -> list:
    """The shape buckets this cell's traffic can reach: one request's
    size for a closed loop; for an open loop everything from the smallest
    request's bucket up, since coalesced requests fill larger ones."""
    from repro.serve import bucket_for, bucket_ladder
    mix = cell.mix
    if mix["loop"] == "closed":
        return [bucket_for(mix["size"], engine.min_bucket,
                           engine.max_bucket)]
    lo = bucket_for(mix["size_min"], engine.min_bucket, engine.max_bucket)
    return list(bucket_ladder(lo, engine.max_bucket))


def build(cell: Cell, base):
    """`KBest(cfg).add(base)` behind a warmed `SearchEngine`."""
    from repro.core.index import KBest
    from repro.serve import SearchEngine
    cfg = cell.config
    t = time.perf_counter()
    index = KBest(index_config(cfg["index"])).add(base)
    log(f"build {time.perf_counter() - t:.1f} s: " + " ".join(
        f"{k}={v:.1f}s" for k, v in index.build_s.items()))
    engine = SearchEngine(index, **cfg["serve"])
    t = time.perf_counter()
    b = buckets(cell, engine)
    engine.warmup(b, k=cell.mix["k"])
    log(f"warm-up of buckets {b}: {engine.n_traces} programs in "
        f"{time.perf_counter() - t:.1f} s")
    engine.reset_stats()
    return index, engine


def memory_peak() -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    return max((s or {}).get("peak_bytes_in_use", 0) for s in stats)


def run(spec: Spec, cell: Cell, seed: int, seconds: float, trace: bool,
        rehearse: bool = False, t_start: Optional[float] = None) -> dict:
    """One run; returns the result line's object."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    device = devices(cell.chips, rehearse)
    if not rehearse:
        log(f"compile cache: {use_compile_cache()}")
    log(f"device: {device}")

    t = time.perf_counter()
    base, pool = data.make_corpus(seed, cell.config["data"])
    log(f"data {base.shape} + pool {pool.shape}: "
        f"{time.perf_counter() - t:.1f} s")
    index, engine = build(cell, base)
    build_s = dict(index.build_s)
    traces = engine.n_traces
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-") \
        if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir.name)
    win = drive.run_window(engine, pool, cell.mix, seed, seconds,
                           jax.profiler.stop_trace if trace else None)
    stats = engine.stats()
    if engine.n_traces != traces:
        raise RuntimeError(f"{engine.n_traces - traces} programs compiled "
                           f"inside the measured window")
    device["memory_peak_bytes"] = memory_peak()
    del index, engine
    gc.collect()

    reduced = None
    if trace:
        t = time.perf_counter()
        reduced = tracing.reduce_dir(trace_dir.name)
        trace_dir.cleanup()
        log(f"trace reduced in {time.perf_counter() - t:.1f} s")
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s

    t = time.perf_counter()
    checks = correct.compare(base, pool, win.served, cell.mix["k"],
                             cell.config["limits"])
    log(f"reference and comparison: {time.perf_counter() - t:.1f} s")

    # what a metric reader (bench/metrics/) may read
    ctx = SimpleNamespace(cell=cell, window=win, stats=stats, trace=reduced,
                          device=device, setup_s=setup_s, build_s=build_s,
                          checks={c.name: c.value for c in checks})
    metrics = {}
    for m in spec.metrics(cell.name, trace):
        v = spec.reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    summary(win, stats, metrics)
    out = {"correct": all(c.ok for c in checks),
           "attempted": len(win.served),
           "failed": sum(s.status != "ok" for s in win.served),
           "metrics": metrics, "device": device}
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced.ops[:10],
                            "idle_gaps": reduced.gaps[:10]}
    out["generator"] = lateness(win)
    out["checks"] = correct.report(checks)
    return out


def lateness(win: drive.Window) -> dict:
    late = win.lateness_s
    if late.size == 0:
        return {"late_p50_ms": 0.0, "late_max_ms": 0.0, "waits": 0}
    return {"late_p50_ms": float(np.median(late) * 1e3),
            "late_max_ms": float(late.max() * 1e3), "waits": int(late.size)}


def summary(win: drive.Window, stats, metrics: dict) -> None:
    lat = [s.done_s - s.due_s for s in win.served]
    log(f"window: {len(win.served)} requests, {win.n_rows} queries in "
        f"{win.n_calls} serve_loop calls, {win.n_dispatches} dispatches; "
        f"last return {win.end_s:.3f} s; latency p50 "
        f"{np.median(lat) * 1e3:.2f} ms; dispatch p50 {stats.lat_p50_ms:.2f} "
        f"ms; generator {lateness(win)}")
    for k, v in metrics.items():
        log(f"metric {k} = {v['value']!r} {v['unit']}")
