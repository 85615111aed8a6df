"""The measured window: drives `repro.serve.serve_loop` on the wall clock.

closed loop  Each client sends one request, waits for its reply, sends the
             next, until the window closes. A request's latency runs from
             its send to its return.
open loop    Requests fall due on the mix's schedule. Whenever the server is
             free, every request already due goes to one `serve_loop` call
             (which coalesces them into bucket-sized dispatches). A
             request's latency runs from its due time to the return of that
             call. The generator's lateness is how long after a request fell
             due it was handed over while the server stood idle: the cost of
             the generator itself, not of queueing.

Host spans (`jax.profiler.TraceAnnotation`) mark the traced part of the
window, each `serve_loop` call, the generator's waits and the result
handling, so a traced run can say what the host did during each idle gap of
the device.

A mix may bound the traced part with `trace_seconds`: the trace then ends at
the first request boundary after that many seconds, and the window goes on
untraced. A closed loop only pauses while the trace is written; a path with
many device ops per dispatch (the graph's lockstep loop) would otherwise
overflow the profiler's buffer and take minutes to write. Without the key
the whole window is traced.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from bench import traffic as traffic_mod

SPAN_WINDOW = "bench.window"     # the traced part of the window
SPAN_SERVE = "bench.serve_loop"
SPAN_WAIT = "bench.wait"
SPAN_RESULTS = "bench.results"


@dataclasses.dataclass
class Served:
    """One request of the window."""

    queries: np.ndarray      # (Q,) indices into the query pool
    due_s: float             # when it was due (from the window's start)
    done_s: float            # when its serve_loop call returned
    status: str
    ids: np.ndarray          # (Q, k)
    dists: np.ndarray        # (Q, k)


@dataclasses.dataclass
class Window:
    served: List[Served]
    seconds: float           # the window's nominal length
    end_s: float             # last return, from the window's start
    n_calls: int             # serve_loop calls
    n_dispatches: int        # engine dispatches over all calls
    n_rows: int              # true queries served over all calls
    lateness_s: np.ndarray   # generator lateness (open loop)
    traced_s: float          # length of the traced part, from the start
    traced_stats: object     # EngineStats at the traced part's end


class _TracedPart:
    """The `bench.window` span from the window's start to the first request
    boundary after `limit_s` (or to the window's end), then `stop`."""

    def __init__(self, engine, limit_s: Optional[float],
                 stop: Optional[Callable[[], None]]):
        self.engine, self.limit_s, self.stop = engine, limit_s, stop
        self.span = TraceAnnotation(SPAN_WINDOW)
        self.span.__enter__()
        self.length_s = None
        self.stats = None

    def boundary(self, now: float, last: bool = False) -> None:
        if self.length_s is not None:
            return
        if not last and (self.limit_s is None or now < self.limit_s):
            return
        self.span.__exit__(None, None, None)
        self.length_s, self.stats = now, self.engine.stats()
        if self.stop is not None:
            self.stop()


def _serve(engine, pool, stream, sizes, k, records, t0, dues):
    from repro.serve import Request, serve_loop
    idx = [stream.take(int(s)) for s in sizes]
    reqs = [Request(queries=pool[i], k=k) for i in idx]
    with TraceAnnotation(SPAN_SERVE):
        rep = serve_loop(engine, reqs)
    done = time.perf_counter() - t0
    with TraceAnnotation(SPAN_RESULTS):
        by_id = sorted(rep.results, key=lambda r: r.request_id)
        for i, due, r in zip(idx, dues, by_id):
            records.append(Served(i, due, done, r.status,
                                  np.asarray(r.ids), np.asarray(r.dists)))
    return rep.n_dispatches, rep.n_served


def run_window(engine, pool: np.ndarray, mix: dict, seed: int,
               seconds: float,
               stop_trace: Optional[Callable[[], None]] = None) -> Window:
    """Serve the mix for `seconds` and wait for every request sent.
    `stop_trace` ends a running profiler trace at the traced part's end."""
    stream = traffic_mod.QueryStream(pool.shape[0], seed)
    k = mix["k"]
    records: List[Served] = []
    lateness = []
    n_calls = n_disp = n_rows = 0
    if mix["loop"] == "open":
        due, sizes = traffic_mod.open_schedule(mix, seconds)
    elif mix["loop"] != "closed" or mix["clients"] != 1:
        raise ValueError(f"unsupported traffic loop {mix}")
    limit = mix.get("trace_seconds") if stop_trace is not None else None
    t0 = time.perf_counter()
    traced = _TracedPart(engine, limit, stop_trace)
    if mix["loop"] == "closed":
        while time.perf_counter() - t0 < seconds:
            sent = time.perf_counter() - t0
            d, r = _serve(engine, pool, stream, [mix["size"]], k,
                          records, t0, [sent])
            n_calls, n_disp, n_rows = n_calls + 1, n_disp + d, n_rows + r
            traced.boundary(time.perf_counter() - t0)
    else:
        i = 0
        while i < due.size:
            now = time.perf_counter() - t0
            traced.boundary(now)
            if due[i] > now:
                with TraceAnnotation(SPAN_WAIT):
                    time.sleep(due[i] - now)
                lateness.append(time.perf_counter() - t0 - due[i])
                continue
            j = int(np.searchsorted(due, now, side="right"))
            d, r = _serve(engine, pool, stream, sizes[i:j], k, records,
                          t0, due[i:j])
            n_calls, n_disp, n_rows = n_calls + 1, n_disp + d, n_rows + r
            i = j
    end = time.perf_counter() - t0
    traced.boundary(end, last=True)
    return Window(records, seconds, end, n_calls, n_disp, n_rows,
                  np.asarray(lateness), traced.length_s, traced.stats)
