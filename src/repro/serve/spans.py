"""In-program spans and counters on the profiler's clock (DESIGN.md §11).

A `Recorder` belongs to one `SearchEngine` and is always on:

  span(name, **attrs)  a context manager that times its block twice over:
                       it enters `jax.profiler.TraceAnnotation(name, **attrs)`,
                       which writes an event only while a profiler session
                       runs, on the device trace's clock; and it adds the
                       block's wall time (`time.perf_counter_ns`) to the
                       recorder's aggregate for `name`: count, total, self
                       time and max.
  count(name, n)       adds n to a counter.

Self time is a span's duration minus the time of the spans of the same
recorder opened directly inside it. Nothing is written to disk. With no
profiler running a span costs two clock reads and one inactive TraceMe,
against dispatches of milliseconds, so there is no switch. A recorder is
not thread-safe: like its engine, it is driven from one thread.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

from jax.profiler import TraceAnnotation


@dataclasses.dataclass(frozen=True)
class SpanStats:
    """Aggregate of every span of one name since the last reset."""

    count: int
    total_ms: float
    self_ms: float
    max_ms: float


class Span:
    """One timed block of one recorder. `start_ns`/`end_ns` stay readable
    after the block, so a caller can take an interval from them instead of
    reading the clock again."""

    __slots__ = ("name", "recorder", "annotation", "start_ns", "end_ns",
                 "child_ns")

    def __init__(self, name: str, recorder: "Recorder", **attrs):
        self.name = name
        self.recorder = recorder
        self.annotation = TraceAnnotation(name, **attrs)
        self.start_ns = self.end_ns = self.child_ns = 0

    def __enter__(self) -> "Span":
        self.recorder._stack.append(self)
        self.annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = time.perf_counter_ns()
        self.annotation.__exit__(*exc)
        dur = self.end_ns - self.start_ns
        stack = self.recorder._stack
        stack.pop()
        if stack:
            stack[-1].child_ns += dur
        self.recorder._add(self.name, dur, dur - self.child_ns)
        return False

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-6


class Recorder:
    """Span aggregates and counters of one engine."""

    def __init__(self):
        self._stack: List[Span] = []
        self.reset()

    def reset(self) -> None:
        self._spans: Dict[str, List[int]] = {}  # count, total, self, max ns
        self.counters: Dict[str, int] = {}

    def span(self, name: str, **attrs) -> Span:
        return Span(name, self, **attrs)

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + int(n)

    def _add(self, name: str, dur_ns: int, self_ns: int) -> None:
        a = self._spans.get(name)
        if a is None:
            a = self._spans[name] = [0, 0, 0, 0]
        a[0] += 1
        a[1] += dur_ns
        a[2] += self_ns
        a[3] = max(a[3], dur_ns)

    def spans(self) -> Dict[str, SpanStats]:
        return {name: SpanStats(c, t * 1e-6, s * 1e-6, m * 1e-6)
                for name, (c, t, s, m) in self._spans.items()}
