"""Reduces a JAX profiler trace (`.xplane.pb`) to device busy and idle time.

  busy      the union of the intervals in which an operation ran on a
            device (its "XLA Ops" and "Async XLA Ops" lines), inside the
            host span that marks the traced part of the measured window
            (`bench.window`), averaged over the devices;
  ops       device seconds per operation ("XLA Ops"), named by the program
            that ran it ("XLA Modules") and the HLO instruction,
            `jit_run(<hash>)/%fusion.26`, summed over the devices and divided
            by their number, longest first;
  gaps      idle time of the devices, split by what the host was doing: the
            benchmark's own host spans (`bench.*`, see bench/drive.py) that
            cover each idle stretch, and `host.other` where none does.

Host and device events of one trace share one clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench.window"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
OTHER = "host.other"


@dataclasses.dataclass
class Reduced:
    busy_s: float                       # mean over devices
    window_s: float
    n_devices: int
    ops: List[Tuple[str, float]]        # (name, seconds), longest first
    gaps: List[Tuple[str, float]]       # (host span, idle seconds)


def union(starts: np.ndarray, ends: np.ndarray
          ) -> Tuple[np.ndarray, np.ndarray]:
    """Merged, sorted, disjoint intervals covering the given ones."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    return s[first], np.maximum.reduceat(e, first)


def clip(starts, ends, lo, hi):
    s, e = np.maximum(starts, lo), np.minimum(ends, hi)
    keep = e > s
    return s[keep], e[keep]


def attribute(gap_s, gap_e, spans: List[Tuple[float, float, str]]
              ) -> Dict[str, float]:
    """Seconds of the gaps [gap_s, gap_e) (ns) covered by each host span
    (disjoint spans, sorted by start); the rest is `host.other`."""
    starts = [s for s, _, _ in spans]
    out: Dict[str, float] = defaultdict(float)
    for a, b in zip(gap_s.tolist(), gap_e.tolist()):
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(spans) and spans[i][0] < b:
            s, e, name = spans[i]
            o = min(b, e) - max(a, s)
            if o > 0:
                out[name] += o
                covered += o
            i += 1
        out[OTHER] += (b - a) - covered
    return {k: v * 1e-9 for k, v in out.items() if v > 0}


def op_name(module: str, hlo: str) -> str:
    """`<program>/<instruction>` from an "XLA Ops" event's HLO text."""
    return f"{module}/{hlo.split(' = ', 1)[0]}"


class _Device:
    def __init__(self):
        self.modules = []     # (start, end, name)
        self.ops = []         # (start, duration, hlo text)
        self.busy = []        # (start, end) of every op, async included


def _read(path: str):
    import jax
    prof = jax.profiler.ProfileData.from_file(path)
    window, spans = None, []
    devices: Dict[str, _Device] = {}
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name.startswith("bench."):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
        elif plane.name.startswith("/device:"):
            dev = devices.setdefault(plane.name, _Device())
            for line in plane.lines:
                if line.name not in (OP_LINE, ASYNC_LINE, MODULE_LINE):
                    continue
                for ev in line.events:
                    s, d = ev.start_ns, ev.duration_ns
                    if line.name == MODULE_LINE:
                        dev.modules.append((s, s + d, ev.name))
                        continue
                    dev.busy.append((s, s + d))
                    if line.name == OP_LINE:
                        dev.ops.append((s, d, ev.name))
    return window, sorted(spans), {k: v for k, v in devices.items()
                                   if v.busy}


def reduce_file(path: str) -> Optional[Reduced]:
    """None where the trace holds no window span or no device op."""
    window, spans, devices = _read(path)
    if window is None or not devices:
        return None
    lo, hi = window
    busy = []
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    for dev in devices.values():
        dev.modules.sort()
        mstart = np.asarray([m[0] for m in dev.modules], np.float64)
        st = np.asarray([o[0] for o in dev.ops], np.float64)
        part = np.minimum(st + np.asarray([o[1] for o in dev.ops]), hi) \
            - np.maximum(st, lo)
        mod = np.searchsorted(mstart, st, side="right") - 1
        raw: Dict[Tuple[int, str], float] = defaultdict(float)
        for m, (_, _, hlo), t in zip(mod.tolist(), dev.ops, part.tolist()):
            if t > 0:
                raw[m, hlo] += t
        for (m, hlo), t in raw.items():
            ops[op_name(dev.modules[m][2] if m >= 0 else "?", hlo)] += t
        b = np.asarray(dev.busy, np.float64)
        us, ue = union(*clip(b[:, 0], b[:, 1], lo, hi))
        busy.append(float(np.sum(ue - us)) * 1e-9)
        gs = np.concatenate([[lo], ue])
        ge = np.concatenate([us, [hi]])
        keep = ge > gs
        for k, v in attribute(gs[keep], ge[keep], spans).items():
            gaps[k] += v
    n = len(devices)

    def by_time(d, scale):
        return sorted(((k, v * scale / n) for k, v in d.items()),
                      key=lambda kv: -kv[1])
    return Reduced(busy_s=float(np.mean(busy)), window_s=(hi - lo) * 1e-9,
                   n_devices=n, ops=by_time(ops, 1e-9),
                   gaps=by_time(gaps, 1.0))


def reduce_dir(trace_dir: str) -> Optional[Reduced]:
    """Reduce the newest `.xplane.pb` under a `jax.profiler` trace dir."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    return reduce_file(max(files, key=os.path.getmtime))
