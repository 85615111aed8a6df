"""Reduces a profiler trace by the program's own spans and scopes.

From the same `.xplane.pb` as bench/tracing.py, and on top of its result:

  idle      idle time of the devices inside the traced part of the window
            (`bench.window`), split by the innermost `kbest.*` host span
            that covers each idle stretch (repro.serve.spans: serve_loop,
            dispatch, pad, enqueue, device_wait, fetch). Time no `kbest.*`
            span covers goes to the innermost `bench.*` span over it
            (bench/drive.py), else to `host.other`;
  device    the device seconds of bench/tracing.py's `ops`, summed by the
            `kbest.*` named scope of each op's HLO instruction
            (`kbest.expand`, `kbest.queue`, `kbest.scan`, ...). The device
            ops of a trace carry no op names, so the scope comes from the
            optimized HLO of their program (`SearchEngine.compiled_text`).
            A fusion takes the scopes of every instruction fused into it:
            where those are more than one, its label joins them with `+`
            (`kbest.expand+kbest.queue`), since its time cannot be split.
            `while`, `conditional` and `call` enclose their bodies' ops and
            are left out; an op whose instruction has no scope, or whose
            program has no HLO text here, counts as `unscoped`.

Both are averaged over the devices, like bench/tracing.py. A program
without these spans and scopes gives only `bench.*` idle and `unscoped`
device time, and nothing raises.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Set, Tuple

import numpy as np

from bench.tracing import (ASYNC_LINE, OP_LINE, OTHER, WINDOW, Reduced,
                           clip, union)

UNSCOPED = "unscoped"
CONTROL_FLOW = {"while", "conditional", "call"}
D2H = "tpu::System::TransferFromDevice"
DISPATCH = "kbest.dispatch"

# `%name = <shape> opcode(` at the start of an HLO instruction line
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


@dataclasses.dataclass
class Program:
    idle: List[Tuple[str, float]]      # (span, idle seconds), longest first
    device: List[Tuple[str, float]]    # (scope, device seconds)
    n_dispatches: int                  # kbest.dispatch spans in the window
    n_d2h: int                         # device-to-host copies in the window


def program_text(engine, buckets: Iterable[int], k: int) -> Dict[str, str]:
    """{HLO module name: optimized HLO text} of the warmed buckets; empty
    where the engine cannot give it."""
    text = getattr(engine, "compiled_text", None)
    if text is None:
        return {}
    out = {}
    for b in buckets:
        hlo = text(b, k=k)
        out[hlo.split(None, 2)[1].rstrip(",")] = hlo
    return out


def instruction(text: str) -> Tuple[str, str]:
    """(name, opcode) of one HLO instruction's text, as an HLO line or a
    trace's device-op event gives it; ("", "") where it is not one."""
    m = _INSTR.match(text)
    return (m.group(1), m.group(2)) if m else ("", "")


def _scope(line: str) -> Optional[str]:
    n = _OP_NAME.search(line)
    parts = n.group(1).split("/") if n else []
    return next((p for p in reversed(parts) if p.startswith("kbest.")),
                None)


def scopes(hlo: str) -> Dict[str, Tuple[str, Optional[str]]]:
    """{instruction: (opcode, scope)}. The scope is the innermost `kbest.*`
    scope of the instruction's op_name and of every instruction in the
    computations it calls (a fusion's body, nested calls included); two or
    more are joined with `+`; None where there is none."""
    own: Dict[str, Optional[str]] = {}
    opcode: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    body: Dict[str, List[str]] = defaultdict(list)
    comp = None
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        name, op = instruction(line)
        if not name:
            continue
        opcode[name], own[name] = op, _scope(line)
        if comp is not None:
            body[comp].append(name)
        c = _CALLS.search(line)
        if c is not None:
            calls[name] = c.group(1)

    memo: Dict[str, Set[str]] = {}

    def inner(name: str) -> Set[str]:
        if name not in memo:
            memo[name] = set()          # a cycle adds nothing
            got = {own[name]} - {None}
            for n in body.get(calls.get(name), ()):
                got |= inner(n)
            memo[name] = got
        return memo[name]

    return {name: (op, "+".join(sorted(inner(name))) or None)
            for name, op in opcode.items()}


def by_scope(ops: List[Tuple[str, float]], hlo: Dict[str, str]
             ) -> List[Tuple[str, float]]:
    """bench/tracing.py's `ops` (`<program>(<id>)/%<instruction>`, device
    seconds) summed by scope, longest first; control flow left out. An op
    of a program with no HLO here is `unscoped`, and is control flow where
    its instruction's name says so (`while.10`), as XLA names them."""
    maps = {name: scopes(text) for name, text in hlo.items()}
    out: Dict[str, float] = defaultdict(float)
    for name, t in ops:
        module, _, instr = name.partition("/")
        instr = instr.lstrip("%")
        opcode, scope = maps.get(module.split("(")[0], {}).get(
            instr, (instr.split(".")[0], None))
        if opcode not in CONTROL_FLOW:
            out[scope or UNSCOPED] += t
    return sorted(out.items(), key=lambda kv: -kv[1])


def segments(spans: List[Tuple[float, float, str]]
             ) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted (start, end, label) pieces of the host timeline:
    the label is the innermost `kbest.*` span over the piece, else the
    innermost `bench.*` one. Pieces no span covers are left out."""
    events = sorted([(s, 1, i) for i, (s, _, _) in enumerate(spans)]
                    + [(e, 0, i) for i, (_, e, _) in enumerate(spans)])
    active = set()
    out = []
    prev = None

    def label():
        best = None
        for i in active:
            s, e, name = spans[i]
            key = (name.startswith("kbest."), s, -e)
            if best is None or key > best[0]:
                best = (key, name)
        return best[1] if best else None

    for t, start, i in events:
        if prev is not None and t > prev and active:
            out.append((prev, t, label()))
        prev = t
        (active.add if start else active.discard)(i)
    return out


def attribute(gap_s, gap_e, spans: List[Tuple[float, float, str]]
              ) -> Dict[str, float]:
    """Seconds of the gaps [gap_s, gap_e) (ns, sorted, disjoint) under each
    label of `segments(spans)`; the rest is `host.other`."""
    seg = segments(spans)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in zip(gap_s.tolist(), gap_e.tolist()):
        covered = 0.0
        while j < len(seg) and seg[j][1] <= a:
            j += 1
        i = j
        while i < len(seg) and seg[i][0] < b:
            o = min(b, seg[i][1]) - max(a, seg[i][0])
            if o > 0:
                out[seg[i][2]] += o
                covered += o
            i += 1
        out[OTHER] += (b - a) - covered
    return {k: v * 1e-9 for k, v in out.items() if v > 0}


def _read(path: str):
    """The window, the `kbest.*`/`bench.*` host spans, the device-to-host
    copies' starts and each device's op intervals (ns)."""
    import jax
    prof = jax.profiler.ProfileData.from_file(path)
    window, spans, d2h, busy = None, [], [], []
    for plane in prof.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name == WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif name.startswith(("kbest.", "bench.")):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, name))
                    elif name == D2H:
                        d2h.append(ev.start_ns)
        elif plane.name.startswith("/device:"):
            b = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                 for line in plane.lines if line.name in (OP_LINE, ASYNC_LINE)
                 for ev in line.events]
            if b:
                busy.append(np.asarray(b, np.float64))
    return window, sorted(spans), d2h, busy


def reduce_file(path: str, hlo: Dict[str, str],
                reduced: Optional[Reduced]) -> Optional[Program]:
    """`reduced` is bench/tracing.py's reduction of the same file. None
    where that is None, or the trace holds no window span or device op."""
    window, spans, d2h, busy = _read(path)
    if reduced is None or window is None or not busy:
        return None
    lo, hi = window
    idle: Dict[str, float] = defaultdict(float)
    for b in busy:
        us, ue = union(*clip(b[:, 0], b[:, 1], lo, hi))
        gs = np.concatenate([[lo], ue])
        ge = np.concatenate([us, [hi]])
        keep = ge > gs
        for k, v in attribute(gs[keep], ge[keep], spans).items():
            idle[k] += v
    return Program(
        idle=sorted(((k, v / len(busy)) for k, v in idle.items()),
                    key=lambda kv: -kv[1]),
        device=by_scope(reduced.ops, hlo),
        n_dispatches=sum(1 for s, e, name in spans
                         if name == DISPATCH and lo <= s < hi),
        n_d2h=sum(1 for t in d2h if lo <= t < hi))


def reduce_dir(trace_dir: str, hlo: Dict[str, str],
               reduced: Optional[Reduced]) -> Optional[Program]:
    """Reduce the newest `.xplane.pb` under a `jax.profiler` trace dir."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    return reduce_file(max(files, key=os.path.getmtime), hlo, reduced)


def log_stats(stats, log) -> None:
    """The engine's span aggregates and counters, one line each (nothing
    where the engine keeps none)."""
    for name, s in sorted(getattr(stats, "spans", {}).items()):
        log(f"span {name}: n={s.count} total={s.total_ms:.3f} ms "
            f"self={s.self_ms:.3f} ms max={s.max_ms:.3f} ms")
    counters = {k: getattr(stats, k, None) for k in (
        "n_queries", "rows_padded", "iters", "hops", "lane_slots",
        "host_p50_ms")}
    if counters["rows_padded"] is not None:
        log("counters " + " ".join(f"{k}={v}" for k, v in counters.items()))
