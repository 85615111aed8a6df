"""Finds what `BENCHMARK.json` names: cells, configurations, traffic mixes
and metric readers.

Everything that belongs to one configuration, one traffic mix or one metric
is a file of its own, found by its name:

    BENCHMARK.json             cells, metrics, and each configuration's file
    bench/traffic/<name>.json  a traffic mix (parameters only)
    bench/metrics/<name>.py    a metric's reader: `read(ctx) -> float | None`

A metric named `<base>.<split>` (e.g. `dispatch_ms.bulk`) is read by
`metrics/<name>.py` if that file exists, else by `metrics/<base>.py`, so
one quantity split by the end-to-end metric it moves has one reader.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class Spec:
    """`BENCHMARK.json` of a checkout, and the files it names."""

    def __init__(self, root: Path = ROOT, bench: Optional[Path] = None):
        self.root = Path(root)
        self.bench = Path(bench) if bench is not None else BENCH
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.data["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json {known}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        """The metrics a run of `cell` reports: its end-to-end metrics
        untraced, its per-layer metrics traced."""
        group = self.data["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: str) -> Callable:
        d = self.bench / "metrics"
        path = d / f"{metric}.py"
        if not path.exists():
            path = d / f"{metric.split('.')[0]}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{path.stem.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
