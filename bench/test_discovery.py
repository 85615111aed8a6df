"""The harness finds cells, configurations, mixes and metrics by name, so a
later change adds them as new files and entries only."""
import json
import re
import shutil
from types import SimpleNamespace

import pytest

from bench import harness
from bench.spec import BENCH, ROOT, Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_new_config_mix_and_metric_are_found(tmp_path):
    shutil.copytree(BENCH / "metrics", tmp_path / "bench" / "metrics")
    shutil.copytree(BENCH / "traffic", tmp_path / "bench" / "traffic")
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / bm["configs"][0]["file"]).read_text())
    cfg["data"]["n"] = 1234
    (tmp_path / "bench" / "configs").mkdir()
    (tmp_path / "bench" / "configs" / "new_cfg.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "new_mix.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "size": 64, "k": 10}))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.setup_s\n")
    bm["configs"].append({"name": "new_cfg", "source": "x",
                          "file": "bench/configs/new_cfg.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "new_cfg.new_mix", "config": "new_cfg",
                            "traffic": "new_mix", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "new_metric", "unit": "s",
                            "better": "lower", "source": "program_span",
                            "layer": "build", "moves": "setup_s",
                            "workloads": ["new_cfg.new_mix"]})
    bm["per_layer"].append({"name": "dispatch_ms.new", "unit": "ms",
                            "better": "lower", "source": "program_span",
                            "layer": "engine", "moves": "qps",
                            "workloads": ["new_cfg.new_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))

    spec = Spec(root=tmp_path, bench=tmp_path / "bench")
    cell = harness.load(spec, "new_cfg.new_mix")
    assert cell.config["data"]["n"] == 1234 and cell.mix["size"] == 64
    names = [m["name"] for m in spec.metrics("new_cfg.new_mix", trace=True)]
    assert names == ["new_metric", "dispatch_ms.new"]
    ctx = SimpleNamespace(setup_s=3.0)
    assert spec.reader("new_metric")(ctx) == 6.0
    # a split name falls back to its base quantity's reader
    assert spec.reader("dispatch_ms.new").__module__ == \
        "bench_metric_dispatch_ms"
    e2e = [m["name"] for m in spec.metrics("new_cfg.new_mix", trace=False)]
    assert "setup_s" in e2e and "p95_ms" not in e2e


@pytest.mark.parametrize("cell", [w["name"] for w in Spec().data[
    "workloads"]])
def test_every_cell_resolves(cell):
    spec = Spec()
    c = harness.load(spec, cell)
    harness.index_config(c.config["index"])
    assert set(c.config["limits"]) == {"recall_at_10", "dist_gap",
                                       "bad_ids"}
    untraced = spec.metrics(cell, trace=False)
    traced = spec.metrics(cell, trace=True)
    assert "setup_s" in [m["name"] for m in untraced] and len(untraced) >= 2
    assert traced
    for m in untraced + traced:
        assert callable(spec.reader(m["name"]))


def test_benchmark_json_keeps_to_the_contract():
    bm = Spec().data
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    # A full check makes 2 + 14 x cells runs of run_seconds + 60 s each,
    # allows 2 x 90 s of compiling per cell and keeps 1200 s spare, all in
    # 43,200 s; later PRs may not change run_seconds, so it has to fit 24
    # cells (which gives 51 s at the most).
    cells = 24
    assert isinstance(bm["run_seconds"], int) and bm["run_seconds"] >= 1
    assert (2 + 14 * cells) * (bm["run_seconds"] + 60) + cells * 2 * 90 \
        + 1200 <= 43200
    cfg_names = [c["name"] for c in bm["configs"]]
    cells = bm["workloads"]
    assert {w["config"] for w in cells} == set(cfg_names)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    metrics = bm["end_to_end"] + bm["per_layer"]
    for x in bm["configs"] + cells + metrics:
        assert NAME.match(x["name"]), x["name"]
    for group in (cfg_names, [w["name"] for w in cells],
                  [m["name"] for m in metrics]):
        assert len(set(group)) == len(group)
    e2e = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bm["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for w in m.get("workloads", []):
            assert w in [c["name"] for c in cells]
    # every entry has just the keys the contract shows; a metric may add
    # `workloads`
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert w["chips"] in (1, 4)
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m
    for x in bm["configs"] + cells:
        assert 1 <= len(x["why"]) <= 200
        assert "\n" not in x["why"] and "\t" not in x["why"]
    for w in cells:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in bm["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in bm["paths"]))
        assert 1 <= len(c["source"]) <= 200 and "\n" not in c["source"]
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
