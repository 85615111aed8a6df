"""The command end to end on the CPU: a rehearsal runs every step and
prints no result line; without a TPU, or without the program, a run
exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.spec import ROOT, Spec

CELLS = [w["name"] for w in Spec().data["workloads"]]


def _run(args, cwd=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _result_line(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_every_step(cell):
    p = _run(["--workload", cell, "--seed", str(2**31 + 9), "--seconds",
              "1.5", "--trace", "1", "--rehearse", "--n", "2000",
              "--pool", "400"])
    assert p.returncode == 0, p.stderr[-3000:]
    assert _result_line(p.stdout) is None
    assert "correct=True" in p.stdout
    checks = [ln for ln in p.stderr.splitlines() if ln.startswith("check ")]
    assert [c.split()[1] for c in checks] == ["recall_at_10", "dist_gap",
                                              "bad_ids"]
    assert p.stderr.rstrip().splitlines()[-1] == checks[-1]


def test_no_tpu_no_result():
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0", "--rehearse", "--n", "500"], cwd=tmp_path)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert _result_line(p.stdout) is None
