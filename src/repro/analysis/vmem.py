"""Check 5 — static VMEM budget estimator (DESIGN.md §15).

For every Pallas kernel wrapper, sum the bytes its BlockSpec blocks and
pltpu.VMEM scratch shapes pin in VMEM at one grid step, and assert the
total stays under a per-kernel budget. DESIGN.md argues throughout that
LUTs and tiles "stay VMEM resident" — this check does the arithmetic,
so a BlockSpec edit that silently blows the ~16 MiB/core budget (and
would spill to HBM on hardware) fails CI instead of shipping.

Shape expressions inside BlockSpec/VMEM calls are symbolic (d, tq, m,
K, C, ...). They are evaluated against representative worst-case
bindings (DIMS below — the largest values the presets/benchmarks use);
names the evaluator cannot resolve fall back to DEFAULT_DIM and are
called out in the report. In/out blocks are counted twice (the pipeline
double-buffers them: step i+1's DMA lands while step i computes);
scratch is single-buffered. Element size is 4 bytes unless the scratch
dtype says otherwise — conservative for the u8/u32 code blocks.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.analysis.common import Tree, Violation, calls_to, keyword_arg, \
    top_level_functions
from repro.analysis.parity import find_kernels

CHECK = "vmem_budget"
KERNELS_DIR = "src/repro/kernels"

# Representative worst-case dimension bindings: tile sizes from the
# wrappers' own clamps, m/K/C/T/L/max_len from the largest preset and
# benchmark configs in the tree.
DIMS: Dict[str, int] = {
    "d": 1024, "tq": 128, "tb": 128, "m": 64, "K": 256, "mh": 32,
    "C": 4096, "T": 1024, "W": 16, "n_beam": 16, "L": 1024,
    "max_len": 4096, "nw": 64, "Q": 8, "B": 8, "P": 8, "nlist": 64,
    "TL": 512, "K4": 16,
}
DEFAULT_DIM = 128
DTYPE_BYTES = {"float32": 4, "int32": 4, "uint32": 4, "float64": 8,
               "int64": 8, "bfloat16": 2, "float16": 2, "uint8": 1,
               "int8": 1, "bool_": 1}
ELEM_BYTES = 4

DEFAULT_BUDGET = 16 * 1024 * 1024          # ~VMEM per TensorCore
# Per-kernel overrides would go here, keyed by wrapper name.
BUDGETS: Dict[str, int] = {}


@dataclasses.dataclass
class KernelEstimate:
    name: str
    path: str
    line: int
    n_blocks: int
    block_bytes: int       # sum over BlockSpec blocks, single-buffered
    scratch_bytes: int
    notes: List[str]

    @property
    def total_bytes(self) -> int:
        return 2 * self.block_bytes + self.scratch_bytes


def _eval_dim(node: ast.expr, notes: List[str]) -> int:
    expr = ast.unparse(node)
    try:
        val = eval(compile(ast.Expression(body=node), "<dim>", "eval"),
                   {"__builtins__": {}}, dict(DIMS))
        return max(int(val), 1)
    except Exception:
        notes.append(f"unresolved dim '{expr}' -> {DEFAULT_DIM}")
        return DEFAULT_DIM


def _shape_elems(node: Optional[ast.expr], notes: List[str]) -> int:
    if not isinstance(node, (ast.Tuple, ast.List)):
        if node is not None:
            notes.append(f"non-literal shape '{ast.unparse(node)}' skipped")
        return 0
    elems = 1
    for e in node.elts:
        elems *= _eval_dim(e, notes)
    return elems


def _scratch_bytes(call: ast.Call, notes: List[str]) -> int:
    shape = call.args[0] if call.args else keyword_arg(call, "shape")
    elems = _shape_elems(shape, notes)
    nbytes = ELEM_BYTES
    dt = call.args[1] if len(call.args) > 1 else keyword_arg(call, "dtype")
    if isinstance(dt, ast.Attribute) and dt.attr in DTYPE_BYTES:
        nbytes = DTYPE_BYTES[dt.attr]
    return elems * nbytes


def estimate(tree: Tree) -> List[KernelEstimate]:
    out: List[KernelEstimate] = []
    for rel, name, lineno in find_kernels(tree):
        mod = tree.parse(rel)
        fns = top_level_functions(mod)
        fn = fns[name]
        notes: List[str] = []

        # BlockSpecs appear inline in the wrapper, or behind module-level
        # helpers the wrapper calls.
        spec_scopes = [fn]
        for call in ast.walk(fn):
            if isinstance(call, ast.Call) and \
                    isinstance(call.func, ast.Name) and \
                    call.func.id in fns and call.func.id != name:
                helper = fns[call.func.id]
                if any(True for _ in calls_to(helper, "BlockSpec")):
                    spec_scopes.append(helper)

        block_bytes = 0
        n_blocks = 0
        for scope in spec_scopes:
            for spec in calls_to(scope, "BlockSpec"):
                shape = spec.args[0] if spec.args else \
                    keyword_arg(spec, "block_shape")
                elems = _shape_elems(shape, notes)
                if elems:
                    n_blocks += 1
                    block_bytes += elems * ELEM_BYTES
        scratch_bytes = sum(_scratch_bytes(c, notes)
                            for c in calls_to(fn, "VMEM"))
        out.append(KernelEstimate(name, rel, lineno, n_blocks,
                                  block_bytes, scratch_bytes, notes))
    return out


def run(tree: Tree) -> List[Violation]:
    violations: List[Violation] = []
    for est in estimate(tree):
        budget = BUDGETS.get(est.name, DEFAULT_BUDGET)
        if est.total_bytes > budget:
            violations.append(Violation(
                CHECK, est.path, est.line,
                f"kernel '{est.name}' estimated VMEM residency "
                f"{est.total_bytes / 2**20:.1f} MiB "
                f"(2x{est.block_bytes / 2**20:.1f} blocks + "
                f"{est.scratch_bytes / 2**20:.1f} scratch) exceeds its "
                f"{budget / 2**20:.0f} MiB budget"))
        if est.n_blocks == 0:
            violations.append(Violation(
                CHECK, est.path, est.line,
                f"kernel '{est.name}' has no resolvable BlockSpec shapes "
                f"— the VMEM estimate would be vacuous"))
    return violations


def report(tree: Tree) -> str:
    """The --report table: per-kernel VMEM residency breakdown."""
    rows = [f"{'kernel':<18} {'blocks':>6} {'block KiB':>10} "
            f"{'scratch KiB':>12} {'est KiB':>8} {'budget':>7}  notes"]
    for est in estimate(tree):
        budget = BUDGETS.get(est.name, DEFAULT_BUDGET)
        rows.append(
            f"{est.name:<18} {est.n_blocks:>6} "
            f"{est.block_bytes / 1024:>10.1f} "
            f"{est.scratch_bytes / 1024:>12.1f} "
            f"{est.total_bytes / 1024:>8.1f} "
            f"{budget / 2**20:>6.0f}M  {'; '.join(est.notes)}")
    return "\n".join(rows)
