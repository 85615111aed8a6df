import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax-importing import: jax locks the device count at
#   first backend init. Only the dry-run sees 512 placeholder devices.

_DOC = """Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell, lower + compile the cell's
step function against the production meshes:

    single-pod : (data=16, model=16)        = 256 chips
    multi-pod  : (pod=2, data=16, model=16) = 512 chips

and record memory_analysis() (proves it fits), cost_analysis() (FLOPs /
bytes for §Roofline) and the per-collective byte counts parsed from the
partitioned HLO (collective term). Artifacts land in
experiments/dryrun/<arch>__<shape>__<mesh>.json — benchmarks/roofline.py
consumes them.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--both-meshes]
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro import configs as cfg_registry
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

ART_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(pred|[suf]\d+|bf16|c64|c128)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every dtype[shape] token in an HLO result spec."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-op result bytes from the (partitioned) HLO module.

    Counts the RESULT size of each collective op once per execution; for
    scan bodies the op appears once in the HLO but runs L times — we scale
    by trip count when the op lives inside a while body annotated with a
    known trip count (conservative: unscaled if unknown, reported raw).
    """
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "fusion" in s.split("=")[-1][:40]:
            continue
        for c in _COLLECTIVES:
            if re.search(rf"= [^=]*\b{re.escape(c)}(-start|-done)?\(", s):
                if c + "-done" in s:     # avoid double count of start/done
                    continue
                lhs = s.split("=")[1] if "=" in s else s
                out[c] += _shape_bytes(lhs.split(c)[0])
                counts[c] += 1
                break
    return {"bytes": out, "ops": counts,
            "total_bytes": sum(out.values())}


def while_trip_counts(hlo_text: str):
    """Best-effort scan trip counts (to scale per-iteration collectives)."""
    trips = re.findall(r"trip_count=(\d+)", hlo_text)
    return [int(t) for t in trips]


def _lower_metrics(arch, shape, mesh, depth, unroll, variant="baseline"):
    """Compile a depth/unroll variant and pull (flops, bytes, coll_bytes)."""
    cell = build_cell(arch, shape, mesh, depth=depth, unroll=unroll,
                      variant=variant)
    with jax.set_mesh(mesh):
        compiled = jax.jit(cell.step_fn, in_shardings=cell.in_shardings) \
            .lower(*cell.args).compile()
        cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll_bytes": float(coll["total_bytes"])}


def extrapolate_cost(arch: str, shape: str, mesh, variant="baseline") -> dict:
    """Loop-trip-corrected per-device cost: XLA counts a scan body once, so
    lower UNROLLED depth-1 and depth-2 variants and extrapolate
        total(L) = f(1) + (L - 1) * (f(2) - f(1)).
    For loop-free archs (deepfm/fm, 1-block bst) one unrolled lowering is
    exact."""
    from repro.launch.specs import cell_depth
    L = cell_depth(arch)
    if L <= 1:
        out = _lower_metrics(arch, shape, mesh, None, True, variant)
        out["method"] = "direct"
        return out
    f1 = _lower_metrics(arch, shape, mesh, 1, True, variant)
    f2 = _lower_metrics(arch, shape, mesh, 2, True, variant)
    out = {k: f1[k] + (L - 1) * max(f2[k] - f1[k], 0.0)
           for k in ("flops", "bytes", "coll_bytes")}
    out["method"] = f"extrapolated(1,2->{L})"
    out["per_layer"] = {k: max(f2[k] - f1[k], 0.0)
                        for k in ("flops", "bytes", "coll_bytes")}
    return out


def run_cell(arch: str, shape: str, multi_pod: bool, save: bool = True,
             variant: str = "baseline") -> dict:
    arch = arch.replace("-", "_").replace(".", "_")   # canonical module name
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    cell = build_cell(arch, shape, mesh, variant=variant)
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings)
        lowered = jitted.lower(*cell.args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    trips = while_trip_counts(hlo)
    dt = time.time() - t0

    mem_d = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
    }
    cost_d = {k: cost.get(k) for k in
              ("flops", "bytes accessed", "transcendentals")} if cost else {}
    try:
        extra = extrapolate_cost(arch, shape, mesh, variant)
    except Exception as e:   # cost model must never fail the dry-run cell
        extra = {"error": repr(e)}
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "variant": variant,
        "kind": cell.kind, "ok": True, "seconds": round(dt, 1),
        "devices": int(len(mesh.devices.reshape(-1))),
        "memory_analysis": mem_d,
        "cost_analysis": cost_d,
        "cost_extrapolated": extra,
        "collectives": coll,
        "while_trip_counts": trips,
        "meta": cell.meta,
    }
    if save:
        ART_DIR.mkdir(parents=True, exist_ok=True)
        suffix = "" if variant == "baseline" else f"__{variant}"
        out = ART_DIR / f"{arch}__{shape}__{mesh_name}{suffix}.json"
        out.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default=None)
    ap.add_argument("--shape", type=str, default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", type=str, default="baseline",
                    help="optimization variant (see launch/specs.VARIANTS)")
    args = ap.parse_args()

    cells = (list(cfg_registry.all_cells()) if args.all
             else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            fname = ART_DIR / f"{arch}__{shape}__{mesh_name}.json"
            if args.skip_existing and fname.exists() \
                    and json.loads(fname.read_text()).get("ok"):
                print(f"[skip] {arch} {shape} {mesh_name}")
                continue
            try:
                rec = run_cell(arch, shape, mp, variant=args.variant)
                mem = rec["memory_analysis"]
                print(f"[ok]   {arch:24s} {shape:14s} {mesh_name:10s} "
                      f"{rec['seconds']:6.1f}s "
                      f"args={_gb(mem['argument_bytes'])} "
                      f"temp={_gb(mem['temp_bytes'])} "
                      f"coll={_gb(rec['collectives']['total_bytes'])}")
            except Exception as e:
                failures.append((arch, shape, mesh_name, repr(e)))
                traceback.print_exc()
                print(f"[FAIL] {arch} {shape} {mesh_name}: {e}")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall requested dry-run cells compiled OK")


def _gb(b):
    return "-" if b is None else f"{b/2**30:.2f}G"


if __name__ == "__main__":
    main()
