"""Ahead-of-time compiles for a TPU v5e that is described, not attached.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
blocks not aligned to the (8, 128) tiling, scalar stores to VMEM, sorts
inside a kernel. These tests compile every public kernel with
interpret=False at the deployment widths d in {96, 128} over a 1M-row
corpus, and the served graph and IVF programs at 1M rows from
ShapeDtypeStructs, so a refused kernel or an index baked into a program
as a constant fails here at no chip time.

The topology is described inside a module-scoped fixture (never at import:
only one process may hold the TPU library, and every xdist worker imports
this file). The persistent compilation cache is off for the module: a
compile for a described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

N = 1_000_000
Q, C, W = 8, 24, 4
NLIST, MAX_LEN, P, L = 1000, 2048, 24, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Route the ops wrappers to Mosaic (interpret=False): the CPU backend
    these tests run on would otherwise select interpret mode."""
    monkeypatch.setattr(ops, "_on_cpu", lambda: False)


def _kernel_args(name, d, S):
    f32, i32, u8, u32 = jnp.float32, jnp.int32, jnp.uint8, jnp.uint32
    m, m4, nw = 16, 32, -(-d // 32)
    ids = S((Q, C), i32)
    beam = S((Q, W * C), i32)
    probes = S((Q, P), i32)
    lists = S((NLIST, MAX_LEN), i32)
    return {
        "batch_dist": (lambda q, x: ops.batch_dist(q, x, metric="l2"),
                       (S((256, d), f32), S((4096, d), f32))),
        "gather_dist": (lambda q, x, i: ops.gather_dist(q, x, i, metric="ip"),
                        (S((Q, d), f32), S((N, d), f32), ids)),
        "sq_gather_dist": (
            lambda q, c, s, z, i: ops.sq_gather_dist(q, c, s, z, i),
            (S((Q, d), f32), S((N, d), u8), S((d,), f32), S((d,), f32), ids)),
        "pq_adc": (ops.pq_adc, (S((Q, m, 256), f32), S((N, m), u8), ids)),
        "pq4_adc": (ops.pq4_adc,
                    (S((Q, m4, 16), f32), S((N, m4 // 2), u8), ids)),
        "bin_dist": (ops.bin_dist, (S((Q, nw), u32), S((N, nw), u32), ids)),
        "fused_expand": (
            lambda q, x, i: ops.fused_expand(q, x, i, metric="l2", L=64,
                                             n_beam=W),
            (S((Q, d), f32), S((N, d), f32), beam)),
        "fused_expand_sq": (
            lambda q, c, s, z, i: ops.fused_expand_sq(q, c, s, z, i, L=64,
                                                      n_beam=W),
            (S((Q, d), f32), S((N, d), u8), S((d,), f32), S((d,), f32),
             beam)),
        "fused_expand_pq": (
            lambda t, c, i: ops.fused_expand_pq(t, c, i, L=64, n_beam=W),
            (S((Q, m, 256), f32), S((N, m), u8), beam)),
        "fused_expand_pq4": (
            lambda t, c, i: ops.fused_expand_pq4(t, c, i, L=64, n_beam=W),
            (S((Q, m4, 16), f32), S((N, m4 // 2), u8), beam)),
        "fused_expand_bin": (
            lambda q, c, i: ops.fused_expand_bin(q, c, i, L=64, n_beam=W),
            (S((Q, nw), u32), S((N, nw), u32), beam)),
        "ivf_scan": (
            lambda t, c, li, p: ops.ivf_scan(t, c, li, p, L=L),
            (S((Q, P, m, 256), f32), S((NLIST, MAX_LEN, m), u8), lists,
             probes)),
        "pq4_ivf_scan": (
            lambda t, c, li, p: ops.pq4_ivf_scan(t, c, li, p, L=L),
            (S((Q, 1, m4, 16), f32), S((NLIST, MAX_LEN, m4 // 2), u8), lists,
             probes)),
        "bin_ivf_scan": (
            lambda q, c, li, p: ops.bin_ivf_scan(q, c, li, p, L=L),
            (S((Q, nw), u32), S((NLIST, MAX_LEN, nw), u32), lists, probes)),
    }[name]


KERNELS = ("batch_dist", "gather_dist", "sq_gather_dist", "pq_adc",
           "pq4_adc", "bin_dist", "fused_expand", "fused_expand_sq",
           "fused_expand_pq", "fused_expand_pq4", "fused_expand_bin",
           "ivf_scan", "pq4_ivf_scan", "bin_ivf_scan")


def test_kernel_list_is_the_ops_surface():
    from repro.analysis import parity
    from repro.analysis.common import Tree
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    assert set(KERNELS) == {n for _, n, _ in parity.find_kernels(Tree(root))}


@pytest.mark.parametrize("d", [96, 128])
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, mosaic, name, d):
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_args(name, d, S)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------- served programs at 1M
D = 96  # the deep_like deployment width


def _index_shapes(family, S):
    """A 1M-row deep_like index made of ShapeDtypeStructs: the presets'
    layout (graph M=24; IVF nlist=1000 with PQ8 m=16), no data."""
    from repro.configs import kbest
    from repro.core import ivf as ivf_mod
    from repro.core import quantize as qz
    from repro.core.index import KBest
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    cfg = (kbest.index_config("deep_like") if family == "graph"
           else kbest.ivf_index_config("deep_like"))
    idx = KBest(cfg)
    idx.db = S((N, D), f32)
    if family == "graph":
        idx.graph = S((N, cfg.build.M), i32)
        idx.order = S((N,), i32)
        idx.entry = 12345
    else:
        m = cfg.quant.pq_m
        idx.ivf = ivf_mod.IVFState(
            centroids=S((NLIST, D), f32), list_ids=S((NLIST, 3072), i32),
            list_codes=S((NLIST, 3072, m), u8),
            pq=qz.PQState(S((m, 256, D // m), f32), m, D // m),
            residual=cfg.ivf.residual)
    return idx


def _large_constants(hlo: str, limit: int = 1 << 20):
    """HLO constants of more than `limit` bytes, as (type, bytes)."""
    import re
    nbytes = {"f32": 4, "s32": 4, "u32": 4, "u8": 1, "s8": 1, "pred": 1,
              "bf16": 2, "f16": 2, "s64": 8, "u64": 8, "f64": 8}
    out = []
    for m in re.finditer(r"= (\w+)\[([\d,]*)\][^=\n]* constant\(", hlo):
        size = nbytes.get(m.group(1), 4)
        for dim in filter(None, m.group(2).split(",")):
            size *= int(dim)
        if size > limit:
            out.append((m.group(0)[:60], size))
    return out


@pytest.mark.parametrize("family", ["graph", "ivf"])
def test_served_program_takes_the_index_as_arguments(one_chip, mosaic,
                                                     family):
    """The engine's compiled program for one bucket, lowered at 1M rows on
    the kernel path: the index bytes arrive as arguments and no constant
    above 1 MiB is embedded in the HLO."""
    import dataclasses
    from repro.serve.engine import SearchEngine

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    idx = _index_shapes(family, S)
    eng = SearchEngine(idx)
    scfg = dataclasses.replace(idx.config.search, dist_impl="kernel")
    bucket = 64
    served = eng._compiled(bucket, scfg)
    compiled = served.func.lower(
        idx, S((bucket, D), jnp.float32), S((bucket,), jnp.bool_)).compile()
    index_bytes = sum(
        int(np.prod(a.shape)) * a.dtype.itemsize
        for a in jax.tree.leaves(idx))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= index_bytes, (mem, index_bytes)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _large_constants(hlo) == []
    assert eng.n_traces == 1
