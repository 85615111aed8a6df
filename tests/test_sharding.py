"""Sharding rules + the device-mesh (shard_map) search path of
core/sharded.py (1-device mesh with production axis names; the 512-device
lowering is exercised by launch/dryrun.py). The device-count-agnostic
ShardedKBest subsystem has its own suite in tests/test_sharded.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_test_mesh
from repro.sharding import rules


def test_lm_param_specs_divisibility_fallback():
    mesh = make_test_mesh()   # (1, 1): every divisibility check passes
    spec = rules.lm_param_spec("layers/wq", (2, 64, 128), mesh)
    assert spec == P(None, None, "model")
    # non-divisible dims must fall back to replicated, never error
    import jax as _jax
    spec2 = rules.lm_param_spec("layers/wq", (2, 64, 127), mesh)
    assert spec2 == P(None, None, "model")  # 127 % 1 == 0 on test mesh


def test_zero1_excludes_used_axes():
    mesh = make_test_mesh()
    s = rules.zero1_state_spec(P(None, "data", None, "model"),
                               (4, 16, 32, 64), mesh)
    # "data" already used -> no duplicate axes
    flat = [a for p in s for a in (p if isinstance(p, tuple) else (p,))]
    named = [a for a in flat if a is not None]
    assert len(named) == len(set(named))


def test_param_tree_shardings_cover_all_leaves():
    from repro import configs as reg
    from repro.models.transformer import init_params
    mesh = make_test_mesh()
    cfg = reg.get("kimi_k2_1t_a32b").smoke_config()
    p = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    sh = rules.tree_param_shardings(p, mesh, "lm")
    n_leaves = len(jax.tree.leaves(p))
    n_sh = len(jax.tree.leaves(
        sh, is_leaf=lambda x: isinstance(x, NamedSharding)))
    assert n_leaves == n_sh
    for s, l in zip(jax.tree.leaves(sh, is_leaf=lambda x: isinstance(x, NamedSharding)),
                    jax.tree.leaves(p)):
        assert len(s.spec) <= len(l.shape)


def test_cache_shardings_long_context():
    mesh = make_test_mesh()
    cache = {"k": jax.ShapeDtypeStruct((4, 1, 512, 2, 16), jnp.bfloat16),
             "v": jax.ShapeDtypeStruct((4, 1, 512, 2, 16), jnp.bfloat16),
             "len": jax.ShapeDtypeStruct((1,), jnp.int32)}
    sh = rules.lm_cache_shardings(cache, mesh)
    # B=1: sequence dim absorbs all axes
    assert sh["k"].spec[2] is not None


def test_distributed_search_parity(deep_ds, deep_index):
    """Sharded search over a 1-device mesh == exact top-k of local search
    on the same shard (the collective path is a no-op at P=1)."""
    from repro.core.sharded import build_sharded_search, make_sharded_arrays
    from repro.core.types import SearchConfig
    mesh = make_test_mesh()
    n = deep_index.db.shape[0]
    cfg = SearchConfig(L=48, k=10, early_term=False, n_entries=1)
    fn = build_sharded_search(mesh, cfg, "ip", n_local=n)
    db, graph, entries, queries = make_sharded_arrays(
        mesh, deep_index.db, deep_index.graph,
        jnp.array([deep_index.entry], jnp.int32),
        jnp.asarray(deep_ds.queries))
    d_sh, i_sh = fn(db, graph, entries, queries)

    from repro.core import search as smod
    dist_fn = smod.make_dist_fn("ip", "ref")
    d_loc, i_loc, _ = smod.search(
        deep_index.graph, jnp.asarray(deep_ds.queries),
        jnp.array([deep_index.entry], jnp.int32), deep_index.db,
        dist_fn=dist_fn, cfg=cfg, n_total=n)
    assert np.array_equal(np.asarray(i_sh), np.asarray(i_loc))


def test_make_sharded_arrays_uneven_rejected_then_padded(deep_index):
    """Uneven row counts pad to the shard boundary with sentinel rows and
    the real rows round-trip bit-exactly through placement (the P=1 mesh
    exercises the assert path; pad_to_shard_boundary's P>1 arithmetic is
    covered host-side in tests/test_sharded.py)."""
    from repro.core.sharded import make_sharded_arrays
    mesh = make_test_mesh()
    db, graph, entries, queries = make_sharded_arrays(
        mesh, deep_index.db, deep_index.graph,
        jnp.array([deep_index.entry], jnp.int32),
        jnp.zeros((4, deep_index.db.shape[1]), jnp.float32))
    assert np.array_equal(np.asarray(db), np.asarray(deep_index.db))
    assert np.array_equal(np.asarray(graph), np.asarray(deep_index.graph))
