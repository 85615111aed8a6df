"""The §Perf optimization variants must be NUMERICALLY equivalent to their
baselines (same math, different layout/schedule)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as reg


def test_lm_sharded_loss_matches_baseline():
    """loss_vocab_axis path == naive path (same logits, different softmax
    factorization) on a 1-device mesh."""
    from repro.launch.mesh import make_test_mesh
    from repro.models import transformer as T
    cfg = reg.get("gemma_2b").smoke_config()
    p = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                          cfg.vocab)}
    l0, _ = T.loss_fn(p, batch, cfg)
    cfg2 = dataclasses.replace(cfg, loss_vocab_axis="model",
                               loss_batch_axes=("data",),
                               loss_vocab_shards=2)
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        l1, _ = jax.jit(lambda p, b: T.loss_fn(p, b, cfg2))(p, batch)
    np.testing.assert_allclose(float(l0), float(l1), rtol=2e-5)


def test_bert4rec_masked_loss_matches_full():
    """masked_positions path == full loss when P covers all masked slots."""
    from repro.models import recsys as R
    cfg = reg.get("bert4rec").smoke_config()
    p = R.init_params(cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    B, S = 4, cfg.seq_len
    seq = jax.random.randint(key, (B, S), 1, cfg.n_items)
    labels = jnp.where(jax.random.bernoulli(key, 0.2, (B, S)),
                       seq, -1).astype(jnp.int32)
    batch = {"seq": jnp.where(labels >= 0, 0, seq), "labels": labels}
    l0, _ = R.loss_fn(p, batch, cfg)
    cfg2 = dataclasses.replace(cfg, masked_positions=S)  # covers everything
    l1, _ = R.loss_fn(p, batch, cfg2)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)


def test_retrieval_shardmap_matches_naive():
    from repro.launch.mesh import make_test_mesh
    from repro.models import recsys as R
    cfg = reg.get("bst").smoke_config()
    p = R.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"hist": jax.random.randint(jax.random.PRNGKey(1),
                                        (3, cfg.seq_len), 0, cfg.n_items)}
    d0, i0 = R.serve_retrieval(p, batch, cfg, k=7)
    mesh = make_test_mesh()
    d1, i1 = R.serve_retrieval_shardmap(p, batch, cfg, mesh, k=7)
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=1e-5)
    assert np.array_equal(np.asarray(i0), np.asarray(i1))


def test_moe_ep_constraints_nop_without_axes():
    """ep_axis=\"\" must leave moe_ffn usable with no mesh at all."""
    from repro.layers.moe import MoEConfig, init_moe, moe_ffn
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff_expert=16)
    p = init_moe(jax.random.PRNGKey(0), 8, cfg, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (12, 8))
    out, aux = moe_ffn(p, x, cfg)
    assert out.shape == x.shape and jnp.isfinite(aux)


def test_moe_keeps_dtype_bf16():
    """the f32-poisoning regression guard (EXPERIMENTS §Perf, H-A2)."""
    from repro.layers.moe import MoEConfig, init_moe, moe_ffn
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff_expert=16)
    p = init_moe(jax.random.PRNGKey(0), 8, cfg, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (12, 8)).astype(jnp.bfloat16)
    out, _ = moe_ffn(p, x, cfg)
    assert out.dtype == jnp.bfloat16


def test_dimenet_remat_matches():
    from repro.data.pipeline import gnn_minibatches
    from repro.models import dimenet as D
    cfg = reg.get("dimenet").smoke_config()
    p = D.init_params(cfg, jax.random.PRNGKey(0))
    it = gnn_minibatches(n_nodes=200, d_feat=cfg.d_feat, batch_nodes=4,
                         fanouts=(3, 2), n_classes=cfg.n_out, triplet_cap=4)
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
    l0, _ = D.loss_fn(p, batch, cfg)
    cfg2 = dataclasses.replace(cfg, remat=True)
    l1, _ = D.loss_fn(p, batch, cfg2)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)


def test_lm_remat_policies_match():
    from repro.models import transformer as T
    cfg = dataclasses.replace(reg.get("qwen2_5_14b").smoke_config(),
                              remat=True)
    p = T.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 9), 0,
                                          cfg.vocab)}
    l0, _ = T.loss_fn(p, batch, cfg)
    for pol in ("dots", "dots_nb"):
        c = dataclasses.replace(cfg, remat_policy=pol)
        l1, _ = T.loss_fn(p, batch, c)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6), pol


def test_moe_shardmap_matches_reference():
    """Explicit-collective MoE (hillclimb A5) == reference moe_ffn, forward
    and gradients, on a 2x2 device mesh (needs no-drop capacity so the
    per-column capacity split cannot change the drop pattern)."""
    import os
    if len(jax.devices()) < 4:
        import pytest
        pytest.skip("needs 4 host devices (run tests with "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    from repro.layers.moe import MoEConfig, init_moe, moe_ffn, moe_ffn_shardmap
    from jax.sharding import AxisType
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg0 = MoEConfig(n_experts=4, top_k=2, d_ff_expert=16, capacity_factor=8.0)
    cfg1 = dataclasses.replace(cfg0, ep_axis="data", tp_axis="model",
                               token_axes=("data",), use_shardmap=True,
                               ep_size=2, tp_size=2)
    p = init_moe(jax.random.PRNGKey(0), 8, cfg0, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    out0, _ = moe_ffn(p, x, cfg0)
    with jax.set_mesh(mesh):
        out1, _ = jax.jit(lambda p, x: moe_ffn_shardmap(p, x, cfg1))(p, x)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                               rtol=1e-5, atol=1e-5)
