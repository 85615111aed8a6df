"""The plain reference against numpy's exact top-k."""
import jax.numpy as jnp
import numpy as np

from bench import data, reference


def _unit(rng, n, d=96):
    x = rng.normal(size=(n, d))
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def test_exact_topk_equals_numpy():
    rng = np.random.default_rng(0)
    base, q = _unit(rng, 1500), _unit(rng, 300)    # 300: a padded block
    d, ids = reference.exact_topk(jnp.asarray(base), q, 10)
    sim = q.astype(np.float64) @ base.astype(np.float64).T
    want = np.argsort(-sim, axis=1, kind="stable")[:, :10]
    assert ids.shape == (300, 10)
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_allclose(d, -np.take_along_axis(sim, want, 1),
                               rtol=0, atol=1e-6)


def test_true_dists_and_invalid_ids():
    rng = np.random.default_rng(1)
    base, q = _unit(rng, 500), _unit(rng, 40)
    ids = rng.integers(0, 500, size=(40, 10))
    ids[0, 0], ids[1, 3] = -1, 500
    got = reference.true_dists(jnp.asarray(base), q, ids)
    want = -np.einsum("qkd,qd->qk", base[np.clip(ids, 0, 499)], q)
    assert np.isinf(got[0, 0]) and np.isinf(got[1, 3])
    ok = np.isfinite(got)
    np.testing.assert_allclose(got[ok], want[ok], rtol=0, atol=1e-6)


def test_bfloat16_control_moves_distances():
    """The control's distances are off by bfloat16 rounding; float32's
    are not."""
    rng = np.random.default_rng(2)
    base, q = _unit(rng, 2000), _unit(rng, 256)
    b = jnp.asarray(base)
    d32, i32 = reference.exact_topk(b, q, 10)
    d16, i16 = reference.exact_topk(b, q, 10, dtype=jnp.bfloat16)
    gap32 = np.abs(d32 - reference.true_dists(b, q, i32)).max()
    gap16 = np.abs(d16 - reference.true_dists(b, q, i16)).max()
    assert gap32 < 1e-5 < 1e-4 < gap16


SPEC = {"n": 300, "queries": 20, "dim": 96, "rank": 32, "noise": 0.1,
        "corpus_seed": 99}


def test_corpus_is_a_function_of_the_seeds():
    a = data.make_corpus(2**31 + 17, SPEC)
    b = data.make_corpus(2**31 + 17, SPEC)
    c = data.make_corpus(17, dict(SPEC, corpus_seed=98))
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(np.asarray(a[0]), np.asarray(c[0]))
    np.testing.assert_allclose(np.linalg.norm(a[1], axis=1), 1.0, atol=1e-5)


def test_corpus_seed_fixes_the_corpus_not_the_queries():
    a, b = data.make_corpus(1, SPEC), data.make_corpus(2**33 + 1, SPEC)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert not np.array_equal(a[1], b[1])
