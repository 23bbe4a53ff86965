"""Per-kernel tests: shape/dtype sweeps vs the pure-jnp oracle (ref.py),
plus statistical properties of the ops-level API."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers.prop import given, settings, st

from repro.kernels import ops, ref
from repro.kernels import squant as sq
from repro.kernels import fused_memory as fm

KEY = jax.random.PRNGKey(0)

SHAPES = [(256, 256), (512, 256), (256, 512)]
BLOCKS = [(256, 256), (128, 256)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _mk(shape, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    u = jax.random.uniform(jax.random.PRNGKey(seed + 1), shape, jnp.float32)
    return x.astype(dtype), u.astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("s", [1, 4])
def test_encode_matches_ref(shape, block, dtype, s):
    bm, bn = block
    if shape[0] % bm or shape[1] % bn:
        pytest.skip("non-multiple")
    x, u = _mk(shape, dtype)
    q, scales = sq.squant_encode(x, u, s=s, block=block, interpret=True)
    qr, sr = ref.squant_encode_ref(x, u, s, bm, bn)
    # f32 accumulation-order differences may flip a stochastic-rounding
    # threshold on a vanishingly small fraction of coordinates
    qn, qrn = np.asarray(q, np.int32), np.asarray(qr, np.int32)
    mismatch = qn != qrn
    assert mismatch.mean() < 1e-4, mismatch.mean()
    assert np.abs(qn - qrn)[mismatch].max(initial=0) <= 1
    np.testing.assert_allclose(np.asarray(scales), np.asarray(sr),
                               rtol=3e-3 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("shape", SHAPES[:2])
@pytest.mark.parametrize("dtype", DTYPES)
def test_decode_matches_ref(shape, dtype):
    block = (256, 256)
    x, u = _mk(shape, jnp.float32, seed=3)
    q, scales = sq.squant_encode(x, u, s=2, block=block, interpret=True)
    out = sq.squant_decode(q, scales, block=block, dtype=dtype, interpret=True)
    outr = ref.squant_decode_ref(q, scales, *block, dtype=dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(outr, np.float32), rtol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("s", [1, 3])
@pytest.mark.parametrize("alpha", [0.25, 0.5])
def test_fused_memory_matches_ref(shape, s, alpha):
    block = (256, 256)
    g, u = _mk(shape, jnp.float32, seed=5)
    h, _ = _mk(shape, jnp.float32, seed=6)

    def tiles(x):                      # [M, N] -> [T, bm, bn] tile stack
        return ref._blockify(x, *block).reshape((-1,) + block)

    q, scales, h_new = fm.fused_memory_update(tiles(g), tiles(h), tiles(u),
                                              alpha, s=s, interpret=True)
    qr, sr, hr = ref.fused_memory_ref(g, h, u, alpha, s, *block)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(tiles(qr)))
    np.testing.assert_allclose(np.asarray(scales).reshape(sr.shape),
                               np.asarray(sr), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(h_new), np.asarray(tiles(hr)),
                               rtol=1e-5, atol=1e-6)


def test_dequant_apply_matches_ref():
    block = (256, 256)
    w, u = _mk((512, 256), jnp.float32, seed=7)
    x, _ = _mk((512, 256), jnp.float32, seed=8)
    q, scales = sq.squant_encode(x, u, s=1, block=block, interpret=True)
    out = sq.dequant_apply(w, q, scales, 0.1, block=block, interpret=True)
    outr = ref.dequant_apply_ref(w, q, scales, 0.1, *block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(outr), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# ops-level (arbitrary shapes, padding, pytrees)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(7,), (100,), (33, 65), (3, 5, 129), (300000,)])
def test_ops_roundtrip_shapes(shape):
    x = jax.random.normal(KEY, shape)
    out = ops.compress(KEY, x, s=1)
    assert out.shape == x.shape and out.dtype == x.dtype
    # dequantized values share sign or are zero
    xn, on = np.asarray(x), np.asarray(out)
    bad = (np.sign(on) != 0) & (np.sign(on) != np.sign(xn))
    assert not bad.any()


def test_ops_unbiased():
    """E[C(x)] = x, checked via per-coordinate z-scores (the per-sample std is
    large by design for s=1: ~scale*sqrt(p))."""
    n_samp = 150
    x = jax.random.normal(KEY, (768,))
    keys = jax.random.split(jax.random.PRNGKey(1), n_samp)
    outs = jax.vmap(lambda k: ops.compress(k, x, s=1))(keys)
    # projection statistic: t_k = <C_k(x), x>/||x||^2 has mean 1 if unbiased
    t = np.asarray(outs @ x / jnp.sum(x * x))
    z = (t.mean() - 1.0) / (t.std(ddof=1) / np.sqrt(n_samp))
    assert abs(z) < 5.0, (t.mean(), z)


def test_ops_variance_bound():
    """Per-tile squant satisfies Assumption 5 with omega = sqrt(tile)/s."""
    d = 256 * 256   # one tile exactly
    x = jax.random.normal(KEY, (d,))
    keys = jax.random.split(jax.random.PRNGKey(2), 50)
    errs = jax.vmap(lambda k: jnp.sum((ops.compress(k, x, s=1) - x) ** 2))(keys)
    omega = np.sqrt(d) / 1.0
    assert float(jnp.mean(errs)) <= omega * float(jnp.sum(x**2)) * 1.1


def test_ops_memory_update_consistency():
    """ops.memory_update == unfused encode/decode pipeline on same bits."""
    g = jax.random.normal(KEY, (500, 300))
    h = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (500, 300))
    dh, h_new, c = ops.memory_update(jax.random.PRNGKey(4), g, h, 0.5, s=1)
    np.testing.assert_allclose(np.asarray(h + 0.5 * dh), np.asarray(h_new),
                               rtol=1e-5, atol=1e-6)


def test_tree_memory_update():
    tree_g = {"w": jax.random.normal(KEY, (64, 32)), "b": jnp.ones((17,))}
    tree_h = jax.tree.map(jnp.zeros_like, tree_g)
    dh, hn = ops.tree_memory_update(KEY, tree_g, tree_h, 0.5, s=1)
    assert jax.tree.structure(dh) == jax.tree.structure(tree_g)
    for a, b in zip(jax.tree.leaves(hn), jax.tree.leaves(dh)):
        np.testing.assert_allclose(np.asarray(a), 0.5 * np.asarray(b), rtol=1e-6)


def test_apply_update():
    w = jax.random.normal(KEY, (100, 100))
    g = jax.random.normal(jax.random.PRNGKey(9), (100, 100))
    c, shape = ops.encode(jax.random.PRNGKey(10), g, s=1)
    w2 = ops.apply_update(w, c, 0.01, shape)
    expect = w - 0.01 * ops.decode(c, shape)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(expect), rtol=1e-5, atol=1e-7)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4000), st.integers(1, 126), st.integers(0, 10**6))
def test_property_roundtrip_grid(n, s, seed):
    """Every decoded coordinate is a multiple of its tile scale, within level bound."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    c, shape = ops.encode(jax.random.PRNGKey(seed + 1), x, s=s)
    out = np.asarray(ops.decode(c, shape))
    q = np.asarray(c.q)
    assert np.abs(q).max() <= s + 1
    # decode is exactly q*scale per tile:
    full = np.asarray(ops.decode(c, (c.q.size,)))
    assert full.shape == (c.q.size,)


# ---------------------------------------------------------------------------
# ring_sum (server-side dequant-accumulate)
# ---------------------------------------------------------------------------

from repro.kernels import ring_sum as rs


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("shape", [(256, 256), (512, 256)])
def test_ring_sum_matches_ref(n, shape):
    q = jax.random.randint(jax.random.PRNGKey(n), (n,) + shape, -3, 4,
                           dtype=jnp.int8)
    scales = jax.random.uniform(jax.random.PRNGKey(n + 1), (n, shape[0], 1))
    out = rs.ring_sum(q, scales, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(rs.ring_sum_ref(q, scales)),
                               rtol=1e-6, atol=1e-5)


def test_ring_sum_roundtrip_consistency():
    """ring_sum of encoded worker deltas == sum of decoded deltas."""
    from repro.core import codec
    n, m, c = 4, 256, 256
    xs = jax.random.normal(jax.random.PRNGKey(0), (n, m, c))
    qs, ss = [], []
    for i in range(n):
        q, s_ = codec.row_squant_encode(jax.random.PRNGKey(i + 1), xs[i], 1)
        qs.append(q)
        ss.append(s_)
    out = rs.ring_sum(jnp.stack(qs), jnp.stack(ss), interpret=True)
    expect = sum(codec.row_squant_decode(q, s_) for q, s_ in zip(qs, ss))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5)
