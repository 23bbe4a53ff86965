"""Bucketizer layout/roundtrip invariants + bucket_ring kernel oracles.

Mesh-free tests of the bucketed wire's building blocks; the multi-device
ring/transport semantics live in tests/test_bucketed.py (subprocess
scenarios with fake CPU devices).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers.prop import given, settings, st

from repro.core import bucketing as B
from repro.core import codec
from repro.core import dist
from repro.kernels import bucket_ring as BK

KEY = jax.random.PRNGKey(7)


def _tree(key, spec):
    leaves = []
    for i, shape in enumerate(spec):
        key, k = jax.random.split(key)
        leaves.append(jax.random.normal(k, shape))
    return {f"leaf_{i}": l for i, l in enumerate(leaves)}


TREES = [
    [(3, 5), (7,), (2, 2, 2)],
    [(1,), ()],                      # scalar leaf
    [(17, 13)],
    [(256,), (31, 9), (4, 4), (5,)],
]


@pytest.mark.parametrize("spec", TREES)
def test_roundtrip_exact(spec):
    tree = _tree(KEY, spec)
    lay = B.make_layout(tree, bucket_bytes=256, max_buckets=4, row=16)
    buckets = B.bucketize(lay, tree)
    assert buckets.shape == lay.shape
    out = B.unbucketize(lay, buckets, like=tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=6, deadline=None)
@given(st.integers(64, 4096), st.integers(1, 8), st.sampled_from([8, 32, 128]))
def test_layout_invariants(bucket_bytes, max_buckets, row):
    tree = _tree(KEY, [(37, 11), (5,), (301,), (2, 3, 7)])
    lay = B.make_layout(tree, bucket_bytes=bucket_bytes,
                        max_buckets=max_buckets, row=row)
    # equal-size buckets, row-aligned, capped count, padding < one bucket
    assert lay.n_buckets <= max_buckets
    assert lay.bucket_elems % row == 0
    assert 0 <= lay.pad < lay.bucket_elems
    assert lay.padded_total == lay.n_buckets * lay.bucket_elems
    assert lay.total == sum(lay.sizes)
    # tail padding is zero-filled and roundtrip drops it
    buckets = B.bucketize(lay, tree)
    flat = np.asarray(buckets).reshape(-1)
    if lay.pad:
        assert (flat[lay.total:] == 0).all()


def test_single_bucket_cap():
    """bucket_bytes=inf collapses to ONE tree-sized bucket, not a giant one."""
    tree = _tree(KEY, [(33, 3), (41,)])
    lay = B.make_layout(tree, bucket_bytes=1 << 40, max_buckets=16, row=32)
    assert lay.n_buckets == 1
    assert lay.bucket_elems < lay.total + lay.row + 32


def test_bucketize_is_linear():
    tree_a = _tree(KEY, [(9, 5), (44,)])
    tree_b = jax.tree.map(lambda x: 2.0 * x + 1.0, tree_a)
    lay = B.make_layout(tree_a, bucket_bytes=128, max_buckets=8, row=8)
    lhs = B.bucketize(lay, jax.tree.map(jnp.add, tree_a, tree_b))
    rhs = B.bucketize(lay, tree_a) + B.bucketize(lay, tree_b)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-6)


def test_bucket_keys_distinct():
    keys = np.asarray(B.bucket_keys(KEY, 8))
    assert len({tuple(k) for k in keys}) == 8


def test_bucket_encode_decode_unbiased_scale():
    """``encode_buckets`` with the wire's ``row_squant`` codec matches
    per-row squant semantics bucket by bucket."""
    tree = _tree(KEY, [(64, 32), (128,)])
    lay = B.make_layout(tree, bucket_bytes=1024, max_buckets=8, row=64)
    buckets = B.bucketize(lay, tree)
    enc = B.encode_buckets(codec.make_codec("row_squant", lay.row, s=3), KEY,
                           buckets)
    q, sc = enc["levels"], enc["scales"]
    assert q.shape == lay.shape and q.dtype == jnp.int8
    assert sc.shape == (lay.n_buckets, lay.rows, 1)
    keys = B.bucket_keys(KEY, lay.n_buckets)
    for b in range(lay.n_buckets):
        qb, sb = codec.row_squant_encode(keys[b], buckets[b], 3)
        np.testing.assert_array_equal(np.asarray(q[b]), np.asarray(qb))
        np.testing.assert_allclose(np.asarray(sc[b]), np.asarray(sb))


@pytest.mark.parametrize("kw, err", [
    ({"reduce_impl": "sequential"}, ValueError),
    ({"reduce_impl": "ring"}, ValueError),
    ({"codec": "nope"}, ValueError),
    ({"wire": "leaf"}, TypeError),
])
def test_dist_config_rejects(kw, err):
    """The mesh has one wire, the bucketed ring: ``DistConfig`` refuses a
    ``wire`` field, a ``reduce_impl`` other than the pipelined ring and its
    psum reference, and an unregistered codec."""
    assert dist.REDUCE_IMPLS == ("pipelined", "psum")
    with pytest.raises(err):
        dist.DistConfig(**kw)


# ---------------------------------------------------------------------------
# kernels/bucket_ring.py
# ---------------------------------------------------------------------------

def _payload(key, n, b, r, c):
    kq, ks = jax.random.split(key)
    q = jax.random.randint(kq, (n, b, r, c), -4, 5, jnp.int8)
    sc = jax.random.uniform(ks, (n, b, r, 1), jnp.float32)
    return q, sc


def test_bucket_acc_matches_oracle():
    q, sc = _payload(KEY, 1, 3, 8, 16)
    acc = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 16))
    out = BK.bucket_acc(acc, q[0], sc[0])
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(BK.bucket_acc_ref(acc, q[0], sc[0])),
                               atol=1e-6)


@pytest.mark.parametrize("rows,block_rows", [(8, 4), (20, 8), (13, 32)])
def test_bucket_acc_block_rows(rows, block_rows):
    """Row-blocked grids, ragged last block included, match the gridless
    fold bitwise."""
    q, sc = _payload(KEY, 1, 2, rows, 16)
    acc = jax.random.normal(jax.random.PRNGKey(2), (2, rows, 16))
    full = BK.bucket_acc(acc, q[0], sc[0])
    blocked = BK.bucket_acc(acc, q[0], sc[0], block_rows=block_rows)
    np.testing.assert_array_equal(np.asarray(full), np.asarray(blocked))


def test_default_block_rows_fit_vmem():
    for c in (64, 256, 1024, 4096):
        br = BK.default_block_rows(c)
        assert br % 32 == 0
        assert br * (c * 9 + 512) <= BK.VMEM_BLOCK_BYTES or br == 32


def test_bucket_ring_sum_matches_hop_chain():
    """The all-at-once kernel == the hop-by-hop bucket_acc chain (up to FMA
    fusion inside one kernel body ~1e-7)."""
    q, sc = _payload(KEY, 5, 4, 8, 16)
    stacked = BK.bucket_ring_sum(q, sc)
    acc = jnp.zeros((4, 8, 16), jnp.float32)
    for i in range(5):
        acc = BK.bucket_acc(acc, q[i], sc[i])
    np.testing.assert_allclose(np.asarray(stacked), np.asarray(acc),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(stacked),
                               np.asarray(BK.bucket_ring_sum_ref(q, sc)),
                               atol=1e-5)
