"""The fused wire round (``kernels/wire_round``) against the XLA chain it
replaces in ``core/dist.artemis_aggregate``, in interpret mode.

Levels, scales, ``h_new``, ``hbar_new`` and the decoded downlink are held
bitwise: the kernels draw the same uniforms and run the same f32 ops."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucketing
from repro.core import codec as wire
from repro.kernels import wire_round as WR

ALPHA = 0.0294


def _stacks(b, r, c, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [sc * jax.random.normal(k, (b, r, c), jnp.float32)
            for k, sc in zip(ks, (1.0, 0.5, 0.3))]


@functools.partial(jax.jit, static_argnames=("s", "mdt"))
def _uplink_chain(key, g, h, active, *, s, mdt):
    """``artemis_aggregate``'s encode and worker memory update."""
    wc = wire.make_codec("row_squant", g.shape[-1], s=s)
    h32 = h.astype(jnp.float32)
    enc = bucketing.encode_buckets(wc, key, (g - h32) * active)
    q, sc = enc["levels"], enc["scales"] * active
    return q, sc, (h32 + ALPHA * wire.row_squant_decode(q, sc)).astype(mdt)


@functools.partial(jax.jit, static_argnames=("s", "p", "n", "mdt"))
def _server_chain(key, hbar, dsum, *, s, p, n, mdt):
    """``artemis_aggregate``'s server memory update and downlink."""
    wc = wire.make_codec("row_squant", dsum.shape[-1], s=s)
    hb = hbar.astype(jnp.float32)
    ghat = hb + dsum / (p * n)
    hbn = (hb + ALPHA * dsum / n).astype(mdt)
    return hbn, bucketing.decode_buckets(
        wc, bucketing.encode_buckets(wc, key, ghat))


# (B, R, C, block_rows, s): one block over all rows (the interpret
# default), a block past R's end, a ragged R under 32-row blocks, and a
# lane-narrow row
UPLINK_CASES = [(3, 200, 256, None, 1), (2, 7, 256, 8, 1),
                (2, 100, 256, 32, 1), (2, 70, 64, 32, 3)]


@pytest.mark.parametrize("b,r,c,br,s", UPLINK_CASES)
@pytest.mark.parametrize("mdt", ["float32", "bfloat16"])
def test_uplink_round_matches_chain(b, r, c, br, s, mdt):
    g, h, _ = _stacks(b, r, c)
    h = h.astype(mdt)
    key = jax.random.PRNGKey(7)
    active = jnp.float32(1.0)
    q, sc, hn = WR.uplink_round(g, h, active, bucketing.bucket_keys(key, b),
                                alpha=ALPHA, s=s, block_rows=br)
    assert (q.dtype, sc.shape, hn.dtype) == (jnp.int8, (b, r, 1), h.dtype)
    # the levels are the codec's own, bucket by bucket with fold_in(key, b)
    # (jitted, as the step runs it: XLA folds the division by s there)
    encode = jax.jit(wire.row_squant_encode, static_argnums=2)
    for i in range(b):
        qi, si = encode(jax.random.fold_in(key, i),
                        g[i] - h[i].astype(jnp.float32), s)
        np.testing.assert_array_equal(np.asarray(q[i]), np.asarray(qi))
        np.testing.assert_array_equal(np.asarray(sc[i]), np.asarray(si))
    qc, scc, hc = _uplink_chain(key, g, h, active, s=s, mdt=mdt)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qc))
    np.testing.assert_array_equal(np.asarray(sc), np.asarray(scc))
    np.testing.assert_array_equal(np.asarray(hn, np.float32),
                                  np.asarray(hc, np.float32))
    assert np.any(np.asarray(q) != 0)


@pytest.mark.parametrize("mdt", ["float32", "bfloat16"])
def test_uplink_round_inactive(mdt):
    """An inactive worker ships zero scales and keeps its memory, whatever
    its gradient holds (NaN rows among it)."""
    b, r, c = 2, 100, 256
    g, h, _ = _stacks(b, r, c, seed=1)
    g = g.at[0, 3].set(jnp.nan)
    h = h.astype(mdt)
    q, sc, hn = WR.uplink_round(g, h, jnp.float32(0.0),
                                bucketing.bucket_keys(jax.random.PRNGKey(2), b),
                                alpha=ALPHA, s=1, block_rows=32)
    assert not np.any(np.asarray(sc))
    np.testing.assert_array_equal(np.asarray(hn, np.float32),
                                  np.asarray(h, np.float32))


SERVER_CASES = [(3, 200, 256, None, 1, 1.0, 1), (2, 7, 256, 8, 1, 1.0, 2),
                (2, 100, 256, 32, 1, 0.5, 4), (2, 70, 64, 32, 3, 0.5, 3)]


@pytest.mark.parametrize("b,r,c,br,s,p,n", SERVER_CASES)
@pytest.mark.parametrize("mdt", ["float32", "bfloat16"])
def test_server_round_matches_chain(b, r, c, br, s, p, n, mdt):
    _, hbar, dsum = _stacks(b, r, c, seed=3)
    hbar = hbar.astype(mdt)
    key = jax.random.PRNGKey(9)
    hbn, ghat = WR.server_round(hbar, dsum, bucketing.bucket_keys(key, b),
                                p=p, n=n, alpha=ALPHA, s=s, block_rows=br)
    assert (hbn.dtype, ghat.dtype) == (hbar.dtype, jnp.float32)
    hc, gc = _server_chain(key, hbar, dsum, s=s, p=p, n=n, mdt=mdt)
    np.testing.assert_array_equal(np.asarray(hbn, np.float32),
                                  np.asarray(hc, np.float32))
    np.testing.assert_array_equal(np.asarray(ghat), np.asarray(gc))
    assert np.any(np.asarray(ghat) != 0)


@pytest.mark.parametrize("b,c,elem_bytes", [(16, 256, 13), (16, 256, 9),
                                            (16, 256, 16), (3, 64, 13)])
def test_default_block_rows(b, c, elem_bytes):
    """Blocks hold whole slices and fit the VMEM budget, the [.., b, 1]
    scales block padded to 8 x 128."""
    br, t = WR.default_block_rows(b, c, elem_bytes), WR.slice_rows(b, c)
    assert br % t == 0
    assert br * (b * c * elem_bytes + -(-b // 8) * 8 * 512) \
        <= WR.VMEM_BLOCK_BYTES
