"""Layer-level correctness: chunked attention/xent vs naive, scan chunking of
SSM/RG-LRU vs step-by-step recurrence, RoPE properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers.prop import given, settings, st

from repro.models import layers as L
from repro.models import rglru as RG
from repro.models import ssm as SSM

KEY = jax.random.PRNGKey(0)


# -- attention -----------------------------------------------------------------

def _naive_attention(q, k, v, causal=True, window=0):
    b, s, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    kk = jnp.repeat(k, rep, 2)
    vv = jnp.repeat(v, rep, 2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.sqrt(d)
    i = jnp.arange(s)
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= i[:, None] >= i[None, :]
    if window:
        mask &= (i[:, None] - i[None, :]) < window
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("q_chunk", [8, 32, 128])
def test_chunked_attention_matches_naive(window, q_chunk):
    b, s, h, kv, d = 2, 64, 4, 2, 16
    q = jax.random.normal(KEY, (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, kv, d))
    pos = jnp.arange(s)[None].repeat(b, 0)
    out = L.attention(q, k, v, pos, pos, window=window, q_chunk=q_chunk)
    ref = _naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


def test_non_causal_attention():
    b, s, h, d = 1, 16, 2, 8
    q = jax.random.normal(KEY, (b, s, h, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d))
    pos = jnp.arange(s)[None].repeat(b, 0)
    out = L.attention(q, k, v, pos, pos, causal=False, q_chunk=8)
    ref = _naive_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4,
                               atol=2e-5)


# -- fused attention (kernels/flash_attn) -----------------------------------------

def _f32_attention(q, k, v):
    """Plain f32 einsum reference for Sq x Sk attention."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") * d ** -0.5
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                      precision="highest")


@pytest.mark.parametrize("sq,sk,d", [
    (300, 300, 64),    # padded to 384: pad keys masked, pad rows dropped
    (112, 300, 48),    # cross-attention, Sq != Sk; scale 48**-0.5 is
                       # applied in the kernel, not folded into q
    (256, 1024, 64)])  # aligned; the backward folds two key tiles into dQ
def test_fused_attention_matches_xla_and_f32(sq, sk, d, monkeypatch):
    """``attention``'s fused branch (run in TPU interpret mode, the shape
    rule lowered to these sizes) against ``_attn_block`` and an f32 einsum:
    forward and the gradients of q, k and v."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import flash_attn as FA
    b, h = 1, 2
    ks = jax.random.split(jax.random.PRNGKey(sq + sk), 4)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, sk, h, d))
    v = jax.random.normal(ks[2], (b, sk, h, d))
    ct = jax.random.normal(ks[3], (b, sq, h, d))
    qp, kp = jnp.arange(sq), jnp.arange(sk)
    calls, kernel = [], FA.flash_attention

    def spy(*a, **kw):
        calls.append(kw)
        return kernel(*a, **kw)

    monkeypatch.setattr(L, "FUSED_MIN_SK", 0)
    monkeypatch.setattr(L, "FUSED_MIN_SQ", 0)
    monkeypatch.setattr(L.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(FA, "flash_attention", spy)

    def fused(q, k, v):
        return L.attention(q, k, v, qp, kp, causal=False)

    def block(q, k, v):
        return L._attn_block(q, k, v, qp, kp, 1, d ** -0.5, 0, False,
                             jnp.float32)

    def fwd_grads(f):
        out, vjp = jax.vjp(f, q, k, v)
        return [out, *vjp(ct)]

    with pltpu.force_tpu_interpret_mode():
        got = fwd_grads(fused)
    assert calls
    for want in (fwd_grads(block), fwd_grads(_f32_attention)):
        for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=2e-5, err_msg=name)


def _f32_causal_attention(q, k, v):
    """Plain f32 einsum reference for causal self-attention."""
    d, s = q.shape[-1], q.shape[1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * d ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                      precision="highest")


@pytest.mark.parametrize("s,block_q,block_k,d", [
    (384, 128, 128, 192),   # three tiles: below, on and above the diagonal
    (300, 128, 128, 192),   # padded to 384: pad keys after every real query
    (512, 256, 128, 192),   # the diagonal crosses two key tiles per query tile
    (768, 128, 256, 192),   # ... and two query tiles per key tile
    (256, 128, 128, 128)])  # qk 128 / v 128, as in a plain MHA layer
def test_fused_causal_attention_matches_xla_and_f32(s, block_q, block_k, d,
                                                    monkeypatch):
    """``attention``'s causal fused branch (TPU interpret mode, the length
    rule lowered, the tiles shrunk to several per sequence) against
    ``_attn_block`` (causal) and an f32 einsum: forward and the gradients
    of q, k and v.  V's head dim is 128; q's is latent attention's 192 or
    128.  Each case has a shape of its own: the jitted kernel wrapper keeps
    its trace, tiles included, per shape."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import flash_attn as FA
    b, h, dv = 1, 2, 128
    ks = jax.random.split(jax.random.PRNGKey(s + block_q), 4)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    ct = jax.random.normal(ks[3], (b, s, h, dv))
    pos = jnp.arange(s)
    calls, kernel = [], FA.causal_flash_attention

    def spy(*a, **kw):
        calls.append(kw)
        return kernel(*a, **kw)

    monkeypatch.setattr(L, "FUSED_CAUSAL_MIN_S", 0)
    monkeypatch.setattr(L.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(FA, "causal_flash_attention", spy)
    monkeypatch.setattr(FA, "BLOCK_Q", block_q)
    monkeypatch.setattr(FA, "BLOCK_K", block_k)

    def fused(q, k, v):
        return L.attention(q, k, v, pos, pos, causal=True)

    def block(q, k, v):
        return L._attn_block(q, k, v, pos, pos, 1, d ** -0.5, 0, True,
                             jnp.float32)

    def fwd_grads(f):
        out, vjp = jax.vjp(f, q, k, v)
        return [out, *vjp(ct)]

    with pltpu.force_tpu_interpret_mode():
        got = fwd_grads(fused)
    assert calls
    for want in (fwd_grads(block), fwd_grads(_f32_causal_attention)):
        for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-4, atol=2e-5, err_msg=name)


# whisper-tiny: 6 heads of 64, as many K/V heads
WT = dict(heads=6, kv_heads=6, head_dim=64)


@pytest.mark.parametrize("sq,sk", [(1500, 1500), (448, 1500)])
def test_attention_path_fused_for_whisper_tiny_on_tpu(sq, sk):
    """The encoder's self-attention at 1500 frames and the 448 x 1500
    cross-attention take the fused path on TPU."""
    assert L.attention_path(sq, sk, **WT, causal=False, backend="tpu") == (
        "fused", "")


@pytest.mark.parametrize("shape,kw,reason", [
    ((1, 1500), dict(causal=True), "causal"),   # a cached decode
    ((1500, 1500), dict(window=512), "window"),
    ((1500, 1500), dict(backend="cpu"), "backend cpu"),
    ((1500, 1500), dict(kv_heads=2), "grouped kv heads"),
    ((1500, 1500), dict(causal=True), "causal"),
    ((1500, 1500), dict(softmax_dtype=jnp.bfloat16), "softmax dtype"),
    ((1500, 1500), dict(head_dim=128), "head dim"),
    ((1500, 1500), dict(v_head_dim=32), "v head dim"),  # latent attention
    ((1500, 1500), dict(sharded=True), "partitioned mesh axis"),
    ((1500, 4096), dict(), "long"),
    ((448, 448), dict(), "short"),              # whisper's decoder length
    ((1, 1500), dict(), "short")])              # cross-attention in decode
def test_attention_path_falls_back_to_xla(shape, kw, reason):
    """Each condition alone sends a call that is otherwise fused to XLA."""
    args = {**WT, "causal": False, "backend": "tpu", **kw}
    assert L.attention_path(*shape, **args) == ("xla", reason)
    assert L.attention_path(1500, 1500, **{**args, **dict(
        causal=False, window=0, backend="tpu", kv_heads=6, head_dim=64,
        softmax_dtype=jnp.float32, sharded=False, v_head_dim=64)}) == (
            "fused", "")


# kanana-2-30b-a3b's latent attention: 32 heads, qk 128 + 64 rotary, v 128
KANANA = dict(heads=32, kv_heads=32, head_dim=192, v_head_dim=128)


@pytest.mark.parametrize("shape,kw,want", [
    ((4096, 4096), dict(), ("fused", "")),      # kanana's training call
    ((2048, 2048), dict(), ("fused", "")),      # FUSED_CAUSAL_MIN_S
    ((8192, 8192), dict(), ("fused", "")),      # MAX_CAUSAL_S
    ((448, 448), dict(), ("xla", "causal")),    # whisper's decoder length
    ((1, 4096), dict(), ("xla", "causal")),     # a cached decode
    ((4096, 4096), dict(kv_heads=8), ("xla", "causal")),
    ((4096, 4096), dict(window=1024), ("xla", "causal")),
    ((4096, 4096), dict(softmax_dtype=jnp.bfloat16), ("xla", "causal")),
    ((4096, 4096), dict(sharded=True), ("xla", "causal")),
    ((16384, 16384), dict(), ("xla", "causal")),
    ((4096, 4096), dict(backend="cpu"), ("xla", "backend cpu"))])
def test_attention_path_causal(shape, kw, want):
    """A causal call is fused only as long self-attention without a cache,
    window, grouped K/V heads, low-precision softmax or partitioned axis;
    V's own head dim and qk 192 do not keep it from the causal kernel."""
    args = {**KANANA, "causal": True, "backend": "tpu", **kw}
    assert L.attention_path(*shape, **args) == want


def _decode_call(q, k, v):
    """One query against a 1500-slot KV cache, as ``attn_apply`` calls it."""
    return L.attention(q[:, :1], k, v, jnp.full((1,), 1499), jnp.arange(1500))


@pytest.mark.parametrize("backend,kw,fused", [
    ("tpu", dict(causal=False), True),
    ("tpu", dict(causal=True), False),
    ("tpu", dict(causal=False, window=512), False),
    ("tpu", dict(decode=True), False),
    ("tpu", dict(causal=False, kv=2), False),
    ("cpu", dict(causal=False), False)])
def test_attention_traces_the_path_it_names(backend, kw, fused, monkeypatch):
    """``attention`` traces a Pallas call exactly where ``attention_path``
    says fused: never on the CPU, nor for causal, cached, windowed or
    grouped calls (those trace the XLA path as before)."""
    monkeypatch.setattr(L.jax, "default_backend", lambda: backend)
    kv = kw.pop("kv", 6)
    q = jax.ShapeDtypeStruct((8, 1500, 6, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((8, 1500, kv, 64), jnp.bfloat16)
    pos = jnp.arange(1500)
    f = (_decode_call if kw.pop("decode", False)
         else lambda q, k, v: L.attention(q, k, v, pos, pos, **kw))
    assert ("pallas_call" in str(jax.make_jaxpr(f)(q, k, k))) == fused


def test_whisper_tiny_attention_sites(monkeypatch):
    """``record_attention_paths`` collects each distinct call of
    whisper-tiny's training loss as traced on TPU at batch 8 x 448: the
    encoder's and the cross-attention's 1500-key calls fused, the causal
    decoder on XLA."""
    from repro import configs
    from repro.models.model import build_model
    monkeypatch.setattr(L.jax, "default_backend", lambda: "tpu")
    cfg = configs.get_config("whisper-tiny")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = configs.input_specs(cfg, configs.InputShape("train", "train", 448, 8))
    with L.record_attention_paths() as calls:
        jax.make_jaxpr(jax.grad(lambda p, b: model.loss(p, b)[0]))(
            params, batch)
    one = lambda s, causal, path, reason: dict(          # noqa: E731
        q=[8, s[0], 6, 64], k=[8, s[1], 6, 64], causal=causal, window=0,
        path=path, reason=reason)
    assert calls == [one((1500, 1500), False, "fused", ""),
                     one((448, 448), True, "xla", "causal"),
                     one((448, 1500), False, "fused", "")]


def test_kanana_attention_sites(monkeypatch):
    """Every latent attention call of kanana-2-30b-a3b's training loss, as
    traced on TPU at 1 x 4,096 tokens (the dense layer and the scanned MoE
    layers make one distinct call), takes the causal fused path: the
    ``attn_path`` that ``train.main`` logs."""
    from repro import configs
    from repro.models.model import build_model
    monkeypatch.setattr(L.jax, "default_backend", lambda: "tpu")
    cfg = configs.get_config("kanana-2-30b-a3b")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = configs.input_specs(cfg,
                                configs.InputShape("train", "train", 4096, 1))
    with L.record_attention_paths() as calls:
        jax.make_jaxpr(jax.grad(lambda p, b: model.loss(p, b)[0]))(
            params, batch)
    assert calls == [dict(q=[1, 4096, 32, 192], k=[1, 4096, 32, 192],
                          causal=True, window=0, path="fused", reason="")]


# -- rope -----------------------------------------------------------------------

def test_rope_preserves_norm():
    x = jax.random.normal(KEY, (2, 8, 4, 16))
    pos = jnp.arange(8)[None].repeat(2, 0)
    y = L.rope(x, pos)
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(y, axis=-1)),
                               np.asarray(jnp.linalg.norm(x, axis=-1)),
                               rtol=1e-5)


def test_rope_relative_property():
    """<rope(q,i), rope(k,j)> depends only on i-j."""
    d = 16
    q = jax.random.normal(KEY, (1, 1, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, d))

    def dot(i, j):
        qi = L.rope(q, jnp.array([[i]]))
        kj = L.rope(k, jnp.array([[j]]))
        return float(jnp.sum(qi * kj))

    assert dot(5, 3) == pytest.approx(dot(9, 7), rel=1e-4)
    assert dot(5, 3) != pytest.approx(dot(5, 4), rel=1e-3)


# -- chunked xent ----------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(2, 40), st.integers(4, 64), st.integers(0, 1000))
def test_chunked_xent_matches_naive(s, v, seed):
    b, d = 2, 8
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, s, d))
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), (d, v))
    labels = jax.random.randint(jax.random.PRNGKey(seed + 2), (b, s), 0, v)
    # mask a few labels
    labels = labels.at[:, 0].set(-1)
    out = L.chunked_xent(x, w, labels, chunk=16)
    logits = x @ w
    logp = jax.nn.log_softmax(logits, -1)
    gold = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    valid = labels >= 0
    ref = -jnp.sum(gold * valid) / jnp.sum(valid)
    np.testing.assert_allclose(float(out), float(ref), rtol=1e-4)


# -- SSM / RG-LRU: chunked scan == step-by-step recurrence -------------------------

def test_mamba_chunked_equals_decode_chain():
    d, b, s = 32, 2, 64
    p = SSM.init_mamba(KEY, d)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (b, s, d))
    full, _ = SSM.mamba_apply(p, x, chunk=16)
    st_ = SSM.init_mamba_state(b, d)
    outs = []
    for t in range(s):
        y, st_ = SSM.mamba_apply(p, x[:, t:t + 1], state=st_)
        outs.append(y)
    step = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(step), rtol=2e-3,
                               atol=2e-4)


def test_rglru_chunked_equals_decode_chain():
    d, lw, b, s = 32, 32, 2, 64
    p = RG.init_rglru(KEY, d, lw)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (b, s, d))
    full, _ = RG.rglru_apply(p, x, chunk=16)
    st_ = RG.init_rglru_state(b, lw)
    outs = []
    for t in range(s):
        y, st_ = RG.rglru_apply(p, x[:, t:t + 1], state=st_)
        outs.append(y)
    step = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(step), rtol=2e-3,
                               atol=2e-4)


def test_rglru_stability():
    """|a_t| < 1: long sequences cannot blow up."""
    d = lw = 16
    p = RG.init_rglru(KEY, d, lw)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 512, d))
    y, _ = RG.rglru_apply(p, x, chunk=64)
    assert np.isfinite(np.asarray(y)).all()
    assert float(jnp.max(jnp.abs(y))) < 1e3


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4), st.integers(0, 100))
def test_mamba_state_invariant_chunks(nc, seed):
    """Property: output independent of the chunk size used for the scan."""
    d, b = 16, 1
    s = 32 * nc
    p = SSM.init_mamba(jax.random.PRNGKey(seed), d)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 1), (b, s, d))
    a, _ = SSM.mamba_apply(p, x, chunk=8)
    c, _ = SSM.mamba_apply(p, x, chunk=s)       # single chunk
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-3,
                               atol=2e-4)
