"""Shared neural-net layers: norms, RoPE, attention, MLPs.

Everything is functional: ``init_*(key, ...) -> params`` and
``apply(params, x, ...) -> y``.  Attention takes one of four paths
(``attention`` says which and when): a fused Pallas flash attention on
TPU, causal or not, a scan over query chunks, or one masked block.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attn as FA


def _init(key, shape, scale=None, dtype=jnp.float32):
    scale = (1.0 / jnp.sqrt(shape[0])) if scale is None else scale
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, eps=1e-6):
    # f32 ACCUMULATION via the reduction dtype (not by converting x: that
    # would make the whole-tensor backward cotangent f32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True, dtype=jnp.float32)
    return (x * jax.lax.rsqrt(var + eps).astype(x.dtype)) * (1.0 + gamma.astype(x.dtype))


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x, positions, theta=10000.0):
    """x: [..., S, H, D]; positions: [..., S] int32."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq          # [..., S, half]
    ang = ang[..., None, :]                                        # [..., S, 1, half]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA, causal, optional sliding window): fused, q-chunked or whole
# ---------------------------------------------------------------------------

def _mask(q_pos, k_pos, window: int, causal: bool):
    """[Sq, Sk] mask: causal, and |q-k| < window when window > 0."""
    if causal:
        m = q_pos[:, None] >= k_pos[None, :]
    else:
        # still exclude invalid (sentinel-position) cache slots
        m = k_pos[None, :] < _INVALID_POS
        m = jnp.broadcast_to(m, (q_pos.shape[0], k_pos.shape[0]))
    if window > 0:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


_INVALID_POS = jnp.iinfo(jnp.int32).max // 2

# Shape rule of the fused path: whisper-tiny's train step on a v5e was
# fastest with its 1500-key calls (encoder self- and cross-attention) fused
# (chip runs in PERF.md, section 6); decode calls (one query) stay on XLA
FUSED_MIN_SK = 1024
FUSED_MIN_SQ = 128
# causal calls: at kanana's 4,096 tokens (qk 192, v 128) the causal kernel
# took 7.3 ms forward and backward against 27.0 ms for the q-chunked XLA
# path on a v5e; whisper-tiny's 448-token decoder lost 8% of the b64 step
# fused (PERF.md, section 6 and section 7 row 27); lengths in between are
# not measured, and shorter causal calls stay on XLA
FUSED_CAUSAL_MIN_S = 2048


def attention_path(sq: int, sk: int, heads: int, kv_heads: int,
                   head_dim: int, *, causal: bool, window: int = 0,
                   softmax_dtype=jnp.float32, sharded: bool = False,
                   backend: str, v_head_dim: Optional[int] = None) -> tuple:
    """The path one ``attention`` call takes: ``("fused", "")`` or
    ``("xla", reason)``, from what the call can see.

    A causal call is fused only as self-attention without a cache
    (``sq == sk``; a cached decode has one query) from
    ``FUSED_CAUSAL_MIN_S`` to ``MAX_CAUSAL_S`` tokens, with no window, one
    K/V head per query head, an f32 softmax and no partitioned axis; every
    other causal call stays on XLA with reason ``"causal"``.  ``sharded``:
    a mesh axis that GSPMD partitions (not manual, size > 1) is in scope,
    and a Mosaic kernel cannot be partitioned.  ``v_head_dim``: V's head
    dim when it differs from q's (latent attention); only the causal
    kernel takes one.
    """
    if backend != "tpu":
        return "xla", f"backend {backend}"
    if causal:
        fused = (not 0 < window < sk and sq == sk and heads == kv_heads
                 and jnp.dtype(softmax_dtype) == jnp.float32 and not sharded
                 and FUSED_CAUSAL_MIN_S <= sk <= FA.MAX_CAUSAL_S)
        return ("fused", "") if fused else ("xla", "causal")
    if v_head_dim is not None and v_head_dim != head_dim:
        return "xla", "v head dim"
    if 0 < window < max(sq, sk):
        return "xla", "window"
    if heads != kv_heads:
        return "xla", "grouped kv heads"
    if jnp.dtype(softmax_dtype) != jnp.float32:
        return "xla", "softmax dtype"
    if head_dim >= FA.LANES:
        return "xla", "head dim"
    if sharded:
        return "xla", "partitioned mesh axis"
    if sk > FA.MAX_SK or sq > FA.MAX_SQ:
        return "xla", "long"
    if sk < FUSED_MIN_SK or sq < FUSED_MIN_SQ:
        return "xla", "short"
    return "fused", ""


def _partitioned_axes(mesh) -> bool:
    return any(t != jax.sharding.AxisType.Manual and n > 1
               for t, n in zip(mesh.axis_types, mesh.axis_sizes))


_traced_paths: Optional[list] = None     # open in record_attention_paths


@contextlib.contextmanager
def record_attention_paths():
    """Yield a list that collects each distinct ``attention`` call traced
    inside the block: its q and k shapes, ``causal``, ``window``, and the
    path and reason ``attention_path`` gave it."""
    global _traced_paths
    outer, _traced_paths = _traced_paths, []
    try:
        yield _traced_paths
    finally:
        _traced_paths = outer


def attention(q, k, v, q_pos, k_pos, *, window: int = 0, q_chunk: int = 512,
              causal: bool = True, softmax_dtype=jnp.float32):
    """q: [B, Sq, H, D]; k: [B, Sk, KV, D]; v: [B, Sk, KV, Dv] (Dv may
    differ from D, as in latent attention); grouped-query attention.

    Four paths, chosen from shapes and flags (``attention_path``):

    - fused (TPU, not causal, no window below the lengths, KV == H, f32
      softmax, head dim below 128, ``FUSED_MIN_SK`` to ``MAX_SK`` keys,
      at least ``FUSED_MIN_SQ`` queries): ``flash_attn.flash_attention``;
      score tiles stay in VMEM, and no [B, H, Sq, Sk] tensor reaches HBM,
      forward or backward;
    - fused causal (TPU, causal self-attention without a cache or window,
      KV == H, f32 softmax, ``FUSED_CAUSAL_MIN_S`` to ``MAX_CAUSAL_S``
      tokens, any head dims): ``flash_attn.causal_flash_attention``, which
      also never computes the key tiles above the diagonal; it takes the
      positions to be ``0..S-1``, as training's are;
    - q-chunked (``Sq > q_chunk`` and ``Sq % q_chunk == 0``): a scan over
      query chunks, each against the full K/V with a position mask; peak
      score memory is [B, H, q_chunk, Sk] in ``softmax_dtype``; under
      autodiff a chunk keeps only its matmul outputs (the scores in q's
      dtype) for the backward and recomputes its softmax;
    - whole block (every other call, e.g. whisper's 1500 frames on the
      CPU): one masked block; peak score memory is [B, H, Sq, Sk].
    """
    b, sq, h, d = q.shape
    kv, dv = k.shape[2], v.shape[-1]
    assert h % kv == 0
    rep = h // kv
    scale = d ** -0.5

    mesh = jax.sharding.get_abstract_mesh()
    path, reason = attention_path(
        sq, k.shape[1], h, kv, d, causal=causal, window=window,
        softmax_dtype=softmax_dtype, sharded=_partitioned_axes(mesh),
        backend=jax.default_backend(), v_head_dim=dv)
    if _traced_paths is not None:
        call = {"q": list(q.shape), "k": list(k.shape), "causal": causal,
                "window": window, "path": path, "reason": reason}
        if call not in _traced_paths:
            _traced_paths.append(call)
    if path == "fused":
        fused = FA.causal_flash_attention if causal else FA.flash_attention
        return fused(q, k, v, scale=scale)

    if sq <= q_chunk or sq % q_chunk != 0:
        return _attn_block(q, k, v, q_pos, k_pos, rep, scale, window, causal,
                           softmax_dtype)

    n_chunks = sq // q_chunk
    qs = q.reshape(b, n_chunks, q_chunk, h, d).transpose(1, 0, 2, 3, 4)
    ps = q_pos.reshape(b, n_chunks, q_chunk).transpose(1, 0, 2) if q_pos.ndim == 2 \
        else q_pos.reshape(n_chunks, q_chunk)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.dots_saveable)
    def body(_, qc_pc):
        qc, pc = qc_pc
        o = _attn_block(qc, k, v, pc, k_pos, rep, scale, window, causal,
                        softmax_dtype)
        return None, o

    _, out = jax.lax.scan(body, None, (qs, ps))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dv)


def _attn_block(q, k, v, q_pos, k_pos, rep, scale, window, causal, softmax_dtype):
    b, sq, h, d = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, sq, kv, rep, d)
    # grouped attention without materializing repeated K/V
    scores = jnp.einsum("bqkrd,bskd->bkrqs", qg, k).astype(softmax_dtype) * scale
    qp = q_pos if q_pos.ndim == 2 else jnp.broadcast_to(q_pos, (b,) + q_pos.shape)
    kp = k_pos if k_pos.ndim == 2 else jnp.broadcast_to(k_pos, (b,) + k_pos.shape)
    mask = jax.vmap(functools.partial(_mask, window=window, causal=causal))(qp, kp)
    scores = jnp.where(mask[:, None, None], scores, jnp.finfo(softmax_dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrqs,bskd->bqkrd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


def init_attn(key, d_model, n_heads, n_kv, head_dim, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {
        "wq": _init(ks[0], (d_model, n_heads * head_dim), dtype=dtype),
        "wk": _init(ks[1], (d_model, n_kv * head_dim), dtype=dtype),
        "wv": _init(ks[2], (d_model, n_kv * head_dim), dtype=dtype),
        "wo": _init(ks[3], (n_heads * head_dim, d_model), dtype=dtype),
    }


def init_attn_cache(b, cache_len, n_kv, head_dim, dtype=jnp.bfloat16,
                    v_head_dim=None):
    """Ring-buffer KV cache. ``pos`` holds the absolute position stored in
    each slot (sentinel = empty); sliding-window archs use cache_len=window."""
    return {
        "k": jnp.zeros((b, cache_len, n_kv, head_dim), dtype),
        "v": jnp.zeros((b, cache_len, n_kv, v_head_dim or head_dim), dtype),
        "pos": jnp.full((cache_len,), _INVALID_POS, jnp.int32),
    }


def attn_apply(p, x, positions, *, n_heads, n_kv, head_dim, window=0,
               causal=True, rope_theta=10000.0, q_chunk=512,
               softmax_dtype=jnp.float32, cache=None,
               pos=None, cross_kv=None):
    """Self- or cross-attention.

    cache: optional ring-buffer cache (decode): the new k/v is written at slot
    ``pos % cache_len`` and attention runs against the whole cache using the
    absolute positions stored per slot.
    cross_kv: optional precomputed (k, v, k_pos) for encoder-decoder cross-attn.
    """
    b, s, _ = x.shape
    q = (x @ p["wq"]).reshape(b, s, n_heads, head_dim)
    if cross_kv is not None:
        ck, cv, k_pos = cross_kv
        out = attention(q, ck, cv, positions, k_pos, window=0, causal=False,
                        q_chunk=q_chunk, softmax_dtype=softmax_dtype)
        return out.reshape(b, s, -1) @ p["wo"], None

    k = (x @ p["wk"]).reshape(b, s, n_kv, head_dim)
    v = (x @ p["wv"]).reshape(b, s, n_kv, head_dim)
    if rope_theta:
        q = rope(q, positions, rope_theta)
        k = rope(k, positions, rope_theta)
    if cache is not None:
        cache_len = cache["k"].shape[1]
        slot = pos % cache_len
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), slot, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), slot, 1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], jnp.full((1,), pos, jnp.int32), slot, 0)
        out = attention(q, ck.astype(q.dtype), cv.astype(q.dtype), positions,
                        cpos, window=window, q_chunk=q_chunk,
                        softmax_dtype=softmax_dtype)
        return out.reshape(b, s, -1) @ p["wo"], {"k": ck, "v": cv, "pos": cpos}
    out = attention(q, k, v, positions, positions, window=window,
                    causal=causal, q_chunk=q_chunk, softmax_dtype=softmax_dtype)
    return out.reshape(b, s, -1) @ p["wo"], None


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, activation, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"w_up": _init(ks[0], (d_model, d_ff), dtype=dtype),
         "w_down": _init(ks[1], (d_ff, d_model), dtype=dtype)}
    if activation == "silu":                  # gated (SwiGLU)
        p["w_gate"] = _init(ks[2], (d_model, d_ff), dtype=dtype)
    return p


def mlp_apply(p, x, activation):
    if activation == "silu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif activation == "relu2":               # squared ReLU (nemotron)
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    elif activation == "gelu":
        h = jax.nn.gelu(x @ p["w_up"])
    else:
        raise ValueError(activation)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Chunked cross-entropy (never materializes [B, S, V])
# ---------------------------------------------------------------------------

def chunked_xent(x, w_out, labels, *, chunk=512):
    """x: [B, S, d], w_out: [d, V], labels: [B, S] (-1 = ignore) -> mean NLL.

    Scans over sequence chunks so peak logits memory is [B, chunk, V].
    """
    b, s, d = x.shape
    if s <= chunk:
        n_tok, nll = _xent_block(x, w_out, labels)
        return nll / jnp.maximum(n_tok, 1.0)
    if s % chunk:                       # pad with ignored labels
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        s += pad
    n = s // chunk
    xs = x.reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, n, chunk).transpose(1, 0, 2)

    def body(acc, xl):
        xc, lc = xl
        n_tok, nll = _xent_block(xc, w_out, lc)
        return (acc[0] + n_tok, acc[1] + nll), None

    (tot_tok, tot_nll), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (xs, ls))
    return tot_nll / jnp.maximum(tot_tok, 1.0)


def _xent_block(x, w_out, labels):
    logits = (x @ w_out).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               axis=-1)[..., 0]
    valid = (labels >= 0).astype(jnp.float32)
    return jnp.sum(valid), jnp.sum((logz - gold) * valid)
