"""Multi-head latent attention (DeepSeek-V2/V3 with no query LoRA).

Per token ``x``:

* ``q = x Wq`` -> [H, nope + rope], split into ``q_nope`` and ``q_pe``;
* ``[c, k_pe] = x Wkv_a`` (``kv_rank`` + ``rope``), ``c = RMSNorm(c)``;
* ``[k_nope, v] = c Wkv_b`` -> [H, nope + v_dim];
* rotary on ``q_pe`` and on ``k_pe``, one rotary key shared by all heads;
* attention of ``[q_nope, q_pe]`` against ``[k_nope, k_pe]`` (scale
  ``(nope + rope)^-0.5``) over ``v``, then ``Wo`` [H * v_dim, d].

The keys and values are expanded per head before ``layers.attention``
(training and prefill compute them for every token anyway); a decode cache
holds them expanded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L


def init_mla(key, d_model, n_heads, nope, rope_dim, v_dim, kv_rank,
             dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    return {
        "wq": L._init(ks[0], (d_model, n_heads * (nope + rope_dim)),
                      dtype=dtype),
        "wkv_a": L._init(ks[1], (d_model, kv_rank + rope_dim), dtype=dtype),
        "kv_norm": jnp.zeros((kv_rank,), jnp.float32),
        "wkv_b": L._init(ks[2], (kv_rank, n_heads * (nope + v_dim)),
                         dtype=dtype),
        "wo": L._init(ks[3], (n_heads * v_dim, d_model), dtype=dtype),
    }


def mla_apply(p, x, positions, *, n_heads, nope, rope_dim, v_dim,
              rope_theta, eps, causal=True, q_chunk=512,
              softmax_dtype=jnp.float32, cache=None, pos=None):
    """x: [B, S, d] -> ([B, S, d], new cache or None).  ``cache``: a
    ring-buffer cache (``layers.init_attn_cache`` with K of width
    ``nope + rope_dim`` and V of ``v_dim``) written at ``pos``."""
    with jax.named_scope("mla"):
        b, s, _ = x.shape
        kv_rank = p["kv_norm"].shape[0]
        q = (x @ p["wq"]).reshape(b, s, n_heads, nope + rope_dim)
        kva = x @ p["wkv_a"]
        c = L.rms_norm(kva[..., :kv_rank], p["kv_norm"], eps)
        kv = (c @ p["wkv_b"]).reshape(b, s, n_heads, nope + v_dim)
        q_pe = L.rope(q[..., nope:], positions, rope_theta)
        k_pe = L.rope(kva[..., None, kv_rank:], positions, rope_theta)
        q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe, (b, s, n_heads, rope_dim))], axis=-1)
        v = kv[..., nope:]
        if cache is not None:
            slot = pos % cache["k"].shape[1]
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, 1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, 1)
            cpos = jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], jnp.full((1,), pos, jnp.int32), slot, 0)
            out = L.attention(q, ck.astype(q.dtype), cv.astype(q.dtype),
                              positions, cpos, q_chunk=q_chunk,
                              softmax_dtype=softmax_dtype)
            new_cache = {"k": ck, "v": cv, "pos": cpos}
        else:
            out = L.attention(q, k, v, positions, positions, causal=causal,
                              q_chunk=q_chunk, softmax_dtype=softmax_dtype)
            new_cache = None
        return out.reshape(b, s, n_heads * v_dim) @ p["wo"], new_cache
