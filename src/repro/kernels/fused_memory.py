"""Fused Artemis worker-side kernel.

Computes, in ONE pass over HBM (reads g, h, u; writes q, scale, h_new):

    delta  = g - h
    (q,sc) = squant_encode(delta)           # one scale per tile
    h_new  = h + alpha * dequant(q, sc)     # memory update (Algorithm 1, line 4)

Unfused this costs 3 reads + 2 writes of gradient-sized buffers plus the
intermediate ``delta`` roundtrip; fused it is 3 reads + 2 writes total with
delta/levels kept in VMEM.

Layout: a stack of T tiles ``[T, bm, bn]``, one grid step and one scale per
tile.  Each block spans a whole tile, so its last two dims equal the
array's and the TPU compiler accepts any (bm, bn): the simulator's
``[N, 1, d]`` (one tile per worker row) as well as ``ops``' (256, 256)
tiles.  Scales come back as ``[T, 1, 1]`` for the same reason.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_kernel(g_ref, h_ref, u_ref, alpha_ref, q_ref, scale_ref, h_new_ref,
                  *, s: int):
    g = g_ref[...]
    h = h_ref[...]
    delta = (g - h).astype(jnp.float32)
    sq = jnp.sum(delta * delta, axis=1, keepdims=True)
    norm = jnp.sqrt(jnp.sum(sq, axis=0, keepdims=True))          # (1, 1)
    # non-finite tile => zero scale: wire payload decodes to 0 and the memory
    # update below degrades to h_new = h (matches squant.py's clamp)
    scale = jnp.where(jnp.isfinite(norm), norm / s, 0.0)
    scale_ref[...] = scale
    safe = jnp.where(norm > 0, norm, 1.0)
    r = jnp.abs(delta) / safe * s
    low = jnp.floor(r)
    psi = low + (u_ref[...].astype(jnp.float32) < (r - low)).astype(jnp.float32)
    q = jnp.sign(delta) * psi
    q_ref[...] = q.astype(jnp.int8)
    alpha = alpha_ref[...].astype(g.dtype)
    h_new_ref[...] = h + alpha * (q.astype(g.dtype) * scale.astype(g.dtype))


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def fused_memory_update(g: jax.Array, h: jax.Array, u: jax.Array,
                        alpha: jax.Array, *, s: int = 1,
                        interpret: bool = True):
    """g, h, u: [T, bm, bn] tile stacks.  Returns (q int8 [T, bm, bn],
    scales f32 [T, 1, 1], h_new [T, bm, bn])."""
    assert 1 <= s <= 126, s
    t, bm, bn = g.shape
    alpha = jnp.asarray(alpha, jnp.float32).reshape(1, 1)
    tile = pl.BlockSpec((None, bm, bn), lambda i: (i, 0, 0))
    one = pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_fused_kernel, s=s),
        grid=(t,),
        in_specs=[tile, tile, tile, pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[tile, one, tile],
        out_shape=[
            jax.ShapeDtypeStruct(g.shape, jnp.int8),
            jax.ShapeDtypeStruct((t, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct(g.shape, g.dtype),
        ],
        interpret=interpret,
    )(g, h, u, alpha)
