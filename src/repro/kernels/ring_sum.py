"""Fused dequant-accumulate across N compressed worker payloads.

The Artemis aggregation hot loop: after the int8 ring delivers every worker's
(levels, scales), each device computes  sum_i q_i * scale_i  — unfused this
reads N int8 buffers + writes N-1 f32 partials; fused it is one pass:
VMEM-resident accumulator, one f32 write.

Layout: q [N, M, C] int8, scales [N, M, 1] f32 (per-row, matching
``core/codec.row_squant_encode``), output [M, C] f32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ring_sum_kernel(q_ref, s_ref, o_ref, *, n: int):
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for i in range(n):                       # N is small (workers); unrolled
        acc += q_ref[i].astype(jnp.float32) * s_ref[i].astype(jnp.float32)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ring_sum(q: jax.Array, scales: jax.Array, *, block=(256, 256),
             interpret: bool = True) -> jax.Array:
    """q: [N, M, C] int8 (M, C block-multiples), scales: [N, M, 1] f32."""
    n, m, c = q.shape
    bm, bc = block
    assert m % bm == 0 and c % bc == 0, (q.shape, block)
    return pl.pallas_call(
        functools.partial(_ring_sum_kernel, n=n),
        grid=(m // bm, c // bc),
        in_specs=[
            pl.BlockSpec((n, bm, bc), lambda i, j: (0, i, j)),
            pl.BlockSpec((n, bm, 1), lambda i, j: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, c), jnp.float32),
        interpret=interpret,
    )(q, scales)


def ring_sum_ref(q: jax.Array, scales: jax.Array) -> jax.Array:
    """Pure-jnp oracle."""
    return jnp.sum(q.astype(jnp.float32) * scales.astype(jnp.float32), axis=0)
