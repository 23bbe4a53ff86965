"""Fused dequant-accumulate for the bucketed pipelined ring.

Generalizes ``ring_sum.py`` (worker-stacked [N, M, C] payloads, all present
at once) to the [buckets, shard] layout of the bucketed wire: at each ring
hop exactly ONE stacked payload — ``q [B, R, C] int8`` levels plus
``scales [B, R, 1] f32`` per-row scales, one pair per bucket — arrives and
is folded into the resident f32 accumulator in a single pass:

    acc[b] += q[b] * scales[b]          (one HBM read of q/scales/acc,
                                         one HBM write of acc)

``core/dist.bucket_ring_reduce`` calls this once per hop *while the next
hop's collective-permute is already in flight* (the double-buffered carry),
so on real hardware the dequant hides under the wire latency.  On CPU the
kernels run in interpret mode, same as the rest of ``kernels/``.

``bucket_ring_sum`` is the all-at-once variant ([N, B, R, C] stacks, the
direct generalization of ``ring_sum.ring_sum``) used as the gather-style
oracle in tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _acc_kernel(acc_ref, q_ref, s_ref, o_ref):
    o_ref[...] = acc_ref[...] + (q_ref[...].astype(jnp.float32)
                                 * s_ref[...].astype(jnp.float32))


# Scoped-VMEM budget for one block's buffers (acc, q, scales, out); the
# pipeline double-buffers them, so twice this must stay under v5e's 16 MiB
# default scoped limit.
VMEM_BLOCK_BYTES = 4 << 20


def default_block_rows(c: int) -> int:
    """Rows per block for a [.., R, c] payload: a multiple of 32 (int8
    sublane tiling) whose acc + q + scales + out buffers fit
    ``VMEM_BLOCK_BYTES``.  The [br, 1] f32 scales block pads to 128 lanes
    in VMEM, so it costs 512 B per row."""
    per_row = c * (4 + 1 + 4) + 128 * 4
    return max(32, VMEM_BLOCK_BYTES // per_row // 32 * 32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def bucket_acc(acc: jax.Array, q: jax.Array, scales: jax.Array, *,
               block_rows: Optional[int] = None,
               interpret: bool = True) -> jax.Array:
    """One ring-hop fold: ``acc + dequant(q, scales)``.

    acc [B, R, C] f32, q [B, R, C] int8, scales [B, R, 1] f32 (per-row,
    matching ``core/codec.row_squant_encode`` vmapped over buckets).
    ``block_rows``: rows per grid block; None sizes it to the VMEM budget
    (``default_block_rows``).  The grid is ``cdiv(R, block_rows)`` per
    bucket: the fold is elementwise, so the ragged last block needs no mask
    (its out-of-range rows are never written back).

    In interpret mode with default blocking the grid is dropped entirely
    (one cell over the whole stack): each interpret-mode grid cell costs a
    dispatch, which at B x (R/br) cells per hop inside the scan ring
    dominated the CPU step (~8x this kernel, measured).  The result is
    bitwise identical; on real hardware the grid is what tiles the payload
    through VMEM, so it stays.
    """
    b, r, c = q.shape
    # named_scope: metadata-only tag so the kernel launch is findable on
    # the profiler timeline (repro.obs spans/Perfetto capture)
    if interpret and block_rows is None:
        with jax.named_scope("bucket_acc"):
            return pl.pallas_call(
                _acc_kernel,
                out_shape=jax.ShapeDtypeStruct((b, r, c), jnp.float32),
                interpret=interpret,
            )(acc, q, scales)
    br = min(r, default_block_rows(c) if block_rows is None else block_rows)
    with jax.named_scope("bucket_acc"):
        return pl.pallas_call(
            _acc_kernel,
            grid=(b, pl.cdiv(r, br)),
            in_specs=[
                pl.BlockSpec((None, br, c), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, br, c), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, br, 1), lambda i, j: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec((None, br, c), lambda i, j: (i, j, 0)),
            out_shape=jax.ShapeDtypeStruct((b, r, c), jnp.float32),
            interpret=interpret,
        )(acc, q, scales)


def bucket_acc_ref(acc: jax.Array, q: jax.Array, scales: jax.Array):
    """Pure-jnp oracle for ``bucket_acc``."""
    return acc + q.astype(jnp.float32) * scales.astype(jnp.float32)


def _sum_kernel(q_ref, s_ref, o_ref, *, n: int):
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for i in range(n):                      # N is small (workers); unrolled
        acc += q_ref[i].astype(jnp.float32) * s_ref[i].astype(jnp.float32)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def bucket_ring_sum(q: jax.Array, scales: jax.Array, *,
                    interpret: bool = True) -> jax.Array:
    """All-at-once reduce: q [N, B, R, C] int8, scales [N, B, R, 1] f32 ->
    [B, R, C] f32.  ``ring_sum.ring_sum`` generalized to the bucketed
    layout; the hop-by-hop ``bucket_acc`` chain must match it bitwise."""
    n, b, r, c = q.shape
    return pl.pallas_call(
        functools.partial(_sum_kernel, n=n),
        grid=(b,),
        in_specs=[
            pl.BlockSpec((n, 1, r, c), lambda i: (0, i, 0, 0)),
            pl.BlockSpec((n, 1, r, 1), lambda i: (0, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, r, c), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, c), jnp.float32),
        interpret=interpret,
    )(q, scales)


def bucket_ring_sum_ref(q: jax.Array, scales: jax.Array) -> jax.Array:
    return jnp.sum(q.astype(jnp.float32) * scales.astype(jnp.float32),
                   axis=0)
