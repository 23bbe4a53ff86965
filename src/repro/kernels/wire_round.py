"""One-pass Artemis round on the bucketed mesh wire (``core/dist.py``).

Two kernels over the bucket stack, each block holding whole rows, so each
row's norm is in VMEM when the row is quantized and no pass over HBM only
takes norms:

* ``uplink_round`` — the worker's side (paper Algorithm 1, lines 3-4):

      delta  = (g - h) * active
      levels, scales = row_squant(delta)        # scales *= active (PP2)
      h_new  = h + alpha * levels * scales

  one read of g and h, one write of the int8 levels, the f32 row scales
  and h_new (over h).
* ``server_round`` — the server's memory and the zero-byte downlink:

      ghat     = hbar + dhat_sum / (p n)
      hbar_new = hbar + alpha * dhat_sum / n
      ghat     = decode(row_squant(ghat))       # the shared downlink key

  one read of hbar and the ring's sum, one write of hbar_new and the
  decoded ghat (over the two).

The rounding uniforms are drawn in the kernel and are bit for bit those of
``jax.random.uniform(fold_in(key, b), [R, C])`` under the partitionable
threefry: element ``(r, c)`` of bucket ``b`` hashes the counter
``(0, r * C + c)`` with the bucket's key, the two output words XORed, and
``((bits >> 9) | 0x3F800000) - 1`` as float.  So the levels equal
``codec.row_squant_encode``'s per bucket, and the f32 arithmetic is the XLA
chain's, op for op; only the number of HBM passes changes.

The kernels take ``[B, R, C]`` stacks and work on their ``[R, B, C]``
view (``rows_major``): a block is ``br`` rows of every bucket, walked in
slices of a few rows so the hash's intermediates stay in vector
registers, and the buckets' key words ride in as ``[B, C]`` tables.  On
the CPU the kernels run in interpret mode as one block and one slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.random import threefry2x32_p

# Scoped-VMEM budget for one block's buffers; the pipeline double-buffers
# them, so twice this must stay under v5e's 16 MiB default scoped limit.
VMEM_BLOCK_BYTES = 4 << 20
# Elements per inner slice: eight f32 vector registers.
SLICE_ELEMS = 8 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def slice_rows(b: int, c: int) -> int:
    """Bucket rows ``r`` per inner slice of a ``[R, b, c]`` block."""
    return max(1, SLICE_ELEMS // (_round_up(b, 8) * _round_up(c, 128)))


def default_block_rows(b: int, c: int, elem_bytes: int) -> int:
    """Rows ``r`` per block of ``[R, b, c]`` operands moving ``elem_bytes``
    bytes per element over all of a kernel's inputs and outputs: a multiple
    of ``slice_rows`` whose buffers fit ``VMEM_BLOCK_BYTES``.  A
    ``[.., b, 1]`` f32 scales block pads to 128 lanes in VMEM."""
    per_row = b * c * elem_bytes + _round_up(b, 8) * 128 * 4
    t = slice_rows(b, c)
    return max(t, VMEM_BLOCK_BYTES // per_row // t * t)


def _uniform(k1, k2, row0, shape):
    """``jax.random.uniform`` of each bucket's ``[R, C]`` draw, at rows
    ``row0 + [0, shape[0])`` of a ``[R, B, C]`` slice; ``k1``, ``k2`` are
    the buckets' key words, ``[B, C]``."""
    c = shape[-1]
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    lo = jax.lax.bitcast_convert_type(rows * c + cols, jnp.uint32)
    b1, b2 = threefry2x32_p.bind(k1[None], k2[None],
                                 jnp.zeros(shape, jnp.uint32), lo)
    fbits = jax.lax.shift_right_logical(b1 ^ b2, jnp.uint32(9)) \
        | jnp.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(fbits, jnp.float32) - 1.0


def _row_round(x, u, s: int):
    """``codec.row_squant_encode`` on f32 rows (last axis) with given
    uniforms: (int8 levels, f32 scales with the last axis kept)."""
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True))
    scale = jnp.where(norm < jnp.inf, norm / s, 0.0)   # NaN and inf -> 0
    safe = jnp.where(norm > 0, norm, 1.0)
    r = jnp.abs(x) / safe * s
    low = jnp.floor(r)
    psi = low + (u < (r - low)).astype(jnp.float32)
    return (jnp.sign(x) * psi).astype(jnp.int8), scale


def _slices(br: int, t: int, body):
    """Run ``body(rows)`` over the block's slices of ``t`` rows."""
    def step(i, carry):
        body(pl.ds(pl.multiple_of(i * t, t), t))
        return carry

    jax.lax.fori_loop(0, br // t, step, 0)


def _uplink_kernel(act_ref, k1_ref, k2_ref, g_ref, h_ref, q_ref, sc_ref,
                   hn_ref, *, s: int, alpha: float, br: int, t: int):
    k1, k2 = k1_ref[...], k2_ref[...]
    active = act_ref[0]
    row0 = pl.program_id(0) * br

    def body(rows):
        h = h_ref[rows].astype(jnp.float32)
        delta = (g_ref[rows] - h) * active
        q, scale = _row_round(
            delta, _uniform(k1, k2, row0 + rows.start, delta.shape), s)
        scale = scale * active
        q_ref[rows] = q
        sc_ref[rows] = scale
        hn_ref[rows] = (h + alpha * (q.astype(jnp.float32) * scale)
                        ).astype(hn_ref.dtype)

    _slices(br, t, body)


def _server_kernel(k1_ref, k2_ref, hbar_ref, dsum_ref, hbn_ref, ghat_ref, *,
                   s: int, alpha: float, pn: float, n_workers: int, br: int,
                   t: int):
    k1, k2 = k1_ref[...], k2_ref[...]
    row0 = pl.program_id(0) * br

    def body(rows):
        hbar = hbar_ref[rows].astype(jnp.float32)
        dsum = dsum_ref[rows]
        hbn_ref[rows] = (hbar + alpha * dsum / n_workers
                         ).astype(hbn_ref.dtype)
        ghat = hbar + dsum / pn
        q, scale = _row_round(
            ghat, _uniform(k1, k2, row0 + rows.start, ghat.shape), s)
        ghat_ref[rows] = q.astype(jnp.float32) * scale

    _slices(br, t, body)


def rows_major(x: jax.Array) -> jax.Array:
    """``[B, R, C]`` -> ``[R, B, C]``: the kernels' view of a bucket stack.
    On the TPU XLA lays a ``[B, R, C]`` stack out row-major in that order
    when R is not a multiple of the 8-row tile and B is (the state's
    memory stacks, e.g. 16 x 103751 x 256), so the view costs no copy
    there."""
    return jnp.swapaxes(x, 0, 1)


def _grid(shape, elem_bytes: int, block_rows, interpret: bool):
    """(rows per block, rows per slice, grid) for a ``[R, B, C]`` view.
    Interpret mode with default blocking runs one block of one slice: each
    grid step and loop trip there costs a dispatch."""
    r, b, c = shape
    if r * c >= 2 ** 31:
        raise ValueError(f"bucket of {r} x {c} exceeds the 31-bit counter")
    if interpret and block_rows is None:
        return r, r, (1,)
    t = slice_rows(b, c)
    br = default_block_rows(b, c, elem_bytes) if block_rows is None \
        else _round_up(block_rows, t)
    br = min(br, _round_up(r, t))
    return br, t, (pl.cdiv(r, br),)


def _key_tables(keys: jax.Array, c: int):
    """[B, 2] key words -> two [B, C] tables, one word per bucket row."""
    keys = keys.astype(jnp.uint32)
    return tuple(jnp.broadcast_to(keys[:, i:i + 1], (keys.shape[0], c))
                 for i in range(2))


@functools.partial(jax.jit, static_argnames=("alpha", "s", "block_rows",
                                             "interpret"))
def uplink_round(g: jax.Array, h: jax.Array, active: jax.Array,
                 keys: jax.Array, *, alpha: float, s: int,
                 block_rows=None, interpret: bool = True):
    """The worker's encode and memory update in one pass.

    g [B, R, C] f32 (the bucketed gradient), h [B, R, C] (the worker's
    memory, any float dtype), ``active`` the f32 participation scalar,
    ``keys`` [B, 2] u32 (``bucketing.bucket_keys`` of the uplink key).
    Returns (levels s8 [B, R, C], scales f32 [B, R, 1], h_new like h).
    ``block_rows`` (rows of every bucket per block) None sizes blocks to
    the VMEM budget."""
    b, r, c = g.shape
    eb = 4 + 1 + 2 * h.dtype.itemsize
    br, t, grid = _grid((r, b, c), eb, block_rows, interpret)
    rows = pl.BlockSpec((br, b, c), lambda j, *_: (j, 0, 0))
    table = pl.BlockSpec((b, c), lambda j, *_: (0, 0))
    kernel = functools.partial(_uplink_kernel, s=s, alpha=alpha, br=br, t=t)
    with jax.named_scope("wire_uplink"):
        q, sc, hn = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=[table, table, rows, rows],
                out_specs=[rows, pl.BlockSpec((br, b, 1),
                                              lambda j, *_: (j, 0, 0)),
                           rows]),
            out_shape=[jax.ShapeDtypeStruct((r, b, c), jnp.int8),
                       jax.ShapeDtypeStruct((r, b, 1), jnp.float32),
                       jax.ShapeDtypeStruct((r, b, c), h.dtype)],
            input_output_aliases={4: 2},        # h_new over h
            name="wire_uplink",
            interpret=interpret,
        )(jnp.reshape(active, (1,)).astype(jnp.float32),
          *_key_tables(keys, c), rows_major(g.astype(jnp.float32)),
          rows_major(h))
    return rows_major(q), rows_major(sc), rows_major(hn)


@functools.partial(jax.jit, static_argnames=("alpha", "s", "p", "n",
                                             "block_rows", "interpret"))
def server_round(hbar: jax.Array, dhat_sum: jax.Array, keys: jax.Array, *,
                 p: float, n: int, alpha: float, s: int, block_rows=None,
                 interpret: bool = True):
    """The server's memory update and the downlink round in one pass.

    hbar [B, R, C] (the server memory, any float dtype), dhat_sum
    [B, R, C] f32 (the ring's sum over ``n`` workers), ``keys`` [B, 2] u32
    (``bucketing.bucket_keys`` of the shared downlink key), ``p`` the
    participation rate.  Returns (hbar_new like hbar, decoded ghat f32)."""
    b, r, c = dhat_sum.shape
    eb = 4 + 4 + 2 * hbar.dtype.itemsize
    br, t, grid = _grid((r, b, c), eb, block_rows, interpret)
    rows = pl.BlockSpec((br, b, c), lambda j: (j, 0, 0))
    table = pl.BlockSpec((b, c), lambda j: (0, 0))
    kernel = functools.partial(_server_kernel, s=s, alpha=alpha, pn=p * n,
                               n_workers=n, br=br, t=t)
    with jax.named_scope("wire_server"):
        hbn, ghat = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[table, table, rows, rows],
            out_specs=[rows, rows],
            out_shape=[jax.ShapeDtypeStruct((r, b, c), hbar.dtype),
                       jax.ShapeDtypeStruct((r, b, c), jnp.float32)],
            input_output_aliases={2: 0, 3: 1},  # over hbar, dhat_sum
            name="wire_server",
            interpret=interpret,
        )(*_key_tables(keys, c), rows_major(hbar), rows_major(dhat_sum))
    return rows_major(hbn), rows_major(ghat)
