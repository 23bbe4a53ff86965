"""Fused attention for calls without a KV cache.

Score tiles stay in VMEM, forward and backward, so the ``[B, H, Sq, Sk]``
score tensor never reaches HBM.  ``models/layers.attention`` decides when a
call comes here (``attention_path``).

Why not the flash attention that jax ships: at head dim 64 on a v5e it
loses to XLA's whole-block path (chip sweep in PERF.md, section 6).  Its
forward pays a segment-id mask and the scale on every score, its backward
recomputes the softmax twice (a dK/dV kernel and a dQ kernel), and it
keeps its row statistics broadcast to 128 lanes in HBM.  This kernel keeps
the vector work per score to what the softmax needs:

- the scores are computed transposed, ``s^T = K q^T`` ([Sk, bq] per query
  tile), so the row statistics reduce over sublanes and land lane-dense
  ([1, bq]); every matmul is plain or contracts both minor dims (as the
  shipped kernel's q k^T does), and XLA lays the operands out
  ([B, H, D, S] or [B, H, S, D]) around the kernel;
- key padding (lengths are padded to ``LANES``) costs no vector op: q^T
  gets an extra row of ones and K an extra column, 0 for a real key and
  ``-1e30`` for a padded one, so the MXU adds the mask to the scores;
- the softmax scale is folded into q where it is a power of two (exact in
  any float format; ``d ** -0.5`` for d = 64), else the kernel applies it;
- the forward sees all keys at once (no online rescaling; ``MAX_SK``);
- the backward is one kernel over key tiles: it recomputes each score
  tile once and accumulates dK and dV in registers and dQ^T in its
  resident output block across the key tiles.

No causal mask: ``attention`` sends causal calls to XLA.

Statistics and accumulation are f32; matmul operands keep the inputs'
dtype (bf16 in the model), as on the XLA path.  On the CPU the kernels run
only inside ``pltpu.force_tpu_interpret_mode()`` (the tests).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# the forward holds a [Sk, BLOCK_Q] f32 score tile (4 MiB at the bounds);
# the backward holds q^T, dO^T and dQ^T of the whole query range
MAX_SK = 2048
MAX_SQ = 8192
PAD_SCORE = -1e30
VMEM_LIMIT = 64 << 20
# query tile of the forward and of the backward's inner loop, key tile of
# the backward (each rounded down to a multiple of ``LANES`` dividing the
# padded length); (512, 512) won whisper-tiny's sites on a v5e (PERF.md,
# section 6)
BLOCK_Q = 512
BLOCK_K = 512


def padded_len(n: int) -> int:
    return -(-n // LANES) * LANES


def _block(n: int, target: int) -> int:
    """The largest multiple of ``LANES`` up to ``target`` dividing ``n``."""
    b = max(min(target, n) // LANES * LANES, LANES)
    while n % b:
        b -= LANES
    return b


# -- kernels ---------------------------------------------------------------------

def _fwd_kernel(qt_ref, k_ref, vt_ref, ot_ref, lse_ref, *, scale):
    qt = qt_ref[0, 0]                                      # [Dp, bq]
    st = jax.lax.dot(k_ref[0, 0], qt,
                     preferred_element_type=jnp.float32)   # [Sk, bq]
    if scale != 1.0:
        st = st * scale
    m = jnp.max(st, axis=0, keepdims=True)                 # [1, bq]
    pt = jnp.exp(st - m)
    l = jnp.sum(pt, axis=0, keepdims=True)
    vt = vt_ref[0, 0]                                      # [D, Sk]
    ot = jax.lax.dot(vt, pt.astype(vt.dtype),
                     preferred_element_type=jnp.float32)   # [D, bq]
    ot_ref[0, 0] = (ot * (1.0 / l)).astype(ot_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


_NT = (((1,), (1,)), ((), ()))       # contract both minor dims: a @ b^T


def _bwd_kernel(k_ref, kt_ref, v_ref, qt_ref, dot_ref, lse_ref, di_ref,
                dk_ref, dvt_ref, dqt_ref, *, scale, block_q):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dqt_ref[...] = jnp.zeros_like(dqt_ref)

    # K [bk, Dp], K^T [Dp, bk], V [bk, D]
    k, kt, v = k_ref[0, 0], kt_ref[0, 0], v_ref[0, 0]
    bk, dp = k.shape
    d = v.shape[1]
    n_q = qt_ref.shape[3] // block_q

    def body(i, carry):
        dk, dvt = carry
        cols = pl.ds(pl.multiple_of(i * block_q, LANES), block_q)
        qt = qt_ref[0, 0, :, cols]                         # [Dp, bq]
        dot_ = dot_ref[0, 0, :, cols]                      # [D, bq]
        st = jax.lax.dot(k, qt, preferred_element_type=jnp.float32)
        if scale != 1.0:
            st = st * scale
        pt = jnp.exp(st - lse_ref[0, 0, :, cols])          # [bk, bq]
        dpt = jax.lax.dot(v, dot_, preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[0, 0, :, cols])
        if scale != 1.0:
            dst = dst * scale
        dst = dst.astype(qt.dtype)
        dvt = dvt + jax.lax.dot_general(dot_, pt.astype(dot_.dtype), _NT,
                                        preferred_element_type=jnp.float32)
        dk = dk + jax.lax.dot_general(dst, qt, _NT,
                                      preferred_element_type=jnp.float32)
        dqt_ref[0, 0, :, cols] += jax.lax.dot(
            kt, dst, preferred_element_type=jnp.float32)
        return dk, dvt

    dk, dvt = jax.lax.fori_loop(
        0, n_q, body, (jnp.zeros((bk, dp), jnp.float32),
                       jnp.zeros((d, bk), jnp.float32)),
        unroll=n_q <= 4)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dvt_ref[0, 0] = dvt.astype(dvt_ref.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _fwd_call(qt, k, vt, scale):
    b, h, dp, sq = qt.shape
    sk, d = k.shape[2], vt.shape[2]
    bq = _block(sq, BLOCK_Q)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(b, h, sq // bq),
        in_specs=[pl.BlockSpec((1, 1, dp, bq), lambda b, h, i: (b, h, 0, i)),
                  pl.BlockSpec((1, 1, sk, dp), lambda b, h, i: (b, h, 0, 0)),
                  pl.BlockSpec((1, 1, d, sk), lambda b, h, i: (b, h, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, d, bq), lambda b, h, i: (b, h, 0, i)),
                   pl.BlockSpec((1, 1, 1, bq), lambda b, h, i: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((b, h, d, sq), vt.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        name="flash_attn_fwd",
    )(qt, k, vt)


def _bwd_call(qt, k, vt, ot, lse, dot_, scale):
    b, h, dp, sq = qt.shape
    sk, d = k.shape[2], vt.shape[2]
    bq, bk = _block(sq, BLOCK_Q), _block(sk, BLOCK_K)
    # di = rowsum(dO * O), the softmax's row term, lane-dense like lse
    di = jnp.sum(ot.astype(jnp.float32) * dot_.astype(jnp.float32), axis=2,
                 keepdims=True)
    kt = k.swapaxes(2, 3)
    v = vt.swapaxes(2, 3)
    kv_block = lambda b, h, j: (b, h, j, 0)              # noqa: E731
    kvt_block = lambda b, h, j: (b, h, 0, j)             # noqa: E731
    whole = lambda b, h, j: (b, h, 0, 0)                 # noqa: E731
    dk, dvt, dqt = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, block_q=bq),
        grid=(b, h, sk // bk),
        in_specs=[pl.BlockSpec((1, 1, bk, dp), kv_block),
                  pl.BlockSpec((1, 1, dp, bk), kvt_block),
                  pl.BlockSpec((1, 1, bk, d), kv_block),
                  pl.BlockSpec((1, 1, dp, sq), whole),
                  pl.BlockSpec((1, 1, d, sq), whole),
                  pl.BlockSpec((1, 1, 1, sq), whole),
                  pl.BlockSpec((1, 1, 1, sq), whole)],
        out_specs=[pl.BlockSpec((1, 1, bk, dp), kv_block),
                   pl.BlockSpec((1, 1, d, bk), kvt_block),
                   pl.BlockSpec((1, 1, dp, sq), whole)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype),
                   jax.ShapeDtypeStruct(qt.shape, jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        name="flash_attn_bwd",
    )(k, kt, v, qt, dot_, lse, di)
    return dqt.astype(qt.dtype), dk, dvt


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(qt, k, vt, scale):
    return _fwd_call(qt, k, vt, scale)[0]


def _attend_fwd(qt, k, vt, scale):
    ot, lse = _fwd_call(qt, k, vt, scale)
    return ot, (qt, k, vt, ot, lse)


def _attend_bwd(scale, res, dot_):
    qt, k, vt, ot, lse = res
    return _bwd_call(qt, k, vt, ot, lse, dot_, scale)


_attend.defvjp(_attend_fwd, _attend_bwd)


# -- wrapper ---------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("scale",))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float) -> jax.Array:
    """q [B, Sq, H, D], k/v [B, Sk, H, D] -> [B, Sq, H, D] in q's dtype:
    every query attends to every key (no causal mask, no window)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    sqp, skp = padded_len(sq), padded_len(sk)
    dp = -(-(d + 1) // 16) * 16          # + the mask row, bf16 sublane tile
    if 2.0 ** round(math.log2(scale)) == scale:
        q, scale = q * jnp.asarray(scale, q.dtype), 1.0
    # q^T [B, H, Dp, Sqp]: rows 0..D-1 the query, row D ones
    qt = jnp.concatenate(
        [q.transpose(0, 2, 3, 1), jnp.ones((b, h, 1, sq), q.dtype),
         jnp.zeros((b, h, dp - d - 1, sq), q.dtype)], axis=2)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, sqp - sq)))
    # K [B, H, Skp, Dp]: column D is 0 for a real key, PAD_SCORE for a pad
    kk = jnp.pad(k.transpose(0, 2, 1, 3),
                 ((0, 0), (0, 0), (0, skp - sk), (0, 0)))
    mask = jnp.where(jnp.arange(skp) < sk, 0.0, PAD_SCORE).astype(k.dtype)
    kk = jnp.concatenate(
        [kk, jnp.broadcast_to(mask[:, None], (b, h, skp, 1)),
         jnp.zeros((b, h, skp, dp - d - 1), k.dtype)], axis=3)
    vt = jnp.pad(v.transpose(0, 2, 3, 1),
                 ((0, 0), (0, 0), (0, 0), (0, skp - sk)))
    ot = _attend(qt, kk, vt, scale)
    return ot[..., :sq].transpose(0, 3, 1, 2).astype(q.dtype)
