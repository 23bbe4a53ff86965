"""Fused attention for calls without a KV cache.

Score tiles stay in VMEM, forward and backward, so the ``[B, H, Sq, Sk]``
score tensor never reaches HBM.  ``models/layers.attention`` decides when a
call comes here (``attention_path``).  Two kernel pairs share their
``pallas_call`` builders, tiles and custom VJP (``_attend``) and differ in
their bodies: ``flash_attention``, where every query sees every key, and
``causal_flash_attention``, where query ``i`` sees keys ``0..i``.

Why not the flash attention that jax ships: at head dim 64 on a v5e it
loses to XLA's whole-block path (chip sweep in PERF.md, section 6).  Its
forward pays a segment-id mask and the scale on every score, its backward
recomputes the softmax twice (a dK/dV kernel and a dQ kernel), and it
keeps its row statistics broadcast to 128 lanes in HBM.  These kernels
keep the vector work per score to what the softmax needs:

- the scores are computed transposed, ``s^T = K q^T`` ([bk, bq] per
  tile), so the row statistics reduce over sublanes and land lane-dense
  ([1, bq]); every matmul is plain or contracts both minor dims (as the
  shipped kernel's q k^T does), and XLA lays the operands out
  ([B, H, D, S] or [B, H, S, D]) around the kernel;
- the backward is one kernel over key tiles: it recomputes each score
  tile once and accumulates dK and dV in registers and dQ^T in its
  resident output block across the query tiles.

Not causal (``flash_attention``, whisper's encoder and cross-attention):

- key padding (lengths are padded to ``LANES``) costs no vector op: q^T
  gets an extra row of ones and K an extra column, 0 for a real key and
  ``-1e30`` for a padded one, so the MXU adds the mask to the scores;
- the softmax scale is folded into q where it is a power of two (exact in
  any float format; ``d ** -0.5`` for d = 64), else the kernel applies it;
- the forward sees all keys at once (no online rescaling; ``MAX_SK``).

Causal (``causal_flash_attention``, latent attention's qk 192 / v 128 at
4,096 tokens), self-attention with ``Sq == Sk`` and positions ``0..S-1``:

- V may have its own head dim; q and K take theirs as they are, with no
  mask row, and the kernels apply the scale to the f32 scores;
- padding at the end needs no mask: every real query lies before every
  padded key, and the padded queries' rows are dropped (their cotangent
  is zero, so they add nothing to dK and dV);
- the forward (grid: batch, head, query tile) keeps K and V^T of the head
  resident and loops over key tiles from 0 to the diagonal with an online
  softmax (running max and sum in f32, lane-dense); only the tiles that
  the diagonal crosses build an iota mask, and the key tiles wholly above
  it are never computed;
- the backward's inner loop over query tiles starts at the tile that
  holds the key tile's diagonal; q^T, dO^T, the lse and the row term
  ``di`` stay resident for the whole query range.

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
from jax.sharding import PartitionSpec as P

LANES = 128
# the forward holds a [Sk, BLOCK_Q] f32 score tile (4 MiB at the bounds);
# the backward holds q^T, dO^T and dQ^T of the whole query range
MAX_SK = 2048
MAX_SQ = 8192
PAD_SCORE = -1e30
VMEM_LIMIT = 64 << 20
# query tile of the forward and of the backward's inner loop, key tile of
# the backward and of the causal forward's loop (each rounded down to a
# multiple of ``LANES`` dividing the padded length); (512, 512) won
# whisper-tiny's sites and kanana's causal call on a v5e (PERF.md,
# section 6)
BLOCK_Q = 512
BLOCK_K = 512
# the causal kernels: the forward holds K and V^T of a head, the backward
# q^T, dO^T and dQ^T (f32) of the whole range; at 8,192 tokens and qk 192,
# v 128 that is about 6 and 12 MiB, double-buffered 12 and 24 MiB
# (compiled for a v5e at 8,192 tokens, qk 192 / v 128 and 128 / 128)
MAX_CAUSAL_S = 8192


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


def _diagonal_mask(st, k0, q0):
    """``st`` ([bk, bq], keys from ``k0``, queries from ``q0``) with each
    key after its query set to ``PAD_SCORE``."""
    key = k0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
    query = q0 + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
    return jnp.where(key <= query, st, PAD_SCORE)


def _causal_fwd_kernel(qt_ref, k_ref, vt_ref, ot_ref, lse_ref, *, scale):
    qt = qt_ref[0, 0]                                      # [Dqk, bq]
    bq = qt.shape[1]
    block_k = _block(k_ref.shape[2], BLOCK_K)
    q0 = pl.program_id(2) * bq
    # key tiles 0..n_full-1 lie wholly at or before the tile's first query;
    # n_full..n_all-1 are crossed by the diagonal; the rest are skipped
    n_full = (q0 + 1) // block_k
    n_all = (q0 + bq + block_k - 1) // block_k

    def step(j, carry, masked):
        m, l, acc = carry
        k0 = pl.multiple_of(j * block_k, LANES)
        st = jax.lax.dot(k_ref[0, 0, pl.ds(k0, block_k), :], qt,
                         preferred_element_type=jnp.float32) * scale
        if masked:
            st = _diagonal_mask(st, k0, q0)
        m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
        alpha = jnp.exp(m - m_new)
        pt = jnp.exp(st - m_new)
        l = alpha * l + jnp.sum(pt, axis=0, keepdims=True)
        vt = vt_ref[0, 0, :, pl.ds(k0, block_k)]          # [Dv, bk]
        acc = alpha * acc + jax.lax.dot(vt, pt.astype(vt.dtype),
                                        preferred_element_type=jnp.float32)
        return m_new, l, acc

    carry = (jnp.full((1, bq), PAD_SCORE, jnp.float32),
             jnp.zeros((1, bq), jnp.float32),
             jnp.zeros((ot_ref.shape[2], bq), jnp.float32))
    carry = jax.lax.fori_loop(0, n_full,
                              functools.partial(step, masked=False), carry)
    m, l, acc = jax.lax.fori_loop(n_full, n_all,
                                  functools.partial(step, masked=True), carry)
    ot_ref[0, 0] = (acc * (1.0 / l)).astype(ot_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


def _causal_bwd_kernel(k_ref, kt_ref, v_ref, qt_ref, dot_ref, lse_ref,
                       di_ref, dk_ref, dvt_ref, dqt_ref, *, scale, block_q):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _():
        dqt_ref[...] = jnp.zeros_like(dqt_ref)

    # K [bk, Dqk], K^T [Dqk, bk], V [bk, Dv]
    k, kt, v = k_ref[0, 0], kt_ref[0, 0], v_ref[0, 0]
    bk, dqk = k.shape
    dv = v.shape[1]
    k0 = j * bk
    n_q = qt_ref.shape[3] // block_q
    # query tiles before i_first see none of the tile's keys; i_first up to
    # i_full - 1 are crossed by the diagonal; from i_full on they see all
    i_first = k0 // block_q
    i_full = jnp.minimum((k0 + bk - 1 + block_q - 1) // block_q, n_q)

    def step(i, carry, masked):
        dk, dvt = carry
        q0 = pl.multiple_of(i * block_q, LANES)
        cols = pl.ds(q0, block_q)
        qt = qt_ref[0, 0, :, cols]                         # [Dqk, bq]
        dot_ = dot_ref[0, 0, :, cols]                      # [Dv, bq]
        st = jax.lax.dot(k, qt, preferred_element_type=jnp.float32) * scale
        if masked:
            st = _diagonal_mask(st, k0, q0)
        pt = jnp.exp(st - lse_ref[0, 0, :, cols])
        dpt = jax.lax.dot(v, dot_, preferred_element_type=jnp.float32)
        dst = (pt * (dpt - di_ref[0, 0, :, cols]) * scale).astype(qt.dtype)
        dvt = dvt + jax.lax.dot_general(dot_, pt.astype(dot_.dtype), _NT,
                                        preferred_element_type=jnp.float32)
        dk = dk + jax.lax.dot_general(dst, qt, _NT,
                                      preferred_element_type=jnp.float32)
        dqt_ref[0, 0, :, cols] += jax.lax.dot(
            kt, dst, preferred_element_type=jnp.float32)
        return dk, dvt

    carry = (jnp.zeros((bk, dqk), jnp.float32), jnp.zeros((dv, bk), jnp.float32))
    carry = jax.lax.fori_loop(i_first, i_full,
                              functools.partial(step, masked=True), carry)
    dk, dvt = jax.lax.fori_loop(i_full, n_q,
                                functools.partial(step, masked=False), carry)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dvt_ref[0, 0] = dvt.astype(dvt_ref.dtype)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _fwd_call(qt, k, vt, scale, causal):
    b, h, dp, sq = qt.shape
    sk, d = k.shape[2], vt.shape[2]
    bq = _block(sq, BLOCK_Q)
    kernel, name = ((_causal_fwd_kernel, "flash_attn_causal_fwd") if causal
                    else (_fwd_kernel, "flash_attn_fwd"))
    return pl.pallas_call(
        functools.partial(kernel, scale=scale),
        grid=(b, h, sq // bq),
        in_specs=[pl.BlockSpec((1, 1, dp, bq), lambda b, h, i: (b, h, 0, i)),
                  pl.BlockSpec((1, 1, sk, dp), lambda b, h, i: (b, h, 0, 0)),
                  pl.BlockSpec((1, 1, d, sk), lambda b, h, i: (b, h, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, d, bq), lambda b, h, i: (b, h, 0, i)),
                   pl.BlockSpec((1, 1, 1, bq), lambda b, h, i: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((b, h, d, sq), vt.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, sq), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel"),
        name=name,
    )(qt, k, vt)


def _bwd_call(qt, k, vt, ot, lse, dot_, scale, causal):
    b, h, dp, sq = qt.shape
    sk, d = k.shape[2], vt.shape[2]
    bq, bk = _block(sq, BLOCK_Q), _block(sk, BLOCK_K)
    kernel, name = ((_causal_bwd_kernel, "flash_attn_causal_bwd") if causal
                    else (_bwd_kernel, "flash_attn_bwd"))
    # di = rowsum(dO * O), the softmax's row term, lane-dense like lse
    di = jnp.sum(ot.astype(jnp.float32) * dot_.astype(jnp.float32), axis=2,
                 keepdims=True)
    kt = k.swapaxes(2, 3)
    v = vt.swapaxes(2, 3)
    kv_block = lambda b, h, j: (b, h, j, 0)              # noqa: E731
    kvt_block = lambda b, h, j: (b, h, 0, j)             # noqa: E731
    whole = lambda b, h, j: (b, h, 0, 0)                 # noqa: E731
    dk, dvt, dqt = pl.pallas_call(
        functools.partial(kernel, scale=scale, block_q=bq),
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
        name=name,
    )(k, kt, v, qt, dot_, lse, di)
    return dqt.astype(qt.dtype), dk, dvt


def _manual(call):
    """``call`` as the mesh in scope allows a Mosaic kernel: GSPMD cannot
    partition one, so where an axis is left to it (the size-1 axes beside
    the Artemis worker axis) the call runs manual over the mesh.  The map
    sits inside the custom VJP and is never differentiated: the transpose
    of a map would sum the inputs' cotangents over every axis it makes
    manual, the workers' among them, adding up their gradients."""
    mesh = jax.sharding.get_abstract_mesh()
    if all(t == jax.sharding.AxisType.Manual for t in mesh.axis_types):
        return call
    return jax.shard_map(call, in_specs=P(), out_specs=P(), check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attend(qt, k, vt, scale, causal):
    return _manual(functools.partial(_fwd_call, scale=scale,
                                     causal=causal))(qt, k, vt)[0]


def _attend_fwd(qt, k, vt, scale, causal):
    ot, lse = _manual(functools.partial(_fwd_call, scale=scale,
                                        causal=causal))(qt, k, vt)
    return ot, (qt, k, vt, ot, lse)


def _attend_bwd(scale, causal, res, dot_):
    return _manual(functools.partial(_bwd_call, scale=scale,
                                     causal=causal))(*res, dot_)


_attend.defvjp(_attend_fwd, _attend_bwd)


# -- wrappers --------------------------------------------------------------------

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
    ot = _attend(qt, kk, vt, scale, False)
    return ot[..., :sq].transpose(0, 3, 1, 2).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale",))
def causal_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           scale: float) -> jax.Array:
    """q, k [B, S, H, Dqk], v [B, S, H, Dv] -> [B, S, H, Dv] in q's dtype:
    query ``i`` attends to keys ``0..i`` (self-attention, no cache, no
    window)."""
    s = q.shape[1]
    pad = padded_len(s) - s
    qt = jnp.pad(q.transpose(0, 2, 3, 1), ((0, 0), (0, 0), (0, 0), (0, pad)))
    kk = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, pad), (0, 0)))
    vt = jnp.pad(v.transpose(0, 2, 3, 1), ((0, 0), (0, 0), (0, 0), (0, pad)))
    ot = _attend(qt, kk, vt, scale, True)
    return ot[..., :s].transpose(0, 3, 1, 2).astype(q.dtype)
