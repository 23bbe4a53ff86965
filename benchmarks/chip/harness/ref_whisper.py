"""Plain reference of the train cells: the encoder-decoder's loss and
gradient, the bucketed Artemis wire, and Adam, in float32 at the highest
matmul precision.  It imports nothing of the program.

It follows the architecture as the configuration file states it is run
(its ``assumed`` list): pre-norm blocks with RMSNorm ``x / rms(x) * (1 +
g)`` (eps 1e-6), no biases, rotary positions (half-split, theta 1e4) on the
self-attention queries and keys of encoder and decoder and none on the
cross-attention, tanh-approximated GELU MLPs, a linear front end over
stubbed frame embeddings, an untied output head, and the mean next-token
cross-entropy over every position but the last.

The wire (paper Algorithm 1 with PP2, full participation): each worker
sends its gradient less its memory, flattened in the parameter tree's leaf
order into equal [B, R, C] buckets (zero-padded tail), each row
stochastically rounded to ``s`` levels of its norm with the uniforms of
``uniform(fold_in(key, bucket), [R, C])``; the server sums what arrives,
forms ``hbar + sum / n``, updates ``hbar += alpha sum / n`` and every
worker ``h += alpha dhat``; the downlink rounds the aggregate the same way
with the round's shared key.  Keys: ``base = fold_in(PRNGKey(seed),
round)``, uplink ``fold_in(base, worker + 1)``, downlink ``fold_in(base,
0)``.  ``alpha = 1 / (2 (omega + 1))`` with ``omega = min(C / s^2,
sqrt(C) / s)``.

``mm="fp8"`` rounds every matmul operand to float8 (e4m3) first: the
control, one precision below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness.flops import bucket_layout

HI = jax.lax.Precision.HIGHEST


def _fp8(x):
    """Round to float8 e4m3 (4 exponent bits, 3 mantissa bits) with a
    per-tensor scale that maps the tensor's largest magnitude to 240, the
    largest finite value of ``reduce_precision``'s e4m3, as fp8 training
    scales.  ``reduce_precision`` rounds for certain: a convert to float8
    and back may be elided by XLA's excess-precision rule."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, 240.0 / amax, 1.0)
    return jax.lax.reduce_precision(x * s, exponent_bits=4,
                                    mantissa_bits=3) / s


@jax.custom_vjp
def _fp8_operand(x):
    return _fp8(x)


_fp8_operand.defvjp(lambda x: (_fp8(x), None), lambda _, g: (_fp8(g),))


def _round(x, mm: str):
    """Matmul operands, and their gradients, at the cell's control
    precision; unchanged for the float32 reference."""
    if mm == "fp8":
        return _fp8_operand(x)
    return x


def _mm(a, b, mm: str):
    return jnp.matmul(_round(a, mm), _round(b, mm), precision=HI)


def _einsum(spec, a, b, mm: str):
    return jnp.einsum(spec, _round(a, mm), _round(b, mm), precision=HI)


def rms_norm(x, g, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def rope(x, pos, theta=10000.0):
    """x [B, S, H, D], pos [S]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freq             # [S, half]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, causal: bool, mm: str):
    """q [B, Sq, H, D], k, v [B, Sk, H, D]."""
    d = q.shape[-1]
    s = _einsum("bqhd,bkhd->bhqk", q, k, mm) * d ** -0.5
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return _einsum("bhqk,bkhd->bqhd", p, v, mm)


def _self_attn(p, x, heads, causal, mm):
    b, s, d = x.shape
    hd = d // heads
    q = _mm(x, p["wq"], mm).reshape(b, s, heads, hd)
    k = _mm(x, p["wk"], mm).reshape(b, s, heads, hd)
    v = _mm(x, p["wv"], mm).reshape(b, s, heads, hd)
    pos = jnp.arange(s)
    o = attention(rope(q, pos), rope(k, pos), v, causal, mm)
    return _mm(o.reshape(b, s, d), p["wo"], mm)


def _cross_attn(p, x, enc, heads, mm):
    b, s, d = x.shape
    f = enc.shape[1]
    hd = d // heads
    q = _mm(x, p["wq"], mm).reshape(b, s, heads, hd)
    k = _mm(enc, p["wk"], mm).reshape(b, f, heads, hd)
    v = _mm(enc, p["wv"], mm).reshape(b, f, heads, hd)
    o = attention(q, k, v, False, mm)
    return _mm(o.reshape(b, s, d), p["wo"], mm)


def _mlp(p, x, mm):
    return _mm(jax.nn.gelu(_mm(x, p["w_up"], mm), approximate=True),
               p["w_down"], mm)


def _layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def loss_fn(params, tokens, frames, cfg: dict, mm: str = "f32"):
    """Mean next-token cross-entropy of ``tokens`` [B, S] given
    ``frames`` [B, F, d]."""
    heads_e = cfg["encoder_attention_heads"]
    heads_d = cfg["decoder_attention_heads"]
    x = _mm(frames.astype(jnp.float32), params["frontend_proj"], mm)
    enc = params["encoder"]
    for i in range(cfg["encoder_layers"]):
        p = _layer(enc, i)
        x = x + _self_attn(p["attn"], rms_norm(x, p["ln1"]), heads_e, False,
                           mm)
        x = x + _mlp(p["mlp"], rms_norm(x, p["ln2"]), mm)
    enc_out = rms_norm(x, params["enc_norm"])
    y = params["embed"][tokens]
    dec = params["layers"]
    for i in range(cfg["decoder_layers"]):
        p = _layer(dec, i)
        y = y + _self_attn(p["attn"], rms_norm(y, p["ln1"]), heads_d, True,
                           mm)
        y = y + _cross_attn(p["xattn"], rms_norm(y, p["lnx"]), enc_out,
                            heads_d, mm)
        y = y + _mlp(p["mlp"], rms_norm(y, p["ln2"]), mm)
    y = rms_norm(y, params["final_norm"])
    logits = _mm(y[:, :-1], params["unembed"], mm)
    labels = tokens[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


@functools.lru_cache(maxsize=None)
def _grad_fn(cfg_items, mm):
    cfg = dict(cfg_items)
    return jax.jit(jax.value_and_grad(
        lambda p, t, f: loss_fn(p, t, f, cfg, mm)))


def loss_and_grad(params, tokens, frames, cfg: dict, mm: str = "f32",
                  block: int = 2):
    """Loss and gradient over all rows, computed ``block`` rows at a time
    (every row has the same number of targets, so the mean over rows is
    the mean of the blocks' means)."""
    fn = _grad_fn(tuple(sorted((k, v) for k, v in cfg.items()
                               if isinstance(v, (int, float, str)))), mm)
    b = tokens.shape[0]
    block = min(block, b)
    assert b % block == 0, (b, block)
    tot_l, tot_g = 0.0, None
    for i in range(0, b, block):
        l, g = fn(params, tokens[i:i + block], frames[i:i + block])
        tot_l = tot_l + l
        tot_g = g if tot_g is None else jax.tree.map(jnp.add, tot_g, g)
    k = b // block
    return tot_l / k, jax.tree.map(lambda a: a / k, tot_g)


# ---------------------------------------------------------------------------
# the wire
# ---------------------------------------------------------------------------

def flatten(tree, shape):
    flat = jnp.concatenate([jnp.ravel(a).astype(jnp.float32)
                            for a in jax.tree.leaves(tree)])
    pad = int(np.prod(shape)) - flat.shape[0]
    return jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)]).reshape(
        shape)


def unflatten(buckets, like):
    leaves, treedef = jax.tree.flatten(like)
    flat = buckets.reshape(-1)
    out, off = [], 0
    for a in leaves:
        n = int(np.prod(a.shape))
        out.append(flat[off:off + n].reshape(a.shape))
        off += n
    return jax.tree.unflatten(treedef, out)


@functools.partial(jax.jit, static_argnames=("s",))
def round_rows(key, x, s: int):
    """Stochastic s-level rounding of every row of ``x`` [B, R, C], one key
    per bucket (``fold_in(key, b)``); returns the decoded rows."""
    def one(b, xb):
        kb = jax.random.fold_in(key, b)
        norm = jnp.sqrt(jnp.sum(xb * xb, axis=-1, keepdims=True))
        scale = jnp.where(jnp.isfinite(norm), norm / s, 0.0)
        r = jnp.abs(xb) / jnp.where(norm > 0, norm, 1.0) * s
        low = jnp.floor(r)
        u = jax.random.uniform(kb, xb.shape, jnp.float32)
        q = jnp.sign(xb) * (low + (u < r - low).astype(jnp.float32))
        return q * scale
    return jax.lax.map(lambda a: one(*a),
                       (jnp.arange(x.shape[0]), x))


def adam(params, m, v, g, t: int, lr: float, b1=0.9, b2=0.95, eps=1e-8):
    tt = jnp.float32(t + 1)
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    c1, c2 = 1 - jnp.float32(b1) ** tt, 1 - jnp.float32(b2) ** tt
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
        params, m, v)
    return params, m, v


def leaf_norms(tree) -> np.ndarray:
    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))
                     for a in jax.tree.leaves(tree)])


def run(params, batches: Sequence[dict], cfg: dict, wire: dict,
        n_workers: int, round0: int, lr: float, steps: int = 3,
        mm: str = "f32", block: int = 2) -> dict:
    """``steps`` Artemis steps from ``params`` over ``batches`` (each
    {"tokens": [n_workers * b, S] int, "frames": [n_workers * b, F, d]},
    worker ``w`` holding rows ``w*b:(w+1)*b``).  Returns the readings the
    cell compares: each step's loss, the per-leaf norms of the first
    gradient Adam received, of the parameters' change over all steps, and
    of the first raw (uncompressed) mean gradient."""
    s = int(wire["s"])
    shape = bucket_layout(sum(int(np.prod(a.shape))
                              for a in jax.tree.leaves(params)),
                          wire["bucket_bytes"], wire["max_buckets"],
                          wire["row"])
    c = shape[-1]
    omega = min(c / s ** 2, math.sqrt(c) / s)
    alpha = 1.0 / (2.0 * (omega + 1.0))
    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    h = [jnp.zeros(shape, jnp.float32) for _ in range(n_workers)]
    hbar = jnp.zeros(shape, jnp.float32)
    root = jax.random.PRNGKey(int(wire["key_seed"]))
    losses, first_g, raw_g = [], None, None
    for k in range(steps):
        toks = np.asarray(batches[k]["tokens"])
        frames = batches[k]["frames"]
        b = toks.shape[0] // n_workers
        base = jax.random.fold_in(root, jnp.int32(round0 + k))
        dsum, lsum, gsum = jnp.zeros(shape, jnp.float32), 0.0, None
        dhats = []
        for w in range(n_workers):
            rows = slice(w * b, (w + 1) * b)
            t_w, f_w = toks[rows], frames[rows]
            l, g = loss_and_grad(params, jnp.asarray(t_w), f_w, cfg, mm,
                                 block=min(block, t_w.shape[0]))
            lsum += float(l)
            gsum = g if gsum is None else jax.tree.map(jnp.add, gsum, g)
            up = jax.random.fold_in(base, jnp.int32(w + 1))
            dhat = round_rows(up, flatten(g, shape) - h[w], s)
            dhats.append(dhat)
            dsum = dsum + dhat
        for w in range(n_workers):
            h[w] = h[w] + alpha * dhats[w]
        ghat = hbar + dsum / n_workers
        hbar = hbar + alpha * dsum / n_workers
        ghat = round_rows(jax.random.fold_in(base, jnp.int32(0)), ghat, s)
        g_tree = unflatten(ghat, params)
        if k == 0:
            first_g = leaf_norms(g_tree)
            raw_g = leaf_norms(jax.tree.map(lambda a: a / n_workers, gsum))
        losses.append(lsum / n_workers)
        params, m, v = adam(params, m, v, g_tree, k, lr)
    change = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return {"losses": losses, "first_grad": first_g, "change": change,
            "raw_grad": raw_g}
