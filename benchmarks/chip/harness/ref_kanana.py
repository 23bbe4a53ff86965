"""Plain reference of the kanana cell: the decoder's loss and gradient in
float32 at the highest matmul precision, and three steps of the bucketed
Artemis wire with Adam.  It imports nothing of the program; the wire's
rounding, the bucket layout and Adam are ``ref_whisper``'s.

It follows the configuration file's equations (HF ``deepseek_v3`` with no
query LoRA) and its ``assumed`` list, on one row ``x`` [S, d] at a time:

* attention (MLA): ``q = x Wq`` -> [S, H, nope + rope]; ``[c, k_pe] = x
  Wkv_a``, ``c = RMSNorm(c)``; ``[k_nope, v] = c Wkv_b`` -> [S, H, nope +
  v]; rotary (half-split, theta from the file) on ``q_pe`` and on the one
  ``k_pe`` all heads share; causal softmax of ``q.k / sqrt(nope + rope)``;
  ``(P v) Wo``;
* MoE layer: ``s = sigmoid(x Wr)`` over all published experts, top-k of
  ``s + b`` (``b`` the selection bias), weights ``s_sel / sum(s_sel) *
  routed_scaling_factor``; the held experts' part ``sum_e w_e SwiGLU_e(x)``
  (each held expert computed densely over every token, weighted by its
  routing weight, zero where not chosen) plus the shared experts' SwiGLU;
* pre-norm blocks with RMSNorm ``x / rms(x) * (1 + g)``, one leading dense
  SwiGLU layer, a final RMSNorm and an untied head; the mean next-token
  cross-entropy over every position but the last.

Parameters are the program's tree (``embed``, ``unembed``, ``final_norm``,
``lead`` [per layer], ``layers`` [stacked]), by name.  To fit one chip
beside nothing else, each layer and each block of ``q_block`` queries is
rematerialised in the backward pass, and the wire and Adam run leaf by leaf
with the initial weights kept on the host.

``mm="fp8"`` rounds every matmul operand to float8 e4m3 (``ref_whisper``):
the control, one precision below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from harness import ref_whisper as W
from harness.flops import bucket_layout

Q_BLOCK = 512


def rope(x, theta):
    """x [S, H, D]: half-split rotary at positions 0..S-1."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_block(q, k, v, start, mm):
    """Queries ``start .. start + len(q)`` against keys ``0 .. start +
    len(q)``; q [Q, H, D], k [K, H, D], v [K, H, Dv]."""
    s = W._einsum("qhd,khd->hqk", q, k, mm) * q.shape[-1] ** -0.5
    qi = start + jnp.arange(q.shape[0])[:, None]
    s = jnp.where(qi >= jnp.arange(k.shape[0])[None, :], s, -jnp.inf)
    return W._einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, mm)


def mla(p, x, cfg: dict, mm: str = "f32", q_block: int = Q_BLOCK):
    """Latent attention of one row x [S, d] -> [S, d]."""
    h = cfg["num_attention_heads"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    theta = float(cfg["rope_theta"])
    s = x.shape[0]
    q = W._mm(x, p["wq"], mm).reshape(s, h, nope + rd)
    kva = W._mm(x, p["wkv_a"], mm)
    c = W.rms_norm(kva[:, :r], p["kv_norm"], cfg["rms_norm_eps"])
    kv = W._mm(c, p["wkv_b"], mm).reshape(s, h, nope + dv)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    k_pe = rope(kva[:, None, r:], theta)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (s, h, rd))], -1)
    v = kv[..., nope:]
    block = jax.checkpoint(_causal_block, static_argnums=(3, 4))
    outs = []
    for a in range(0, s, q_block):
        e = min(a + q_block, s)
        outs.append(block(q[a:e], k[:e], v[:e], a, mm))
    o = jnp.concatenate(outs, 0)
    return W._mm(o.reshape(s, h * dv), p["wo"], mm)


def swiglu(p, x, mm: str = "f32"):
    return W._mm(jax.nn.silu(W._mm(x, p["w_gate"], mm))
                 * W._mm(x, p["w_up"], mm), p["w_down"], mm)


def routing(p, x, cfg: dict, mm: str = "f32"):
    """(chosen expert ids [S, K], their weights [S, K]) over all the
    router's experts."""
    scores = jax.nn.sigmoid(W._mm(x, p["router"], mm))
    _, idx = jax.lax.top_k(scores + p["router_bias"],
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, w / jnp.sum(w, -1, keepdims=True) * cfg["routed_scaling_factor"]


def moe_routed(p, x, cfg: dict, first: int, mm: str = "f32"):
    """The held experts' part of the MoE layer for one row x [S, d]: held
    expert ``j`` is expert ``first + j`` of the router."""
    idx, w = routing(p, x, cfg, mm)
    y = jnp.zeros_like(x)
    for j in range(p["w_up"].shape[0]):
        wt = jnp.sum(jnp.where(idx == first + j, w, 0.0), axis=-1)
        e = {k: p[k][j] for k in ("w_gate", "w_up", "w_down")}
        y = y + wt[:, None] * swiglu(e, x, mm)
    return y


def _slice(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def _block(p, x, cfg, first, mm, q_block):
    eps = cfg["rms_norm_eps"]
    x = x + mla(p["attn"], W.rms_norm(x, p["ln1"], eps), cfg, mm, q_block)
    y = W.rms_norm(x, p["ln2"], eps)
    if "mlp" in p:
        return x + swiglu(p["mlp"], y, mm)
    return x + moe_routed(p["moe"], y, cfg, first, mm) + swiglu(
        p["shared"], y, mm)


# one compiled unit per layer kind and shape, rematerialised in the
# backward; the loss around the layers runs op by op, so the layers that
# share a shape share one compile (forward and backward)
_layer = jax.jit(jax.checkpoint(_block, static_argnums=(2, 3, 4, 5)),
                 static_argnums=(2, 3, 4, 5))


def row_logits(params, tokens, cfg: dict, mm: str = "f32",
               q_block: int = Q_BLOCK):
    """Logits [S, V] of one row ``tokens`` [S]."""
    first = cfg["first_held_expert"]
    layer = _layer
    cfg_t = _frozen(_numbers(cfg))
    x = params["embed"][tokens]
    for p in params["lead"]:
        x = layer(p, x, cfg_t, first, mm, q_block)
    for i in range(cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]):
        x = layer(_slice(params["layers"], i), x, cfg_t, first, mm, q_block)
    x = W.rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return W._mm(x, params["unembed"], mm)


def row_loss(params, tokens, cfg: dict, mm: str = "f32",
             q_block: int = Q_BLOCK):
    """Mean next-token cross-entropy of one row ``tokens`` [S]."""
    logits = row_logits(params, tokens, cfg, mm, q_block)[:-1]
    gold = jnp.take_along_axis(logits, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


class _frozen(dict):
    """A hashable config, so that it can be a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _numbers(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def loss_and_grad(params, tokens, cfg: dict, mm: str = "f32",
                  q_block: int = Q_BLOCK):
    """Loss and gradient over the rows of ``tokens`` [B, S], one row at a
    time (every row has as many targets, so the mean over rows is the mean
    of the rows' means)."""
    def fn(p, t):
        return jax.value_and_grad(row_loss)(p, t, cfg, mm, q_block)

    tot_l, tot_g = 0.0, None
    for row in np.asarray(tokens):
        l, g = fn(params, jnp.asarray(row))
        tot_l = tot_l + l
        tot_g = g if tot_g is None else jax.tree.map(jnp.add, tot_g, g)
        del g
    n = tokens.shape[0]
    if n == 1:
        return tot_l, tot_g
    return tot_l / n, jax.tree.map(lambda a: a / n, tot_g)


@functools.partial(jax.jit, static_argnums=2)
def _delta(g, h, shape):
    """The uplink's ``flatten(g) - h``, fused so that no flat copy of the
    gradient stands beside the result."""
    return W.flatten(g, shape) - h


@functools.partial(jax.jit, donate_argnums=0)
def _axpy(a, x, alpha):
    return a + alpha * x


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _server(hbar, dsum, alpha, n):
    """(ghat, the new hbar) from the summed uplink."""
    return hbar + dsum / n, hbar + alpha * dsum / n


def run(params, batches: Sequence[dict], cfg: dict, wire: dict,
        n_workers: int, round0: int, lr: float, steps: int = 3,
        mm: str = "f32", q_block: int = Q_BLOCK) -> dict:
    """``steps`` Artemis steps from ``params`` (a tree of host arrays) over
    ``batches`` (each {"tokens": [n_workers * b, S]}, worker ``w`` holding
    rows ``w*b:(w+1)*b``), as ``ref_whisper.run`` does them.  Returns each
    step's loss and the per-leaf norms of the first gradient Adam received,
    of the parameters' change over all steps, and of the first raw mean
    gradient."""
    s = int(wire["s"])
    leaves0, treedef = jax.tree.flatten(params)
    shape = bucket_layout(sum(int(np.prod(a.shape)) for a in leaves0),
                          wire["bucket_bytes"], wire["max_buckets"],
                          wire["row"])
    c = shape[-1]
    omega = min(c / s ** 2, math.sqrt(c) / s)
    alpha = 1.0 / (2.0 * (omega + 1.0))
    cur = [jnp.asarray(a, jnp.float32) for a in leaves0]
    m = [jnp.zeros_like(a) for a in cur]
    v = [jnp.zeros_like(a) for a in cur]
    h = [jnp.zeros(shape, jnp.float32) for _ in range(n_workers)]
    hbar = jnp.zeros(shape, jnp.float32)
    root = jax.random.PRNGKey(int(wire["key_seed"]))
    losses, first_g, raw_g = [], None, None
    for k in range(steps):
        toks = np.asarray(batches[k]["tokens"])
        b = toks.shape[0] // n_workers
        base = jax.random.fold_in(root, jnp.int32(round0 + k))
        ptree = jax.tree.unflatten(treedef, cur)
        dsum, lsum, gsum = None, 0.0, None
        dhats = []
        for w in range(n_workers):
            l, g = loss_and_grad(ptree, toks[w * b:(w + 1) * b], cfg, mm,
                                 q_block)
            lsum += float(l)
            if k == 0 and n_workers == 1:
                raw_g = W.leaf_norms(g)
            elif k == 0:
                gsum = g if gsum is None else jax.tree.map(jnp.add, gsum, g)
            up = jax.random.fold_in(base, jnp.int32(w + 1))
            d = _delta(g, h[w], shape)
            del g
            dhats.append(W.round_rows(up, d, s))
            del d
            dsum = dhats[-1] if dsum is None else dsum + dhats[-1]
        if gsum is not None:
            raw_g = W.leaf_norms(gsum) / n_workers
            del gsum
        del ptree
        for w in range(n_workers):
            h[w] = _axpy(h[w], dhats[w], alpha)
        del dhats
        ghat, hbar = _server(hbar, dsum, alpha, n_workers)
        del dsum
        ghat = W.round_rows(jax.random.fold_in(base, jnp.int32(0)), ghat, s)
        g_leaves = jax.tree.leaves(W.unflatten(ghat, jax.tree.unflatten(
            treedef, cur)))
        del ghat
        if k == 0:
            first_g = W.leaf_norms(g_leaves)
        losses.append(lsum / n_workers)
        for i in range(len(cur)):
            cur[i], m[i], v[i] = W.adam(cur[i], m[i], v[i], g_leaves[i], k,
                                        lr)
            g_leaves[i] = None
    change = np.array([float(jnp.linalg.norm((a - jnp.asarray(a0)).ravel()))
                       for a, a0 in zip(cur, leaves0)])
    return {"losses": losses, "first_grad": first_g, "change": change,
            "raw_grad": raw_g}
