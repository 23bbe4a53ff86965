"""Mixture-of-Experts layer: dropless, over the experts this chip holds.

Expert parallelism is stated, not inferred: each MoE layer's ``E`` routed
experts are divided over the chips of an expert group, and a layer is given
the contiguous share it holds (``first_expert`` and the leading dim of its
expert weights).  It routes every token over all ``E`` experts, as the
router's published width says, and computes the part of the result that its
own experts give; the other shares' parts belong to other chips and are
neither computed nor stood in for.  With one share (``first_expert`` 0 and
all ``E`` experts held) it is the whole layer.

Routing (per token, scores in float32):

* ``softmax``: ``s = softmax(x Wr)``, top-k of ``s``;
* ``sigmoid`` (DeepSeek-V3's ``noaux_tc``): ``s = sigmoid(x Wr)``, top-k of
  ``s + b`` with ``b`` the selection bias (``router_bias``, not trained);

then weights ``w = s_sel / sum(s_sel) * routed_scale``.

Dispatch is dropless: the token-expert assignments are sorted by held
expert (assignments to other shares last), each held expert's rows go
through its SwiGLU as one grouped matmul (``jax.lax.ragged_dot``, group
sizes = the held experts' loads), and the outputs are scattered back to
their tokens with their weights.  There is no capacity and nothing is
dropped.  A shared expert (``shared``), applied to every token, is added.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L


def init_moe(key, d_model, d_ff, n_experts, n_held, activation,
             router="softmax", dtype=jnp.float32):
    """Router over all ``n_experts``; weights of the ``n_held`` held."""
    ks = jax.random.split(key, 4)
    p = {
        "router": L._init(ks[0], (d_model, n_experts), scale=0.02,
                          dtype=jnp.float32),
        "w_up": L._init(ks[1], (n_held, d_model, d_ff), dtype=dtype),
        "w_down": L._init(ks[2], (n_held, d_ff, d_model), dtype=dtype),
    }
    if activation == "silu":
        p["w_gate"] = L._init(ks[3], (n_held, d_model, d_ff), dtype=dtype)
    if router == "sigmoid":
        p["router_bias"] = jnp.zeros((n_experts,), jnp.float32)
    return p


def route(p, x, *, top_k: int, router: str = "softmax",
          routed_scale: float = 1.0):
    """x: [T, d] -> (scores [T, E] f32, expert ids [T, K], weights [T, K])."""
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    if router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        pick = scores + jax.lax.stop_gradient(p["router_bias"])
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        pick = scores
    _, idx = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * routed_scale
    return scores, idx, w


def _grouped_ffn(p, xs, sizes, activation):
    """Each held expert's FFN over its rows of ``xs`` (sorted by expert;
    rows past ``sum(sizes)`` give zeros).

    Rows past the groups belong to no expert, and XLA's TPU kernel leaves
    its output there unwritten, in the forward and in the backward's
    product for the rows' cotangent.  So each product's rows past the
    groups are zeroed on the way out, and on the way in, which zeroes the
    cotangent that the backward sends back through them."""
    rows = (jnp.arange(xs.shape[0]) < jnp.sum(sizes))[:, None]

    def mm(a, w):
        a = jnp.where(rows, a, 0)
        return jnp.where(rows, jax.lax.ragged_dot(a, w, sizes), 0)

    if activation == "silu":
        h = jax.nn.silu(mm(xs, p["w_gate"])) * mm(xs, p["w_up"])
    elif activation == "gelu":
        h = jax.nn.gelu(mm(xs, p["w_up"]))
    else:
        h = jnp.square(jax.nn.relu(mm(xs, p["w_up"])))
    return mm(h, p["w_down"])


def moe_apply(p, x, *, top_k: int, first_expert: int = 0,
              router: str = "softmax", routed_scale: float = 1.0,
              activation: str = "silu", shared=None):
    """x: [B, S, d] -> (out [B, S, d], stats).

    ``stats``: ``aux`` (the load-balance loss ``E * sum_e mean_t(p_te)^2``
    of the normalised scores), ``moe_held_assignments`` (token-expert
    assignments to held experts) and ``moe_max_load`` (the largest held
    expert's load), all float32 scalars.
    """
    with jax.named_scope("moe"):
        b, s, d = x.shape
        t = b * s
        xf = x.reshape(t, d)
        n_held = p["w_up"].shape[0]
        with jax.named_scope("route"):
            scores, idx, w = route(p, xf, top_k=top_k, router=router,
                                   routed_scale=routed_scale)
            local = (idx - first_expert).reshape(-1)           # [T*K]
            held = (local >= 0) & (local < n_held)
            expert = jnp.where(held, local, n_held)
            order = jnp.argsort(expert, stable=True)
            tok = order // top_k                 # token of each sorted row
            w_rows = jnp.where(held, w.reshape(-1), 0.0)[order]
            sizes = jnp.bincount(expert, length=n_held + 1)[:n_held]
        with jax.named_scope("experts"):
            y = _grouped_ffn(p, xf[tok], sizes.astype(jnp.int32), activation)
            out = jnp.zeros((t, d), y.dtype).at[tok].add(
                y * w_rows[:, None].astype(y.dtype))
        if shared is not None:
            with jax.named_scope("shared"):
                out = out + L.mlp_apply(shared, xf, activation)
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        stats = {"aux": scores.shape[-1] * jnp.sum(me * me),
                 "moe_held_assignments": jnp.sum(sizes).astype(jnp.float32),
                 "moe_max_load": jnp.max(sizes).astype(jnp.float32)}
        return out.reshape(b, s, d), stats
