"""kanana-2-30b-a3b at its REDUCED size on the CPU, against the plain
float32 reference (``benchmarks/chip/harness/ref_kanana.py``, the one the
chip cell's comparison uses) on seeded random weights: latent attention,
the MoE layer on each share of its experts, the shares adding up to the
uncut layer, dropless routing, the whole model's loss and gradient, and one
``train.main`` run through the bucketed Artemis wire.

Tolerances: program and reference both compute in float32 here (the
program's compute dtype is set to float32); what differs is the order of
summation (the program's q-chunked attention, grouped matmul and
scatter-add against the reference's query blocks and dense per-expert
loop), which moves float32 results by a few ulps per reduction: 2e-5
relative on outputs, 1e-4 on gradients after the backward's longer chains.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import train
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models.model import build_model

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip"))
from harness import ref_kanana as REF  # noqa: E402

CFG = dataclasses.replace(configs.get_config("kanana-2-30b-a3b", reduced=True),
                          compute_dtype="float32")
B, S = 2, 128
RTOL_OUT, RTOL_GRAD = 2e-5, 1e-4


def ref_cfg(cfg):
    """The reference's configuration (the HF key names of the cell's
    file) for a ModelConfig."""
    return {"num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": cfg.hd, "qk_rope_head_dim": cfg.qk_rope_dim,
            "v_head_dim": cfg.v_hd, "kv_lora_rank": cfg.kv_lora_rank,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "num_experts_per_tok": cfg.top_k,
            "routed_scaling_factor": cfg.routed_scale,
            "first_held_expert": cfg.first_held,
            "num_hidden_layers": cfg.n_layers,
            "first_k_dense_replace": cfg.first_dense}


def close(a, b, rtol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.linalg.norm(a - b) <= rtol * np.linalg.norm(b), (
        np.linalg.norm(a - b) / np.linalg.norm(b))


def _x(key=1, shape=(B, S, CFG.d_model)):
    return jax.random.normal(jax.random.PRNGKey(key), shape)


def _moe_params(cfg, key=2, held=None):
    """Router over all experts with a random selection bias, weights of
    ``held`` experts (default: this share's)."""
    p = MOE.init_moe(jax.random.PRNGKey(key), cfg.d_model, cfg.d_ff,
                     cfg.n_experts, held or cfg.n_held, cfg.activation,
                     router=cfg.router)
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(key + 1),
                                                (cfg.n_experts,))
    return p


def test_mla_matches_reference():
    p = MLA.init_mla(jax.random.PRNGKey(0), CFG.d_model, CFG.n_heads, CFG.hd,
                     CFG.qk_rope_dim, CFG.v_hd, CFG.kv_lora_rank)
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                           p["kv_norm"].shape)
    x = _x()
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    out, _ = MLA.mla_apply(p, x, pos, n_heads=CFG.n_heads, nope=CFG.hd,
                           rope_dim=CFG.qk_rope_dim, v_dim=CFG.v_hd,
                           rope_theta=CFG.rope_theta, eps=CFG.norm_eps,
                           q_chunk=CFG.q_chunk)
    # the reference in blocks of 48 queries (3 blocks, the last partial)
    ref = jnp.stack([REF.mla(p, x[i], ref_cfg(CFG), q_block=48)
                     for i in range(B)])
    assert out.shape == (B, S, CFG.d_model)
    close(out, ref, RTOL_OUT)


@pytest.mark.parametrize("shard", [0, 3])
def test_moe_layer_matches_reference(shard):
    cfg = dataclasses.replace(CFG, expert_shard=shard)
    p = _moe_params(cfg)
    shared = L.init_mlp(jax.random.PRNGKey(5), cfg.d_model,
                        cfg.n_shared * cfg.d_ff, cfg.activation)
    x = _x()
    out, stats = MOE.moe_apply(p, x, top_k=cfg.top_k,
                               first_expert=cfg.first_held, router="sigmoid",
                               routed_scale=cfg.routed_scale, shared=shared)
    rc = ref_cfg(cfg)
    ref = jnp.stack([REF.moe_routed(p, x[i], rc, cfg.first_held)
                     + REF.swiglu(shared, x[i]) for i in range(B)])
    close(out, ref, RTOL_OUT)
    idx, _ = jax.vmap(lambda r: REF.routing(p, r, rc))(x)
    held = (idx >= cfg.first_held) & (idx < cfg.first_held + cfg.n_held)
    assert float(stats["moe_held_assignments"]) == int(held.sum())
    loads = [int((idx == cfg.first_held + j).sum()) for j in range(cfg.n_held)]
    assert float(stats["moe_max_load"]) == max(loads)


def test_shares_add_up_to_the_uncut_layer():
    """Each of 16 chips holds one of 16 experts: the routed parts of all
    16 shares, plus the shared experts counted once, are the uncut
    reference layer (all 16 experts held)."""
    cfg = dataclasses.replace(CFG, expert_shards=16)
    full = _moe_params(cfg, held=cfg.n_experts)
    shared = L.init_mlp(jax.random.PRNGKey(5), cfg.d_model,
                        cfg.n_shared * cfg.d_ff, cfg.activation)
    x = _x()
    total, assigned = L.mlp_apply(shared, x, cfg.activation), 0.0
    for k in range(cfg.expert_shards):
        part = {**full, **{w: full[w][k:k + 1]
                           for w in ("w_gate", "w_up", "w_down")}}
        y, stats = MOE.moe_apply(part, x, top_k=cfg.top_k, first_expert=k,
                                 router="sigmoid",
                                 routed_scale=cfg.routed_scale)
        total = total + y
        assigned += float(stats["moe_held_assignments"])
    rc = dict(ref_cfg(cfg), first_held_expert=0)
    ref = jnp.stack([REF.moe_routed(full, x[i], rc, 0)
                     + REF.swiglu(shared, x[i]) for i in range(B)])
    close(total, ref, RTOL_OUT)
    assert assigned == B * S * cfg.top_k


def test_dropless_when_every_token_picks_one_expert():
    """A selection bias that sends every token to held expert 1: that
    expert's load is every token (8x the mean load, far past any capacity
    factor), and no token's part is lost."""
    p = _moe_params(CFG)
    p["router_bias"] = p["router_bias"].at[1].add(100.0)
    x = _x()
    out, stats = MOE.moe_apply(p, x, top_k=CFG.top_k, first_expert=0,
                               router="sigmoid",
                               routed_scale=CFG.routed_scale)
    assert float(stats["moe_max_load"]) == B * S
    ref = jnp.stack([REF.moe_routed(p, x[i], ref_cfg(CFG), 0)
                     for i in range(B)])
    close(out, ref, RTOL_OUT)


_RAGGED_DOT = jax.lax.ragged_dot


@jax.custom_vjp
def _ragged_dot_with_stale_rows(a, w, sizes):
    """``jax.lax.ragged_dot`` as XLA's TPU kernel leaves it: rows past the
    groups hold stale values, in the output and in the backward's
    cotangent for ``a``."""
    stale = (jnp.arange(a.shape[0]) >= jnp.sum(sizes))[:, None]
    return jnp.where(stale, 1e4, _RAGGED_DOT(a, w, sizes))


def _stale_bwd(res, ct):
    a, w, sizes = res
    da, dw = jax.vjp(lambda a, w: _RAGGED_DOT(a, w, sizes), a, w)[1](ct)
    stale = (jnp.arange(a.shape[0]) >= jnp.sum(sizes))[:, None]
    return jnp.where(stale, -1e4, da), dw, None


_ragged_dot_with_stale_rows.defvjp(
    lambda a, w, sizes: (_ragged_dot_with_stale_rows(a, w, sizes),
                         (a, w, sizes)), _stale_bwd)


def test_rows_past_the_groups_never_reach_the_result(monkeypatch):
    """Stale rows past the held experts' groups (the assignments to other
    shares) change neither the layer's output nor any gradient."""
    p = _moe_params(CFG)
    x = _x()

    def run():
        def loss(p, x):
            out, _ = MOE.moe_apply(p, x, top_k=CFG.top_k, router="sigmoid",
                                   routed_scale=CFG.routed_scale)
            return jnp.sum(out * jnp.cos(jnp.arange(CFG.d_model))), out
        return jax.grad(loss, argnums=(0, 1), has_aux=True)(p, x)

    (g_p, g_x), out = run()
    monkeypatch.setattr(jax.lax, "ragged_dot", _ragged_dot_with_stale_rows)
    (s_p, s_x), s_out = run()
    for a, b in zip(jax.tree.leaves((out, g_p, g_x)),
                    jax.tree.leaves((s_out, s_p, s_x))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _model_and_params(cfg):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # non-zero norm gains and selection bias, as after some training
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "ln" in name or "router_bias" in name:
            a = 0.05 * jax.random.normal(jax.random.PRNGKey(100 + i), a.shape)
        out.append(a)
    return model, jax.tree_util.tree_unflatten(treedef, out)


def test_model_loss_and_grad_match_reference():
    model, params = _model_and_params(CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, CFG.vocab)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        model.loss, has_aux=True))(params, {"tokens": tokens})
    ref_loss, ref_grads = REF.loss_and_grad(params, tokens, ref_cfg(CFG),
                                            q_block=48)
    close(loss, ref_loss, RTOL_OUT)
    assert float(metrics["nll"]) == pytest.approx(float(loss))
    flat = jax.tree_util.tree_flatten_with_path(ref_grads)[0]
    got = dict(jax.tree_util.tree_flatten_with_path(grads)[0])
    for path, g in flat:
        if "router_bias" in jax.tree_util.keystr(path):
            # the selection bias only picks experts: no gradient
            assert float(jnp.max(jnp.abs(got[path]))) == 0.0
            continue
        close(got[path], g, RTOL_GRAD)


def test_decode_matches_reference_forward():
    """Decoding token by token through the (expanded K/V) cache gives the
    reference's full-forward logits at every position."""
    model, params = _model_and_params(CFG)
    t = 16
    tokens = jax.random.randint(jax.random.PRNGKey(8), (B, t), 0, CFG.vocab)
    step = jax.jit(model.decode_step)
    cache = model.init_cache(B, t)
    ref = jnp.stack([REF.row_logits(params, tokens[i], ref_cfg(CFG))
                     for i in range(B)])
    for i in range(t):
        logits, cache = step(params, cache, tokens[:, i], jnp.int32(i))
        close(logits, ref[:, i], RTOL_OUT)


def test_train_main_runs_the_bucketed_artemis_wire():
    logs = train.main(["--arch", "kanana-2-30b-a3b", "--reduced",
                       "--steps", "2", "--batch", "2", "--seq", "64",
                       "--dist", "artemis", "--log-every", "1"])
    assert len(logs) == 2
    cfg = configs.get_config("kanana-2-30b-a3b", reduced=True)
    routed = 2 * 64 * cfg.top_k * (cfg.n_layers - cfg.first_dense)
    for rec in logs:
        assert np.isfinite(rec["loss"])
        assert 0 < rec["moe_max_load"] <= rec["moe_held_assignments"] < routed
