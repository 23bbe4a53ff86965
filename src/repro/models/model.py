"""The unified model: init / train loss / prefill / decode for every family.

Layers are stacked and iterated with ``lax.scan`` (uniform families) so the
HLO stays small even for 88-layer configs; hybrid (patterned) families scan
over repeating groups.  All long-sequence paths are chunked (attention by
query block, cross-entropy by sequence block, SSM scans by chunk).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.models import ssm as SSM
from repro.models.config import ModelConfig

PyTree = Any


# ---------------------------------------------------------------------------
# per-layer init / apply
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, kind: str, key, cross: bool = False,
                d_ff: Optional[int] = None):
    ks = jax.random.split(key, 8)
    d, dt = cfg.d_model, cfg.pdtype
    p: Dict[str, Any] = {"ln1": jnp.zeros((d,), jnp.float32)}
    if kind in ("attn+mlp", "attn+moe", "la") and cfg.mla:
        p["attn"] = MLA.init_mla(ks[0], d, cfg.n_heads, cfg.hd,
                                 cfg.qk_rope_dim, cfg.v_hd, cfg.kv_lora_rank,
                                 dtype=dt)
    elif kind in ("attn+mlp", "attn+moe", "la"):
        p["attn"] = L.init_attn(ks[0], d, cfg.n_heads, cfg.n_kv, cfg.hd, dtype=dt)
    if kind == "mamba":
        p["mamba"] = SSM.init_mamba(ks[1], d, cfg.ssm_state, cfg.ssm_conv,
                                    cfg.ssm_expand, dtype=dt)
        return p
    if kind == "rg":
        p["rg"] = RG.init_rglru(ks[2], d, cfg.lru_width, dtype=dt)
    p["ln2"] = jnp.zeros((d,), jnp.float32)
    if kind == "attn+moe":
        p["moe"] = MOE.init_moe(ks[3], d, cfg.d_ff, cfg.n_experts, cfg.n_held,
                                cfg.activation, router=cfg.router, dtype=dt)
        if cfg.n_shared:
            p["shared"] = L.init_mlp(ks[6], d, cfg.n_shared * cfg.d_ff,
                                     cfg.activation, dtype=dt)
    else:
        p["mlp"] = L.init_mlp(ks[4], d, d_ff or cfg.d_ff, cfg.activation,
                              dtype=dt)
    if cross:
        p["lnx"] = jnp.zeros((d,), jnp.float32)
        p["xattn"] = L.init_attn(ks[5], d, cfg.n_heads, cfg.n_kv, cfg.hd, dtype=dt)
    return p


def _zero_stats(cfg: ModelConfig) -> Dict[str, jax.Array]:
    """The per-layer statistics a step's layers add up: the MoE
    load-balance loss and, for MoE models, the expert counters."""
    stats = {"aux": jnp.zeros((), jnp.float32)}
    if cfg.family == "moe":
        stats["moe_held_assignments"] = jnp.zeros((), jnp.float32)
        stats["moe_max_load"] = jnp.zeros((), jnp.float32)
    return stats


def _add_stats(a, b):
    """Sums over layers, but the largest load is a maximum."""
    return {k: jnp.maximum(a[k], b[k]) if k == "moe_max_load" else a[k] + b[k]
            for k in a}


def _apply_layer(cfg: ModelConfig, kind: str, p, x, positions, *, causal=True,
                 cache=None, pos=None, cross_kv=None):
    """Returns (x, stats, new_cache); ``stats`` as ``_zero_stats``."""
    cd = cfg.cdtype
    stats = _zero_stats(cfg)
    new_cache = cache

    def norm(g, y):
        return L.rms_norm(y, g, cfg.norm_eps)

    if kind == "mamba":
        y, st = SSM.mamba_apply(_cast(p["mamba"], cd), norm(p["ln1"], x),
                                d_state=cfg.ssm_state, chunk=cfg.scan_chunk,
                                state=cache)
        return x + y, stats, st

    if kind == "rg":
        y, st = RG.rglru_apply(_cast(p["rg"], cd), norm(p["ln1"], x),
                               chunk=cfg.scan_chunk, state=cache)
        x = x + y
        new_cache = st
    elif cfg.mla:
        y, new_cache = MLA.mla_apply(
            _cast(p["attn"], cd), norm(p["ln1"], x), positions,
            n_heads=cfg.n_heads, nope=cfg.hd, rope_dim=cfg.qk_rope_dim,
            v_dim=cfg.v_hd, rope_theta=cfg.rope_theta, eps=cfg.norm_eps,
            causal=causal, q_chunk=cfg.q_chunk,
            softmax_dtype=jnp.dtype(cfg.softmax_dtype), cache=cache, pos=pos)
        x = x + y
    else:  # attention kinds
        window = 0
        if kind == "la":
            window = cfg.local_window
        elif cfg.attn_kind == "sliding":
            window = cfg.window
        y, nc = L.attn_apply(_cast(p["attn"], cd), norm(p["ln1"], x), positions,
                             n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
                             window=window, causal=causal,
                             rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk,
                             softmax_dtype=jnp.dtype(cfg.softmax_dtype),
                             cache=cache, pos=pos)
        x = x + y
        new_cache = nc
        if "xattn" in p:
            y, _ = L.attn_apply(_cast(p["xattn"], cd), norm(p["lnx"], x),
                                positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
                                head_dim=cfg.hd, causal=False,
                                rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk,
                                cross_kv=cross_kv)
            x = x + y

    if "moe" in p:
        # the router stays float32: its scores pick the experts
        moe_p = {**_cast(p["moe"], cd), "router": p["moe"]["router"]}
        y, moe_stats = MOE.moe_apply(
            moe_p, norm(p["ln2"], x), top_k=cfg.top_k,
            first_expert=cfg.first_held, router=cfg.router,
            routed_scale=cfg.routed_scale, activation=cfg.activation,
            shared=_cast(p["shared"], cd) if "shared" in p else None)
        stats = _add_stats(stats, moe_stats)
        x = x + y
    elif "mlp" in p:
        x = x + L.mlp_apply(_cast(p["mlp"], cd), norm(p["ln2"], x), cfg.activation)
    return x, stats, new_cache


def _remat_policy(cfg):
    if cfg.remat_policy is None:
        return None
    return getattr(jax.checkpoint_policies, cfg.remat_policy)


def _cast(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype in (jnp.float32, jnp.bfloat16) else a,
        tree)


def _init_layer_cache(cfg: ModelConfig, kind: str, b: int, cache_len: int):
    if kind == "mamba":
        return SSM.init_mamba_state(b, cfg.d_model, cfg.ssm_state, cfg.ssm_conv,
                                    cfg.ssm_expand, dtype=cfg.cdtype)
    if kind == "rg":
        return RG.init_rglru_state(b, cfg.lru_width, dtype=cfg.cdtype)
    clen = cache_len
    if kind == "la":
        clen = min(cache_len, cfg.local_window)
    elif cfg.attn_kind == "sliding":
        clen = min(cache_len, cfg.window)
    if cfg.mla:     # keys and values expanded per head
        return L.init_attn_cache(b, clen, cfg.n_heads,
                                 cfg.hd + cfg.qk_rope_dim, dtype=cfg.cdtype,
                                 v_head_dim=cfg.v_hd)
    return L.init_attn_cache(b, clen, cfg.n_kv, cfg.hd, dtype=cfg.cdtype)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg.validate()
        self.kinds = cfg.layer_kinds()
        # leading dense layers (MoE models) run unscanned before the stack
        self.n_lead = cfg.first_dense if cfg.family == "moe" else 0
        self.n_stack = cfg.n_layers - self.n_lead
        self.uniform = len(set(self.kinds[self.n_lead:])) == 1
        if not self.uniform:
            period = len(cfg.pattern)
            self.n_groups = cfg.n_layers // period
            self.tail_kinds = self.kinds[self.n_groups * period:]
        # sharding hooks (set by the launcher; see launch/mesh.py):
        #   layer_constraint(p_slice) — pins the per-layer param slice inside
        #     the scan body so GSPMD all-gathers ONE layer per iteration
        #     instead of hoisting a full-stack gather out of the loop;
        #   act_constraint(x) — anchors activation batch sharding.
        self.layer_constraint = None
        self.act_constraint = None

    def set_sharding(self, layer_constraint=None, act_constraint=None):
        self.layer_constraint = layer_constraint
        self.act_constraint = act_constraint
        return self

    # -- init ---------------------------------------------------------------

    def init(self, key) -> PyTree:
        cfg = self.cfg
        ks = jax.random.split(key, 8)
        scale = 0.02
        params: Dict[str, Any] = {
            "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model), jnp.float32)
                      * scale).astype(cfg.pdtype),
            "unembed": (jax.random.normal(ks[1], (cfg.d_model, cfg.vocab), jnp.float32)
                        * scale).astype(cfg.pdtype),
            "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        }
        cross = cfg.family == "encdec"
        if self.n_lead:
            lkeys = jax.random.split(ks[7], self.n_lead)
            params["lead"] = [
                _init_layer(cfg, "attn+mlp", k, d_ff=cfg.dense_ff or None)
                for k in lkeys]
        if self.uniform:
            kind = self.kinds[-1]
            lkeys = jax.random.split(ks[2], self.n_stack)
            per = [_init_layer(cfg, kind, k, cross=cross) for k in lkeys]
            params["layers"] = jax.tree.map(lambda *a: jnp.stack(a), *per)
        else:
            period = len(cfg.pattern)
            gkeys = jax.random.split(ks[6], self.n_groups * period).reshape(
                self.n_groups, period, -1)
            groups = []
            for j, kind in enumerate(cfg.pattern):
                per = [_init_layer(cfg, kind, gkeys[g, j]) for g in range(self.n_groups)]
                groups.append(jax.tree.map(lambda *a: jnp.stack(a), *per))
            params["groups"] = tuple(groups)
            tkeys = jax.random.split(ks[3], max(len(self.tail_kinds), 1))
            params["tail"] = [
                _init_layer(cfg, kind, tkeys[i])
                for i, kind in enumerate(self.tail_kinds)]
        if cfg.family == "encdec":
            ekeys = jax.random.split(ks[4], cfg.enc_layers)
            per = [_init_layer(cfg, "attn+mlp", k) for k in ekeys]
            params["encoder"] = jax.tree.map(lambda *a: jnp.stack(a), *per)
            params["enc_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
        if cfg.family in ("vlm", "encdec"):
            params["frontend_proj"] = (
                jax.random.normal(ks[5], (cfg.d_model, cfg.d_model), jnp.float32)
                * cfg.d_model ** -0.5).astype(cfg.pdtype)
        return params

    # -- shared stacks --------------------------------------------------------

    def _run_lead(self, params, x, positions, *, cache=None, pos=None):
        """The leading dense layers, each its own remat block."""
        cfg = self.cfg
        stats, new_cache = _zero_stats(cfg), []

        def layer(p_l, xx, c_l):
            if self.act_constraint is not None:
                xx = self.act_constraint(xx)
            return _apply_layer(cfg, "attn+mlp", p_l, xx.astype(cfg.cdtype),
                                positions, cache=c_l, pos=pos)

        if cfg.remat:
            layer = jax.checkpoint(layer, policy=_remat_policy(cfg))
        for i, p_l in enumerate(params["lead"]):
            x, st, nc = layer(p_l, x, None if cache is None else cache[i])
            stats = _add_stats(stats, st)
            new_cache.append(nc)
        return x, stats, new_cache

    def _run_uniform(self, params, x, positions, *, causal=True, cache=None,
                     pos=None, cross_kv=None):
        cfg = self.cfg
        kind = self.kinds[-1]

        def body(carry, inp):
            xx, stats = carry
            if cache is None and cross_kv is None:
                p_l, c_l, xkv = inp, None, None
            elif cache is None:
                p_l, xkv = inp
                c_l = None
            elif cross_kv is None:
                p_l, c_l = inp
                xkv = None
            else:
                p_l, c_l, xkv = inp
            if self.layer_constraint is not None:
                p_l = self.layer_constraint(p_l)
            if self.act_constraint is not None:
                xx = self.act_constraint(xx)
            # pin the residual stream (== the remat-saved stack) to the
            # compute dtype: anything that upcasts it to f32 doubles the
            # dominant memory-roofline term (measured on mistral-large)
            xx = xx.astype(cfg.cdtype)
            xx, st, nc = _apply_layer(cfg, kind, p_l, xx, positions,
                                      causal=causal, cache=c_l, pos=pos,
                                      cross_kv=xkv)
            return (xx, _add_stats(stats, st)), nc

        if cfg.remat:
            body = jax.checkpoint(body, policy=_remat_policy(cfg))
        xs: Any = params
        if cache is not None and cross_kv is not None:
            xs = (params, cache, cross_kv)
        elif cache is not None:
            xs = (params, cache)
        elif cross_kv is not None:
            xs = (params, cross_kv)
        (x, stats), new_cache = jax.lax.scan(body, (x, _zero_stats(cfg)), xs,
                                             unroll=min(cfg.scan_unroll,
                                                        self.n_stack))
        return x, stats, new_cache

    def _run_pattern(self, params, x, positions, *, cache=None, pos=None):
        cfg = self.cfg
        pattern = cfg.pattern

        def gbody(carry, inp):
            xx, stats = carry
            ps = inp[0] if cache is not None else inp
            cs = inp[1] if cache is not None else (None,) * len(pattern)
            if self.layer_constraint is not None:
                ps = tuple(self.layer_constraint(p) for p in ps)
            if self.act_constraint is not None:
                xx = self.act_constraint(xx)
            ncs = []
            for kind, p_l, c_l in zip(pattern, ps, cs):
                xx, st, nc = _apply_layer(cfg, kind, p_l, xx, positions,
                                          cache=c_l, pos=pos)
                stats = _add_stats(stats, st)
                ncs.append(nc)
            return (xx, stats), tuple(ncs)

        if cfg.remat:
            gbody = jax.checkpoint(gbody, policy=_remat_policy(cfg))
        xs = (params["groups"], cache["groups"]) if cache is not None else params["groups"]
        (x, stats), new_gcache = jax.lax.scan(
            gbody, (x, _zero_stats(cfg)), xs,
            unroll=min(cfg.scan_unroll, max(self.n_groups, 1)))
        new_tail = []
        for i, kind in enumerate(self.tail_kinds):
            c_l = cache["tail"][i] if cache is not None else None
            x, st, nc = _apply_layer(cfg, kind, params["tail"][i], x, positions,
                                     cache=c_l, pos=pos)
            stats = _add_stats(stats, st)
            new_tail.append(nc)
        new_cache = ({"groups": new_gcache, "tail": new_tail}
                     if cache is not None else None)
        return x, stats, new_cache

    def _encode(self, params, frames):
        """Audio encoder over stubbed frame embeddings [B, F, d]."""
        cfg = self.cfg
        x = (frames.astype(cfg.cdtype) @ _cast(params["frontend_proj"], cfg.cdtype))
        pos = jnp.arange(frames.shape[1])[None, :].repeat(frames.shape[0], 0)

        def body(carry, p_l):
            xx, _ = carry
            xx, _, _ = _apply_layer(cfg, "attn+mlp", p_l, xx, pos, causal=False)
            return (xx, jnp.zeros((), jnp.float32)), None

        if cfg.remat:
            body = jax.checkpoint(body, policy=_remat_policy(cfg))
        (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                 params["encoder"])
        return L.rms_norm(x, params["enc_norm"], cfg.norm_eps)

    def _cross_kv(self, params, enc_out):
        """Precompute per-decoder-layer cross K/V: [L, B, F, KV, hd]."""
        cfg = self.cfg
        b, f, _ = enc_out.shape
        wk = _cast(params["layers"]["xattn"]["wk"], cfg.cdtype)   # [L, d, kv*hd]
        wv = _cast(params["layers"]["xattn"]["wv"], cfg.cdtype)
        ck = jnp.einsum("bfd,ldh->lbfh", enc_out, wk).reshape(
            -1, b, f, cfg.n_kv, cfg.hd)
        cv = jnp.einsum("bfd,ldh->lbfh", enc_out, wv).reshape(
            -1, b, f, cfg.n_kv, cfg.hd)
        k_pos = jnp.arange(f)
        return (ck, cv, jnp.broadcast_to(k_pos, (ck.shape[0],) + k_pos.shape))

    # -- forward (train / prefill) -------------------------------------------

    def _forward(self, params, batch):
        """Returns (hidden states [B, S_total, d], stats, text_offset)."""
        cfg = self.cfg
        tok = batch["tokens"]
        # gather f32 rows locally, convert to bf16 BEFORE the model-axis
        # all-gather of activations (casting the whole table first makes XLA
        # gather-then-convert, moving f32 activations over ICI; §Perf)
        x = params["embed"][tok].astype(cfg.cdtype)
        offset = 0
        if cfg.family == "vlm":
            emb = batch["embeds"].astype(cfg.cdtype) @ _cast(
                params["frontend_proj"], cfg.cdtype)
            x = jnp.concatenate([emb, x], axis=1)
            offset = cfg.n_patches
        positions = jnp.arange(x.shape[1])[None, :].repeat(x.shape[0], 0)
        cross_kv = None
        if cfg.family == "encdec":
            enc_out = self._encode(params, batch["frames"])
            cross_kv = self._cross_kv(params, enc_out)
        stats = _zero_stats(cfg)
        if self.n_lead:
            x, stats, _ = self._run_lead(params, x, positions)
        if self.uniform:
            x, st, _ = self._run_uniform(params["layers"], x, positions,
                                         cross_kv=cross_kv)
        else:
            x, st, _ = self._run_pattern(params, x, positions)
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, _add_stats(stats, st), offset

    def loss(self, params, batch):
        """Next-token cross-entropy (+ the weighted MoE load-balance loss).
        labels: tokens shifted.  Metrics: ``nll`` and the layers' stats
        (``_zero_stats``)."""
        cfg = self.cfg
        x, stats, off = self._forward(params, batch)
        tok = batch["tokens"]
        h = x[:, off:, :]                       # text region
        labels = jnp.concatenate(
            [tok[:, 1:], jnp.full((tok.shape[0], 1), -1, tok.dtype)], axis=1)
        nll = L.chunked_xent(h, _cast(params["unembed"], cfg.cdtype), labels,
                             chunk=cfg.xent_chunk)
        return nll + cfg.aux_weight * stats["aux"], {"nll": nll, **stats}

    def prefill_logits(self, params, batch):
        """Forward returning ONLY the last position's logits [B, V]."""
        cfg = self.cfg
        x, _, _ = self._forward(params, batch)
        last = x[:, -1, :]
        return (last @ _cast(params["unembed"], cfg.cdtype)).astype(jnp.float32)

    # -- decode ---------------------------------------------------------------

    def init_cache(self, batch_size: int, cache_len: int):
        cfg = self.cfg
        if self.uniform:
            kind = self.kinds[-1]
            one = _init_layer_cache(cfg, kind, batch_size, cache_len)
            cache = {"layers": jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_stack,) + a.shape).copy(),
                one)}
            if self.n_lead:
                cache["lead"] = [
                    _init_layer_cache(cfg, "attn+mlp", batch_size, cache_len)
                    for _ in range(self.n_lead)]
            return cache
        groups = []
        for kind in cfg.pattern:
            one = _init_layer_cache(cfg, kind, batch_size, cache_len)
            groups.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.n_groups,) + a.shape).copy(), one))
        tail = [_init_layer_cache(cfg, kind, batch_size, cache_len)
                for kind in self.tail_kinds]
        return {"groups": tuple(groups), "tail": tail}

    def decode_step(self, params, cache, token, pos, enc_out=None):
        """One serve step: token [B] int32, pos scalar int32.

        Returns (logits [B, V], new_cache).
        """
        cfg = self.cfg
        b = token.shape[0]
        x = _cast(params["embed"], cfg.cdtype)[token][:, None, :]   # [B, 1, d]
        positions = jnp.full((b, 1), pos, jnp.int32)
        cross_kv = None
        if cfg.family == "encdec":
            assert enc_out is not None
            cross_kv = self._cross_kv(params, enc_out)
        if self.uniform:
            new_cache = {}
            if self.n_lead:
                x, _, new_cache["lead"] = self._run_lead(
                    params, x, positions, cache=cache["lead"], pos=pos)
            x, _, nc = self._run_uniform(params["layers"], x, positions,
                                         cache=cache["layers"], pos=pos,
                                         cross_kv=cross_kv)
            new_cache["layers"] = nc
        else:
            x, _, new_cache = self._run_pattern(params, x, positions,
                                                cache=cache, pos=pos)
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = (x[:, 0] @ _cast(params["unembed"], cfg.cdtype)).astype(jnp.float32)
        return logits, new_cache


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
