"""ModelConfig: one dataclass describing every supported architecture family."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                 # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int                # 0 for attention-free families
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    activation: str = "silu"    # silu (gated) | gelu | relu2
    # attention
    attn_kind: str = "full"     # full | sliding
    window: int = 4096          # sliding-window size when attn_kind == sliding
    rope_theta: float = 10000.0
    q_chunk: int = 512
    # multi-head latent attention (DeepSeek-V2/V3; on when kv_lora_rank > 0):
    # head_dim is the per-head non-rotary q/k width, qk_rope_dim the rotary
    # width (one rotary key shared by all heads), v_head_dim V's head width
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # MoE (dropless).  n_experts is the router's width; each MoE layer is
    # divided over expert_shards chips and this one holds the contiguous
    # n_experts // expert_shards experts of share expert_shard
    n_experts: int = 0
    top_k: int = 0
    router: str = "softmax"     # softmax | sigmoid (+ selection bias, noaux_tc)
    routed_scale: float = 1.0   # routed_scaling_factor on the top-k weights
    n_shared: int = 0           # shared experts, one SwiGLU of n_shared * d_ff
    expert_shards: int = 1
    expert_shard: int = 0
    aux_weight: float = 0.01    # weight of the load-balance loss
    first_dense: int = 0        # leading dense layers (first_k_dense_replace)
    dense_ff: int = 0           # their MLP width (0: d_ff)
    # SSM (mamba-1)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    scan_chunk: int = 256
    # hybrid (RG-LRU + local attention)
    pattern: Tuple[str, ...] = ()      # period, e.g. ('rg','rg','la')
    lru_width: int = 0
    local_window: int = 2048
    # encoder-decoder (audio)
    enc_layers: int = 0
    n_frames: int = 0           # stubbed audio frame embeddings
    # VLM
    n_patches: int = 0          # stubbed vision patch embeddings
    # numerics / training
    norm_eps: float = 1e-6
    xent_chunk: int = 512
    softmax_dtype: str = "float32"   # attention score/softmax accumulation
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    # None = full remat; else a jax.checkpoint_policies name, e.g.
    # "dots_with_no_batch_dims_saveable" (keep matmul outputs, recompute rest)
    remat_policy: Optional[str] = None
    # unroll factor for the layer scan. 1 = rolled (fast compile; XLA cost
    # analysis counts the body ONCE). Full unroll (= n_layers) gives honest
    # per-step roofline accounting at higher compile cost.
    scan_unroll: int = 1
    source: str = ""            # citation for the assigned config

    @property
    def hd(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // max(self.n_heads, 1)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def v_hd(self) -> int:
        return self.v_head_dim or self.hd

    @property
    def n_held(self) -> int:
        """Routed experts this chip holds in each MoE layer."""
        return self.n_experts // self.expert_shards

    @property
    def first_held(self) -> int:
        return self.expert_shard * self.n_held

    @property
    def sub_quadratic(self) -> bool:
        """Can this arch serve long_500k decode (O(1)/O(window) state)?"""
        return self.family in ("ssm", "hybrid") or self.attn_kind == "sliding"

    @property
    def has_decode(self) -> bool:
        return True   # all assigned families have a decoder

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer kind list of length n_layers."""
        if self.family == "ssm":
            return ("mamba",) * self.n_layers
        if self.family == "hybrid":
            assert self.pattern
            reps = -(-self.n_layers // len(self.pattern))
            return (self.pattern * reps)[: self.n_layers]
        if self.family == "moe":
            return (("attn+mlp",) * self.first_dense
                    + ("attn+moe",) * (self.n_layers - self.first_dense))
        return ("attn+mlp",) * self.n_layers   # dense, vlm, encdec decoder

    def validate(self):
        if self.n_heads:
            assert self.n_heads % max(self.n_kv, 1) == 0, "GQA requires H % KV == 0"
        if self.family == "moe":
            assert self.n_experts > 0 and 0 < self.top_k <= self.n_experts
            assert self.n_experts % self.expert_shards == 0
            assert 0 <= self.expert_shard < self.expert_shards
            assert self.router in ("softmax", "sigmoid")
            assert 0 <= self.first_dense < self.n_layers
        if self.family == "ssm":
            assert self.ssm_state > 0
        if self.family == "hybrid":
            assert self.pattern and self.lru_width > 0
        if self.family == "encdec":
            assert self.enc_layers > 0 and self.n_frames > 0
        if self.family == "vlm":
            assert self.n_patches > 0
        return self
