"""kanana-2-30b-a3b [https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601]
— DeepSeek-V3-style MoE (``model_type`` deepseek_v3): multi-head latent
attention with no query LoRA, 128 sigmoid-routed experts (6 per token,
selection bias, normalised weights x 2.448) plus 2 shared experts, one
leading dense layer.

Published: 48 layers, hidden 2048, 32 heads, ``kv_lora_rank`` 512,
``qk_nope_head_dim`` 128, ``qk_rope_head_dim`` 64, ``v_head_dim`` 128,
expert width 768, dense width 6144, vocabulary 128256, RMSNorm eps 1e-6,
rope theta 1e6.

``CONFIG`` is one chip's share of a stated deployment, at the published
widths: each MoE layer's 128 experts divided over 16 chips (this one holds
experts 0-7; the router keeps all 128), attention, router, shared experts
and the dense layer whole on every chip, the vocabulary divided over 8
chips (16,032 rows), and the dense layer plus 4 MoE layers (the rest lie
on further chips, as pipeline stages): 425.0 M parameters.
"""
import dataclasses
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="kanana-2-30b-a3b", family="moe",
    n_layers=5, d_model=2048, n_heads=32, n_kv=32, d_ff=768, vocab=16032,
    head_dim=128, kv_lora_rank=512, qk_rope_dim=64, v_head_dim=128,
    activation="silu", rope_theta=1e6, norm_eps=1e-6,
    n_experts=128, top_k=6, router="sigmoid", routed_scale=2.448,
    n_shared=2, expert_shards=16, aux_weight=0.0,
    first_dense=1, dense_ff=6144,
    source="https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601",
)

REDUCED = dataclasses.replace(
    CONFIG, name="kanana-reduced", n_layers=3, d_model=64, n_heads=4,
    n_kv=4, d_ff=32, vocab=512, head_dim=16, kv_lora_rank=32,
    qk_rope_dim=8, v_head_dim=8, n_experts=16, top_k=4, expert_shards=4,
    dense_ff=96, q_chunk=64, xent_chunk=64, remat=False)
