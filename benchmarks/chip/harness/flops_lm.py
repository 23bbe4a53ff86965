"""Operations computed from shapes for the decoder-only LM cells (latent
attention, a leading dense layer, MoE layers with held experts and shared
experts), for MFU and the held experts' roofline share.

The count follows ``flops.py``: forward matrix products at 2 m k n, three
times that for forward plus backward, no recomputation; the full attention
square (no halving for the causal mask), as PaLM's accounting does; norms,
softmax, routing's top-k and elementwise work left out.  The embedding is a
gather, not a product.  Held experts are counted at their expected share
of the assignments, ``tokens * top_k * held / published``: each assignment
is one token through one expert's three [d, f] products.  The configuration
is the cell's file (HF key names).
"""
from __future__ import annotations


def expert_flops_per_assignment(cfg: dict) -> float:
    """Forward FLOPs of one token through one routed expert (SwiGLU)."""
    return 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def lm_forward_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Forward matmul FLOPs per step by part, for ``batch`` rows of ``seq``
    tokens."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    f, e = cfg["moe_intermediate_size"], cfg["n_routed_experts_published"]
    held, k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    t = batch * seq
    proj = 2 * t * (d * h * (nope + rd) + d * (r + rd) + r * h * (nope + dv)
                    + h * dv * d)
    square = 2 * batch * seq * seq * h * (nope + rd + dv)
    return {
        "attn_proj": (n_dense + n_moe) * proj,
        "attn_square": (n_dense + n_moe) * square,
        "dense_mlp": n_dense * 3 * 2 * t * d * cfg["intermediate_size"],
        "router": n_moe * 2 * t * d * e,
        "experts": n_moe * t * k * held / e * expert_flops_per_assignment(cfg),
        "shared": n_moe * 3 * 2 * t * d * f * cfg["n_shared_experts"],
        "head": 2 * t * d * cfg["vocab_size"],
    }


def lm_train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Forward plus backward model FLOPs of one step over ``batch`` rows."""
    return 3.0 * sum(lm_forward_flops(cfg, batch, seq).values())
