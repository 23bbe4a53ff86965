"""Architecture registry + the 4 assigned input shapes + input_specs().

``input_specs`` builds ShapeDtypeStruct stand-ins for every model input --
weak-type-correct, shardable, zero allocation -- used by the multi-pod dry-run.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig

ARCHS = {
    "whisper-tiny": "whisper_tiny",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "minitron-8b": "minitron_8b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "nemotron-4-15b": "nemotron_4_15b",
    "llava-next-mistral-7b": "llava_next_mistral_7b",
    "mixtral-8x22b": "mixtral_8x22b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "mistral-large-123b": "mistral_large_123b",
    "starcoder2-7b": "starcoder2_7b",
    "kanana-2-30b-a3b": "kanana_2_30b_a3b",
}


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    kind: str          # train | prefill | decode
    seq: int
    batch: int


SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", "train", 4_096, 256),
    "prefill_32k": InputShape("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": InputShape("decode_32k", "decode", 32_768, 128),
    "long_500k": InputShape("long_500k", "decode", 524_288, 1),
}


def get_config(arch: str, reduced: bool = False) -> ModelConfig:
    if arch not in ARCHS:
        raise ValueError(f"unknown arch {arch!r}; choose from {sorted(ARCHS)}")
    mod = importlib.import_module(f"repro.configs.{ARCHS[arch]}")
    return mod.REDUCED if reduced else mod.CONFIG


def applicable(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    """Returns None if (arch, shape) should run, else a skip reason."""
    if cfg.family == "encdec" and shape.name == "long_500k":
        return "encoder-decoder ASR family: 500k-token decode is meaningless"
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("full-attention architecture: long_500k requires sub-quadratic "
                "decode (skip noted in DESIGN.md)")
    return None


def input_specs(cfg: ModelConfig, shape: InputShape, model=None) -> dict:
    """ShapeDtypeStruct stand-ins for the step function's data arguments."""
    i32 = jnp.int32
    f32 = jnp.float32
    b, s = shape.batch, shape.seq
    sd = jax.ShapeDtypeStruct

    if shape.kind in ("train", "prefill"):
        if cfg.family == "vlm":
            return {"tokens": sd((b, s - cfg.n_patches), i32),
                    "embeds": sd((b, cfg.n_patches, cfg.d_model), f32)}
        if cfg.family == "encdec":
            return {"tokens": sd((b, s), i32),
                    "frames": sd((b, cfg.n_frames, cfg.d_model), f32)}
        return {"tokens": sd((b, s), i32)}

    # decode: one token against a seq-long cache
    from repro.models.model import build_model
    mdl = model or build_model(cfg)
    cache = jax.eval_shape(lambda: mdl.init_cache(b, s))
    specs = {"token": sd((b,), i32), "pos": sd((), i32), "cache": cache}
    if cfg.family == "encdec":
        specs["enc_out"] = sd((b, cfg.n_frames, cfg.d_model),
                              jnp.dtype(cfg.compute_dtype))
    return specs
