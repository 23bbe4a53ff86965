"""Per-architecture smoke tests: REDUCED variant of each assigned family runs
one forward/train step + one decode step on CPU; shapes + no NaNs asserted.

XLA-CPU compile time dominates (~5-15 s per arch), so only two representative
architectures (dense transformer + SSM) run in the default tier-1 set; the
rest carry the ``slow`` marker and run in the CI full stage (``-m slow``).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models.model import build_model

KEY = jax.random.PRNGKey(0)
B, S = 2, 128

# default-tier coverage: one dense transformer and the latent-attention MoE
# (SSM/MoE layer math is unit-tested directly in test_layers.py /
# test_moe.py / test_kanana.py; full zoo runs via -m slow)
FAST_ARCHS = {"starcoder2-7b", "kanana-2-30b-a3b"}


def _arch_params(archs):
    return [pytest.param(a, marks=() if a in FAST_ARCHS else
                         (pytest.mark.slow,)) for a in sorted(archs)]


@functools.lru_cache(maxsize=None)
def _model_and_params(arch):
    """Share the built model + init across the train/decode/prefill tests."""
    cfg = configs.get_config(arch, reduced=True)
    model = build_model(cfg)
    return cfg, model, model.init(KEY)


def _batch(cfg):
    kt = jax.random.PRNGKey(1)
    if cfg.family == "vlm":
        return {
            "tokens": jax.random.randint(kt, (B, S - cfg.n_patches), 0, cfg.vocab),
            "embeds": jax.random.normal(kt, (B, cfg.n_patches, cfg.d_model)),
        }
    if cfg.family == "encdec":
        return {
            "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab),
            "frames": jax.random.normal(kt, (B, cfg.n_frames, cfg.d_model)),
        }
    return {"tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab)}


@pytest.mark.parametrize("arch", _arch_params(configs.ARCHS))
def test_train_step(arch):
    cfg, model, params = _model_and_params(arch)
    batch = _batch(cfg)

    @jax.jit
    def step(p, b):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(p, b)
        return loss, metrics, grads

    loss, metrics, grads = step(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), (arch, float(loss))
    # a near-uniform untrained model should sit near log(vocab)
    assert float(metrics["nll"]) < np.log(cfg.vocab) + 2.0
    gnorm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32)**2)
                               for g in jax.tree.leaves(grads))))
    assert np.isfinite(gnorm) and gnorm > 0, arch


@pytest.mark.parametrize("arch", _arch_params(configs.ARCHS))
def test_decode_step(arch):
    cfg, model, params = _model_and_params(arch)
    cache = model.init_cache(B, 64)
    token = jnp.zeros((B,), jnp.int32)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = jax.random.normal(KEY, (B, cfg.n_frames, cfg.d_model)
                                    ).astype(cfg.cdtype)

    @jax.jit
    def step(p, c, t, pos):
        return model.decode_step(p, c, t, pos, enc_out=enc_out)

    logits, cache = step(params, cache, token, jnp.int32(0))
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all(), arch
    logits2, cache = step(params, cache, token + 1, jnp.int32(1))
    assert np.isfinite(np.asarray(logits2)).all(), arch
    # different input token must change the output
    assert not jnp.array_equal(logits, logits2), arch


@pytest.mark.parametrize("arch", _arch_params(["starcoder2-7b",
                                               "falcon-mamba-7b",
                                               "recurrentgemma-2b",
                                               "mixtral-8x22b"]))
def test_decode_matches_prefill(arch):
    """Greedy decode step-by-step == teacher-forced forward (same tokens);
    MoE routing is per token and dropless, so it agrees too."""
    cfg, model, params = _model_and_params(arch)
    T = 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab)

    # teacher-forced hidden states -> logits at each position
    x, _, _ = model._forward(params, {"tokens": toks})
    from repro.models.model import _cast
    full_logits = np.asarray(
        (x @ _cast(params["unembed"], cfg.cdtype)).astype(jnp.float32))

    cache = model.init_cache(B, T)
    step = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, t, pos))
    for i in range(T):
        logits, cache = step(params, cache, toks[:, i], jnp.int32(i))
        np.testing.assert_allclose(np.asarray(logits), full_logits[:, i],
                                   rtol=5e-2, atol=5e-2)


@pytest.mark.slow
def test_sliding_window_ring_buffer():
    """Mixtral-reduced: decode beyond the window keeps cache size fixed and
    only attends to the last `window` tokens."""
    cfg, model, params = _model_and_params("mixtral-8x22b")
    cache = model.init_cache(B, 4096)   # request long; ring caps at window
    k_shape = jax.tree.leaves(cache)[0].shape
    step = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, t, pos))
    logits, cache = step(params, cache, jnp.zeros((B,), jnp.int32),
                         jnp.int32(cfg.window + 5))
    assert np.isfinite(np.asarray(logits)).all()
    assert jax.tree.leaves(cache)[0].shape == k_shape


def test_long_500k_skips():
    shp = configs.SHAPES["long_500k"]
    runs = {a for a in configs.ARCHS
            if configs.applicable(configs.get_config(a), shp) is None}
    assert runs == {"falcon-mamba-7b", "recurrentgemma-2b", "mixtral-8x22b"}
