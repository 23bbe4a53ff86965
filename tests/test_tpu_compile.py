"""Compile the main path's Pallas kernels for a TPU v5e chip that is
described, not attached: the chip's compiler refuses block shapes, scoped
VMEM overruns and layouts that interpret mode accepts.

The topology is described inside a module fixture, so only the worker that
runs these tests loads the TPU compiler, and the tests skip where it cannot
be described.  Nothing here runs a kernel.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core import dist
from repro.kernels import bucket_ring as BK
from repro.kernels import flash_attn as FA
from repro.kernels import fused_memory as fm
from repro.kernels import ring_sum as rs
from repro.launch import mesh as M
from repro.models import layers as L
from repro.models.model import build_model
from repro.optim import adam

# whisper-tiny (56.5 M params) on the default bucketed wire: 16 buckets of
# 13795 rows x 256
WHISPER_TINY_BUCKETS = (16, 13795, 256)


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2."""
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for the described chip cannot be read back from the
    # persistent cache without one, so keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


def _compile_text(fn, one_chip, *specs):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_bucket_acc_whisper_tiny_layout(one_chip):
    b, r, c = WHISPER_TINY_BUCKETS
    text = _compile_text(
        lambda acc, q, s: BK.bucket_acc(acc, q, s, interpret=False),
        one_chip, ((b, r, c), jnp.float32), ((b, r, c), jnp.int8),
        ((b, r, 1), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("tiles", [(20, 1, 20),        # sim grid: N=20, d=20
                                   (16, 256, 256)])    # ops: 1024^2, 256^2
def test_fused_memory_update(one_chip, tiles):
    text = _compile_text(
        lambda g, h, u: fm.fused_memory_update(g, h, u, 0.25, s=1,
                                               interpret=False),
        one_chip, *[(tiles, jnp.float32)] * 3)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,block", [((20, 1, 20), (1, 20)),
                                         ((4, 1024, 1024), (256, 256))])
def test_ring_sum(one_chip, shape, block):
    n, m, _ = shape
    text = _compile_text(
        lambda q, s: rs.ring_sum(q, s, block=block, interpret=False),
        one_chip, (shape, jnp.int8), ((n, m, 1), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("sq,sk", [(1500, 1500),     # encoder self (1536 padded)
                                   (448, 1500)])     # cross-attention
def test_flash_attention_whisper_tiny_b8(one_chip, sq, sk):
    """Forward and backward of the fused attention at whisper-tiny b8
    shapes (8 x 6 heads of 64, 1500 frames padded to 1536): Mosaic's block
    and VMEM checks run without a chip."""
    def loss(q, k, v):
        o = FA.flash_attention(q, k, v, scale=0.125)
        return jnp.sum(o.astype(jnp.float32))

    text = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                         ((8, sq, 6, 64), jnp.bfloat16),
                         ((8, sk, 6, 64), jnp.bfloat16),
                         ((8, sk, 6, 64), jnp.bfloat16))
    # the forward (with its row statistics) and the one backward kernel
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("s,d,dv", [
    (4096, 192, 128),                 # kanana-2-30b-a3b's latent attention
    (FA.MAX_CAUSAL_S, 192, 128),      # the rule's longest fused call
    (FA.MAX_CAUSAL_S, 128, 128)])     # ... with a plain MHA layer's dims
def test_causal_flash_attention_kanana(one_chip, s, d, dv):
    """Forward and backward of the causal kernel for 32 heads in bf16: at
    kanana-2-30b-a3b's latent attention (1 x 4,096 tokens, qk 192, v 128)
    and at ``MAX_CAUSAL_S`` tokens, where Mosaic's VMEM check bounds the
    resident blocks."""
    def loss(q, k, v):
        o = FA.causal_flash_attention(q, k, v, scale=d ** -0.5)
        return jnp.sum(o.astype(jnp.float32))

    text = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                         ((1, s, 32, d), jnp.bfloat16),
                         ((1, s, 32, d), jnp.bfloat16),
                         ((1, s, 32, dv), jnp.bfloat16))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    for name in ("flash_attn_causal_fwd", "flash_attn_causal_bwd"):
        assert name in text, name


def test_mla_layer_kernels_in_mla_scope(one_chip, monkeypatch):
    """In one latent attention layer's compiled ``value_and_grad`` at
    kanana's widths, both causal kernels carry the ``mla`` scope, plain or
    wrapped by the transform (``jvp(mla)``): ``mla_ms.train`` reads its
    time there."""
    from repro.models import mla as MLA
    monkeypatch.setattr(L.jax, "default_backend", lambda: "tpu")
    cfg = configs.get_config("kanana-2-30b-a3b")
    params = jax.eval_shape(lambda key: MLA.init_mla(
        key, cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.qk_rope_dim,
        cfg.v_head_dim, cfg.kv_lora_rank, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), params)
    x = jax.ShapeDtypeStruct((1, 4096, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        y, _ = MLA.mla_apply(p, x, jnp.arange(4096)[None],
                             n_heads=cfg.n_heads, nope=cfg.head_dim,
                             rope_dim=cfg.qk_rope_dim, v_dim=cfg.v_head_dim,
                             rope_theta=cfg.rope_theta, eps=cfg.norm_eps)
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    kernels = [re.search(r'op_name="([^"]*)"', l).group(1)
               for l in text.splitlines() if "tpu_custom_call" in l]
    assert len(kernels) == 2, kernels
    in_mla = re.compile(r"(^|/)(\w+\()*mla\)*/")
    for op_name in kernels:
        assert in_mla.search(op_name), op_name


def test_artemis_step_fused_attention_on_four_chips(v5e_2x2, monkeypatch):
    """The bucketed Artemis step on a 4x1 mesh (one worker per chip, as
    ``train --mesh 4x1`` and ``chip_smoke.py --four-chips`` run it) with
    the fused attention: the kernel sits inside the worker shard_map under
    remat, scan and grad, wrapped manual over the size-1 model axis.
    Reduced whisper-tiny with its 1500 frames, 128 tokens per worker."""
    monkeypatch.setattr(L.jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(configs.get_config("whisper-tiny", reduced=True),
                              n_frames=1500)
    mesh = Mesh(np.array(v5e_2x2).reshape(4, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    dcfg = dist.DistConfig(worker_axes=("data",), variant="artemis")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pshard = M.params_shardings(mesh, params)
    model.set_sharding(M.layer_constraint_fn(mesh, ("data",)),
                       M.act_constraint_fn(mesh, ("data",)))
    init_state, step = dist.make_train_step(model, adam(3e-3), dcfg, mesh)

    def placed(tree, shardings):
        return jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sh), tree, shardings)

    rows = NamedSharding(mesh, P("data"))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 128), jnp.int32,
                                            sharding=rows),
             "frames": jax.ShapeDtypeStruct((4, 1500, cfg.d_model),
                                            jnp.float32, sharding=rows)}
    with jax.set_mesh(mesh):
        state = jax.eval_shape(init_state, placed(params, pshard))
        state = placed(state, dist.state_shardings(mesh, state, pshard, dcfg))
        with L.record_attention_paths() as calls:
            lowered = jax.jit(step).lower(state, batch)
        text = lowered.compile().as_text()
    # encoder self (1500 x 1500), decoder self (causal), cross (128 x 1500)
    assert [c["path"] for c in calls] == ["fused", "xla", "fused"]
    assert calls[0]["q"] == [1, 1500, 4, 32]
    for name in ("flash_attn_fwd", "flash_attn_bwd", "bucket_acc"):
        assert name in text, name
    # the model's backward sums nothing over the workers: each worker's
    # gradient is its own until the wire (a map around the kernel that the
    # backward transposes would psum the attention's cotangents)
    model_sums = [l for l in text.splitlines()
                  if re.search(r"all-reduce(-start)?\(", l) and "/model/" in l]
    assert not model_sums, model_sums[:2]
