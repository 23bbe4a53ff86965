"""Compile the main path's Pallas kernels for a TPU v5e chip that is
described, not attached: the chip's compiler refuses block shapes, scoped
VMEM overruns and layouts that interpret mode accepts.

The topology is described inside a module fixture, so only the worker that
runs these tests loads the TPU compiler, and the tests skip where it cannot
be described.  Nothing here runs a kernel.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bucket_ring as BK
from repro.kernels import fused_memory as fm
from repro.kernels import ring_sum as rs

# whisper-tiny (56.5 M params) on the default bucketed wire: 16 buckets of
# 13795 rows x 256
WHISPER_TINY_BUCKETS = (16, 13795, 256)


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


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
