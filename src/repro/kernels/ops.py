"""Public shape-agnostic API over the Pallas compression kernels.

Handles packing arbitrary-shaped arrays (or whole gradient pytrees) into the
padded 2-D block layout the kernels expect, PRNG, and interpret-mode
auto-detection (interpret on CPU; compiled Mosaic on TPU).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import fused_memory as _fm
from repro.kernels import squant as _sq

DEFAULT_BLOCK = _sq.DEFAULT_BLOCK


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        from repro.kernels import default_interpret
        return default_interpret()
    return interpret


def _pack(x: jax.Array, block) -> Tuple[jax.Array, Tuple[int, ...]]:
    """Flatten + zero-pad to an [M, bn] block-multiple 2-D layout."""
    bm, bn = block
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = bn
    rows = -(-n // cols)                    # ceil
    rows = -(-rows // bm) * bm              # round rows up to bm
    padded = jnp.zeros((rows * cols,), x.dtype).at[:n].set(flat)
    return padded.reshape(rows, cols), x.shape


def _unpack(x2d: jax.Array, shape) -> jax.Array:
    n = 1
    for s in shape:
        n *= s
    return x2d.reshape(-1)[:n].reshape(shape)


class Compressed(NamedTuple):
    """Wire format: int8 levels + f32 per-tile scales + original shape info."""
    q: jax.Array          # int8 [M, N]
    scales: jax.Array     # f32 [M//bm, N//bn]

    @property
    def wire_bytes(self) -> int:
        return self.q.size + 4 * self.scales.size


def encode(key: jax.Array, x: jax.Array, *, s: int = 1, block=DEFAULT_BLOCK,
           interpret: Optional[bool] = None) -> Tuple[Compressed, Tuple[int, ...]]:
    x2d, shape = _pack(x, block)
    u = jax.random.uniform(key, x2d.shape, dtype=x2d.dtype)
    q, scales = _sq.squant_encode(x2d, u, s=s, block=block,
                                  interpret=_auto_interpret(interpret))
    return Compressed(q, scales), shape


def decode(c: Compressed, shape, *, block=DEFAULT_BLOCK, dtype=jnp.float32,
           interpret: Optional[bool] = None) -> jax.Array:
    out = _sq.squant_decode(c.q, c.scales, block=block, dtype=dtype,
                            interpret=_auto_interpret(interpret))
    return _unpack(out, shape)


def compress(key: jax.Array, x: jax.Array, *, s: int = 1, block=DEFAULT_BLOCK,
             interpret: Optional[bool] = None) -> jax.Array:
    """Round-trip encode+decode — an unbiased Assumption-5 compressor usable
    anywhere a `Compressor.compress` is expected."""
    c, shape = encode(key, x, s=s, block=block, interpret=interpret)
    return decode(c, shape, block=block, dtype=x.dtype, interpret=interpret)


def memory_update(key: jax.Array, g: jax.Array, h: jax.Array, alpha,
                  *, s: int = 1, block=DEFAULT_BLOCK,
                  interpret: Optional[bool] = None):
    """Fused Artemis worker step on an arbitrary-shaped gradient.

    Returns (delta_hat (decoded, g.shape), h_new (g.shape), compressed wire).
    """
    g2d, shape = _pack(g, block)
    h2d, _ = _pack(h, block)
    u = jax.random.uniform(key, g2d.shape, dtype=g2d.dtype)
    itp = _auto_interpret(interpret)
    # _pack's layout is one block column wide, so its (bm, bn) tiles are
    # consecutive row blocks: the [T, bm, bn] tile stack is a free reshape
    bm, bn = block
    q, scales, h_new = _fm.fused_memory_update(
        g2d.reshape(-1, bm, bn), h2d.reshape(-1, bm, bn),
        u.reshape(-1, bm, bn), alpha, s=s, interpret=itp)
    h_new2d = h_new.reshape(g2d.shape)
    c = Compressed(q.reshape(g2d.shape), scales.reshape(-1, 1))
    delta_hat = decode(c, shape, block=block, dtype=g.dtype, interpret=itp)
    return delta_hat, _unpack(h_new2d, shape), c


def apply_update(w: jax.Array, c: Compressed, gamma, shape=None, *,
                 block=DEFAULT_BLOCK, interpret: Optional[bool] = None) -> jax.Array:
    """Fused w' = w - gamma * dequant(c)."""
    shape = w.shape if shape is None else shape
    w2d, _ = _pack(w, block)
    out = _sq.dequant_apply(w2d, c.q, c.scales, gamma, block=block,
                            interpret=_auto_interpret(interpret))
    return _unpack(out, shape)


# ---------------------------------------------------------------------------
# Pytree helpers (gradient trees)
# ---------------------------------------------------------------------------

def tree_compress(key: jax.Array, tree, *, s: int = 1, block=DEFAULT_BLOCK,
                  interpret: Optional[bool] = None):
    """Apply the round-trip compressor leaf-wise with independent keys."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    out = [compress(k, leaf, s=s, block=block, interpret=interpret)
           for k, leaf in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)


def tree_memory_update(key: jax.Array, grads, h, alpha, *, s: int = 1,
                       block=DEFAULT_BLOCK, interpret: Optional[bool] = None):
    """Fused memory update over a gradient pytree. Returns (delta_hat, h_new)."""
    gl, treedef = jax.tree.flatten(grads)
    hl = treedef.flatten_up_to(h)
    keys = jax.random.split(key, len(gl))
    dh, hn = [], []
    for k, g, hh in zip(keys, gl, hl):
        d, h2, _ = memory_update(k, g, hh, alpha, s=s, block=block,
                                 interpret=interpret)
        dh.append(d)
        hn.append(h2)
    return jax.tree.unflatten(treedef, dh), jax.tree.unflatten(treedef, hn)
