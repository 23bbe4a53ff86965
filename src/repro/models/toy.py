"""Tiny many-leaf regression model for exercising the dist wire layer.

The transformer zoo in ``models/model.py`` is the right workload for
rooflines, but its forward pass dwarfs the aggregation cost on CPU — useless
for benchmarking the wire itself.  ``ToyMLP`` is the opposite: a dirt-cheap
forward over a pytree with MANY leaves of mixed shapes (matrices + biases),
so a step is dominated by the wire: bucketize, encode, ring and memory.
Used by ``tests/helpers/bucket_scenarios.py``, ``benchmarks/fault_bench.py``
and the ``repro.analysis`` audits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


class ToyMLP:
    """n_layers x (w [d,d] + b [d]) + head [d,1]: 2*n_layers+1 leaves.

    Implements the same ``loss(params, batch) -> (loss, {"nll", "aux"})``
    contract as ``models/model.Model``, so ``dist.make_train_step`` and
    ``dist.make_local_step`` consume it unchanged.
    """

    def __init__(self, n_layers: int = 12, d: int = 64):
        self.n_layers = n_layers
        self.d = d

    def init(self, key):
        params = {}
        for i in range(self.n_layers):
            kw, key = jax.random.split(key)
            params[f"layer_{i:02d}"] = {
                "w": jax.random.normal(kw, (self.d, self.d)) / self.d ** 0.5,
                "b": jnp.zeros((self.d,)),
            }
        params["head"] = jax.random.normal(key, (self.d, 1)) / self.d ** 0.5
        return params

    def loss(self, params, batch):
        x = batch["x"]
        for i in range(self.n_layers):
            p = params[f"layer_{i:02d}"]
            x = jnp.tanh(x @ p["w"] + p["b"])
        pred = x @ params["head"]
        mse = jnp.mean(jnp.square(pred - batch["y"]))
        return mse, {"nll": mse, "aux": jnp.zeros((), jnp.float32)}

    def batch(self, key, n: int = 32):
        kx, ky = jax.random.split(key)
        x = jax.random.normal(kx, (n, self.d))
        y = jnp.sum(jnp.sin(x[:, :4]), axis=-1, keepdims=True)
        y = y + 0.1 * jax.random.normal(ky, (n, 1))
        return {"x": x, "y": y}
