"""Device milliseconds per step under the model's ``mla`` scope
(``models/mla.py``): latent attention's projections, rotary, the attention
itself and the output projection, forward, remat recompute and backward.

The scope opens inside ``jax.value_and_grad``; ``harness.scopes`` matches
it plain or wrapped (``jvp(mla)``).  Ops are matched through the compiled
HLO's ``op_name`` as in ``model_ms.train``.  None without a trace or where
no op carries the scope (a model with no latent attention)."""
from harness import scopes


def read(rec):
    s = scopes.scope_s_per_step(rec, "mla")
    return None if s is None else 1e3 * s
