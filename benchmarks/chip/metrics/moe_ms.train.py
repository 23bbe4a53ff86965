"""Device milliseconds per step under the model's ``moe`` scope
(``models/moe.py``): routing (``moe/route``), the held experts' grouped
matmuls with their gather and scatter (``moe/experts``) and the shared
experts (``moe/shared``), forward, remat recompute and backward.

The scope opens inside ``jax.value_and_grad``; ``harness.scopes`` matches
it plain or wrapped, and counts the grouped matmul's Mosaic calls, which
XLA names ``ragged-dot-*`` without the scope.  None without a trace or where no op carries the
scope (a model with no MoE layer)."""
from harness import scopes


def read(rec):
    s = scopes.scope_s_per_step(rec, "moe", also=scopes.RAGGED_DOT)
    return None if s is None else 1e3 * s
