"""Device time under a named scope that opens inside a transformed
function.

A ``jax.named_scope`` opened inside ``jax.value_and_grad`` reaches the
compiled HLO's ``op_name`` plain (``.../moe/experts/...``) or wrapped by the
transform (``jvp(mla)``, ``transpose(jvp(mla))``), depending on where the
op sits (CPU compile, jax 0.9).  ``trace.in_scope`` matches path parts
exactly; here a part matches a name when it is that name inside any number
of such wrappers, and a scope path (``moe/experts``) matches consecutive
parts.

XLA's TPU expansion of ``jax.lax.ragged_dot`` runs the grouped matmul in
Mosaic calls whose op_name is the expansion's own (``ragged-dot-none``,
``ragged-dot-metadata``; v5e compile, jax 0.9), without the scope it was
made in.  The program's one grouped matmul is the MoE layer's held experts
(``models/moe.py``, scope ``moe/experts``), so a reader of that scope
counts ops under ``RAGGED_DOT`` too.
"""
from __future__ import annotations

import re
from typing import Optional

from harness import trace as TR

RAGGED_DOT = "ragged-dot"


def _part(name: str):
    return re.compile(r"(?:[\w.]+\()*" + re.escape(name) + r"\)*$")


def in_wrapped_scope(op_name: str, path: str) -> bool:
    want = [_part(n) for n in path.split("/")]
    parts = op_name.split("/")
    return any(all(w.match(parts[i + j]) for j, w in enumerate(want))
               for i in range(len(parts) - len(want) + 1))


def scope_s_per_step(rec: dict, path: str,
                     also: str = None) -> Optional[float]:
    """Device seconds per step (per chip, over the traced window) covered
    by ops under ``path``, or whose op_name starts with ``also``; None
    without a trace or where no op is."""
    red = rec.get("trace")
    if red is None or not rec.get("steps") or not red.devices:
        return None

    def hit(o):
        return in_wrapped_scope(o.scope, path) or bool(
            also and o.scope.startswith(also))

    spent = sum(TR.covered((o.start, o.end) for o in ops if hit(o))
                for ops in red.devices) / len(red.devices)
    return spent / rec["steps"] if spent > 0 else None
