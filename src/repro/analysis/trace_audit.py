"""Compile-count contract auditor (DESIGN.md §10).

The sweep engine's whole value proposition (PR 1: 19x cold / 327x warm) is
"one trace for the whole grid".  Nothing enforced that dynamically: a future
change that sneaks a Python-varying value into the jitted signature silently
reverts to per-cell retracing and every benchmark number rots.  This module
*executes* registered entry points under ``jax_log_compiles`` and asserts
each one compiles exactly once across a multi-cell workload.

Mechanics: with ``jax.config jax_log_compiles`` on, the ``jax._src.dispatch``
logger emits one ``Finished XLA compilation of jit(<name>) in ...`` record
per backend compilation.  We attach a capturing handler to exactly that
logger (attaching to several jax loggers double-counts via propagation) and
count records per jit name.

Entry points audited (each runs a *multi-cell* workload):

  sweep_grid          run_sweep over {2 variants}x{2 gammas}x{2 seeds} —
                      expects exactly one ``jit(sweep)`` compile, and the
                      engine's own ``trace_count`` delta == 1.
  artemis_round_dense 3 rounds of artemis_round(backend='dense') under one
  artemis_round_pallas  jit wrapper — one compile each.
  bucket_ring         the mesh backend's pipelined bucketed ring train step
                      (subprocess: needs 8 fake CPU devices via XLA_FLAGS
                      *before* jax initializes) — one ``jit(step_fn)``.

``audit_no_retrace(fn, calls, name)`` is the reusable core: tests use it to
prove the auditor *does* flag a deliberately retracing callable.
"""
from __future__ import annotations

import logging
import os
import re
import subprocess
import sys
from contextlib import contextmanager
from typing import Callable, Dict, List, Sequence, Tuple

from repro.analysis.findings import Finding

RULES = {
    "trace-retrace": "error",      # entry point compiled != expected count
    "trace-entry-error": "error",  # entry point raised while auditing
}

_COMPILE_RE = re.compile(r"Finished XLA compilation of jit\(([^)]*)\)")
# the one logger that emits exactly one record per compilation in this jax
_LOGGER = "jax._src.dispatch"


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.names: List[str] = []

    def emit(self, record):
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            self.names.append(m.group(1))


@contextmanager
def compile_log():
    """Context manager yielding a list of jit names compiled inside it."""
    import jax
    cap = _Capture()
    logger = logging.getLogger(_LOGGER)
    # pxla logs a second "Compiling <name> ..." record per compile; jax
    # installs its OWN stream handlers on both loggers when the flag flips,
    # so muting propagation is not enough — swap the handler lists out
    # entirely for the duration (capture only; stderr stays clean)
    pxla = logging.getLogger("jax._src.interpreters.pxla")
    prev = jax.config.jax_log_compiles
    jax.config.update("jax_log_compiles", True)
    saved = [(lg, list(lg.handlers), lg.propagate, lg.level)
             for lg in (logger, pxla)]
    logger.handlers = [cap]
    logger.propagate = False
    if logger.level > logging.WARNING:
        logger.setLevel(logging.WARNING)
    # NullHandler, not [] — an empty handler list falls through to
    # logging.lastResort, which prints the bare record to stderr anyway
    pxla.handlers = [logging.NullHandler()]
    pxla.propagate = False
    try:
        yield cap.names
    finally:
        jax.config.update("jax_log_compiles", prev)
        for lg, handlers, prop, level in saved:
            lg.handlers = handlers
            lg.propagate = prop
            lg.setLevel(level)


def compile_counts(names: Sequence[str]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for n in names:
        out[n] = out.get(n, 0) + 1
    return out


def audit_no_retrace(fn: Callable, calls: Sequence[tuple], name: str,
                     *, expect: int = 1,
                     entry: str = "<anonymous>") -> List[Finding]:
    """Run ``fn(*args)`` for each args tuple; assert jit ``name`` compiled
    exactly ``expect`` times across ALL calls."""
    import jax
    with compile_log() as names:
        for args in calls:
            jax.block_until_ready(fn(*args))
    got = compile_counts(names).get(name, 0)
    if got != expect:
        return [Finding(
            rule="trace-retrace", severity="error", path=entry, line=0,
            message=f"jit({name}) compiled {got}x across {len(calls)} "
                    f"call(s), expected {expect} — the one-trace contract "
                    f"is broken (a traced-signature leak retraces per call)")]
    return []


# ---------------------------------------------------------------------------
# registered entry points
# ---------------------------------------------------------------------------

def _audit_sweep_grid() -> List[Finding]:
    import jax
    from repro.core import artemis as art
    from repro.core import federated as fed
    from repro.core import sweep as sw

    n, d = 4, 8
    prob, _ = fed.make_lsr_problem(jax.random.PRNGKey(0), n_workers=n,
                                   n_per=20, d=d, noise=0.3)
    cfgs = [art.variant_config(v, d, n, p=0.7) for v in ("sgd", "artemis")]
    t0 = sw.trace_count()
    with compile_log() as names:
        sw.run_sweep(prob, cfgs, [0.01, 0.02], [0, 1], iters=8, batch=2)
    findings = []
    got = compile_counts(names).get("sweep", 0)
    if got != 1:
        findings.append(Finding(
            rule="trace-retrace", severity="error", path="sweep_grid", line=0,
            message=f"jit(sweep) compiled {got}x for a 2x2x2 grid, expected "
                    f"exactly 1 (one-trace sweep contract, DESIGN.md §2)"))
    traces = sw.trace_count() - t0
    if traces > 1:
        findings.append(Finding(
            rule="trace-retrace", severity="error", path="sweep_grid", line=0,
            message=f"sweep engine trace counter advanced {traces}x for one "
                    f"grid (expected <=1) — per-cell retracing is back"))
    return findings


def _artemis_entry(backend: str) -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from repro.core import artemis as art

    d, n = 16, 4
    cfg = art.variant_config("artemis", d, n, s=1, p=0.5)
    state = art.init_state(cfg)

    def artemis_round_entry(state, grads, key):
        return art.artemis_round(cfg, state, grads, key, backend=backend)

    fn = jax.jit(artemis_round_entry)
    calls = []
    key = jax.random.PRNGKey(3)
    for i in range(3):
        key, k1, k2 = jax.random.split(key, 3)
        grads = jax.random.normal(k1, (n, d))
        calls.append((state, grads, k2))
    return audit_no_retrace(fn, calls, "artemis_round_entry",
                            entry=f"artemis_round_{backend}")


def _audit_artemis_dense() -> List[Finding]:
    return _artemis_entry("dense")


def _audit_artemis_pallas() -> List[Finding]:
    return _artemis_entry("pallas")


# the bucket-ring audit must configure 8 fake CPU devices before jax loads,
# so it runs in a child interpreter (same pattern as tests/helpers mesh
# scenarios); the child prints compile counts on the last line.
_CHILD_OK_RE = re.compile(r"^AUDIT ([a-zA-Z_0-9]+)=(\d+)$", re.M)


def _audit_bucket_ring() -> List[Finding]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis.trace_audit",
         "--child", "bucket_ring"],
        capture_output=True, text=True, env=env, timeout=600)
    if res.returncode != 0:
        tail = (res.stderr or res.stdout).strip().splitlines()[-12:]
        return [Finding(
            rule="trace-entry-error", severity="error", path="bucket_ring",
            line=0,
            message="bucket_ring audit child failed: " + " | ".join(tail))]
    counts = {m.group(1): int(m.group(2))
              for m in _CHILD_OK_RE.finditer(res.stdout)}
    got = counts.get("step_fn", 0)
    if got != 1:
        return [Finding(
            rule="trace-retrace", severity="error", path="bucket_ring",
            line=0,
            message=f"jit(step_fn) compiled {got}x over 3 pipelined-ring "
                    f"rounds on the 8-device mesh, expected exactly 1")]
    return []


def _child_bucket_ring():
    """Child-process body: 3 rounds of the bucketed pipelined mesh step
    (same construction idiom as tests/helpers/bucket_scenarios._setup)."""
    import jax
    from repro.core import dist
    from repro.launch.mesh import make_host_mesh
    from repro.models.toy import ToyMLP
    from repro.optim import sgd

    mesh = make_host_mesh((2, 2), ("p", "q"))
    model = ToyMLP(n_layers=2, d=32)
    params = model.init(jax.random.PRNGKey(0))
    dcfg = dist.DistConfig(worker_axes=("p", "q"), variant="artemis", s=3,
                           reduce_impl="pipelined")
    init_state, step_fn = dist.make_train_step(model, sgd(0.05), dcfg, mesh)
    state = init_state(params)
    jstep = jax.jit(step_fn)
    with compile_log() as names:
        for i in range(3):
            state, _ = jstep(state, model.batch(jax.random.PRNGKey(i), n=16))
        jax.block_until_ready(state)
    for name, count in sorted(compile_counts(names).items()):
        print(f"AUDIT {name}={count}")  # repro-lint: allow=print-in-library (subprocess protocol)


ENTRY_POINTS: Dict[str, Callable[[], List[Finding]]] = {
    "sweep_grid": _audit_sweep_grid,
    "artemis_round_dense": _audit_artemis_dense,
    "artemis_round_pallas": _audit_artemis_pallas,
    "bucket_ring": _audit_bucket_ring,
}


def audit_entry_points(only: Sequence[str] = ()) -> List[Finding]:
    findings: List[Finding] = []
    for name, fn in ENTRY_POINTS.items():
        if only and name not in only:
            continue
        try:
            findings.extend(fn())
        except Exception as e:                        # pragma: no cover
            findings.append(Finding(
                rule="trace-entry-error", severity="error", path=name,
                line=0, message=f"entry point raised {type(e).__name__}: {e}"))
    return findings


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        if sys.argv[2] == "bucket_ring":
            _child_bucket_ring()
        else:
            raise SystemExit(f"unknown child entry {sys.argv[2]!r}")
    else:
        fs = audit_entry_points(sys.argv[1:])
        for f in fs:
            print(f.format())  # repro-lint: allow=print-in-library (CLI entry)
        raise SystemExit(1 if fs else 0)
