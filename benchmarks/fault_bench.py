"""Fault-injection benchmarks: scrubbing overhead + recovery smoke matrix.

Three entry points:

  * ``fault_overhead_suite`` (via ``benchmarks/run.py``): measures the cost
    of the server defenses at two scales —
      - mesh backend (the number that matters): end-to-end bucketed-wire
        train step with finite/checksum scrubbing ON vs OFF, on a simulated
        multi-worker mesh (subprocess; fake CPU devices).  Budget:
        <5%/round — scrubbing is a few elementwise isfinite/where passes
        over payloads a real model's fwd/bwd dwarfs.
      - sweep engine (informational): the same toggle on the tiny-problem
        sweep grid, where rounds are a handful of flops and the relative
        overhead is intrinsically inflated.
    It prints its rows and writes no file.

  * ``python benchmarks/fault_bench.py --matrix``: the CI fault-matrix
    smoke — injects NaN blowups, huge finite blowups, and wire bit-flips
    and asserts the self-healing server actually recovers (finite
    converging losses, sentinel rollbacks engaged, zero-fault identity
    bitwise).  Exits non-zero on any failed recovery.

  * ``--step-child <scrub>``: internal subprocess body for the mesh
    measurement (device count must be fixed before jax initializes).
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FAST = False      # set by benchmarks/run.py --fast: one cell, few iters

OVERHEAD_BUDGET_PCT = 5.0


# ---------------------------------------------------------------------------
# mesh-backend step overhead (subprocess: fake devices precede jax init)
# ---------------------------------------------------------------------------

def run_step_child(scrub: bool):
    import jax
    from repro.core import dist, faults
    from repro.launch.mesh import make_host_mesh
    from repro.models.toy import ToyMLP
    from repro.optim import sgd

    workers = 4 if FAST else 8
    mesh = make_host_mesh((workers,), ("pod",))
    model = ToyMLP(n_layers=4, d=256)
    params = model.init(jax.random.PRNGKey(0))
    dcfg = dist.DistConfig(
        worker_axes=("pod",), variant="artemis", s=3, p_participation=0.7,
        bucket_row=64,
        faults=faults.FaultConfig(scrub=True) if scrub else None)
    init_state, step_fn = dist.make_train_step(model, sgd(0.05), dcfg, mesh)
    state = init_state(params)
    batch = model.batch(jax.random.PRNGKey(1), n=32)
    jstep = jax.jit(step_fn)
    state, out = jstep(state, batch)
    jax.block_until_ready(out)
    # best-of-reps: a single long loop folds transient machine load into
    # the mean; the min over short reps is the stable per-step cost
    reps, iters = (2, 5) if FAST else (8, 10)
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        for _ in range(iters):
            state, out = jstep(state, batch)
        jax.block_until_ready(out)
        best = min(best, (time.time() - t0) / iters)
    print(json.dumps({"step_us": best * 1e6}))


def _step_us(scrub: bool) -> float:
    # fake CPU devices; pinned to the CPU so the child never competes for
    # an accelerator this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % (
        4 if FAST else 8)
    src = os.path.join(_ROOT, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, os.path.abspath(__file__), "--step-child",
           "1" if scrub else "0"] + (["--fast"] if FAST else [])
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"step child failed:\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])["step_us"]


# ---------------------------------------------------------------------------
# sweep-engine overhead (informational: toy rounds inflate the relative cost)
# ---------------------------------------------------------------------------

def _sweep_walls():
    import jax
    from repro.core import artemis as art
    from repro.core import faults
    from repro.core import federated as fed
    from repro.core import sweep as sw

    n, d = 20, 20
    iters = 20 if FAST else 200
    prob, _ = fed.make_lsr_problem(jax.random.PRNGKey(11), n_workers=n,
                                   n_per=200, d=d, noise=0.4)
    variants = ["artemis"] if FAST else ["qsgd", "artemis", "dore"]

    def grid(fc):
        return [dataclasses.replace(art.variant_config(v, d, n, p=0.7),
                                    faults=fc) for v in variants]

    def timed(fc):
        kw = dict(gammas=[0.02, 0.05], seeds=[0, 1], iters=iters, batch=4,
                  eval_every=10 if not FAST else 1)
        sw.run_sweep(prob, grid(fc), **kw)            # compile + warm cache
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            sw.run_sweep(prob, grid(fc), **kw)
            best = min(best, time.time() - t0)
        return best

    cells = len(variants) * 2 * 2
    return (cells, iters, timed(None), timed(faults.FaultConfig(scrub=True)),
            timed(faults.FaultConfig(bitflip_rate=0.01, scrub=True,
                                     sentinel=1e6)))


def fault_overhead_suite():
    """Scrubbing cost: mesh step (<5% budget) + sweep engine (informational)."""
    # paired back-to-back measurements, median of per-pair ratios: ambient
    # load on the simulated mesh drifts on a seconds scale, so a ratio taken
    # within one pair is far more stable than any absolute best-of
    pairs = []
    for _ in range(1 if FAST else 3):
        pairs.append((_step_us(scrub=False), _step_us(scrub=True)))
    pairs.sort(key=lambda p: (p[1] - p[0]) / p[0])
    base_us, scrub_us = pairs[len(pairs) // 2]
    mesh_pct = (scrub_us - base_us) / base_us * 100.0

    cells, iters, sw_base, sw_scrub, sw_full = _sweep_walls()
    return [
        ("fault/mesh_step", base_us, "defenses off"),
        ("fault/mesh_step_scrub", scrub_us,
         f"overhead={mesh_pct:+.1f}% budget<{OVERHEAD_BUDGET_PCT:.0f}% "
         f"ok={mesh_pct < OVERHEAD_BUDGET_PCT}"),
        ("fault/sweep_scrub", sw_scrub * 1e6 / (cells * iters),
         f"toy-round overhead={(sw_scrub - sw_base) / sw_base * 100:+.1f}% "
         "(informational)"),
        ("fault/sweep_full_defense", sw_full * 1e6 / (cells * iters),
         f"scrub+flip+sentinel wall_s={sw_full:.3f}"),
    ]


ALL = [fault_overhead_suite]


# ---------------------------------------------------------------------------
# --matrix: CI recovery smoke
# ---------------------------------------------------------------------------

def run_matrix():
    import jax
    import numpy as np
    from repro.core import artemis as art
    from repro.core import faults
    from repro.core import federated as fed
    from repro.core import sweep as sw

    n, d = 8, 16
    prob, _ = fed.make_lsr_problem(jax.random.PRNGKey(3), n_workers=n,
                                   n_per=50, d=d, noise=0.3)

    def run(fc, backend=None):
        cfg = dataclasses.replace(art.variant_config("artemis", d, n, p=0.7),
                                  faults=fc)
        return sw.run_sweep(prob, [cfg], [0.02], [0], iters=40, batch=4,
                            backend=backend)

    # zero-fault identity: the harness itself must be invisible when off
    base, zero = run(None), run(faults.FaultConfig())
    assert np.array_equal(base.losses, zero.losses), "zero-fault identity"

    # NaN blowups + scrubbing: corrupt workers masked inactive, run converges
    res = run(faults.FaultConfig(blowup_rate=0.25, scrub=True))
    last, first = res.losses[0, 0, 0, -1], res.losses[0, 0, 0, 0]
    assert np.all(np.isfinite(res.losses)) and last < first, "scrub recovery"

    # huge finite blowups + sentinel: rollback engaged, gamma backed off
    res = run(faults.FaultConfig(blowup_rate=0.1, blowup_value=1e15,
                                 scrub=True, sentinel=1e3))
    assert np.all(np.isfinite(res.losses)), "sentinel kept losses finite"
    assert int(res.rollbacks[0, 0, 0]) >= 1, "sentinel never rolled back"
    assert float(res.gamma_scale[0, 0, 0]) < 1.0, "gamma never backed off"

    # wire bit-flips on the quantized (pallas) wire: scrub + sentinel recover
    res = run(faults.FaultConfig(bitflip_rate=0.05, scrub=True, sentinel=1e4),
              backend="pallas")
    assert np.all(np.isfinite(res.losses)), "bitflip recovery (pallas wire)"

    print("fault matrix: OK (identity, scrub, sentinel, bitflip)")


if __name__ == "__main__":
    if "--fast" in sys.argv:
        FAST = True
    if "--step-child" in sys.argv:
        i = sys.argv.index("--step-child")
        run_step_child(sys.argv[i + 1] == "1")
    elif "--matrix" in sys.argv:
        run_matrix()
    else:
        print("name,us_per_call,derived")
        for row in fault_overhead_suite():
            print(f"{row[0]},{row[1]:.1f},{row[2]}")
