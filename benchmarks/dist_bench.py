"""Distributed benchmarks.

Default suites:
  * sweep engine — the batched one-trace grid vs the seed's per-cell Python
    loop on the paper's experiment grid (6 variants x 4 step-sizes x 3
    seeds, 200 rounds), plus the ``group_by_variant=True`` partitioned mode
    (V traces, 1x arithmetic — the §5 crossover data).  Written to
    BENCH_sweep.json.
  * bucketed ring — the bucketed pipelined compressed wire vs the legacy
    per-leaf sequential rings, timed end-to-end on a simulated multi-host
    mesh (subprocess with fake CPU devices; ``bucket_ring_bench.py``) with
    the compiled HLO's collective bytes checked against the roofline wire
    model.  Written to BENCH_dist.json.

The legacy host-mesh optimizer-step suite times the train step per variant
on the host mesh.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import artemis as art
from repro.core import federated as fed
from repro.core import sweep as sw

FAST = False      # set by benchmarks/run.py --fast: one cell, few iters

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_sweep.json")
BENCH_DIST_JSON = os.path.join(os.path.dirname(__file__), "..",
                               "BENCH_dist.json")

VARIANTS = ["sgd", "qsgd", "diana", "biqsgd", "artemis", "dore"]
GAMMA_FRACS = [0.125, 0.25, 0.5, 1.0]
SEEDS = [0, 1, 2]
ITERS = 200
EVAL_EVERY = 10


def sweep_engine_suite():
    """One-trace multi-variant grid vs the seed's per-cell loop."""
    n, d = 20, 20
    variants = VARIANTS[:1] if FAST else VARIANTS
    fracs = GAMMA_FRACS[:1] if FAST else GAMMA_FRACS
    seeds = SEEDS[:1] if FAST else SEEDS
    iters = 20 if FAST else ITERS

    prob, _ = fed.make_lsr_problem(jax.random.PRNGKey(11), n_workers=n,
                                   n_per=200, d=d, noise=0.4)
    cfgs = [art.variant_config(v, d, n) for v in variants]
    g_ref = fed.gamma_max(prob, art.variant_config("artemis", d, n))
    gammas = [f * g_ref for f in fracs]
    cells = len(cfgs) * len(gammas) * len(seeds)

    # --- the seed's per-cell Python loop: one trace + per-round loss each ---
    t0 = time.time()
    for cfg in cfgs:
        for g in gammas:
            for s in seeds:
                fed.run_percell(prob, cfg, gamma=g, iters=iters,
                                key=jax.random.PRNGKey(s), batch=1)
    percell_s = time.time() - t0

    # --- sweep engine: cold (includes the single compile), then warm -------
    t0 = time.time()
    res_cold = sw.run_sweep(prob, cfgs, gammas, seeds, iters, batch=1,
                            eval_every=EVAL_EVERY if not FAST else 1)
    cold_s = time.time() - t0
    t0 = time.time()
    res_warm = sw.run_sweep(prob, cfgs, gammas, seeds, iters, batch=1,
                            eval_every=EVAL_EVERY if not FAST else 1)
    warm_s = time.time() - t0

    # --- grouped mode: V single-variant traces, 1x round arithmetic -------
    t0 = time.time()
    res_gcold = sw.run_sweep(prob, cfgs, gammas, seeds, iters, batch=1,
                             eval_every=EVAL_EVERY if not FAST else 1,
                             group_by_variant=True)
    gcold_s = time.time() - t0
    t0 = time.time()
    res_gwarm = sw.run_sweep(prob, cfgs, gammas, seeds, iters, batch=1,
                             eval_every=EVAL_EVERY if not FAST else 1,
                             group_by_variant=True)
    gwarm_s = time.time() - t0

    report = {
        "grid": {"variants": variants, "n_gammas": len(gammas),
                 "n_seeds": len(seeds), "cells": cells, "iters": iters,
                 "eval_every": EVAL_EVERY if not FAST else 1,
                 "n_workers": n, "dim": d},
        "percell_wall_s": round(percell_s, 3),
        "sweep_cold_wall_s": round(cold_s, 3),
        "sweep_warm_wall_s": round(warm_s, 3),
        "speedup_cold": round(percell_s / cold_s, 2),
        "speedup_warm": round(percell_s / warm_s, 2),
        "cells_per_sec_warm": round(cells / warm_s, 2),
        "traces_cold": res_cold.traces,
        "traces_warm": res_warm.traces,
        "grouped_cold_wall_s": round(gcold_s, 3),
        "grouped_warm_wall_s": round(gwarm_s, 3),
        "grouped_traces_cold": res_gcold.traces,
        "grouped_traces_warm": res_gwarm.traces,
        "device": jax.devices()[0].device_kind,
        "jax": jax.__version__,
    }
    if not FAST:
        with open(BENCH_JSON, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")

    rows = [
        ("sweep/percell_loop", percell_s * 1e6 / (cells * iters),
         f"wall_s={percell_s:.2f} traces~{cells}"),
        ("sweep/engine_cold", cold_s * 1e6 / (cells * iters),
         f"wall_s={cold_s:.2f} traces={res_cold.traces} "
         f"speedup={percell_s / cold_s:.1f}x"),
        ("sweep/engine_warm", warm_s * 1e6 / (cells * iters),
         f"wall_s={warm_s:.2f} traces={res_warm.traces} "
         f"speedup={percell_s / warm_s:.1f}x"),
        ("sweep/grouped_cold", gcold_s * 1e6 / (cells * iters),
         f"wall_s={gcold_s:.2f} traces={res_gcold.traces}"),
        ("sweep/grouped_warm", gwarm_s * 1e6 / (cells * iters),
         f"wall_s={gwarm_s:.2f} traces={res_gwarm.traces} "
         f"vs_batched_warm={warm_s / gwarm_s:.2f}x"),
    ]
    return rows


def bucketed_ring_suite():
    """Bucketed pipelined ring vs per-leaf sequential rings, end-to-end step
    time on a simulated multi-host mesh.  Runs ``bucket_ring_bench.py`` in a
    subprocess (fake-device count must be set before jax initializes) and
    writes the full report to BENCH_dist.json."""
    script = os.path.join(os.path.dirname(__file__), "bucket_ring_bench.py")
    cmd = [sys.executable, script, "--workers", "4" if FAST else "8"]
    if FAST:
        cmd.append("--fast")
    # the child simulates its mesh on fake CPU devices; pinning it to the
    # CPU keeps it off an accelerator this process may already hold
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = (os.path.abspath(src) + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=900,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"bucket_ring_bench failed:\n{proc.stderr[-3000:]}")
    report = json.loads(proc.stdout)
    with open(BENCH_DIST_JSON, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")

    rows = []
    for wire in ("leaf", "bucketed"):
        w = report["wires"][wire]
        s8 = w["hlo_collective_bytes"].get("collective-permute/s8", 0)
        rows.append((f"bucket_ring/{wire}", w["step_us"],
                     f"hlo_s8_bytes={s8} compile_s={w['compile_s']}"))
    guard = report["wires"]["bucketed"]["wire_guard"]
    rows.append(("bucket_ring/speedup", 0.0,
                 f"bucketed_vs_leaf={report['speedup_bucketed_vs_leaf']}x "
                 f"wire_guard_ok={guard['ok']} rel_err={guard['rel_err']:.3f}"))
    return rows


# ---------------------------------------------------------------------------
# legacy host-mesh optimizer-step suite (explicit-sharding jax API)
# ---------------------------------------------------------------------------

def _wire_bytes(params, variant, n_workers, s=1):
    """Analytic per-step inter-worker bytes per worker (uplink+downlink)."""
    total_f32 = sum(l.size * 4 for l in jax.tree.leaves(params))
    total_int8 = sum(l.size for l in jax.tree.leaves(params))
    scales = sum((l.size // l.shape[-1] if l.ndim else 1) * 4
                 for l in jax.tree.leaves(params))
    ring_f32 = 2 * (n_workers - 1) / n_workers * total_f32      # all-reduce
    ring_q = (n_workers - 1) * (total_int8 + scales) / n_workers
    if variant == "sgd":
        return ring_f32
    up = ring_q
    dwn = 0.0 if variant in ("biqsgd", "artemis") else ring_f32 / 2
    return up + dwn


def dist_step_suite():
    from repro import configs
    from repro.core import dist
    from repro.launch import mesh as M
    from repro.models.model import build_model
    from repro.optim import sgd

    rows = []
    mesh = M.make_host_mesh()
    cfg = configs.get_config("starcoder2-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 128), 0,
                                          cfg.vocab)}
    n_workers = jax.device_count()
    with jax.set_mesh(mesh):
        for variant in ["none", "sgd", "qsgd", "artemis"]:
            dcfg = None if variant == "none" else dist.DistConfig(
                worker_axes=("data",), variant=variant)
            init_state, step_fn = dist.make_train_step(model, sgd(0.01), dcfg,
                                                       mesh)
            state = init_state(params)
            jstep = jax.jit(step_fn)
            state, out = jstep(state, batch)
            jax.block_until_ready(out)
            t0 = time.time()
            for _ in range(3):
                state, out = jstep(state, batch)
            jax.block_until_ready(out)
            us = (time.time() - t0) / 3 * 1e6
            wire = _wire_bytes(params, variant if variant != "none" else "sgd",
                               max(n_workers, 2))
            rows.append((f"dist_step/{variant}", us,
                         f"wire_bytes_per_worker={wire:.3e}"))
    return rows


ALL = [sweep_engine_suite, bucketed_ring_suite, dist_step_suite]
