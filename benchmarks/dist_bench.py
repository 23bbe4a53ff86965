"""Sweep-engine benchmark: the batched one-trace grid vs the seed's per-cell
Python loop on the paper's experiment grid (6 variants x 4 step-sizes x 3
seeds, 200 rounds), plus the ``group_by_variant=True`` partitioned mode (V
traces, 1x arithmetic — the §5 crossover data).  It prints its rows through
``benchmarks/run.py`` and writes no file.

The grid constants below are also the paper grid ``chip_smoke.py`` runs.
"""
from __future__ import annotations

import time

import jax

from repro.core import artemis as art
from repro.core import federated as fed
from repro.core import sweep as sw

FAST = False      # set by benchmarks/run.py --fast: one cell, few iters

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


ALL = [sweep_engine_suite]
