"""Driver for the sim cells: the program's sweep engine
(``repro.core.sweep.run_sweep``) over the paper's grid, call after call,
then a sample of the calls held to the reference.

The problem (the workers' data) is the configuration's: the program
compiles it into the grid program as constants, so one problem per cell
keeps every run on one compiled program.  ``--seed`` draws the algorithm
seeds: call ``i`` of a run gets ``S`` keys split from ``fold_in(key(seed),
i)``, so no call repeats another and none retraces.  Every call runs at
float32 matmul precision (``jax.default_matmul_precision("float32")``),
the precision the configuration states; the TPU's default would round
matmul operands to bfloat16.  A traced run traces a window of at most
``trace_calls`` calls.
"""
from __future__ import annotations

import os
import shutil
import time

import numpy as np

from harness import compare, ref_lsr, runtime
from harness import trace as TR


class Program:
    def __init__(self, cell):
        import jax
        from repro.core import artemis as art
        from repro.core import federated as fed

        cfg, tr = cell.config, cell.traffic
        self.jax = jax
        self.n, self.d = cfg["n_workers"], cfg["dim"]
        X, Y = ref_lsr.make_problem(cfg["problem_key"], self.n,
                                    cfg["n_per_worker"], self.d,
                                    cfg["noise"])
        self.X, self.Y = X, Y
        self.prob = fed.Problem(X=X, Y=Y, kind="lsr")
        self.variants = tr["variants"]
        s = cfg["s"]
        self.cfgs = [art.variant_config(v, self.d, self.n, s=s, p=cfg["p"])
                     for v in self.variants]
        g = ref_lsr.gamma_max(np.asarray(X), self.d, self.n, s, cfg["p"])
        self.gammas = [f * g for f in tr["gamma_fracs"]]
        self.kw = dict(batch=tr["batch"], eval_every=tr["eval_every"],
                       backend=tr["backend"])
        self.iters = tr["iters"]
        self.n_seeds = tr["seeds_per_call"]
        self.cells = len(self.variants) * len(self.gammas) * self.n_seeds
        self.precision = cfg["matmul_precision"]

    def keys(self, seed: int, calls: int) -> np.ndarray:
        """[calls, S, 2] uint32 keys; call i's are split from
        fold_in(PRNGKey(seed), i)."""
        jax = self.jax
        base = jax.random.PRNGKey(runtime.sub_seeds(seed, 1)[0])
        f = jax.jit(jax.vmap(lambda i: jax.random.split(
            jax.random.fold_in(base, i), self.n_seeds)))
        return np.asarray(f(np.arange(calls, dtype=np.int32)))

    def call(self, keys):
        from repro.core import sweep as sw
        return sw.run_sweep(self.prob, self.cfgs, self.gammas, keys,
                            self.iters, **self.kw)


def reference(prog: Program, cell, keys, mm: str = "f32"):
    tr = cell.traffic
    return ref_lsr.run_grid(prog.X, prog.Y, prog.variants, prog.gammas, keys,
                            iters=prog.iters, eval_every=tr["eval_every"],
                            batch=tr["batch"], s=cell.config["s"],
                            p=cell.config["p"], margin=tr["margin"], mm=mm)


MAX_CALLS = 1 << 14


def run(ctx) -> dict:
    import jax

    cell, tr = ctx.cell, ctx.cell.traffic
    prog = Program(cell)
    keys = prog.keys(ctx.seed, MAX_CALLS)
    with jax.default_matmul_precision(prog.precision):
        for i in range(tr["warm_calls"]):
            prog.call(keys[MAX_CALLS - 1 - i])
    setup_s = time.perf_counter() - ctx.t_start

    spans = runtime.Spans(annotate=ctx.trace)
    trace_dir = os.path.join(ctx.out_dir, "trace")
    results, retraces = [], 0
    with runtime.CompileCounter() as cc, \
            runtime.profiled(trace_dir, ctx.trace), \
            jax.default_matmul_precision(prog.precision), \
            spans.span(TR.WINDOW_SPAN):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds and not (
                ctx.trace and len(results) >= tr["trace_calls"]):
            with spans.span("bench/grid"):
                res = prog.call(keys[len(results)])
            results.append(res.losses)
            retraces += res.traces
        window_s = time.perf_counter() - t0
    calls = len(results)
    dev = runtime.device_summary()
    rec = {"calls": calls, "window_s": window_s, "cells": prog.cells,
           "iters": prog.iters, "device_kind": dev["kind"]}
    if ctx.trace:
        rec["trace"] = TR.reduce_profile(TR.load(trace_dir), {})
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- a sample of the calls, drawn from the seed, against the reference
    rng = np.random.default_rng(runtime.sub_seeds(ctx.seed, 2)[1])
    k = min(tr["checked_calls"], calls)
    pick = sorted(set([calls - 1] + list(rng.choice(calls, k - 1,
                                                    replace=False))))
    gaps = []
    for i in pick:
        ref, frag = reference(prog, cell, keys[i])
        gaps.append(ref_lsr.grid_gap(results[i], ref, frag, tr["eval_every"],
                                     tr["rise"]))
    numbers = {"grid_loss_gap": max(gaps),
               "window_compiles": float(cc.count),
               "window_retraces": float(retraces)}
    verdict = compare.judge(numbers, cell.limits)
    return {"correct": verdict["correct"], "checks": verdict["checks"],
            "attempted": calls, "failed": 0,
            "e2e": {"setup_s": setup_s,
                    "sim_cell_rounds_per_s":
                        calls * prog.cells * prog.iters / window_s},
            "rec": rec, "device": dev, "numbers": numbers}


def readings(ctx, seeds, control=None, fault=None):
    """Per seed: the widest gap over ``checked_calls`` grid calls of the
    program (or of the reference at ``control`` precision in its place)
    from the reference.  Faults are planted by the tests."""
    import jax

    if fault:
        raise ValueError("sim faults are planted by the tests")
    cell, tr = ctx.cell, ctx.cell.traffic
    prog = Program(cell)
    for seed in seeds:
        t0 = time.perf_counter()
        keys = prog.keys(seed, tr["checked_calls"])
        gaps, fragile_cells, looks = [], 0, []
        for i in range(len(keys)):
            if control:
                out, _ = reference(prog, cell, keys[i], mm=control)
            else:
                with jax.default_matmul_precision(prog.precision):
                    out = prog.call(keys[i]).losses
            ref, frag = reference(prog, cell, keys[i])
            fragile_cells += int(np.sum(frag >= 0))
            gaps.append(ref_lsr.grid_gap(out, ref, frag, tr["eval_every"],
                                         tr["rise"]))
            looks.append(_look(ref_lsr.gap_points(out, ref, frag,
                                                  tr["eval_every"], np.inf),
                               ref))
        yield {"seed": seed, "grid_loss_gap": max(gaps), "gaps": gaps,
               "fragile_cells": fragile_cells,
               "look": {k: max(x[k] for x in looks) for k in looks[0]},
               "seconds": time.perf_counter() - t0}


def _look(g, ref):
    """Where a call's widest gaps lie over every finite point before the
    first fragile round: per variant, at the first eval point, and while
    the reference loss stays under ten times its first."""
    def mx(a):
        return float(np.nanmax(a)) if np.isfinite(a).any() else 0.0
    out = {f"variant{v}": mx(g[v]) for v in range(g.shape[0])}
    out["first_eval"] = mx(g[..., 0])
    out["not_rising"] = mx(np.where(ref < 10 * ref[..., :1], g, np.nan))
    return out

