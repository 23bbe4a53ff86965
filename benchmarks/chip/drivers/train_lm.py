"""Driver for the decoder-only LM train cells: the program's jitted Artemis
train step, with its state donated, on token batches alone, timed over a
window, then held to the reference (``harness/ref_kanana.py``).

It is ``drivers/train.py`` with four differences:

* the step is jitted with ``donate_argnums=0``, as ``launch/train.py``
  jits it, so one model state is live; the initial weights are copied to
  the host before the first step, for the reference and the change;
* batches are ``{"tokens"}`` only, drawn by ``data.synthetic.TokenStream``
  from the vocabulary slice the configuration states;
* weights as ``drivers/train.py`` draws them, with the MoE selection bias
  (``router_bias``, a buffer that is not trained) zero;
* the planted fault ``half_batch`` leaves out the second half of each row's
  tokens (a row per worker cannot be halved), and the window records the
  program's MoE counters of every step.
"""
from __future__ import annotations

import gc
import os
import shutil
import sys
import time
from typing import Optional

import numpy as np

from harness import compare, flops, flops_lm, runtime
from harness import ref_kanana as REF
from harness import spec
from harness import trace as TR

BASE = spec.load_driver("train")
COUNTERS = ("moe_held_assignments", "moe_max_load")


class Program(BASE.Program):

    def __init__(self, cell, fault: Optional[str] = None):
        super().__init__(cell, fault=None if fault == "half_batch" else fault)
        jax = self.jax
        step = self.jstep
        if fault == "half_batch":
            inner, half = step, self.seq // 2

            def step(state, batch):
                return inner(state, {"tokens": batch["tokens"][:, :half]})
        self.jstep = jax.jit(step, donate_argnums=0)
        weights = BASE._weights_fn(self.shapes)
        self.make_weights = jax.jit(lambda key: _zero_bias(weights(key)),
                                    out_shardings=self.pshard)

    def batch_source(self, data_seed: int, spans: runtime.Spans):
        """step -> {"tokens"}, as ``launch.train.batch_source`` builds it
        for a decoder-only model, seeded; timed as ``bench/batch``."""
        from repro.data.pipeline import ShardedBatches
        from repro.data.synthetic import TokenStream, TokenStreamConfig

        stream = TokenStream(TokenStreamConfig(
            vocab=self.mcfg.vocab, seq_len=self.seq, batch=self.batch,
            seed=data_seed))
        batches = ShardedBatches(stream, self.mesh, batch_axes=("data",))

        def batch_at(i: int) -> dict:
            with spans.span("bench/batch"):
                return batches.batch_at(i)

        return batch_at

    def fresh_state(self, w_seed: int, round0: int):
        """(initial weights on the host, the placed state)."""
        jax = self.jax
        with jax.set_mesh(self.mesh):
            params = self.make_weights(jax.random.PRNGKey(w_seed))
            p0 = jax.device_get(params)
            state = self.init_state(params)
            art = state.artemis
            step = jax.device_put(np.int32(round0), art.step.sharding)
            state = state._replace(artemis=art._replace(step=step))
        return p0, state


def _zero_bias(params):
    import jax
    import jax.numpy as jnp

    def zero(path, a):
        name = getattr(path[-1], "key", "")
        return jnp.zeros_like(a) if name == "router_bias" else a

    return jax.tree_util.tree_map_with_path(zero, params)


def program_readings(prog: Program, state, p0, batch_at, steps: int):
    """Run ``steps`` steps through the window's call and feed; returns
    (state, readings, host token copies)."""
    jax = prog.jax
    losses, tokens = [], []
    first = None
    with jax.set_mesh(prog.mesh):
        for i in range(steps):
            b = batch_at(i)
            tokens.append(np.asarray(jax.device_get(b["tokens"])))
            state, (loss, _) = prog.jstep(state, b)
            losses.append(float(loss))
            if i == 0:
                first = (np.asarray(prog.norms(state.opt_state["m"]))
                         / (1 - BASE.B1))
        change = np.asarray(prog.diff_norms(
            state.params, jax.device_put(p0, prog.pshard)))
    return state, {"losses": losses, "first_grad": first,
                   "change": change}, tokens


def reference_readings(cell, p0, tokens, n_workers: int, round0: int,
                       mm: str = "f32") -> dict:
    tr = cell.traffic
    return REF.run(p0, [{"tokens": t} for t in tokens], cell.config,
                   tr["wire"], n_workers, round0, tr["lr"],
                   steps=len(tokens), mm=mm)


def run(ctx) -> dict:
    import jax

    cell = ctx.cell
    tr = cell.traffic
    steps_ref = tr["check_steps"]
    w_seed, data_seed, round0 = runtime.sub_seeds(ctx.seed, 3)
    prog = Program(cell, fault=ctx.fault)
    spans = runtime.Spans(annotate=ctx.trace)
    batch_at = prog.batch_source(data_seed, spans)
    p0, state = prog.fresh_state(w_seed, round0)
    state, prog_r, tokens = program_readings(prog, state, p0, batch_at,
                                             steps_ref)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - ctx.t_start

    # ---- the window -----------------------------------------------------
    spans.reset()
    log_every = tr["log_every"]
    read, counters, i, n = [], [], steps_ref, 0
    trace_dir = os.path.join(ctx.out_dir, "trace")
    with runtime.CompileCounter() as cc, \
            runtime.profiled(trace_dir, ctx.trace), \
            jax.set_mesh(prog.mesh), spans.span(TR.WINDOW_SPAN):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds and not (
                ctx.trace and n >= tr["trace_steps"]):
            b = batch_at(i)
            with spans.span("bench/step"):
                state, (loss, metrics) = prog.jstep(state, b)
            counters.append({k: metrics[k] for k in COUNTERS
                             if k in metrics})
            if n % log_every == 0:
                read.append(float(loss))
            i += 1
            n += 1
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    compiles = cc.count
    batch_s = spans.totals.get("bench/batch", 0.0)
    dev = runtime.device_summary()
    counts = {k: np.asarray([c[k] for c in jax.device_get(counters)])
              for k in counters[0]} if counters else {}

    rec = {"steps": n, "window_s": window_s, "chips": cell.chips,
           "device_kind": dev["kind"], "n_workers": prog.n_workers,
           "host_batch_s": batch_s,
           "train_flops_per_step": flops_lm.lm_train_flops(
               cell.config, prog.batch, prog.seq),
           "bucket_layout": flops.bucket_layout(
               sum(int(np.prod(a.shape))
                   for a in jax.tree.leaves(prog.shapes)),
               tr["wire"]["bucket_bytes"], tr["wire"]["max_buckets"],
               tr["wire"]["row"])}
    if counts:
        # the program's MoE counters over the window's steps
        rec.update(moe_held_assignments=float(
                       counts["moe_held_assignments"].mean()),
                   moe_max_load=float(counts["moe_max_load"].max()),
                   expert_flops_per_assignment=(
                       flops_lm.expert_flops_per_assignment(cell.config)))
        print(f"counters: moe_held_assignments "
              f"{rec['moe_held_assignments']!r} per step, moe_max_load "
              f"{rec['moe_max_load']!r}", file=sys.stderr)
    if ctx.trace:
        with jax.set_mesh(prog.mesh):
            text = prog.jstep.lower(state, batch_at(i)).compile().as_text()
        rec["trace"] = TR.reduce_profile(TR.load(trace_dir),
                                         TR.op_names(text))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- free the program's state, then the reference --------------------
    del state
    gc.collect()
    ref_r = reference_readings(cell, p0, tokens, prog.n_workers, round0)
    numbers = compare.train_numbers(prog_r, ref_r)
    numbers["window_compiles"] = float(compiles)
    numbers["window_nonfinite_losses"] = float(
        sum(1 for x in read if not np.isfinite(x)))
    verdict = compare.judge(numbers, cell.limits)
    tokens_done = n * prog.batch * prog.seq
    return {"correct": verdict["correct"], "checks": verdict["checks"],
            "attempted": n, "failed": int(numbers["window_nonfinite_losses"]),
            "e2e": {"setup_s": setup_s,
                    "train_tokens_per_s": tokens_done / window_s},
            "rec": rec, "device": dev, "numbers": numbers}


def readings(ctx, seeds, control: Optional[str] = None,
             fault: Optional[str] = None):
    """Correctness readings only, one per seed, for setting limits: the
    program's (or, with ``control``, the reference's at that precision put
    in its place; or the program with ``fault`` planted) against the
    reference.  Yields one dict per seed."""
    import jax

    cell = ctx.cell
    prog = Program(cell, fault=fault)
    steps_ref = cell.traffic["check_steps"]
    for seed in seeds:
        t0 = time.perf_counter()
        w_seed, data_seed, round0 = runtime.sub_seeds(seed, 3)
        batch_at = prog.batch_source(data_seed, runtime.Spans())
        p0, state = prog.fresh_state(w_seed, round0)
        if control:
            tokens = [np.asarray(jax.device_get(batch_at(i)["tokens"]))
                      for i in range(steps_ref)]
            del state
            gc.collect()
            prog_r = reference_readings(cell, p0, tokens, prog.n_workers,
                                        round0, mm=control)
        else:
            state, prog_r, tokens = program_readings(prog, state, p0,
                                                     batch_at, steps_ref)
            del state
            gc.collect()
        t1 = time.perf_counter()
        ref_r = reference_readings(cell, p0, tokens, prog.n_workers, round0)
        out = compare.train_numbers(prog_r, ref_r)
        out.update(seed=seed, program_s=t1 - t0,
                   reference_s=time.perf_counter() - t1,
                   losses_prog=prog_r["losses"], losses_ref=ref_r["losses"])
        yield out
