"""Driver for the train cells: the program's jitted Artemis train step on
its own input pipeline, timed over a window, then held to the reference.

Set-up builds what ``repro.launch.train.setup`` and ``batch_source`` build,
from the same public functions, but from ``--seed``:

* weights: the harness draws them in one jitted call on the device,
  placed as ``launch.mesh.params_shardings`` places them;
* tokens: ``data.synthetic.TokenStream`` seeded from ``--seed``, sharded by
  ``data.pipeline.ShardedBatches`` over the worker axis; frames drawn on the
  device from ``--seed`` and the step, as ``batch_source`` draws them;
* rounding keys: the step's keys are ``fold_in(PRNGKey(DistConfig.seed),
  round)``, with the round counter in the state; the harness starts the
  counter at an offset drawn from ``--seed``, so the keys differ per seed
  while the compiled program stays the same.

The first three steps run through the same jitted call and feed as the
window's and are what the reference follows; they also warm the step up.
The window then runs steps until ``--seconds`` have passed, reading the
loss back every ``log_every`` steps, and closes on ``block_until_ready``.
A traced run traces a window of at most ``trace_steps`` steps.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import time
from typing import Optional

import numpy as np

from harness import compare, flops, runtime
from harness import ref_whisper as REF
from harness import trace as TR

B1 = 0.9           # Adam's first-moment decay (repro.optim.adam default)


class Program:
    """The program's objects for one cell, built once per process."""

    def __init__(self, cell, fault: Optional[str] = None):
        import jax
        from repro import configs
        from repro.core import dist
        from repro.launch import mesh as M
        from repro.launch import train as T
        from repro.models.model import build_model
        from repro.optim import adam

        self.jax = jax
        cfg, tr = cell.config, cell.traffic
        run_as = cfg["run_as"]
        self.mcfg = dataclasses.replace(configs.get_config(run_as["registry"]),
                                        **run_as["fields"])
        self.mesh = T.make_mesh(tr["mesh"])
        self.n_workers = self.mesh.shape["data"]
        if self.n_workers != tr["workers"]:
            raise RuntimeError(f"mesh {tr['mesh']} gives {self.n_workers} "
                               f"workers, the traffic asks {tr['workers']}")
        wire = tr["wire"]
        self.dcfg = dist.DistConfig(
            worker_axes=("data",), variant=tr["variant"], s=wire["s"],
            seed=wire["key_seed"], bucket_bytes=wire["bucket_bytes"],
            max_buckets=wire["max_buckets"], bucket_row=wire["row"])
        self.opt = adam(tr["lr"])
        self.model = build_model(self.mcfg)
        self.shapes = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.pshard = M.params_shardings(self.mesh, self.shapes)
        banned = self.dcfg.worker_axes
        self.model.set_sharding(M.layer_constraint_fn(self.mesh, banned),
                                M.act_constraint_fn(self.mesh, banned))
        gspecs = jax.tree.map(lambda ns: M.strip_axes(ns.spec, banned),
                              self.pshard)
        self.init_state, step_fn = dist.make_train_step(
            self.model, self.opt, self.dcfg, self.mesh, grad_specs=gspecs)
        if fault == "unchanged":
            inner = step_fn

            def step_fn(state, batch):
                return state, inner(state, batch)[1]
        elif fault == "half_batch":
            inner = step_fn
            n = self.n_workers

            def step_fn(state, batch):
                def half(a):
                    w = a.reshape((n, a.shape[0] // n) + a.shape[1:])
                    w = w[:, : w.shape[1] // 2]
                    return w.reshape((-1,) + a.shape[1:])
                return inner(state, jax.tree.map(half, batch))
        self.jstep = jax.jit(step_fn)
        self.make_weights = jax.jit(_weights_fn(self.shapes),
                                    out_shardings=self.pshard)
        self.norms = jax.jit(_leaf_norms)
        self.diff_norms = jax.jit(
            lambda a, b: _leaf_norms(jax.tree.map(lambda x, y: x - y, a, b)))
        self.batch = tr["batch_per_worker"] * self.n_workers
        self.seq = tr["seq"]

    def batch_source(self, data_seed: int, frame_seed: int,
                     spans: runtime.Spans):
        """step -> {"tokens", "frames"}, as ``launch.train.batch_source``
        builds it, seeded; each call timed as the span ``bench/batch``."""
        jax = self.jax
        from repro.data.pipeline import ShardedBatches
        from repro.data.synthetic import TokenStream, TokenStreamConfig

        stream = TokenStream(TokenStreamConfig(
            vocab=self.mcfg.vocab, seq_len=self.seq, batch=self.batch,
            seed=data_seed))
        batches = ShardedBatches(stream, self.mesh, batch_axes=("data",))
        fkey = jax.random.PRNGKey(frame_seed)
        frames_at = _frames_fn(jax, self.batch, self.mcfg.n_frames,
                               self.mcfg.d_model, batches.sharding)

        def batch_at(i: int) -> dict:
            with spans.span("bench/batch"):
                return {**batches.batch_at(i), "frames": frames_at(fkey, i)}

        return batch_at, (lambda i: frames_at(fkey, i))

    def fresh_state(self, w_seed: int, round0: int):
        jax = self.jax
        with jax.set_mesh(self.mesh):
            params = self.make_weights(jax.random.PRNGKey(w_seed))
            state = self.init_state(params)
            art = state.artemis
            step = jax.device_put(np.int32(round0), art.step.sharding)
            state = state._replace(artemis=art._replace(step=step))
        return params, state


def _weights_fn(shapes):
    """key -> weights: matrices N(0, 1/fan_in), embedding and head
    N(0, 0.02^2), norm gains zero (applied as 1 + g)."""
    import jax
    import jax.numpy as jnp

    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, leaf) in enumerate(flat):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            k = jax.random.fold_in(key, i)
            if leaf.ndim <= 1 or name.split("/")[-1].startswith("ln") \
                    or name.endswith("norm"):
                out.append(jnp.zeros(leaf.shape, leaf.dtype))
                continue
            scale = 0.02 if name in ("embed", "unembed") \
                else leaf.shape[-2] ** -0.5
            out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                        * scale).astype(leaf.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make


def _frames_fn(jax, batch, n_frames, d_model, sharding):
    return jax.jit(
        lambda key, i: jax.random.normal(jax.random.fold_in(key, i),
                                         (batch, n_frames, d_model)),
        out_shardings=sharding)


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


def program_readings(prog: Program, state, params0, batch_at, steps: int):
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
                first = np.asarray(prog.norms(state.opt_state["m"])) / (1 - B1)
        change = np.asarray(prog.diff_norms(state.params, params0))
    return state, {"losses": losses, "first_grad": first,
                   "change": change}, tokens


def reference_readings(cell, params0, tokens, frames_of, n_workers: int,
                       round0: int, mm: str = "f32") -> dict:
    import jax
    tr = cell.traffic
    batches = [{"tokens": t,
                "frames": jax.device_put(np.asarray(frames_of(i)))}
               for i, t in enumerate(tokens)]
    return REF.run(params0, batches, cell.config, tr["wire"], n_workers,
                   round0, tr["lr"], steps=len(tokens), mm=mm,
                   block=tr["ref_block"])


def _host_params(jax, params):
    """The initial weights as the reference takes them: one plain copy on
    the first device, gathered from however the program placed them."""
    return jax.tree.map(lambda a: jax.device_put(np.asarray(a)), params)


def run(ctx) -> dict:
    import jax

    cell = ctx.cell
    tr = cell.traffic
    steps_ref = tr["check_steps"]
    w_seed, data_seed, frame_seed, round0 = runtime.sub_seeds(ctx.seed, 4)
    prog = Program(cell, fault=ctx.fault)
    spans = runtime.Spans(annotate=ctx.trace)
    batch_at, frames_of = prog.batch_source(data_seed, frame_seed, spans)
    params, state = prog.fresh_state(w_seed, round0)
    state, prog_r, tokens = program_readings(prog, state, params, batch_at,
                                             steps_ref)
    jax.block_until_ready(state)
    setup_s = time.perf_counter() - ctx.t_start

    # ---- the window -----------------------------------------------------
    spans.reset()
    log_every = tr["log_every"]
    read, i, n = [], steps_ref, 0
    trace_dir = os.path.join(ctx.out_dir, "trace")
    with runtime.CompileCounter() as cc, \
            runtime.profiled(trace_dir, ctx.trace), \
            jax.set_mesh(prog.mesh), spans.span(TR.WINDOW_SPAN):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds and not (
                ctx.trace and n >= tr["trace_steps"]):
            b = batch_at(i)
            with spans.span("bench/step"):
                state, (loss, _) = prog.jstep(state, b)
            if n % log_every == 0:
                read.append(float(loss))
            i += 1
            n += 1
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    compiles = cc.count
    batch_s = spans.totals.get("bench/batch", 0.0)
    dev = runtime.device_summary()

    rec = {"steps": n, "window_s": window_s, "chips": cell.chips,
           "device_kind": dev["kind"], "n_workers": prog.n_workers,
           "host_batch_s": batch_s,
           "train_flops_per_step": flops.encdec_train_flops(
               cell.config, prog.batch, prog.seq),
           "bucket_layout": flops.bucket_layout(
               sum(int(np.prod(a.shape))
                   for a in jax.tree.leaves(prog.shapes)),
               tr["wire"]["bucket_bytes"], tr["wire"]["max_buckets"],
               tr["wire"]["row"])}
    if ctx.trace:
        with jax.set_mesh(prog.mesh):
            text = prog.jstep.lower(state, batch_at(i)).compile().as_text()
        rec["trace"] = TR.reduce_profile(TR.load(trace_dir),
                                         TR.op_names(text))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- free the program's state, then the reference --------------------
    p0 = _host_params(jax, params)
    del state, params
    gc.collect()
    ref_r = reference_readings(cell, p0, tokens, frames_of, prog.n_workers,
                               round0)
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
    for seed in seeds:
        t0 = time.perf_counter()
        w_seed, data_seed, frame_seed, round0 = runtime.sub_seeds(seed, 4)
        batch_at, frames_of = prog.batch_source(data_seed, frame_seed,
                                                runtime.Spans())
        params, state = prog.fresh_state(w_seed, round0)
        steps_ref = cell.traffic["check_steps"]
        p0 = _host_params(jax, params)
        if control:
            tokens = [np.asarray(jax.device_get(batch_at(i)["tokens"]))
                      for i in range(steps_ref)]
            del state
            prog_r = reference_readings(cell, p0, tokens, frames_of,
                                        prog.n_workers, round0, mm=control)
        else:
            state, prog_r, tokens = program_readings(prog, state, params,
                                                     batch_at, steps_ref)
            del state
        del params
        gc.collect()
        t1 = time.perf_counter()
        ref_r = reference_readings(cell, p0, tokens, frames_of,
                                   prog.n_workers, round0)
        out = compare.train_numbers(prog_r, ref_r)
        out.update(seed=seed, program_s=t1 - t0,
                   reference_s=time.perf_counter() - t1,
                   losses_prog=prog_r["losses"], losses_ref=ref_r["losses"])
        yield out
