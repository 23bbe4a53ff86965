"""Training driver.

Runs real steps on whatever devices exist (CPU host mesh for local runs; the
production mesh on a real cluster).  Supports every Artemis variant over a
configurable worker axis, checkpointing, and loss logging.

  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-7b --reduced \
      --steps 100 --batch 8 --seq 128 --dist artemis --workers data
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import time
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

from repro import configs
from repro.checkpoint import checkpointer
from repro.core import dist
from repro.core import faults
from repro.data.pipeline import ShardedBatches
from repro.data.synthetic import TokenStream, TokenStreamConfig
from repro.launch import mesh as M
from repro.launch.compile_cache import enable_compile_cache
from repro.models import layers as L
from repro.models.model import build_model
from repro.obs import events as obs_events
from repro.obs import spans as obs_spans
from repro.optim import adam, sgd


MOE_COUNTERS = ("moe_held_assignments", "moe_max_load")


def _copy(tree):
    return jax.tree.map(lambda a: a.copy(), tree)


def make_mesh(spec: Optional[str] = None) -> Mesh:
    """``"DxM"`` -> a (data=D, model=M) mesh; None -> every device on data."""
    if spec is None:
        return M.make_host_mesh()
    d, m = (int(x) for x in spec.split("x"))
    return M.make_host_mesh((d, m), ("data", "model"))


def setup(cfg, mesh: Mesh, dcfg: Optional[dist.DistConfig], opt):
    """(model, grad_specs, placed initial state, step_fn) as ``main`` trains
    them: params from ``PRNGKey(0)`` under the mesh sharding policy."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pshard = M.params_shardings(mesh, params)
    banned = dcfg.worker_axes if dcfg else ()
    model.set_sharding(M.layer_constraint_fn(mesh, banned),
                       M.act_constraint_fn(mesh, banned))
    gspecs = (jax.tree.map(lambda ns: M.strip_axes(ns.spec, banned), pshard)
              if dcfg else None)
    init_state, step_fn = dist.make_train_step(model, opt, dcfg, mesh,
                                               grad_specs=gspecs)
    with jax.set_mesh(mesh):
        state = init_state(jax.device_put(params, pshard))
    return model, gspecs, state, step_fn


def batch_source(cfg, mesh: Mesh, batch: int, seq: int, workers: str):
    """step -> the synthetic training batch, sharded over the batch axes."""
    stream = TokenStream(TokenStreamConfig(vocab=cfg.vocab, seq_len=seq,
                                           batch=batch))
    batches = ShardedBatches(stream, mesh, batch_axes=(workers, "data"))
    if cfg.family != "encdec":
        return batches.batch_at
    # the audio front end is stubbed (configs.input_specs): frame embeddings
    # [B, n_frames, d_model] are drawn on the device from the stream's seed
    # and the step, so no host copy enters the step
    fkey = jax.random.PRNGKey(stream.cfg.seed)
    frames_at = jax.jit(
        lambda i: jax.random.normal(jax.random.fold_in(fkey, i),
                                    (batch, cfg.n_frames, cfg.d_model)),
        out_shardings=batches.sharding)

    def batch_at(step: int) -> dict:
        return {**batches.batch_at(step), "frames": frames_at(step)}

    return batch_at


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adam", choices=["sgd", "adam"])
    ap.add_argument("--dist", default="none",
                    choices=["none"] + list(dist.VARIANTS))
    ap.add_argument("--workers", default="data", help="worker axis name")
    ap.add_argument("--s", type=int, default=1, help="quantization levels")
    ap.add_argument("--participation", type=float, default=1.0)
    ap.add_argument("--local-steps", type=int, default=1,
                    help="communicate every k steps (grad accumulation)")
    ap.add_argument("--mesh", default=None,
                    help="mesh shape, e.g. 4x2 => data=4, model=2")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restart from ckpt-dir/LATEST if present (without "
                         "this flag an existing checkpoint is ignored)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--log-file", default=None)
    ap.add_argument("--events", default=None,
                    help="repro.obs JSONL event log (omit: echo-only)")
    # --- fault injection + self-healing (core/faults.py, DESIGN.md §8) ---
    ap.add_argument("--straggler-rate", type=float, default=0.0)
    ap.add_argument("--p-stay", type=float, default=None,
                    help="Markov P(active->active); default i.i.d.")
    ap.add_argument("--bitflip-rate", type=float, default=0.0)
    ap.add_argument("--blowup-rate", type=float, default=0.0)
    ap.add_argument("--blowup-value", type=float, default=float("nan"))
    ap.add_argument("--scrub", action="store_true",
                    help="server-side finite/checksum payload scrubbing")
    ap.add_argument("--sentinel", type=float, default=0.0,
                    help="loss threshold: blown-up loss rolls back to the "
                         "last checkpoint with lr backoff (0 = off)")
    ap.add_argument("--backoff", type=float, default=0.5)
    ap.add_argument("--max-rollbacks", type=int, default=3)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = configs.get_config(args.arch, reduced=args.reduced)
    mesh = make_mesh(args.mesh)

    fc = faults.FaultConfig(
        straggler_rate=args.straggler_rate, p_stay=args.p_stay,
        bitflip_rate=args.bitflip_rate, blowup_rate=args.blowup_rate,
        blowup_value=args.blowup_value, scrub=args.scrub,
        sentinel=args.sentinel, backoff=args.backoff)
    dcfg = None
    if args.dist != "none":
        dcfg = dist.DistConfig(worker_axes=(args.workers,), variant=args.dist,
                               s=args.s, p_participation=args.participation,
                               local_steps=args.local_steps,
                               faults=fc if fc.enabled else None)

    opt = adam(args.lr) if args.optimizer == "adam" else sgd(args.lr)
    model, gspecs, state, step_fn = setup(cfg, mesh, dcfg, opt)
    local_fn = (dist.make_local_step(model, dcfg, mesh)
                if dcfg and dcfg.local_steps > 1 else None)
    batch_at = batch_source(cfg, mesh, args.batch, args.seq, args.workers)

    # console output + optional JSONL log share one schema-checked sink
    log = obs_events.EventLog(args.events, echo=True)
    log.start(config={"arch": args.arch, "dist": args.dist,
                      "steps": args.steps, "batch": args.batch,
                      "seq": args.seq, "lr": args.lr},
              fingerprint=f"{args.arch}:{args.dist}:s{args.s}")
    with jax.set_mesh(mesh):
        # the state is donated: the step's new state takes the old one's
        # buffers, so one state (not one per step in flight) is live
        jstep = jax.jit(step_fn, donate_argnums=0)
        if (args.resume and args.ckpt_dir
                and checkpointer.latest_step(args.ckpt_dir) is not None):
            # one restore, one (re)trace: the killed run's state slots into
            # the same jitted step, so resuming compiles exactly once
            state = checkpointer.restore(args.ckpt_dir, state)
            log.emit("note", text=f"restored step {int(state.step)}")

        logs = []
        # host spans of the loop (obs.spans; summed per name at the end):
        # train/batch (input), train/dispatch (the step call returns once
        # the step is queued), train/loss_fetch (the read-back, where the
        # host waits for the device)
        obs_spans.reset()
        t0 = time.perf_counter()
        compiled = False        # first jstep call = compile + one step
        jlocal = jax.jit(local_fn, donate_argnums=0) if local_fn else None
        # host-side divergence sentinel: a copy of the last good state (the
        # step donates the state it is given) + geometric lr backoff
        keep = _copy if args.sentinel > 0 else (lambda st: None)
        good_state, lr_scale, rollbacks = keep(state), 1.0, 0
        start = int(state.step)
        i = start
        # the steady window opens at the first step after the compile and
        # closes once the device has finished the last step
        n_steady = 0
        with contextlib.ExitStack() as steady_window:
            while i < start + args.steps:
                with obs_spans.span("train/batch"):
                    batch = batch_at(i)
                if jlocal is not None and (i + 1) % args.local_steps:
                    with obs_spans.span("train/dispatch"):
                        state, (loss, metrics) = jlocal(state, batch)
                elif not compiled:
                    # compile-vs-execute split: the first communicating step
                    # pays the trace+compile; block so the span measures it
                    with obs_spans.span("train/compile+first_step"), \
                            L.record_attention_paths() as attn_calls:
                        state, (loss, metrics) = jstep(state, batch)
                        jax.block_until_ready(loss)
                    compiled = True
                    # the path each distinct attention call took in the
                    # step's trace
                    log.emit("attn_path", sites=attn_calls)
                else:
                    if not n_steady:
                        steady_window.enter_context(
                            obs_spans.span("train/steady_steps"))
                    n_steady += 1
                    with obs_spans.span("train/dispatch"):
                        state, (loss, metrics) = jstep(state, batch)
                if i % args.log_every == 0 or i == start + args.steps - 1:
                    with obs_spans.span("train/loss_fetch"):
                        loss_f = float(loss)
                    bad = not np.isfinite(loss_f) or (
                        args.sentinel > 0 and loss_f > args.sentinel)
                    if bad and args.sentinel > 0:
                        rollbacks += 1
                        if rollbacks > args.max_rollbacks:
                            raise RuntimeError(
                                f"loss diverged {rollbacks} times; giving up")
                        lr_scale *= args.backoff
                        state = keep(good_state)
                        opt2 = (adam(args.lr * lr_scale)
                                if args.optimizer == "adam"
                                else sgd(args.lr * lr_scale))
                        _, step_fn2 = dist.make_train_step(
                            model, opt2, dcfg, mesh, grad_specs=gspecs)
                        jstep = jax.jit(step_fn2, donate_argnums=0)
                        log.emit("rollback", step=int(state.step),
                                 count=rollbacks, lr_scale=lr_scale)
                        i = int(state.step)
                        continue
                    rec = {"step": int(state.step), "loss": round(loss_f, 4),
                           "nll": round(float(metrics["nll"]), 4),
                           "wall_s": round(time.perf_counter() - t0, 1),
                           "rollbacks": rollbacks}
                    # MoE expert counters: assignments to the held experts
                    # (over the layers) and the largest held expert's load
                    rec.update({k: int(metrics[k]) for k in MOE_COUNTERS
                                if k in metrics})
                    logs.append(rec)
                    log.emit("train_step", **rec)
                    assert np.isfinite(loss_f), "loss diverged"
                    good_state = keep(state)
                if (args.ckpt_every and args.ckpt_dir
                        and int(state.step) % args.ckpt_every == 0):
                    checkpointer.save(args.ckpt_dir, int(state.step), state)
                i += 1
            jax.block_until_ready(state)
        if args.ckpt_dir:
            checkpointer.save(args.ckpt_dir, int(state.step), state)
    wall = time.perf_counter() - t0
    # one event per span name: count and total (spans kept in memory and
    # written once, so the loop itself writes nothing)
    for agg in obs_spans.summarize_spans():
        log.emit("span", name=agg["name"], dur_s=agg["total_s"],
                 count=agg["count"])
    log.end(status="ok", wall_s=round(wall, 3))
    log.close()
    if args.log_file:
        with open(args.log_file, "w") as f:
            json.dump(logs, f, indent=1)
    return logs


if __name__ == "__main__":
    main()
