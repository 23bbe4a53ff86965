"""Smoke run of the main path on TPU, through the entry points users call.

    python chip_smoke.py               # one chip: (a) train, (b) paper sweep
    python chip_smoke.py --four-chips  # four chips: (c) mesh train step only

(a) ``repro.launch.train.main`` trains whisper-tiny at its full published
    config with ``--dist artemis`` (bucketed pipelined int8 wire) for 5
    steps, batch 8, seq 448, from random weights (``PRNGKey(0)``).  The same
    step is then compiled once: it must hold a ``tpu_custom_call`` (the
    ``bucket_acc`` ring kernel), and its aggregate must match the dense
    ``reduce_impl="psum"`` step on the same state and batch.
(b) ``repro.core.sweep.run_sweep`` runs the paper grid of
    ``benchmarks/dist_bench.py`` (6 variants x 4 step sizes x 3 seeds, d=20,
    N=20, 200 rounds) with the fused Pallas uplink and with the dense one; the
    final losses must agree and the carry donation must hold.
(c) ``--four-chips``: (a) on a 4x1 mesh, one Artemis worker per chip, the
    ring against the psum reduce of the same payloads; every chip must hold
    its worker's shard and the ring's collective-permutes must be compiled.

Timings and checks print as JSON lines; the last line is
``{"ok": true, "device": {...}}``.  Without a TPU, with
``REPRO_INTERPRET`` asking for Pallas interpret mode, without the repo's
``src/`` beside this file, or when any check fails, the script exits
nonzero and prints no result line.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "whisper-tiny"
BATCH, SEQ, STEPS = 8, 448, 5
# ring vs psum: max |aggregate difference| over max |aggregate|.  The two
# sum the same dequantized payloads in a different order (f32).
AGG_RTOL = 1e-5
# pallas vs dense sweep: |Lp - Ld| <= RTOL |Ld| + ATOL at every eval point
# where the dense loss is finite (the tolerance of tests/test_sweep.py).
# The grid's Dore cells diverge on both backends at these step sizes; their
# series are compared until the dense one overflows.
SWEEP_RTOL, SWEEP_ATOL = 1e-4, 1e-6


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)


def report(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


@contextlib.contextmanager
def step_compiles():
    """Collect the lowerings of ``jit(step_fn)`` made inside the block: one
    per compile, whether or not the persistent cache then serves it."""
    import jax

    hits = []

    def listen(event, duration, **kw):
        if (event == "/jax/core/compile/jaxpr_to_mlir_module_duration"
                and kw.get("fun_name") == "jit(step_fn)"):
            hits.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield hits
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def phase_train(mesh_spec):
    """(a)/(c): train through ``train.main``, then compile the same step and
    hold its ring aggregate to the psum reference."""
    import jax
    import numpy as np

    from repro import configs
    from repro.core import dist
    from repro.launch import train
    from repro.obs import spans as obs_spans
    from repro.optim import adam

    argv = ["--arch", ARCH, "--dist", "artemis", "--batch", str(BATCH),
            "--seq", str(SEQ), "--steps", str(STEPS), "--log-every", "1"]
    if mesh_spec:
        argv += ["--mesh", mesh_spec, "--workers", "data"]
    with step_compiles() as compiles:
        logs = train.main(argv)
    losses = [r["loss"] for r in logs]
    check(len(losses) == STEPS and all(np.isfinite(losses)),
          f"train losses not finite: {losses}")
    # a second compile would land inside the steady window
    check(len(compiles) == 1, f"train step compiled {len(compiles)} times")
    first = obs_spans.total("train/compile+first_step")
    steady = obs_spans.total("train/steady_steps")
    report("train", arch=ARCH, batch=BATCH, seq=SEQ, steps=STEPS,
           losses=losses, step_compiles=len(compiles),
           compile_and_first_step_s=first,
           steady_s_per_step=steady / (STEPS - 1),
           steady_tokens_per_s=BATCH * SEQ * (STEPS - 1) / steady)

    cfg = configs.get_config(ARCH)
    mesh = train.make_mesh(mesh_spec)
    n_workers = mesh.shape["data"]
    dcfg = dist.DistConfig(worker_axes=("data",), variant="artemis")
    opt = adam(3e-3)                     # train.main's default --lr
    model, _, state, step = train.setup(cfg, mesh, dcfg, opt)
    _, step_psum = dist.make_train_step(
        model, opt, dataclasses.replace(dcfg, reduce_impl="psum"), mesh)
    batch = train.batch_source(cfg, mesh, BATCH, SEQ, "data")(0)
    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        ring = jax.jit(step).lower(state, batch).compile()
        compile_s = time.perf_counter() - t0
        psum = jax.jit(step_psum).lower(state, batch).compile()
        text = ring.as_text()
        check("tpu_custom_call" in text,
              "compiled train step holds no tpu_custom_call (bucket_acc)")
        if n_workers > 1:
            check("collective-permute" in text,
                  "compiled train step holds no ring collective-permute")
        s_ring, (l_ring, _) = ring(state, batch)
        s_psum, (l_psum, _) = psum(state, batch)
    mem = ring.memory_analysis()
    # hbar starts at zero, so after one step it is alpha/N times the summed
    # dequantized uplink: the aggregate itself, before the downlink encode
    a = np.asarray(s_ring.artemis.hbar)
    b = np.asarray(s_psum.artemis.hbar)
    scale = float(np.max(np.abs(b)))
    err = float(np.max(np.abs(a - b))) / scale
    check(scale > 0 and np.all(np.isfinite(a)),
          f"ring aggregate degenerate (max |psum agg| = {scale})")
    check(err <= AGG_RTOL,
          f"ring vs psum aggregate: rel err {err:.3e} > {AGG_RTOL}")
    check(abs(float(l_ring) - float(l_psum)) <= 1e-6,
          f"ring vs psum loss: {float(l_ring)} vs {float(l_psum)}")
    h = s_ring.artemis.h
    devices = {sh.device for sh in h.addressable_shards}
    check(len(devices) == n_workers == len(jax.devices())
          and all(sh.data.shape[0] == 1 for sh in h.addressable_shards),
          f"worker memory shards on {len(devices)} devices, want one "
          f"[1, ...] shard per chip")
    report("train_step_check", workers=n_workers, compile_s=compile_s,
           tpu_custom_call=True,
           collective_permute="collective-permute" in text,
           ring_vs_psum_rel_err=err, rel_tol=AGG_RTOL,
           loss_ring=float(l_ring), loss_psum=float(l_psum),
           memory_shards=len(devices),
           temp_bytes=mem.temp_size_in_bytes,
           argument_bytes=mem.argument_size_in_bytes)


def phase_sweep():
    """(b): the paper grid, fused Pallas uplink vs dense."""
    import jax
    import numpy as np

    from benchmarks import dist_bench as grid
    from repro.core import artemis as art
    from repro.core import federated as fed
    from repro.core import sweep as sw

    n = d = 20
    prob, _ = fed.make_lsr_problem(jax.random.PRNGKey(11), n_workers=n,
                                   n_per=200, d=d, noise=0.4)
    cfgs = [art.variant_config(v, d, n) for v in grid.VARIANTS]
    g_ref = fed.gamma_max(prob, art.variant_config("artemis", d, n))
    gammas = [f * g_ref for f in grid.GAMMA_FRACS]
    kw = dict(batch=1, eval_every=grid.EVAL_EVERY)
    cells = len(cfgs) * len(gammas) * len(grid.SEEDS)
    series = {}
    for backend in ("pallas", "dense"):
        t0 = time.perf_counter()
        compiled = sw.lower_sweep(prob, cfgs, gammas, grid.SEEDS, grid.ITERS,
                                  backend=backend, **kw).compile()
        compile_s = time.perf_counter() - t0
        has_kernel = "tpu_custom_call" in compiled.as_text()
        check(has_kernel or backend == "dense",
              "compiled pallas sweep holds no tpu_custom_call")
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            t0 = time.perf_counter()
            res = sw.run_sweep(prob, cfgs, gammas, grid.SEEDS, grid.ITERS,
                               backend=backend, **kw)
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            warm = sw.run_sweep(prob, cfgs, gammas, grid.SEEDS, grid.ITERS,
                                backend=backend, **kw)
            warm_s = time.perf_counter() - t0
        dropped = [str(w.message) for w in caught
                   if "donated buffers" in str(w.message)]
        check(not dropped, f"{backend} sweep dropped its donation: {dropped}")
        check(warm.traces == 0, f"{backend} sweep retraced when warm")
        check(np.array_equal(res.losses, warm.losses, equal_nan=True),
              f"{backend} sweep not reproducible across calls")
        series[backend] = res.losses
        report("sweep", backend=backend, cells=cells, iters=grid.ITERS,
               compile_s=compile_s, first_call_s=first_s, warm_s=warm_s,
               cells_per_s_warm=cells / warm_s,
               rounds_per_s_warm=cells * grid.ITERS / warm_s,
               tpu_custom_call=has_kernel)
    lp, ld = series["pallas"], series["dense"]
    finite = np.isfinite(ld)
    diff = np.abs(lp - ld)
    bad = finite & ~(diff <= SWEEP_RTOL * np.abs(ld) + SWEEP_ATOL)
    rel = np.where(finite, diff / np.abs(ld), 0.0)
    converged = np.isfinite(ld[..., -1]) & (ld[..., -1] < ld[..., 0])
    report("sweep_check", max_rel_diff=float(rel.max()),
           points_compared=int(finite.sum()), points_over_tol=int(bad.sum()),
           cells_converged=int(converged.sum()),
           cells_diverged=int((~converged).sum()),
           rtol=SWEEP_RTOL, atol=SWEEP_ATOL)
    check(not bad.any(),
          f"pallas vs dense losses differ at {int(bad.sum())} eval points "
          f"(max rel {float(rel.max()):.3e})")
    others = np.array([v != "dore" for v in grid.VARIANTS])
    check(np.isfinite(ld[others][..., -1]).all(),
          "a non-Dore cell of the dense sweep diverged")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4x1-mesh train step (needs 4 chips)")
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the repo's sources are not at {src}")
    sys.path[:0] = [src]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp

    import jax

    from repro.kernels import default_interpret
    from repro.launch.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX runs on {dev.platform!r}")
    if default_interpret():
        fail("REPRO_INTERPRET asks for Pallas interpret mode; this run "
             "must compile the kernels for the chip")
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        fail(f"needs {want} chips, JAX sees {len(jax.devices())}")
    cache = enable_compile_cache()
    report("setup", device_kind=dev.device_kind, count=len(jax.devices()),
           jax=jax.__version__, compile_cache=cache)

    t0 = time.perf_counter()
    if args.four_chips:
        phase_train("4x1")
    else:
        phase_train(None)
        phase_sweep()
    report("done", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
