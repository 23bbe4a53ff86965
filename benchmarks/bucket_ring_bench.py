"""Standalone wire-layer benchmark: bucketed pipelined ring vs per-leaf rings.

Times a full Artemis train step on a simulated W-worker CPU mesh (fake
devices; XLA device count is locked at first jax import, hence a standalone
script run in a subprocess by ``benchmarks/dist_bench.bucketed_ring_suite``)
for each wire, records the compiled HLO's collective bytes by dtype, and
emits one JSON report on stdout with the roofline wire-model numbers
alongside the measurements.

    python benchmarks/bucket_ring_bench.py [--workers 8] [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

parser = argparse.ArgumentParser()
parser.add_argument("--workers", type=int, default=8)
parser.add_argument("--fast", action="store_true")
ARGS = parser.parse_args()

os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={ARGS.workers}")

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402

from repro.core import dist                              # noqa: E402
from repro.launch import roofline                        # noqa: E402
from repro.launch.mesh import make_host_mesh             # noqa: E402
from repro.models.toy import ToyMLP                      # noqa: E402
from repro.optim import sgd                              # noqa: E402


def bench_wire(wire: str, model, params, batch, mesh, *, steps: int):
    dcfg = dist.DistConfig(worker_axes=("pod",), variant="artemis", s=3,
                           wire=wire, bucket_bytes=4096, max_buckets=16,
                           bucket_row=64)
    init_state, step_fn = dist.make_train_step(model, sgd(0.05), dcfg, mesh)
    state = init_state(params)

    t0 = time.time()
    compiled = jax.jit(step_fn).lower(state, batch).compile()
    compile_s = time.time() - t0
    hlo = compiled.as_text()
    by_dtype = roofline.collective_dtype_bytes(hlo)

    jstep = jax.jit(step_fn)
    for _ in range(2):                                     # warmup
        state, out = jstep(state, batch)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(steps):
        state, (loss, _) = jstep(state, batch)
    loss = float(jax.block_until_ready(loss))
    step_us = (time.time() - t0) / steps * 1e6

    n = ARGS.workers
    if wire == "bucketed":
        lay = dcfg.layout(params)
        mdl = roofline.bucketed_wire_model(
            n_workers=n, n_buckets=lay.n_buckets, rows=lay.rows, row=lay.row)
        guard = roofline.wire_bytes_match(hlo, mdl)
        extra = {"layout": {"n_buckets": lay.n_buckets, "rows": lay.rows,
                            "row": lay.row, "pad": lay.pad},
                 "wire_guard": guard}
    else:
        shapes = [tuple(l.shape) for l in jax.tree.leaves(params)]
        mdl = roofline.leaf_wire_model(shapes, n_workers=n)
        extra = {"n_leaves": len(shapes)}
    return {
        "step_us": round(step_us, 1),
        "compile_s": round(compile_s, 3),
        "final_loss": loss,
        "hlo_collective_bytes": {f"{k}/{d}": v
                                 for (k, d), v in sorted(by_dtype.items())},
        "model": {k: (round(v, 12) if isinstance(v, float) else v)
                  for k, v in mdl.items()},
        **extra,
    }


def main():
    steps = 3 if ARGS.fast else 10
    model = ToyMLP(n_layers=6 if ARGS.fast else 12, d=64)
    params = model.init(jax.random.PRNGKey(0))
    batch = model.batch(jax.random.PRNGKey(1), n=4 * ARGS.workers)
    mesh = make_host_mesh((ARGS.workers,), ("pod",))

    wires = {w: bench_wire(w, model, params, batch, mesh, steps=steps)
             for w in ("leaf", "bucketed")}
    report = {
        "workers": ARGS.workers,
        "fast": ARGS.fast,
        "steps_timed": steps,
        "model": {"n_layers": model.n_layers, "d": model.d,
                  "n_leaves": len(jax.tree.leaves(params)),
                  "n_params": int(sum(l.size for l in jax.tree.leaves(params)))},
        "wires": wires,
        "speedup_bucketed_vs_leaf": round(
            wires["leaf"]["step_us"] / wires["bucketed"]["step_us"], 2),
        "device": jax.devices()[0].device_kind,
        "jax": jax.__version__,
    }
    json.dump(report, sys.stdout, indent=2)
    sys.stdout.write("\n")


if __name__ == "__main__":
    main()
