import os
# a CPU-mesh simulation by design: it never competes for an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on the production mesh with ShapeDtypeStruct stand-ins (no
allocation), print memory/cost analysis, and record roofline terms.

MUST be run as its own process (the device-count flag above is consumed at
first jax init).  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-7b \
      --shape train_4k --mesh pod [--dist artemis] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun.json
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core import dist
from repro.launch import mesh as M
from repro.launch import roofline as R
from repro.models.model import build_model
from repro.optim import sgd


def _param_structs(model):
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))


def _parse_overrides(items):
    out = {}
    for it in items or []:
        k, v = it.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v == "None":
            v = None
        out[k] = v
    return out


def lower_one(arch: str, shape_name: str, mesh_kind: str, dist_variant: str,
              verbose: bool = True, cfg_overrides: dict = None,
              dist_overrides: dict = None):
    import dataclasses
    cfg = configs.get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = configs.SHAPES[shape_name]
    skip = configs.applicable(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "dist": dist_variant, "status": "skip", "reason": skip}

    mesh = M.make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    model = build_model(cfg)
    params = _param_structs(model)
    pshard = M.params_shardings(mesh, params)
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            dcfg = None
            if dist_variant != "none":
                waxes = ("pod",) if "pod" in mesh.axis_names else ("data",)
                dcfg = dist.DistConfig(worker_axes=waxes, variant=dist_variant,
                                       **(dist_overrides or {}))
            banned = dcfg.worker_axes if dcfg else ()
            model.set_sharding(
                None if os.environ.get("REPRO_NO_LAYER_CONSTRAINT")
                else M.layer_constraint_fn(mesh, banned),
                None if os.environ.get("REPRO_NO_ACT_CONSTRAINT")
                else M.act_constraint_fn(mesh, banned))
            opt = sgd(1e-2)
            gspecs = jax.tree.map(
                lambda ns: M.strip_axes(ns.spec, banned), pshard) if dcfg else None
            init_state, step_fn = dist.make_train_step(model, opt, dcfg, mesh,
                                                       grad_specs=gspecs)
            state = jax.eval_shape(init_state, params)
            sshard = dist.state_shardings(mesh, state, pshard, dcfg)
            batch = configs.input_specs(cfg, shape, model)
            bshard = M.batch_shardings(mesh, batch)
            fn = jax.jit(step_fn, in_shardings=(sshard, bshard))
            lowered = fn.lower(state, batch)
        elif shape.kind == "prefill":
            model.set_sharding(M.layer_constraint_fn(mesh),
                               M.act_constraint_fn(mesh))
            batch = configs.input_specs(cfg, shape, model)
            bshard = M.batch_shardings(mesh, batch)
            fn = jax.jit(model.prefill_logits, in_shardings=(pshard, bshard))
            lowered = fn.lower(params, batch)
        else:  # decode
            model.set_sharding(M.layer_constraint_fn(mesh),
                               M.act_constraint_fn(mesh))
            specs = configs.input_specs(cfg, shape, model)
            cshard = M.cache_shardings(mesh, specs["cache"])
            tshard = M.batch_shardings(mesh, {"t": specs["token"]})["t"]
            args = [params, specs["cache"], specs["token"], specs["pos"]]
            shards = [pshard, cshard, tshard, NamedSharding(mesh, P())]
            if cfg.family == "encdec":
                def serve(p, c, t, pos, enc):
                    return model.decode_step(p, c, t, pos, enc_out=enc)
                args.append(specs["enc_out"])
                shards.append(M.batch_shardings(mesh, {"e": specs["enc_out"]})["e"])
            else:
                def serve(p, c, t, pos):
                    return model.decode_step(p, c, t, pos)
            fn = jax.jit(serve, in_shardings=tuple(shards))
            lowered = fn.lower(*args)

        compiled = lowered.compile()

    t1 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = R.collective_bytes(hlo)
    chips = mesh.devices.size
    rl = R.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_kind, chips=chips,
        kind=shape.kind,
        hlo_flops=float(cost.get("flops", 0.0)),
        hlo_bytes=float(cost.get("bytes accessed", 0.0)),
        coll_bytes=coll,
        model_flops=R.model_flops(cfg, params, shape.kind, shape.batch,
                                  shape.seq) / chips,
    ).finalize()

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "dist": dist_variant, "status": "ok",
        "compile_s": round(t1 - t0, 1),
        "memory_analysis": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        **rl.as_dict(),
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_kind} x {dist_variant}] "  # repro-lint: allow=print-in-library (CLI driver)
              f"compile={rec['compile_s']}s flops/dev={rl.hlo_flops:.3e} "
              f"bytes/dev={rl.hlo_bytes:.3e} "
              f"coll={sum(coll.values()):.3e}B dominant={rl.dominant}")
        print("  memory_analysis:", rec["memory_analysis"])  # repro-lint: allow=print-in-library (CLI driver)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--dist", default="none",
                    help="none|sgd|qsgd|diana|biqsgd|artemis (train shapes)")
    ap.add_argument("--all", action="store_true",
                    help="full matrix: every arch x shape; baseline on pod mesh "
                         "+ artemis multipod for train shapes")
    ap.add_argument("--cfg-override", action="append", default=[],
                    help="ModelConfig field override, e.g. remat_policy=dots_saveable")
    ap.add_argument("--dist-override", action="append", default=[],
                    help="DistConfig field override, e.g. memory_dtype=bfloat16")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    results = []
    if args.all:
        # XLA SPMD partitioner bugs abort the process (CHECK failures), so
        # each combo runs in its own subprocess.
        import subprocess
        import sys
        import tempfile
        combos = []
        for arch in configs.ARCHS:
            for shape in configs.SHAPES:
                for mesh_kind in ("pod", "multipod"):
                    dists = ["none"]
                    if (configs.SHAPES[shape].kind == "train"
                            and mesh_kind == "multipod"):
                        dists.append("artemis")
                    for dv in dists:
                        combos.append((arch, shape, mesh_kind, dv))
        for arch, shape, mesh_kind, dv in combos:
            with tempfile.NamedTemporaryFile(suffix=".json") as tf:
                proc = subprocess.run(
                    [sys.executable, "-m", "repro.launch.dryrun",
                     "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                     "--dist", dv, "--out", tf.name],
                    capture_output=True, text=True, timeout=1800)
                try:
                    with open(tf.name) as f:
                        rec = json.load(f)[0]
                except Exception:  # noqa: BLE001
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "dist": dv, "status": "error",
                           "error": (proc.stderr or proc.stdout)[-800:]}
                results.append(rec)
                print(f"{arch} x {shape} x {mesh_kind} x {dv}: {rec['status']}"  # repro-lint: allow=print-in-library (CLI driver)
                      + (f" ({rec.get('dominant','')})"
                         if rec["status"] == "ok" else ""),
                      flush=True)
    else:
        meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]
        for mk in meshes:
            results.append(lower_one(
                args.arch, args.shape, mk, args.dist,
                cfg_overrides=_parse_overrides(args.cfg_override),
                dist_overrides=_parse_overrides(args.dist_override)))

    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print("wrote", args.out)  # repro-lint: allow=print-in-library (CLI driver)
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"dryrun: {n_ok} ok, {n_skip} skip, {n_err} error")  # repro-lint: allow=print-in-library (CLI driver)
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
