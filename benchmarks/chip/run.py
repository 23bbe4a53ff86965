"""Run one cell of the benchmark once, on the chip this process holds.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` at the repo root) names a configuration and a
traffic mix; the mix names its driver (``drivers/<driver>.py``).  The driver
sets up from ``--seed``, warms every shape up, measures for ``--seconds``,
then checks what the timed path produced against the plain reference.  The
last line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics, each read by ``metrics/<name>.py``), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``: each number compared,
beside its limit.  The checks are also the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, or without the
repo's ``src/`` beside this directory, it exits 2 and prints no result.

For setting limits (not run by the benchmark itself):

    python benchmarks/chip/run.py --workload <name> --readings 1,2,3 \
        [--control fp8 | --fault unchanged|half_batch]

prints one JSON line of correctness readings per seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import math      # noqa: E402
import os        # noqa: E402
import sys       # noqa: E402
import types     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def fail(msg: str, code: int = 2):
    print(f"run.py: {msg}", file=sys.stderr)
    sys.exit(code)


def prepare_env():
    """Everything that has to be set before JAX starts."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"the program's sources are not at {src}")
    sys.path[:0] = [src, HERE]
    # the persistent compilation cache lives at one fixed path inside the
    # checkout; the program's own entry points read the same variable
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def start_jax(chips: int):
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    from repro.kernels import default_interpret
    if default_interpret():
        fail("REPRO_INTERPRET asks for Pallas interpret mode on the chip")


def _num(x):
    x = float(x)
    return x if math.isfinite(x) else str(x)


def result_line(cell, res: dict, trace: bool) -> dict:
    from harness import spec, trace as TR
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] in res["e2e"]:
                metrics[m["name"]] = {"value": res["e2e"][m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = spec.load_reader(m["name"])(res["rec"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(res["device"])
    out = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
           "failed": int(res["failed"]), "metrics": metrics}
    if trace:
        red = res["rec"]["trace"]
        device["busy_s"] = TR.mean_busy_s(red)
        device["window_s"] = red.window_s
        out["device"] = device
        out["breakdown"] = {"device_ops": TR.top_ops(red),
                            "idle_gaps": TR.idle_gaps(red)}
    else:
        out["device"] = device
    out["checks"] = {k: {"value": _num(v["value"]), "limit": _num(v["limit"])}
                     for k, v in res["checks"].items()}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--readings", default=None,
                    help="comma-separated seeds: correctness readings only")
    ap.add_argument("--control", default=None,
                    help="readings of the reference at this precision in "
                         "the program's place (fp8, high)")
    ap.add_argument("--fault", default=None,
                    help="readings of the program with this fault planted")
    args = ap.parse_args(argv)

    prepare_env()
    from harness import compare, spec
    try:
        cell = spec.resolve(args.workload, ROOT)
    except (KeyError, FileNotFoundError) as e:
        fail(str(e))
    start_jax(cell.chips)
    driver = spec.load_driver(cell.driver)
    out_dir = os.path.join(ROOT, ".bench_out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    ctx = types.SimpleNamespace(cell=cell, seed=args.seed,
                                seconds=args.seconds, trace=bool(args.trace),
                                out_dir=out_dir, t_start=T_START,
                                control=args.control, fault=args.fault)
    if args.readings:
        seeds = [int(s) for s in args.readings.split(",")]
        for r in driver.readings(ctx, seeds, control=args.control,
                                 fault=args.fault):
            print(json.dumps(r, default=float), flush=True)
        return 0
    if args.control or args.fault:
        fail("--control and --fault go with --readings")
    res = driver.run(ctx)
    line = result_line(cell, res, bool(args.trace))
    print(compare.format_checks(res["checks"]), file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
