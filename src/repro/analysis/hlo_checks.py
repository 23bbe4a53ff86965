"""Static HLO inspection: wire compression, donation aliasing, host
transfers (DESIGN.md §10).

Generalizes ``launch/roofline.wire_bytes_match`` (which pins exact byte
counts for the CI codecs) into invariants that hold for EVERY registered
codec on the mesh wire:

  hlo-uncompressed-wire   error  a compressed codec's collective-permute /
                                 all-reduce traffic is f32-heavier than the
                                 codec's own declared ``wire_bytes`` split —
                                 i.e. something decompressed the payload
                                 before the wire.  Also fires when a dtype
                                 the codec ships (s8 levels, s32 indices)
                                 is absent from the wire entirely.
  hlo-f32-allreduce-payload error a payload-sized f32 all-reduce appears in
                                 a compressed-wire program (a psum of
                                 dequantized gradients sneaking past the
                                 ring).  Metric scalars (a few bytes) pass.
  hlo-missing-donation    error  the sweep engine's donated grid carries
                                 (w, ArtemisState) are not all aliased to
                                 outputs (``tf.aliasing_output`` in lowered
                                 StableHLO / ``input_output_alias`` in the
                                 compiled module).
  hlo-host-transfer       error  infeed/outfeed/send/recv/host-callback ops
                                 in a compiled module that should be
                                 device-resident end to end.

The codec matrix needs a multi-device mesh, so it runs in a child
interpreter with 8 fake CPU devices (same pattern as trace_audit's
bucket_ring entry); findings come back as JSON lines.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from typing import Dict, List, Sequence

from repro.analysis.findings import Finding

RULES = {
    "hlo-uncompressed-wire": "error",
    "hlo-f32-allreduce-payload": "error",
    "hlo-missing-donation": "error",
    "hlo-host-transfer": "error",
    "hlo-entry-error": "error",
}

# extra f32 share of the wire we tolerate beyond the codec's declaration
# (padding, layout fragmentation); a decompressed payload jumps f32 from a
# few percent to ~50-100%, far past this
F32_SLACK = 0.15

_ALIAS_RE = re.compile(r"tf\.aliasing_output")
_HOST_RE = re.compile(
    r"\b(infeed|outfeed|send|send-done|recv|recv-done)\b"
    r"|xla_python_cpu_callback|xla_ffi_python|CustomCall.*host")


def count_output_aliases(stablehlo_text: str) -> int:
    return len(_ALIAS_RE.findall(stablehlo_text))


def host_transfer_findings(hlo_text: str, entry: str) -> List[Finding]:
    hits = sorted({m.group(0) for m in _HOST_RE.finditer(hlo_text)})
    if not hits:
        return []
    return [Finding(
        rule="hlo-host-transfer", severity="error", path=entry, line=0,
        message=f"compiled module contains host-transfer op(s) "
                f"{', '.join(hits)} — the program is expected to stay "
                f"device-resident (a debug callback or numpy round-trip "
                f"leaked into the traced region)")]


def wire_findings(measured: Dict[tuple, int], declared: Dict[str, float],
                 entry: str, *, payload_f32_bytes: float) -> List[Finding]:
    """Check measured collective bytes-per-dtype against the codec's own
    declared wire split.

    measured: roofline.collective_dtype_bytes output ({(op, dtype): bytes}).
    declared: codec ``wire_bytes`` split ({hlo_dtype: bytes}) for one
        payload — only the *fractions* are used, so hop counts and bucket
        multiplicity cancel out.
    payload_f32_bytes: size of ONE uncompressed f32 payload — the threshold
        separating metric all-reduces (bytes) from gradient-sized ones.
    """
    findings: List[Finding] = []
    cp = {dt: float(b) for (op, dt), b in measured.items()
          if op == "collective-permute"}
    total_decl = sum(declared.values())
    total_cp = sum(cp.values())
    compressed = {dt for dt, b in declared.items() if dt != "f32" and b > 0}
    if compressed and total_cp > 0 and total_decl > 0:
        frac_decl = declared.get("f32", 0.0) / total_decl
        frac_meas = cp.get("f32", 0.0) / total_cp
        if frac_meas > frac_decl + F32_SLACK:
            findings.append(Finding(
                rule="hlo-uncompressed-wire", severity="error", path=entry,
                line=0,
                message=f"f32 is {frac_meas:.0%} of collective-permute "
                        f"bytes but the codec declares {frac_decl:.0%} "
                        f"(scales/values only) — the payload crossed the "
                        f"wire decompressed"))
        for dt in sorted(compressed):
            if cp.get(dt, 0.0) <= 0:
                findings.append(Finding(
                    rule="hlo-uncompressed-wire", severity="error",
                    path=entry, line=0,
                    message=f"codec declares {dt} payload leaves but no "
                            f"{dt} collective-permute appears in HLO — the "
                            f"compressed leg of the wire is gone"))
    if compressed:
        ar_f32 = float(measured.get(("all-reduce", "f32"), 0))
        if ar_f32 >= payload_f32_bytes:
            findings.append(Finding(
                rule="hlo-f32-allreduce-payload", severity="error",
                path=entry, line=0,
                message=f"f32 all-reduce moves {ar_f32:.0f} bytes >= one "
                        f"uncompressed payload ({payload_f32_bytes:.0f}) — "
                        f"a dense psum is bypassing the compressed ring "
                        f"(metric scalars are orders of magnitude smaller)"))
    return findings


# ---------------------------------------------------------------------------
# entry: sweep donation + host transfers (single device)
# ---------------------------------------------------------------------------

def audit_sweep() -> List[Finding]:
    import jax
    from repro.core import artemis as art
    from repro.core import federated as fed
    from repro.core import sweep as sw

    n, d = 4, 8
    prob, _ = fed.make_lsr_problem(jax.random.PRNGKey(1), n_workers=n,
                                   n_per=20, d=d, noise=0.3)
    cfgs = [art.variant_config(v, d, n, p=0.7) for v in ("sgd", "artemis")]
    lowered = sw.lower_sweep(prob, cfgs, [0.01, 0.02], [0, 1], iters=8,
                             batch=2)
    findings: List[Finding] = []
    # the donated carry is (w0b, st0b): 1 + len(ArtemisState leaves) buffers
    expected = 1 + len(jax.tree.leaves(art.init_state(cfgs[0])))
    got = count_output_aliases(lowered.as_text())
    if got < expected:
        findings.append(Finding(
            rule="hlo-missing-donation", severity="error", path="sweep_grid",
            line=0,
            message=f"only {got}/{expected} donated grid-carry buffers are "
                    f"aliased to outputs (tf.aliasing_output) — the sweep "
                    f"no longer updates the carry in place"))
    compiled_text = lowered.compile().as_text()
    if "input_output_alias" not in compiled_text:
        findings.append(Finding(
            rule="hlo-missing-donation", severity="error", path="sweep_grid",
            line=0,
            message="compiled sweep module has no input_output_alias "
                    "entries — XLA dropped every donation"))
    findings.extend(host_transfer_findings(compiled_text, "sweep_grid"))
    return findings


# ---------------------------------------------------------------------------
# entry: codec matrix on the mesh wire (8-device child)
# ---------------------------------------------------------------------------

def _child_mesh_wires():
    """Child body: lower the mesh train step for every registered codec on
    a 4-worker mesh; print findings as JSON lines."""
    import jax
    import numpy as np
    from repro.core import codec as wire
    from repro.core import dist
    from repro.launch import roofline
    from repro.launch.mesh import make_host_mesh
    from repro.models.toy import ToyMLP
    from repro.optim import sgd

    mesh = make_host_mesh((4,), ("pod",))
    model = ToyMLP(n_layers=2, d=32)
    params = model.init(jax.random.PRNGKey(0))
    n_elems = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    batch = model.batch(jax.random.PRNGKey(1), n=16)
    codecs = [c for c in wire.available() if c != "none"]  # identity alias
    for cname in codecs:
        entry = f"mesh:{cname}"
        try:
            dcfg = dist.DistConfig(worker_axes=("pod",),
                                   variant="artemis", s=3,
                                   reduce_impl="pipelined", codec=cname)
            init_state, step_fn = dist.make_train_step(
                model, sgd(0.05), dcfg, mesh)
            state = init_state(params)
            hlo = jax.jit(step_fn).lower(state, batch).compile().as_text()
            lay = dcfg.layout(params)
            wc = dcfg.wire_codec(lay.row)
            declared = {dt: float(b) for dt, b in
                        wc.wire_bytes((lay.rows, lay.row)).items()}
            fs = wire_findings(
                roofline.collective_dtype_bytes(hlo), declared, entry,
                payload_f32_bytes=4.0 * n_elems)
            fs.extend(host_transfer_findings(hlo, entry))
        except Exception as e:
            fs = [Finding(rule="hlo-entry-error", severity="error",
                          path=entry, line=0,
                          message=f"lowering failed: "
                                  f"{type(e).__name__}: {e}")]
        for f in fs:
            print("HLOJSON " + json.dumps({  # repro-lint: allow=print-in-library (subprocess protocol)
                "rule": f.rule, "severity": f.severity, "path": f.path,
                "line": f.line, "message": f.message}))
    print("HLODONE")  # repro-lint: allow=print-in-library (subprocess protocol)


def audit_mesh_wires() -> List[Finding]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "repro.analysis.hlo_checks",
         "--child", "mesh_wires"],
        capture_output=True, text=True, env=env, timeout=600)
    if res.returncode != 0 or "HLODONE" not in res.stdout:
        tail = (res.stderr or res.stdout).strip().splitlines()[-12:]
        return [Finding(
            rule="hlo-entry-error", severity="error", path="mesh_wires",
            line=0,
            message="mesh wire audit child failed: " + " | ".join(tail))]
    findings = []
    for line in res.stdout.splitlines():
        if line.startswith("HLOJSON "):
            findings.append(Finding(**json.loads(line[len("HLOJSON "):])))
    return findings


def audit_all(*, mesh: bool = True) -> List[Finding]:
    findings: List[Finding] = []
    for name, fn in (("sweep", audit_sweep),
                     ("mesh_wires", audit_mesh_wires if mesh else None)):
        if fn is None:
            continue
        try:
            findings.extend(fn())
        except Exception as e:                        # pragma: no cover
            findings.append(Finding(
                rule="hlo-entry-error", severity="error", path=name, line=0,
                message=f"audit raised {type(e).__name__}: {e}"))
    return findings


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "--child":
        if sys.argv[2] == "mesh_wires":
            _child_mesh_wires()
        else:
            raise SystemExit(f"unknown child entry {sys.argv[2]!r}")
    else:
        fs = audit_all()
        for f in fs:
            print(f.format())  # repro-lint: allow=print-in-library (CLI entry)
        raise SystemExit(1 if fs else 0)
