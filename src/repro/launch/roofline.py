"""Roofline-term derivation from compiled dry-run artifacts.

  compute    = HLO_FLOPs / (chips * 197e12)          [bf16 peak / chip]
  memory     = HLO_bytes / (chips * 819e9)           [HBM bw / chip]
  collective = collective_bytes / (chips * 50e9)     [ICI link bw / chip]

HLO_FLOPs / bytes come from ``compiled.cost_analysis()``.  collective_bytes
is parsed from the compiled HLO text: we sum the RESULT-shape bytes of every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
instruction (a consistent per-device proxy: for all-reduce it is the tensor
size ~ bytes sent per device on a ring; for all-gather it is the bytes
received).  MODEL_FLOPS = 6*N_active*D (train) or 2*N_active*D (inference)
per step.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import numpy as np

PEAK_FLOPS = 197e12       # bf16 / chip (TPU v5e)
HBM_BW = 819e9            # bytes/s / chip
ICI_BW = 50e9             # bytes/s / link (1 effective link per chip assumed)
COLL_LAT = 2e-6           # per-collective launch/sync latency (s) — the term
                          # that makes many tiny rings latency-bound

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# result like:  %x = f32[2,16]{1,0} all-gather(...)   OR tuple results
_INSTR_RE = re.compile(
    r"=\s*(\(?[a-z0-9]+\[[^=]*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind (deduping start/done pairs)."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    seen_start = set()
    for m in re.finditer(
            r"%?([\w.\-]*)\s*=\s*(\(?[^=]*?)\s+"
            r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
            r"(-start|-done)?\(", hlo_text):
        name, shapes, kind, phase = m.groups()
        if phase == "-done":
            continue            # counted at -start
        out[kind] += _shape_bytes(shapes)
    return out


def collective_dtype_bytes(hlo_text: str) -> Dict[tuple, int]:
    """Result-shape bytes keyed by (collective kind, dtype) — the wire-format
    guard uses this to pin the compressed ring to s8 payloads."""
    out: Dict[tuple, int] = {}
    for m in re.finditer(
            r"%?([\w.\-]*)\s*=\s*(\(?[^=]*?)\s+"
            r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
            r"(-start|-done)?\(", hlo_text):
        name, shapes, kind, phase = m.groups()
        if phase == "-done":
            continue            # counted at -start
        for dt, dims in _SHAPE_RE.findall(shapes):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            key = (kind, dt)
            out[key] = out.get(key, 0) + n * _DTYPE_BYTES[dt]
    return out


# ---------------------------------------------------------------------------
# Compressed-collective wire model (the bucketed pipelined ring)
# ---------------------------------------------------------------------------

def _codec_split(codec, shape) -> Dict[str, float]:
    """Per-message payload bytes by HLO dtype, read off the codec itself
    (``core/codec.Codec.wire_bytes``) — the single source of truth the
    model below shares with the actual encoders."""
    return {dt: float(b) for dt, b in codec.wire_bytes(tuple(shape)).items()}


def _default_bucket_split(rows: int, row: int) -> Dict[str, float]:
    # the native row-scale squant wire: int8 levels + f32 per-row scales
    return {"s8": float(rows * row), "f32": float(4 * rows)}


def bucketed_wire_model(*, n_workers: int, n_buckets: int, rows: int,
                        row: int, codec=None, ici_bw: float = ICI_BW,
                        hbm_bw: float = HBM_BW,
                        coll_lat: float = COLL_LAT) -> Dict[str, float]:
    """Collective-bytes + exposed-comm-time model for the bucketed ring
    (core/dist.bucket_ring_reduce; geometry from core/bucketing.BucketLayout).

    Per hop, ONE stacked payload moves — for the default squant wire,
    ``n_buckets*rows*row`` int8 levels plus ``4*n_buckets*rows`` f32
    row-scales (one collective-permute per payload leaf).  Passing a
    ``core/codec.py`` codec derives the byte split from its
    ``wire_bytes((rows, row))`` instead (e.g. sparsify ships s32 indices +
    f32 values).  The scan body appears ONCE in HLO (``hlo_bytes_by_dtype``
    is what a static HLO parse sees) and executes ``n_workers-1`` times
    (``wire_bytes_per_step``).  The pipelined schedule overlaps each hop's
    wire time with the previous payload's dequant-accumulate, so only
    ``max(comm, dequant) - dequant`` per hop is *exposed*; the sequential
    schedule exposes all of it.
    """
    hops = n_workers - 1
    split = (_codec_split(codec, (rows, row)) if codec is not None
             else _default_bucket_split(rows, row))
    by_dtype = {dt: n_buckets * b for dt, b in split.items()}
    level_b = by_dtype.get("s8", 0.0)          # int8 levels (0 for identity)
    scale_b = sum(b for dt, b in by_dtype.items() if dt != "s8")
    payload = level_b + scale_b
    n_leaves = len([b for b in by_dtype.values() if b > 0])
    hop_comm = payload / ici_bw + max(n_leaves, 1) * coll_lat
    # dequant-accumulate: read payload + acc (4B/elem), write acc (4B/elem)
    elems = float(n_buckets * rows * row)
    hop_deq = (payload + 8.0 * elems) / hbm_bw
    return {
        "payload_bytes": payload,
        "hlo_s8_bytes": level_b,
        "hlo_scale_bytes": scale_b,
        "hlo_bytes_by_dtype": by_dtype,
        "wire_bytes_per_step": hops * payload,
        "comm_s": hops * hop_comm,
        "dequant_s": n_workers * hop_deq,
        "step_comm_serial_s": hops * (hop_comm + hop_deq) + hop_deq,
        "step_comm_pipelined_s": hops * max(hop_comm, hop_deq) + hop_deq,
        "exposed_comm_s": hops * max(0.0, hop_comm - hop_deq),
    }


def wire_bytes_match(hlo_text: str, model: Dict[str, float], *,
                     tol: float = 0.10) -> Dict[str, float]:
    """Measured-vs-model check for the compressed ring's HLO wire format.

    Returns {measured_s8, measured_scale_f32, model_s8, rel_err, ok,
    by_dtype}.  ``model["hlo_bytes_by_dtype"]`` (``bucketed_wire_model``)
    is checked per payload dtype: every dtype the codec ships must appear
    as collective-permute bytes within ``tol``.
    """
    by = collective_dtype_bytes(hlo_text)
    s8 = float(by.get(("collective-permute", "s8"), 0))
    f32 = float(by.get(("collective-permute", "f32"), 0))
    out = {"measured_s8": s8, "measured_scale_f32": f32}
    want_by = model["hlo_bytes_by_dtype"]
    checks = {}
    ok = True
    worst = 0.0
    for dt, want in want_by.items():
        if want <= 0:
            continue
        got = float(by.get(("collective-permute", dt), 0))
        rel = abs(got - want) / max(want, 1.0)
        checks[dt] = {"measured": got, "model": float(want), "rel_err": rel}
        worst = max(worst, rel)
        ok = ok and rel <= tol and got > 0
    out.update({"model_s8": float(want_by.get("s8", 0.0)),
                "rel_err": worst, "ok": ok, "by_dtype": checks})
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    kind: str                       # train | prefill | decode
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: Dict[str, int]
    model_flops: float
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    useful_ratio: float = 0.0

    def finalize(self):
        total_coll = float(sum(self.coll_bytes.values()))
        # cost_analysis flops/bytes are per-device after SPMD partitioning
        self.compute_s = self.hlo_flops / PEAK_FLOPS
        self.memory_s = self.hlo_bytes / HBM_BW
        self.collective_s = total_coll / ICI_BW
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.dominant = max(terms, key=terms.get)
        # both model_flops and hlo_flops are per-device here
        self.useful_ratio = self.model_flops / max(self.hlo_flops, 1.0)
        return self

    def as_dict(self):
        d = dataclasses.asdict(self)
        return d


def count_params(params_tree) -> int:
    import jax
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(params_tree)))


def active_params(cfg, params_tree) -> float:
    """Active parameter count: MoE experts scaled by top_k / n_experts."""
    import jax
    total, expert_total = 0.0, 0.0
    flat = jax.tree_util.tree_flatten_with_path(params_tree)[0]
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        n = float(np.prod(leaf.shape))
        if "moe" in key and "router" not in key:
            expert_total += n
        else:
            total += n
    if cfg.n_experts:
        total += expert_total * cfg.top_k / cfg.n_experts
    return total


def model_flops(cfg, params_tree, kind: str, batch: int, seq: int) -> float:
    n_active = active_params(cfg, params_tree)
    if kind == "train":
        return 6.0 * n_active * batch * seq
    if kind == "prefill":
        return 2.0 * n_active * batch * seq
    return 2.0 * n_active * batch * 1      # decode: one token


def markdown_table(rows) -> str:
    hdr = ("| arch | shape | mesh | dist | kind | compute (s) | memory (s) | "
           "collective (s) | dominant | MODEL/HLO flops |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{r.get('dist', 'none')} | {r['kind']} | "
            f"{r['compute_s']:.3e} | {r['memory_s']:.3e} | "
            f"{r['collective_s']:.3e} | **{r['dominant']}** | "
            f"{r['useful_ratio']:.2f} |")
    return hdr + "\n".join(lines)
