"""In-trace telemetry: a pure-pytree metrics carry (DESIGN.md §11).

The quantities that drive the paper's convergence story — compression-error
variance at the optimum (Assumption 5), the memory-drift term
``||h_i - grad F_i(w*)||`` that controls the linear-rate threshold, the
Remark-3 bit ledger, participation/fault counts — are all *inside* the
compiled programs (``core/sweep.py`` grids, ``core/dist.py`` mesh steps).
This module gives every layer one way to surface them:

  * a **metric catalogue** (``Metric`` descriptors registered in
    ``CATALOGUE``) naming each counter/gauge/histogram once, with kind,
    unit, and doc — the JSONL event schema, the dashboard, and DESIGN.md
    §11 all derive from it;
  * a **telemetry carry**: a flat ``{name: jnp.float32 array}`` dict that
    rides inside ``lax.scan`` carries like any other pytree.  Counters
    accumulate monotonically, stride gauges accumulate a sum that the eval
    point divides by the stride, histograms accumulate fixed-edge bucket
    counts (static edges — nothing data-dependent in the trace).

Discipline (load-bearing, pinned by tests/test_obs.py):

  * telemetry is **statically gated** — a disabled config never constructs
    the carry, so the trace (and therefore the trajectory, bit-for-bit, and
    the compile count) is byte-identical to a build that predates this
    module;
  * everything here is pure pytree arithmetic — **no host callbacks** ever
    run inside the hot scan; values come back with the ordinary scan
    outputs at ``eval_every`` points and are written to the JSONL sink
    (``obs/events.py``) on the host afterwards.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax.numpy as jnp
import numpy as np

KINDS = ("counter", "gauge", "hist")


@dataclasses.dataclass(frozen=True)
class Metric:
    """One registered metric: the unit of the catalogue and the schema."""
    name: str
    kind: str                 # 'counter' | 'gauge' | 'hist'
    doc: str
    unit: str = ""
    edges: Optional[Tuple[float, ...]] = None   # hist bucket edges (static)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"metric kind {self.kind!r} not in {KINDS}")
        if (self.kind == "hist") != (self.edges is not None):
            raise ValueError(f"metric {self.name!r}: hist <=> edges")


_CATALOGUE: Dict[str, Metric] = {}


def register(m: Metric) -> Metric:
    if m.name in _CATALOGUE and _CATALOGUE[m.name] != m:
        raise ValueError(f"metric {m.name!r} already registered differently")
    _CATALOGUE[m.name] = m
    return m


def catalogue() -> Tuple[Metric, ...]:
    """All registered metrics, registration-ordered (dicts preserve it)."""
    return tuple(_CATALOGUE.values())


def get(name: str) -> Metric:
    return _CATALOGUE[name]


# log10-spaced edges for squared-error histograms: bucket 0 is underflow
# (< 1e-12), the last bucket catches overflow and NaN (searchsorted sends
# NaN past every edge because every comparison is False)
ERR_EDGES = tuple(float(10.0 ** e) for e in range(-12, 7, 2))


def hist_zeros(m: Metric) -> jnp.ndarray:
    return jnp.zeros((len(m.edges) + 1,), jnp.float32)


def hist_add(counts: jnp.ndarray, m: Metric, value) -> jnp.ndarray:
    """Bucket one scalar observation into fixed-edge counts (in-trace)."""
    idx = jnp.searchsorted(jnp.asarray(m.edges, jnp.float32),
                           jnp.asarray(value, jnp.float32))
    return counts.at[idx].add(1.0)


def hist_edges_list(m: Metric):
    return [float(e) for e in m.edges]


# ---------------------------------------------------------------------------
# sweep-engine telemetry (core/sweep.py; one entry per round, emit per eval)
# ---------------------------------------------------------------------------

SWEEP_COUNTERS = tuple(register(Metric(n, "counter", d, unit=u)).name
                       for n, u, d in [
    ("avail", "workers", "availability draws that came up active "
                         "(pre-straggler Bernoulli/Markov mask)"),
    ("active", "workers", "workers that actually completed the round "
                          "(post straggler drop + entry scrub)"),
    ("straggler_drops", "workers", "available workers that missed the "
                                   "round deadline"),
    ("blowup_hits", "workers", "gradients replaced by blowup_value by the "
                               "fault injector"),
    ("entry_scrub_drops", "workers", "workers masked inactive because their "
                                     "gradient arrived non-finite"),
    ("wire_scrubbed", "payloads", "uplink payloads dropped by the server "
                                  "checksum (codec.validate)"),
    ("uplink_bits", "bits", "paper-side Elias-coded uplink cost "
                            "(DESIGN.md §4)"),
    ("dwnlink_bits", "bits", "paper-side downlink broadcast cost"),
    ("catchup_bits", "bits", "Remark-3 catch-up downloads of returning "
                             "workers"),
])

SWEEP_GAUGES = tuple(register(Metric(n, "gauge", d, unit=u)).name
                     for n, u, d in [
    ("err_up", "norm^2", "mean per-worker uplink compression error "
                         "||Delta_hat - Delta||^2 (Assumption 5), stride "
                         "mean"),
    ("err_dwn", "norm^2", "downlink compression error ||omega - ghat||^2, "
                          "stride mean"),
    ("ghat_norm", "norm", "server aggregate norm ||ghat||, stride mean"),
])

SWEEP_EVAL_GAUGES = tuple(register(Metric(n, "gauge", d, unit=u)).name
                          for n, u, d in [
    ("mem_drift", "norm", "mean_i ||h_i - grad F_i(w*)|| — the memory-"
                          "drift term of the linear-rate threshold "
                          "(sampled at eval points; w*=0 when no w_star "
                          "was passed)"),
    ("e_norm", "norm", "mean error-feedback buffer norm ||e_i|| (zero "
                       "unless Dore/EF)"),
    ("rollbacks", "count", "divergence-sentinel rollbacks so far "
                           "(cumulative at eval points)"),
])

ERR_UP_HIST = register(Metric(
    "err_up_hist", "hist",
    "distribution of per-round uplink compression error (log10 buckets; "
    "first bucket underflow, last bucket overflow/NaN)",
    unit="rounds", edges=ERR_EDGES))

SWEEP_METRICS = SWEEP_COUNTERS + SWEEP_GAUGES + SWEEP_EVAL_GAUGES + (
    ERR_UP_HIST.name,)

# Carry representation: ONE packed f32 vector for every scalar slot
# (counters first, then stride-gauge sums) plus the histogram. A dict of
# 12 scalar carries costs ~12 extra ops per scan iteration — pure dispatch
# overhead that showed up as ~20% on CPU microbenchmarks; one [12] vector
# add is ~3 ops regardless of how many metrics ride along. The packed
# layout is private: sweep_round feeds it, sweep_emit unpacks to names.
_PACK = SWEEP_COUNTERS + SWEEP_GAUGES
_PACK_IDX = {n: i for i, n in enumerate(_PACK)}
# reset multiplier: keep counters (1), zero stride-gauge sums (0)
_STRIDE_KEEP = np.asarray([0.0 if n in SWEEP_GAUGES else 1.0
                           for n in _PACK], np.float32)


def sweep_zeros() -> Dict[str, jnp.ndarray]:
    """Fresh telemetry carry for one sweep cell (vmap batches it)."""
    return {"pack": jnp.zeros((len(_PACK),), jnp.float32),
            ERR_UP_HIST.name: hist_zeros(ERR_UP_HIST)}


def sweep_round(**values) -> jnp.ndarray:
    """One round's raw readings as the packed vector (every lax.switch
    branch must return the same structure, so missing entries default to
    zero)."""
    return jnp.stack([jnp.asarray(values.get(n, 0.0), jnp.float32)
                      for n in _PACK])


def sweep_accumulate(acc: Dict[str, jnp.ndarray],
                     tel: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    return {"pack": acc["pack"] + tel,
            ERR_UP_HIST.name: hist_add(acc[ERR_UP_HIST.name], ERR_UP_HIST,
                                       tel[_PACK_IDX["err_up"]])}


def sweep_emit(acc: Dict[str, jnp.ndarray], eval_every: int,
               **eval_gauges) -> Dict[str, jnp.ndarray]:
    """The per-eval-point reading, unpacked to metric names: cumulative
    counters + hist, stride-mean gauges, plus eval-time gauges
    (mem_drift/e_norm/rollbacks)."""
    pack = acc["pack"]
    out = {c: pack[_PACK_IDX[c]] for c in SWEEP_COUNTERS}
    for g in SWEEP_GAUGES:
        out[g] = pack[_PACK_IDX[g]] / float(eval_every)
    out[ERR_UP_HIST.name] = acc[ERR_UP_HIST.name]
    for g in SWEEP_EVAL_GAUGES:
        out[g] = jnp.asarray(eval_gauges.get(g, 0.0), jnp.float32)
    return out


def sweep_reset_stride(acc: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Zero the stride-mean sums after an eval emission (counters and the
    histogram stay cumulative)."""
    return {"pack": acc["pack"] * jnp.asarray(_STRIDE_KEEP),
            ERR_UP_HIST.name: acc[ERR_UP_HIST.name]}


# ---------------------------------------------------------------------------
# mesh-backend telemetry (core/dist.py; per-step scalars, no carry needed)
# ---------------------------------------------------------------------------

MESH_METRICS = tuple(register(Metric(n, "gauge", d, unit=u)).name
                     for n, u, d in [
    ("wire_bytes", "bytes", "physical payload bytes this worker moved on "
                            "the inter-worker wire this step (hops x "
                            "codec.wire_bytes; reconciles against "
                            "launch/roofline wire models)"),
    ("mesh_active", "frac", "participation mask of this round (pmean over "
                            "workers = participating fraction)"),
    ("mesh_scrubbed", "payloads", "payload units (buckets/leaves) dropped "
                                  "by the server checksum this step"),
    ("mesh_blowup_hits", "count", "gradient blowups injected this step"),
    ("mesh_fused_round", "flag", "1 when this worker's round ran on the "
                                 "fused wire kernels (kernels/wire_round), "
                                 "0 on the XLA chain"),
])


def mesh_zeros() -> Dict[str, jnp.ndarray]:
    return {m: jnp.zeros((), jnp.float32) for m in MESH_METRICS}


def tree_to_numpy(tel) -> Dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in tel.items()}
