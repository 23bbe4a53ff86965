"""Artemis (paper Algorithm 1) as a functional JAX module.

One round maps stacked per-worker stochastic gradients ``g: [N, d]`` to the
descent direction ``Omega: [d]`` plus the next algorithm state.  All six
framework variants are obtained from the same code path:

    variant     C_up        C_dwn      memory(alpha)
    sgd         identity    identity   0
    qsgd        squant      identity   0
    diana       squant      identity   >0
    biqsgd      squant      squant     0
    artemis     squant      squant     >0
    sgd-mem     identity    identity   >0      (PP2 benchmark of Fig. 6)

Partial participation: ``active`` is a {0,1} mask of shape [N].
 * PP1 — server holds per-worker memories; ghat = mean_S(Delta_hat_i + h_i)/p.
 * PP2 — server holds ONE memory hbar reused for inactive workers (novel algo):
         ghat = hbar + (1/(pN)) sum_S Delta_hat_i ;  hbar += (alpha/N) sum_S Delta_hat_i.

Error feedback (beyond paper, Dore-style) is available via ``error_feedback=True``.

Both uplinks and the downlink dispatch on registered ``core/codec.py``
codecs: the dense path vmaps the codec round-trip (any registered operator —
sparsify, topk, tile_squant...), while ``backend="pallas"`` rides the fused
kernels for codecs that declare the matching ``fused_uplink`` family and
falls back to the dense path for the rest (no more hard-fails on
``cfg.up != "squant"``; EF is supported on both).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import codec as wire
from repro.core import compression as comp
from repro.core import faults

BACKENDS = ("dense", "pallas")


@dataclasses.dataclass(frozen=True)
class ArtemisConfig:
    dim: int
    n_workers: int
    up: str = "squant"            # uplink codec name (core/codec.py registry)
    dwn: str = "squant"           # downlink codec name
    up_kwargs: dict = dataclasses.field(default_factory=dict)
    dwn_kwargs: dict = dataclasses.field(default_factory=dict)
    alpha: Optional[float] = None  # memory rate; None -> 1/(2(omega_up+1)); 0 disables
    p: float = 1.0                 # participation probability (Assumption 6)
    pp_mode: str = "pp2"           # 'pp1' | 'pp2'
    error_feedback: bool = False   # Dore-like EF (beyond paper)
    backend: str = "dense"         # 'dense' | 'pallas' (fused uplink kernels)
    faults: Optional[faults.FaultConfig] = None  # fault injection + defenses

    def codecs(self) -> Tuple[wire.Codec, wire.Codec]:
        # kwargs may be a dict or a (hashable) tuple of (key, value) pairs
        c_up = wire.make_codec(self.up, self.dim, **dict(self.up_kwargs))
        c_dwn = wire.make_codec(self.dwn, self.dim, **dict(self.dwn_kwargs))
        return c_up, c_dwn

    def compressors(self) -> Tuple[comp.Compressor, comp.Compressor]:
        c_up, c_dwn = self.codecs()
        return comp.from_codec(c_up), comp.from_codec(c_dwn)

    def resolved_alpha(self) -> float:
        if self.alpha is not None:
            return float(self.alpha)
        c_up, _ = self.codecs()
        if c_up.omega == 0.0:
            return 0.0   # no uplink compression -> memory unnecessary by default
        return 1.0 / (2.0 * (c_up.omega + 1.0))


class ArtemisState(NamedTuple):
    h: jax.Array        # [N, d] per-worker memories (zeros when alpha == 0)
    hbar: jax.Array     # [d] server memory  (PP2; == mean(h) under full participation)
    e: jax.Array        # [N, d] error-feedback buffers (zeros unless enabled)
    step: jax.Array     # scalar int32


def init_state(cfg: ArtemisConfig, dtype=jnp.float32) -> ArtemisState:
    n, d = cfg.n_workers, cfg.dim
    return ArtemisState(
        h=jnp.zeros((n, d), dtype),
        hbar=jnp.zeros((d,), dtype),
        e=jnp.zeros((n, d), dtype),
        step=jnp.zeros((), jnp.int32),
    )


def variant_config(variant: str, dim: int, n_workers: int, s: int = 1,
                   p: float = 1.0, pp_mode: str = "pp2",
                   alpha: Optional[float] = None) -> ArtemisConfig:
    """Build the config for one of the named paper variants."""
    table = {
        "sgd":      dict(up="identity", dwn="identity", alpha=0.0),
        "qsgd":     dict(up="squant", dwn="identity", alpha=0.0),
        "diana":    dict(up="squant", dwn="identity", alpha=alpha),
        "biqsgd":   dict(up="squant", dwn="squant", alpha=0.0),
        "artemis":  dict(up="squant", dwn="squant", alpha=alpha),
        "sgd-mem":  dict(up="identity", dwn="identity", alpha=alpha if alpha is not None else 0.5),
        "dore":     dict(up="squant", dwn="squant", alpha=alpha, error_feedback=True),
    }
    if variant not in table:
        raise ValueError(f"unknown variant {variant!r}; choose from {sorted(table)}")
    kw = table[variant]
    return ArtemisConfig(dim=dim, n_workers=n_workers, p=p, pp_mode=pp_mode,
                         up_kwargs={"s": s}, dwn_kwargs={"s": s}, **kw)


def _uplink_dense(cfg: ArtemisConfig, c_up: wire.Codec, state: ArtemisState,
                  grads: jax.Array, up_keys: jax.Array, active: jax.Array,
                  alpha: float, fc: faults.FaultConfig, flt_key):
    """Reference uplink: vmap the codec round-trip over workers.  Works for
    EVERY registered codec — the faulted wire corrupts and validates the
    payload representation itself (levels/indices/scales), not the decoded
    value, so an index bit-flip on a sparsify payload is as real as a scale
    flip on squant."""
    delta = grads - state.h                                # [N,d]
    if cfg.error_feedback:
        delta = delta + state.e
    if not fc.wire_faults:
        delta_hat = jax.vmap(c_up)(up_keys, delta)         # [N,d]
        if cfg.error_feedback:
            new_e = state.e + (grads - state.h) - delta_hat
            new_e = active * new_e + (1 - active) * state.e
        else:
            new_e = state.e
        # only active workers compress/communicate & update their local memory
        delta_hat = active * delta_hat
        new_h = state.h + alpha * delta_hat                # inactive rows unchanged
        sum_hat = jnp.sum(delta_hat, axis=0)               # [d]
        return delta_hat, new_h, new_e, sum_hat, jnp.float32(0.0)
    # --- faulted wire: only sent (active) payloads can be corrupted --------
    payload = jax.vmap(c_up.encode)(up_keys, delta)        # leaves: [N, ...]
    if fc.bitflip_rate > 0.0:
        payload = faults.corrupt_payload(flt_key, payload, fc.bitflip_rate,
                                         only=active[:, 0])
    ok = active
    if fc.scrub:
        # failed checksum => treat the worker as inactive this round
        valid = jax.vmap(c_up.validate)(payload)           # [N]
        ok = active * valid[:, None]
        payload = faults.scrub_payload(payload, valid)
    sent = jax.vmap(c_up.decode)(payload)
    sent = faults.nan_to_zero(sent) * ok if fc.scrub else sent * active
    if cfg.error_feedback:
        new_e = state.e + (grads - state.h) - sent
        new_e = ok * new_e + (1 - ok) * state.e
    else:
        new_e = state.e
    # the fault model corrupts the encoder's output buffer, so the worker
    # memory tracks exactly what the server accepted (scrubbed rows: nothing)
    new_h = state.h + alpha * sent
    sum_hat = jnp.sum(sent, axis=0)
    scrubbed = jnp.sum(active) - jnp.sum(ok)
    return sent, new_h, new_e, sum_hat, scrubbed


def _uplink_pallas(cfg: ArtemisConfig, c_up: wire.Codec, state: ArtemisState,
                   grads: jax.Array, up_keys: jax.Array, active: jax.Array,
                   alpha: float, fc: faults.FaultConfig, flt_key):
    """Fused uplink for codecs of the ``squant_rows`` family: worker encode +
    memory update in one HBM pass (kernels/fused_memory.py) and server
    dequant-accumulate (kernels/ring_sum).

    Each worker row is one kernel tile (``[N, 1, d]``, a grid over N), so the
    per-tile scale is the per-worker global L2 norm — identical semantics to
    ``squant`` on the dense path (same keys, same uniforms, same levels up
    to fp reassociation).
    Error feedback folds in by encoding ``g + e - h`` instead of ``g - h``
    (the EF buffer update happens outside the kernel).
    """
    from repro.kernels import default_interpret
    from repro.kernels.fused_memory import fused_memory_update
    from repro.kernels.ring_sum import ring_sum

    n, d = cfg.n_workers, cfg.dim
    s = int(cfg.up_kwargs.get("s", 1))
    itp = default_interpret()
    g_in = grads + state.e if cfg.error_feedback else grads
    # same uniforms the dense compressor would draw under vmap
    u = jax.vmap(lambda k: jax.random.uniform(k, (d,)))(up_keys)
    q, scales, h_fused = fused_memory_update(
        g_in[:, None], state.h[:, None], u[:, None], alpha, s=s,
        interpret=itp)
    q, scales, h_fused = q[:, 0], scales[:, 0], h_fused[:, 0]
    if not fc.wire_faults:
        # inactive workers neither transmit nor touch their memory
        new_h = active * h_fused + (1 - active) * state.h
        if cfg.error_feedback:
            delta_full = q.astype(grads.dtype) * scales     # unmasked decode
            new_e = state.e + (grads - state.h) - delta_full
            new_e = active * new_e + (1 - active) * state.e
        else:
            new_e = state.e
        act_scales = scales * active                        # [N,1]
        sum_hat = ring_sum(q[:, None, :], act_scales[:, :, None],
                           block=(1, d), interpret=itp).reshape(d)
        delta_hat = q.astype(grads.dtype) * act_scales      # [N,d] decoded
        return delta_hat, new_h, new_e, sum_hat, jnp.float32(0.0)
    # --- faulted wire: the kernel's payload is a row_squant WirePayload ----
    # (scale = norm/s; decode is q * scale), so the generic payload fault
    # operators and validate apply unchanged
    wc = wire.make_codec("row_squant", d, s=s)
    payload = wire.WirePayload(
        {"levels": q, "scales": scales},
        wire.PayloadMeta("row_squant", (n, d), str(grads.dtype), (("s", s),)))
    if fc.bitflip_rate > 0.0:
        payload = faults.corrupt_payload(flt_key, payload, fc.bitflip_rate,
                                         only=active[:, 0])
    ok = active
    if fc.scrub:
        valid = jax.vmap(wc.validate)(payload)              # [N]
        ok = active * valid[:, None]
        payload = faults.scrub_payload(payload, valid)
    q, scales = payload["levels"], payload["scales"]
    act_scales = scales * ok                                # [N,1]
    sum_hat = ring_sum(q[:, None, :], act_scales[:, :, None],
                       block=(1, d), interpret=itp).reshape(d)
    delta_hat = q.astype(grads.dtype) * act_scales          # [N,d] decoded
    if cfg.error_feedback:
        new_e = state.e + (grads - state.h) - delta_hat
        new_e = ok * new_e + (1 - ok) * state.e
    else:
        new_e = state.e
    # worker memory tracks the accepted payload (see _uplink_dense)
    new_h = state.h + alpha * delta_hat
    scrubbed = jnp.sum(active) - jnp.sum(ok)
    return delta_hat, new_h, new_e, sum_hat, scrubbed


def artemis_round(cfg: ArtemisConfig, state: ArtemisState, grads: jax.Array,
                  key: jax.Array, active: Optional[jax.Array] = None,
                  backend: Optional[str] = None):
    """One communication round.

    Args:
      grads:  [N, d] per-worker stochastic gradients g_{k+1}^i(w_k).
      active: optional {0,1} float mask [N]; default all-active.
      backend: 'dense' (reference) or 'pallas' (fused uplink kernels for
        codecs that declare the matching ``fused_uplink`` family; others
        fall back to the dense path); default ``cfg.backend``.

    Returns:
      omega:  [d] the (doubly) compressed descent direction Omega_{k+1}.
      state':  updated ArtemisState.
      stats:  dict of bit costs and diagnostics for this round.
    """
    c_up, c_dwn = cfg.codecs()
    alpha = cfg.resolved_alpha()
    n, d = cfg.n_workers, cfg.dim
    backend = cfg.backend if backend is None else backend
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; choose from {BACKENDS}")
    if active is None:
        active = jnp.ones((n,), grads.dtype)
    active = active.astype(grads.dtype)[:, None]          # [N,1]

    up_key, dwn_key = jax.random.split(jax.random.fold_in(key, state.step))
    up_keys = jax.random.split(up_key, n)
    fc = faults.of(cfg.faults)
    # fault stream branches off the round key via a salt so the base
    # up/dwn draws are untouched (zero-fault => byte-identical trace)
    flt_key = (jax.random.fold_in(jax.random.fold_in(key, state.step),
                                  faults.FAULT_SALT)
               if fc.wire_faults else None)

    # ---- workers: compress gradient differences ---------------------------
    use_fused = backend == "pallas" and c_up.fused_uplink == "squant_rows"
    uplink = _uplink_pallas if use_fused else _uplink_dense
    delta_hat, new_h, new_e, sum_hat, scrubbed = uplink(
        cfg, c_up, state, grads, up_keys, active, alpha, fc, flt_key)

    # ---- server: reconstruct, aggregate, compress downlink ----------------
    if cfg.pp_mode == "pp2":
        ghat = state.hbar + sum_hat / (cfg.p * n)
        new_hbar = state.hbar + alpha * sum_hat / n
    elif cfg.pp_mode == "pp1":
        # server-side copies of h_i; only ACTIVE memories are read
        ghat = sum_hat / (cfg.p * n) + jnp.sum(active * state.h, axis=0) / (cfg.p * n)
        new_hbar = jnp.mean(new_h, axis=0)
    else:
        raise ValueError(f"unknown pp_mode {cfg.pp_mode!r}")

    omega = c_dwn(dwn_key, ghat)

    delta = grads - state.h
    if cfg.error_feedback:
        delta = delta + state.e
    n_active = jnp.sum(active)
    # Metering rule (see DESIGN.md §4 / federated.run): the broadcast reaches
    # only the participating workers; returners' catch-up is metered by the
    # simulator on top of this.
    stats = {
        "uplink_bits": n_active * c_up.bits(d),
        "dwnlink_bits": n_active * c_dwn.bits(d),
        "compress_err_up": jnp.mean(jnp.sum((delta_hat - active * delta) ** 2, -1)),
        "compress_err_dwn": jnp.sum((omega - ghat) ** 2),
        "ghat_norm": jnp.linalg.norm(ghat),
        "wire_scrubbed": scrubbed,   # payloads dropped by the server this round
    }
    return omega, ArtemisState(new_h, new_hbar, new_e, state.step + 1), stats
