"""Mesh-distributed Artemis: compressed gradient aggregation over a worker axis.

Workers are slices of the device mesh along ``worker_axes`` (the 'pod' axis on
the production multi-pod mesh: the slow DCN inter-pod links play the paper's
bandwidth-constrained uplink/downlink).  The train step is wrapped in a
*partial-manual* ``shard_map``: worker axes are manual — so ``jax.grad``
inside yields the per-worker gradient, un-psum'd — while the remaining
data/model axes stay auto, letting GSPMD shard the model inside each worker
exactly as in the uncompressed baseline.

Wire layer (``wire="bucketed"``, the default — DESIGN.md §7): the gradient
pytree is flattened into <= K equal byte-size f32 buckets
(``core/bucketing.py``), each bucket squant-encoded into one contiguous
``int8 levels + f32 row-scales`` payload, and the payloads move around a
**pipelined double-buffered ring**: inside a ``lax.scan`` over the N-1 hops,
hop j's ``ppermute`` of the stacked bucket payload is issued while hop j-1's
payload is dequant-accumulated by ``kernels/bucket_ring.py`` — the carry
holds the in-flight payload, so on real hardware the dequant hides under the
wire latency and the step is bandwidth- (not latency-) bound.  The legacy
``wire="leaf"`` path keeps the seed's one-ring-per-leaf schedule (N-1
*sequential* hops per leaf) as the benchmark baseline.

Wire format is real either way: the uplink moves **int8 levels + per-row f32
scales** across workers (visible in compiled HLO as s8 collective-permutes —
``launch/roofline.bucketed_wire_model`` predicts the bytes and
``tests/helpers/bucket_scenarios.py::hlo_wire_guard`` pins them in CI).  The
downlink broadcast costs ZERO bytes: every worker compresses the identical
aggregate with an identical PRNG key (the TPU-native replacement for the
server->worker broadcast).

State per paper Algorithm 1 (PP2):
  h    — per-worker memory h_i; bucketed: one [W, B, R, C] stack sharded
         over the worker axes (leaf wire: per-leaf [W, ...] trees).
  hbar — server memory \bar h; replicated (every worker updates it with the
         same summed quantity, so it stays bitwise identical).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import bucketing
from repro.core import codec as wire
from repro.core import faults as FLT
from repro.kernels import bucket_ring as BK
from repro.kernels import default_interpret
from repro.launch.mesh import strip_axes

PyTree = Any

VARIANTS = ("sgd", "qsgd", "diana", "biqsgd", "artemis", "dore")

WIRES = ("bucketed", "leaf")
REDUCE_IMPLS = ("pipelined", "sequential", "psum")


@dataclasses.dataclass(frozen=True)
class DistConfig:
    worker_axes: Tuple[str, ...] = ("pod",)
    variant: str = "artemis"
    s: int = 1                      # quantization levels
    alpha: Optional[float] = None   # None -> 1/(2(omega+1)), omega = sqrt(row)/s
    p_participation: float = 1.0    # PP2 over workers when < 1
    memory_dtype: str = "float32"   # h storage dtype (bf16 = beyond-paper)
    error_feedback: bool = False    # Dore-style EF on the uplink (beyond paper)
    local_steps: int = 1            # communicate every k steps (Remark 2 /
                                    # Local-SGD direction; 1 = every step)
    seed: int = 17
    # --- wire layer (DESIGN.md §7) ---
    wire: str = "bucketed"          # "bucketed" flat ring | legacy "leaf" loop
    bucket_bytes: int = bucketing.DEFAULT_BUCKET_BYTES
    max_buckets: int = bucketing.DEFAULT_MAX_BUCKETS
    bucket_row: int = bucketing.DEFAULT_ROW      # per-row-scale tile C
    reduce_impl: str = "pipelined"  # "pipelined" scan ring | "sequential"
                                    # unrolled hops | "psum" dense reference
    # --- wire codec (core/codec.py registry; DESIGN.md §9) ---
    codec: str = "squant"           # "squant" = the native row-scale wire
                                    # format; any registered codec works
    codec_kwargs: Tuple[Tuple[str, Any], ...] = ()
    # --- fault injection + server defenses (core/faults.py, DESIGN.md §8) ---
    faults: Optional[FLT.FaultConfig] = None
    # --- observability (repro.obs, DESIGN.md §11) ---
    # STATIC gate: False builds the byte-identical legacy step.  True makes
    # the aggregates return a third `obs` dict (repro.obs.telemetry
    # MESH_METRICS: physical wire bytes/step reconciled against the
    # launch/roofline models, participation, scrub/blowup counts) which the
    # train step psums over workers and attaches to the step metrics under
    # "obs".  All values are computed from quantities the step already has —
    # no extra collectives beyond the psums of four scalars.
    telemetry: bool = False

    def __post_init__(self):
        if self.wire not in WIRES:
            raise ValueError(f"wire={self.wire!r} not in {WIRES}")
        if self.reduce_impl not in REDUCE_IMPLS:
            raise ValueError(
                f"reduce_impl={self.reduce_impl!r} not in {REDUCE_IMPLS}")
        name = {"squant": "row_squant"}.get(self.codec, self.codec)
        if name not in wire.available():
            raise ValueError(
                f"codec={self.codec!r} not in {wire.available()}")

    @property
    def up_compress(self) -> bool:
        return self.variant in ("qsgd", "diana", "biqsgd", "artemis", "dore")

    @property
    def dwn_compress(self) -> bool:
        return self.variant in ("biqsgd", "artemis", "dore")

    @property
    def memory(self) -> bool:
        return self.variant in ("diana", "artemis", "dore")

    @property
    def use_ef(self) -> bool:
        return self.error_feedback or self.variant == "dore"

    @property
    def bucketed(self) -> bool:
        return self.wire == "bucketed"

    def layout(self, tree: PyTree) -> bucketing.BucketLayout:
        return bucketing.make_layout(tree, bucket_bytes=self.bucket_bytes,
                                     max_buckets=self.max_buckets,
                                     row=self.bucket_row)

    def wire_codec(self, row: int) -> wire.Codec:
        """The codec that runs on this wire for messages with last-axis
        length ``row`` (which fixes omega).  ``codec="squant"`` maps to the
        native per-row-scale mesh format ``row_squant``."""
        name = {"squant": "row_squant"}.get(self.codec, self.codec)
        kw = dict(self.codec_kwargs)
        if name == "row_squant":
            kw.setdefault("s", self.s)
        return wire.make_codec(name, row, **kw)


# ---------------------------------------------------------------------------
# distributed-friendly per-row s-quantization (sharding-transparent)
# ---------------------------------------------------------------------------

# The row-scale wire format now lives in core/codec.py ("row_squant") so the
# kernels, the mesh wires, and the simulator share one definition; these
# aliases keep the historical dist-level entry points.
squant_encode = wire.row_squant_encode
squant_decode = wire.row_squant_decode


def _omega_row(row_len: int, s: int) -> float:
    return wire.squant_omega(row_len, s)


def default_alpha(params: PyTree, s: int) -> float:
    """1 / (2 (omega_max + 1)) over leaves (Thm 1 condition)."""
    rows = max(int(l.shape[-1]) if l.ndim else 1 for l in jax.tree.leaves(params))
    return float(1.0 / (2.0 * (_omega_row(rows, s) + 1.0)))


def default_alpha_bucketed(row: int, s: int) -> float:
    """Thm 1 alpha for the bucketed wire: every row has length ``row``."""
    return float(1.0 / (2.0 * (_omega_row(row, s) + 1.0)))


def _codec_alpha(cfg: "DistConfig", rows) -> float:
    """Thm 1 alpha from the wire codec's omega (max over message rows).
    For the native squant wire this equals ``default_alpha*`` bit-for-bit
    (same doubles through the same formula)."""
    om = max(cfg.wire_codec(int(r)).omega for r in rows)
    return float(1.0 / (2.0 * (om + 1.0)))


def _payload_nbytes(payload) -> float:
    """Byte size of one encoded wire payload.  Shapes are static at trace
    time, so this is a Python constant — the telemetry wire-byte counter
    costs nothing in the compiled step.  Equals the codec's declared
    ``wire_bytes`` split summed over dtypes (the encoders ship exactly the
    arrays they declare; the HLO wire guard pins that equivalence)."""
    return float(sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                     for l in jax.tree.leaves(payload)))


# ---------------------------------------------------------------------------
# bucketed ring transports (run INSIDE the worker-manual shard_map)
# ---------------------------------------------------------------------------

def bucket_encode(key: jax.Array, buckets: jax.Array, s: int):
    """Per-bucket squant encode: [B, R, C] -> (q int8 [B,R,C], scales
    [B,R,1] f32), one PRNG key per bucket (``bucketing.bucket_keys``).
    Kept for benchmarks/tests; the aggregate now goes through
    ``bucketing.encode_buckets`` with an arbitrary codec."""
    keys = bucketing.bucket_keys(key, buckets.shape[0])
    return jax.vmap(lambda k, x: squant_encode(k, x, s))(keys, buckets)


def payload_decode(codec: wire.Codec, payload: wire.WirePayload) -> jax.Array:
    """Decode a bucket-stacked payload (leaves carry a leading B axis)."""
    return jax.vmap(codec.decode)(payload)


def _payload_acc(codec: wire.Codec, acc: jax.Array,
                 payload: wire.WirePayload, interpret: bool) -> jax.Array:
    """One dequant-accumulate: the native row-scale payload rides the fused
    kernels/bucket_ring path; any other codec decodes then adds."""
    if not codec.fused_acc:
        return acc + payload_decode(codec, payload)
    fold = functools.partial(BK.bucket_acc, interpret=interpret)
    # GSPMD cannot partition a Mosaic kernel: where the worker shard_map
    # leaves mesh axes auto, run it manual over every axis (the bucket stack
    # is replicated over the auto ones)
    mesh = jax.sharding.get_abstract_mesh()
    if any(t != jax.sharding.AxisType.Manual for t in mesh.axis_types):
        fold = jax.shard_map(fold, in_specs=P(), out_specs=P(),
                             check_vma=False)
    return fold(acc, payload["levels"], payload["scales"])


def bucket_ring_reduce(codec: wire.Codec, payload: wire.WirePayload,
                       axes: Tuple[str, ...], n: int, *,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Pipelined double-buffered ring all-reduce of compressed payloads.

    ``lax.scan`` over the N-1 hops; the carry holds the in-flight payload
    (a codec ``WirePayload`` pytree — every leaf gets its own ``ppermute``).
    Each hop issues the next ``ppermute`` *and* dequant-accumulates the
    payload it currently holds — the two are data-independent inside the
    step, so the compiler overlaps the collective with the compute (comm
    hides under dequant or vice versa).  Accumulation order (own payload
    first, then arrivals from w-1, w-2, ...) matches the sequential
    transport bit-for-bit.
    """
    itp = default_interpret() if interpret is None else interpret
    acc = jnp.zeros(jax.eval_shape(lambda p: payload_decode(codec, p),
                                   payload).shape, jnp.float32)
    if n == 1:
        return _payload_acc(codec, acc, payload, itp)
    perm = [(j, (j + 1) % n) for j in range(n)]

    def hop(carry, _):
        pc, a = carry
        # named_scope: metadata-only annotation so the hop's ppermute +
        # dequant-accumulate are findable on the profiler timeline
        with jax.named_scope("ring_hop"):
            pn = jax.tree.map(lambda l: jax.lax.ppermute(l, axes, perm), pc)
            a = _payload_acc(codec, a, pc, itp)
        return (pn, a), None

    (pl, acc), _ = jax.lax.scan(hop, (payload, acc), None, length=n - 1)
    return _payload_acc(codec, acc, pl, itp)


def bucket_ring_reduce_sequential(codec: wire.Codec,
                                  payload: wire.WirePayload,
                                  axes: Tuple[str, ...], n: int) -> jax.Array:
    """The pre-bucketing transport applied to the bucket payload: N-1
    *blocking* hops with a dequant-accumulate stall between each (the
    per-leaf ring of ``wire="leaf"``, kept as the pipelining baseline)."""
    acc = payload_decode(codec, payload)
    if n == 1:
        return acc
    perm = [(j, (j + 1) % n) for j in range(n)]
    pr = payload
    for _ in range(n - 1):
        pr = jax.tree.map(lambda l: jax.lax.ppermute(l, axes, perm), pr)
        acc = acc + payload_decode(codec, pr)
    return acc


# ---------------------------------------------------------------------------
# Artemis aggregation (runs INSIDE the worker-manual shard_map)
# ---------------------------------------------------------------------------

class ArtemisDistState(NamedTuple):
    h: PyTree        # per-worker memories; bucketed [W, B, R, C] stack
    hbar: PyTree     # replicated server memory; bucketed [B, R, C]
    e: PyTree        # per-worker EF buffers (Dore; zeros-scalar stub if off)
    acc: PyTree      # per-worker local grad accumulator (local_steps > 1)
    prev_active: jax.Array  # [W] last-round availability (Markov chain state)
    step: jax.Array


def init_dist_state(cfg: Optional["DistConfig"], params: PyTree,
                    n_workers: int = 1) -> ArtemisDistState:
    if cfg is not None and cfg.bucketed:
        shape = cfg.layout(params).shape

        def full(dt):
            return jnp.zeros((n_workers,) + shape, dt)

        def stub():
            return jnp.zeros((n_workers,), jnp.float32)

        if cfg.memory:
            mdt = jnp.dtype(cfg.memory_dtype)
            h, hbar = full(mdt), jnp.zeros(shape, mdt)
        else:
            h, hbar = stub(), jnp.zeros((), jnp.float32)
        e = full(jnp.float32) if cfg.use_ef else stub()
        acc = full(jnp.float32) if cfg.local_steps > 1 else stub()
        return ArtemisDistState(h=h, hbar=hbar, e=e, acc=acc,
                                prev_active=jnp.zeros((n_workers,),
                                                      jnp.float32),
                                step=jnp.zeros((), jnp.int32))

    def full(dt):
        return jax.tree.map(lambda p: jnp.zeros((n_workers,) + p.shape, dt),
                            params)

    def stub():
        return jax.tree.map(lambda p: jnp.zeros((n_workers,), jnp.float32),
                            params)

    if cfg is not None and cfg.memory:
        mdt = jnp.dtype(cfg.memory_dtype)
        h = full(mdt)
        hbar = jax.tree.map(lambda p: jnp.zeros(p.shape, mdt), params)
    else:
        h = stub()
        hbar = jax.tree.map(lambda p: jnp.zeros((), jnp.float32), params)
    e = full(jnp.float32) if (cfg is not None and cfg.use_ef) else stub()
    acc = full(jnp.float32) if (cfg is not None and cfg.local_steps > 1) else stub()
    return ArtemisDistState(h=h, hbar=hbar, e=e, acc=acc,
                            prev_active=jnp.zeros((n_workers,), jnp.float32),
                            step=jnp.zeros((), jnp.int32))


def _round_keys(cfg: DistConfig, step: jax.Array, wid: jax.Array,
                prev: jax.Array):
    """(uplink key — distinct per worker, downlink key — SHARED, active mask,
    availability, fault key).

    Shared by the leaf and bucketed paths so switching the wire never changes
    the participation pattern or the downlink stream.  ``prev`` is this
    worker's last-round availability (the Markov chain state); ``part`` is
    this round's availability BEFORE stragglers drop out — the chain evolves
    on availability, not on who made the deadline."""
    fc = FLT.of(cfg.faults)
    base = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
    up_key = jax.random.fold_in(base, wid + 1)
    dwn_key = jax.random.fold_in(base, 0)
    # separate salted stream: the base up/dwn/participation draws never move
    flt_key = jax.random.fold_in(jax.random.fold_in(base, FLT.FAULT_SALT), wid)
    if cfg.p_participation < 1.0 or fc.markov:
        act_key = jax.random.fold_in(jax.random.fold_in(base, 999), wid)
        u = jax.random.uniform(act_key, ())
        part = FLT.participation(fc, cfg.p_participation, u, prev, step)
    else:
        part = jnp.float32(1.0)
    active = part
    if fc.straggler_rate > 0.0:
        u_s = jax.random.uniform(jax.random.fold_in(flt_key, 1), ())
        active = active * (u_s >= fc.straggler_rate).astype(jnp.float32)
    return up_key, dwn_key, active, part, flt_key


def artemis_aggregate_bucketed(cfg: DistConfig, state: ArtemisDistState,
                               gbuckets: jax.Array,
                               layout: bucketing.BucketLayout,
                               n_workers: int, wid: jax.Array):
    """Bucketed per-worker grads [B, R, C] -> (descent buckets, new state).

    Inside shard_map, where each per-worker state leaf is the local
    [1, B, R, C] slice.  The uplink sum runs over ``cfg.reduce_impl``:
    the pipelined scan ring (default), the sequential unrolled ring (the
    pre-bucketing schedule — bit-identical result), or a dense
    dequantize-then-psum (the equivalence-test reference).
    """
    axes = cfg.worker_axes
    n = n_workers
    fc = FLT.of(cfg.faults)
    wc = cfg.wire_codec(layout.row)
    alpha = cfg.alpha if cfg.alpha is not None else (
        _codec_alpha(cfg, [layout.row]) if cfg.memory else 0.0)
    p = cfg.p_participation
    mdt = jnp.dtype(cfg.memory_dtype)

    obs_blow = jnp.zeros((), jnp.float32)
    obs_scrub = jnp.zeros((), jnp.float32)
    obs_bytes = 0.0

    with jax.named_scope("encode"):
        up_key, dwn_key, active, part, flt_key = _round_keys(
            cfg, state.step, wid, state.prev_active[0])
        g32 = gbuckets.astype(jnp.float32)
        if fc.blowup_rate > 0.0:
            hit = jax.random.bernoulli(jax.random.fold_in(flt_key, 2),
                                       fc.blowup_rate, ())
            g32 = jnp.where(hit, jnp.float32(fc.blowup_value), g32)
            if cfg.telemetry:
                obs_blow = hit.astype(jnp.float32)
        if fc.scrub:
            # non-finite local gradient => worker masked inactive BEFORE any
            # arithmetic (0 * NaN is NaN, so the rows are zeroed too)
            finite = jnp.all(jnp.isfinite(g32)).astype(jnp.float32)
            active = active * finite
            g32 = FLT.nan_to_zero(g32)
        h = (state.h[0].astype(jnp.float32) if cfg.memory
             else jnp.zeros_like(g32))
        e_buf = state.e[0] if cfg.use_ef else None
        delta = (g32 - h) * active
        if cfg.use_ef:
            delta = delta + e_buf

        ok = active
        if cfg.up_compress:
            enc = bucketing.encode_buckets(wc, up_key, delta)
            # PP2: an inactive worker's payload (its EF buffer under Dore)
            # must contribute EXACTLY zero to the sum — zero the wire float
            # leaves (the scales for squant, the values for sparsify).
            enc = FLT.mask_payload(enc, active)
            if fc.bitflip_rate > 0.0:
                # only a payload actually on the wire can pick up flipped bits
                enc = FLT.corrupt_payload(jax.random.fold_in(flt_key, 3), enc,
                                          fc.bitflip_rate, only=active)
            if fc.scrub:
                # per-BUCKET checksum: a corrupt bucket is dropped through the
                # same zero-scale path as inactivity; its h/e slices stay put
                valid = jax.vmap(wc.validate)(enc)         # [B]
                ok = active * valid.reshape(-1, 1, 1)      # [B,1,1] broadcast
                enc = FLT.scrub_payload(enc, valid)
                if cfg.telemetry:
                    obs_scrub = valid.shape[0] - jnp.sum(valid)
        else:
            dhat_i = delta * active

    with jax.named_scope("ring"):
        if not cfg.up_compress:
            dhat_sum = jax.lax.psum(dhat_i, axes)
            obs_bytes = 4.0 * float(np.prod(g32.shape))
        elif cfg.reduce_impl == "psum":
            dhat_sum = jax.lax.psum(payload_decode(wc, enc), axes)
            # all-reduce proxy: result bytes ~ bytes sent per device on a
            # ring (the same convention launch/roofline uses)
            obs_bytes = 4.0 * float(np.prod(g32.shape))
        elif cfg.reduce_impl == "sequential":
            dhat_sum = bucket_ring_reduce_sequential(wc, enc, axes, n)
            obs_bytes = (n - 1) * _payload_nbytes(enc)
        else:
            dhat_sum = bucket_ring_reduce(wc, enc, axes, n)
            obs_bytes = (n - 1) * _payload_nbytes(enc)

    with jax.named_scope("memory"):
        if cfg.up_compress:
            dhat_i = payload_decode(wc, enc)
        if cfg.use_ef:
            e_new = (ok * (delta - dhat_i) + (1 - ok) * e_buf)[None]
        else:
            e_new = state.e
        if cfg.memory:
            hbar = state.hbar.astype(jnp.float32)
            ghat = hbar + dhat_sum / (p * n)
            h_new = (h + alpha * dhat_i).astype(mdt)[None]
            hbar_new = (hbar + alpha * dhat_sum / n).astype(mdt)
        else:
            ghat = dhat_sum / (p * n)
            h_new, hbar_new = state.h, state.hbar
    if cfg.dwn_compress:
        # zero-byte broadcast: identical key -> identical compression
        with jax.named_scope("downlink"):
            ghat = payload_decode(wc, bucketing.encode_buckets(wc, dwn_key,
                                                               ghat))

    new_state = ArtemisDistState(h_new, hbar_new, e_new, state.acc,
                                 jnp.reshape(part, (1,)), state.step + 1)
    if not cfg.telemetry:
        return ghat, new_state
    obs = {"wire_bytes": jnp.float32(obs_bytes),
           "mesh_active": jnp.reshape(active, ()).astype(jnp.float32),
           "mesh_scrubbed": obs_scrub.astype(jnp.float32),
           "mesh_blowup_hits": obs_blow}
    return ghat, new_state, obs


def artemis_aggregate(cfg: DistConfig, state: ArtemisDistState, grads: PyTree,
                      n_workers: int, wid: jax.Array,
                      grad_specs: Optional[PyTree] = None):
    """Legacy leaf wire: per-worker grads -> (descent direction, new state).
    One int8 ring per pytree leaf, N-1 sequential hops each.  Inside
    shard_map, where each h leaf is the local [1, ...] slice.

    grad_specs: optional tree of PartitionSpecs (auto axes only) matching
    grads — WITHOUT it GSPMD tends to replicate the int8 payload before the
    inter-worker all-gather, inflating collective bytes ~256x (measured; see
    EXPERIMENTS.md §Perf iteration 1)."""
    axes = cfg.worker_axes
    n = n_workers
    fc = FLT.of(cfg.faults)
    up_key, dwn_key, active, part, flt_key = _round_keys(
        cfg, state.step, wid, state.prev_active[0])
    if fc.blowup_rate > 0.0:
        blow_hit = jax.random.bernoulli(jax.random.fold_in(flt_key, 2),
                                        fc.blowup_rate, ())
    leaf_rows = [int(l.shape[-1]) if l.ndim else 1
                 for l in jax.tree.leaves(grads)]
    alpha = cfg.alpha if cfg.alpha is not None else (
        _codec_alpha(cfg, leaf_rows) if cfg.memory else 0.0)

    leaves, treedef = jax.tree.flatten(grads)
    h_l = treedef.flatten_up_to(state.h)
    hbar_l = treedef.flatten_up_to(state.hbar)
    e_l = treedef.flatten_up_to(state.e)
    spec_l = (treedef.flatten_up_to(grad_specs) if grad_specs is not None
              else [None] * len(leaves))
    p = cfg.p_participation

    def _pin(x, spec, extra_lead=0):
        if spec is None:
            return x
        full = P(*(((),) * extra_lead + tuple(spec)[:x.ndim - extra_lead]
                   + (None,) * max(0, x.ndim - extra_lead - len(tuple(spec)))))
        return jax.lax.with_sharding_constraint(x, full)

    def _pin_rows(x, spec):
        # scale has the last dim collapsed to 1 -> drop its sharding
        if spec is None:
            return x
        t = tuple(spec)[:x.ndim]
        t = t[:-1] + (None,) if t else t
        return jax.lax.with_sharding_constraint(
            x, P(*(t + (None,) * (x.ndim - len(t)))))

    mdt = jnp.dtype(cfg.memory_dtype)
    obs_blow = jnp.zeros((), jnp.float32)
    if cfg.telemetry and fc.blowup_rate > 0.0:
        obs_blow = blow_hit.astype(jnp.float32)
    obs_scrub = jnp.zeros((), jnp.float32)
    obs_bytes = 0.0
    out_agg, out_h, out_hbar, out_e = [], [], [], []
    for i, g in enumerate(leaves):
        g32 = g.astype(jnp.float32)
        act_l = active
        if fc.blowup_rate > 0.0:
            g32 = jnp.where(blow_hit, jnp.float32(fc.blowup_value), g32)
        if fc.scrub:
            # non-finite leaf => this worker sits the leaf's ring out
            finite = jnp.all(jnp.isfinite(g32)).astype(jnp.float32)
            act_l = act_l * finite
            g32 = FLT.nan_to_zero(g32)
        h = h_l[i][0].astype(jnp.float32) if cfg.memory else jnp.zeros_like(g32)
        e_buf = e_l[i][0] if cfg.use_ef else None
        delta = (g32 - h) * act_l
        if cfg.use_ef:
            delta = delta + e_buf
        ok_l = act_l
        if cfg.up_compress:
            wcl = cfg.wire_codec(int(g.shape[-1]) if g.ndim else 1)
            p_l = wcl.encode(jax.random.fold_in(up_key, i), delta)
            # PP2: an inactive worker's payload (its EF buffer under Dore)
            # must contribute EXACTLY zero to the ring sum — zero the wire
            # float leaves (the scales for squant).
            p_l = FLT.mask_payload(p_l, act_l)
            if fc.bitflip_rate > 0.0:
                p_l = FLT.corrupt_payload(jax.random.fold_in(flt_key, 10 + i),
                                          p_l, fc.bitflip_rate, only=act_l)
            if fc.scrub:
                # per-LEAF checksum -> dropped via the zero-scale path
                valid = wcl.validate(p_l)
                ok_l = act_l * valid
                p_l = FLT.scrub_payload(p_l, valid)
                if cfg.telemetry:
                    obs_scrub = obs_scrub + (1.0 - valid)
            if "levels" in p_l.data:
                # levels keep the leaf's auto-axis sharding; scales have the
                # last dim collapsed (other codecs ship 1-D index/value
                # payloads the leaf specs don't apply to)
                p_l = p_l.replace(levels=_pin(p_l["levels"], spec_l[i]),
                                  scales=_pin_rows(p_l["scales"], spec_l[i]))
            # ---- the actual wire: an int8 ring. all_gather over a manual
            # axis forces replication of the auto-sharded dims (measured
            # 256x byte blowup); collective-permute keeps each hop at
            # exactly one int8 shard, so the ring is N-1 shard-sized hops.
            perm = [(j, (j + 1) % n) for j in range(n)]
            dhat_sum = wcl.decode(p_l)
            pr = p_l
            for _ in range(n - 1):
                pr = jax.tree.map(lambda l: jax.lax.ppermute(l, axes, perm),
                                  pr)
                dhat_sum = dhat_sum + wcl.decode(pr)
            dhat_sum = _pin(dhat_sum, spec_l[i])
            dhat_i = wcl.decode(p_l)
            obs_bytes += (n - 1) * _payload_nbytes(p_l)
        else:
            dhat_i = delta * act_l
            dhat_sum = jax.lax.psum(dhat_i, axes)
            obs_bytes += 4.0 * float(np.prod(g.shape) if g.ndim else 1)
        if cfg.use_ef:
            # EF accumulates what compression lost (Dore-style)
            out_e.append((ok_l * (delta - dhat_i)
                          + (1 - ok_l) * e_buf)[None])
        else:
            out_e.append(e_l[i])
        if cfg.memory:
            hbar = hbar_l[i].astype(jnp.float32)
            ghat = hbar + dhat_sum / (p * n)
            out_h.append((h + alpha * dhat_i).astype(mdt)[None])
            out_hbar.append((hbar + alpha * dhat_sum / n).astype(mdt))
        else:
            ghat = dhat_sum / (p * n)
            out_h.append(h_l[i])
            out_hbar.append(hbar_l[i])
        if cfg.dwn_compress:
            # zero-byte broadcast: identical key -> identical compression
            wcd = cfg.wire_codec(int(g.shape[-1]) if g.ndim else 1)
            ghat = wcd.decode(wcd.encode(jax.random.fold_in(dwn_key, i),
                                         ghat))
        out_agg.append(ghat.astype(g.dtype))

    agg = jax.tree.unflatten(treedef, out_agg)
    new_state = ArtemisDistState(jax.tree.unflatten(treedef, out_h),
                                 jax.tree.unflatten(treedef, out_hbar),
                                 jax.tree.unflatten(treedef, out_e),
                                 state.acc, jnp.reshape(part, (1,)),
                                 state.step + 1)
    if not cfg.telemetry:
        return agg, new_state
    obs = {"wire_bytes": jnp.float32(obs_bytes),
           "mesh_active": jnp.reshape(active, ()).astype(jnp.float32),
           "mesh_scrubbed": obs_scrub.astype(jnp.float32),
           "mesh_blowup_hits": obs_blow}
    return agg, new_state, obs


# ---------------------------------------------------------------------------
# Train-step factory
# ---------------------------------------------------------------------------

class TrainState(NamedTuple):
    params: PyTree
    opt_state: PyTree
    artemis: ArtemisDistState
    step: jax.Array


def _mesh_axis_sizes(mesh: Mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def state_specs(dcfg: Optional[DistConfig], state_struct: TrainState) -> TrainState:
    """Worker-axis PartitionSpecs for shard_map in/out (manual axes only)."""
    waxes = dcfg.worker_axes if dcfg else ()
    rep = P()
    art = ArtemisDistState(
        h=jax.tree.map(lambda _: P(waxes), state_struct.artemis.h),
        hbar=jax.tree.map(lambda _: rep, state_struct.artemis.hbar),
        e=jax.tree.map(lambda _: P(waxes), state_struct.artemis.e),
        acc=jax.tree.map(lambda _: P(waxes), state_struct.artemis.acc),
        prev_active=P(waxes),
        step=rep)
    return TrainState(
        params=jax.tree.map(lambda _: rep, state_struct.params),
        opt_state=jax.tree.map(lambda _: rep, state_struct.opt_state),
        artemis=art, step=rep)


def make_local_step(model, dcfg: DistConfig, mesh: Mesh):
    """Accumulate-only step for ``local_steps > 1`` (Remark 2 / Local-SGD
    direction, realized as gradient accumulation so params stay replicated):
    run this k-1 times between make_train_step's communicating step. ZERO
    inter-worker collectives in its HLO — the roofline-visible comm saving.
    (Bucketed wire: the accumulator lives in bucket space, so the
    communicating step folds it in without re-flattening.)
    """
    waxes = dcfg.worker_axes

    def local_fn(state: TrainState, batch):
        sspec = state_specs(dcfg, state)
        bspec = jax.tree.map(lambda _: P(waxes), batch)

        def inner(st: TrainState, bt):
            with jax.named_scope("model"):
                (loss, metrics), grads = jax.value_and_grad(
                    model.loss, has_aux=True)(st.params, bt)
            with jax.named_scope("wire"), jax.named_scope("bucketize"):
                if dcfg.bucketed:
                    gb = bucketing.bucketize(dcfg.layout(grads), grads)
                    acc = st.artemis.acc + gb[None]
                else:
                    acc = jax.tree.map(
                        lambda a, g: a + g.astype(a.dtype)[None],
                        st.artemis.acc, grads)
            return (st._replace(artemis=st.artemis._replace(acc=acc)),
                    (loss, metrics))

        return jax.shard_map(inner, mesh=mesh, in_specs=(sspec, bspec),
                             out_specs=(sspec, P()),
                             axis_names=set(waxes),
                             check_vma=False)(state, batch)

    return local_fn


def state_shardings(mesh: Mesh, state, pshard, dcfg: Optional[DistConfig]):
    """Shardings for TrainState: params per policy; h gets a leading worker
    dim over worker_axes; hbar like params; opt_state like params.

    These are exactly the shardings ``make_train_step``'s step emits, so a
    state placed on them round-trips through the step without a re-layout —
    and without the silent second XLA compile that a SingleDeviceSharding
    initial state costs (the jaxpr is cached but the executable is keyed on
    arg shardings; the trace audit pins this to one compile).

    Bucketed wire: the artemis leaves are single stacked arrays, not
    per-param trees — h/e/acc carry a leading worker dim ([W, B, R, C] or a
    [W] stub) sharded over the worker axes, hbar ([B, R, C]) is replicated
    (every worker applies the identical summed update).

    Params (the policy's ``pshard``) lose the worker axes: the step takes
    and returns them replicated over the workers, and an FSDP-style split
    over a worker axis would make the second step compile again."""
    rep = NamedSharding(mesh, P())
    if dcfg is not None and dcfg.worker_axes:
        pshard = jax.tree.map(
            lambda ns: NamedSharding(mesh, strip_axes(ns.spec,
                                                      dcfg.worker_axes)),
            pshard)
    if dcfg is not None and dcfg.bucketed:
        waxes = dcfg.worker_axes
        wsh = NamedSharding(mesh, P(waxes))
        opt_sh = jax.tree.map(lambda l: rep, state.opt_state) \
            if state.opt_state != () else ()
        return TrainState(
            params=pshard, opt_state=opt_sh,
            artemis=ArtemisDistState(
                h=jax.tree.map(lambda _: wsh, state.artemis.h),
                hbar=jax.tree.map(lambda _: rep, state.artemis.hbar),
                e=jax.tree.map(lambda _: wsh, state.artemis.e),
                acc=jax.tree.map(lambda _: wsh, state.artemis.acc),
                prev_active=wsh,
                step=rep),
            step=rep)

    def shift(ns):
        spec = ns.spec
        waxes = dcfg.worker_axes if dcfg else ()
        return NamedSharding(mesh, P(waxes, *spec))

    def worker_tree(struct_tree, full: bool):
        if full:
            return jax.tree.map(shift, pshard)
        return jax.tree.map(lambda _: rep, struct_tree)

    if dcfg is not None and dcfg.memory:
        h_sh = worker_tree(state.artemis.h, True)
        hbar_sh = jax.tree.map(lambda ns: ns, pshard)
    else:
        h_sh = worker_tree(state.artemis.h, False)
        hbar_sh = jax.tree.map(lambda _: rep, state.artemis.hbar)
    e_sh = worker_tree(state.artemis.e, dcfg is not None and dcfg.use_ef)
    acc_sh = worker_tree(state.artemis.acc,
                         dcfg is not None and dcfg.local_steps > 1)
    opt_sh = jax.tree.map(lambda l: rep, state.opt_state) \
        if state.opt_state != () else ()
    waxes_sh = NamedSharding(mesh, P(dcfg.worker_axes if dcfg else ()))
    return TrainState(
        params=pshard, opt_state=opt_sh,
        artemis=ArtemisDistState(h=h_sh, hbar=hbar_sh, e=e_sh, acc=acc_sh,
                                 prev_active=waxes_sh, step=rep),
        step=rep)


def reduce_metrics(metrics: dict, axes) -> dict:
    """The model's step metrics over the workers: the mean of each, but a
    maximum (``moe_max_load``) is the largest."""
    return {k: (jax.lax.pmax if k == "moe_max_load" else jax.lax.pmean)(
        m, axes) for k, m in metrics.items()}


def make_train_step(model, optimizer, dcfg: Optional[DistConfig], mesh: Mesh,
                    grad_specs: Optional[PyTree] = None):
    """Build (init_state_fn, step_fn).

    dcfg=None   -> plain data-parallel baseline (jit only; XLA aggregates).
    dcfg given  -> Artemis over dcfg.worker_axes via partial-manual shard_map
                   (bucketed flat-ring wire by default; dcfg.wire="leaf" for
                   the legacy per-leaf rings).
    grad_specs  -> PartitionSpec tree (auto axes only) pinning the compressed
                   payload sharding inside the leaf-wire aggregation
                   (strongly recommended at scale; see artemis_aggregate).
    """
    sizes = _mesh_axis_sizes(mesh)
    n_workers = 1
    if dcfg:
        for a in dcfg.worker_axes:
            n_workers *= sizes[a]

    def _param_shard(leaf):
        # keep a caller-placed NamedSharding on this mesh; everything else
        # (fresh single-device init, abstract leaves) replicates
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh == mesh:
            return sh
        return NamedSharding(mesh, P())

    def init_state(params) -> TrainState:
        opt_state = optimizer.init(params)
        art = init_dist_state(dcfg, params, n_workers)
        state = TrainState(params, opt_state, art, jnp.zeros((), jnp.int32))
        # place the fresh state exactly where the step's outputs will live:
        # a SingleDeviceSharding state makes the SECOND step recompile the
        # whole program for the post-step NamedShardings
        pshard = jax.tree.map(_param_shard, params)
        return jax.device_put(state, state_shardings(mesh, state, pshard,
                                                     dcfg))

    k_local = dcfg.local_steps if dcfg else 1

    telem = dcfg is not None and dcfg.telemetry

    def sgd_core(params, opt_state, art, stepno, batch, wid):
        # the phase scopes open outside jax.value_and_grad: under a transform
        # a scope's name comes out wrapped (``jvp(model)``), not as a plain
        # op_name path part
        with jax.named_scope("model"):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, batch)
        obs = None
        if dcfg is not None and dcfg.worker_axes and dcfg.bucketed:
            with jax.named_scope("wire"):
                layout = dcfg.layout(grads)
                with jax.named_scope("bucketize"):
                    gb = bucketing.bucketize(layout, grads)
                    if k_local > 1:
                        # fold in the locally-accumulated gradients since
                        # last sync
                        gb = (art.acc[0] + gb) / k_local
                        art = art._replace(acc=jnp.zeros_like(art.acc))
                out = artemis_aggregate_bucketed(dcfg, art, gb, layout,
                                                 n_workers, wid)
                (agg_b, art, obs) = out if telem else out + (None,)
                with jax.named_scope("unbucketize"):
                    agg = bucketing.unbucketize(layout, agg_b, like=grads)
        else:
            if k_local > 1:
                grads = jax.tree.map(lambda a, g: (a[0] + g) / k_local,
                                     art.acc, grads)
                art = art._replace(acc=jax.tree.map(
                    lambda a: jnp.zeros_like(a), art.acc))
            if dcfg is not None and dcfg.worker_axes:
                with jax.named_scope("wire"):
                    out = artemis_aggregate(dcfg, art, grads, n_workers, wid,
                                            grad_specs)
                (agg, art, obs) = out if telem else out + (None,)
            else:
                agg = grads
                art = art._replace(step=art.step + 1)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(agg, opt_state, stepno)
            params = jax.tree.map(
                lambda pp, u: (pp - u.astype(pp.dtype)).astype(pp.dtype),
                params, updates)
        return params, opt_state, art, loss, metrics, obs

    if dcfg is None or not dcfg.worker_axes:
        def step_fn(state: TrainState, batch):
            params, opt_state, art, loss, metrics, _ = sgd_core(
                state.params, state.opt_state, state.artemis, state.step,
                batch, jnp.zeros((), jnp.int32))
            return (TrainState(params, opt_state, art, state.step + 1),
                    (loss, metrics))
        return init_state, step_fn

    waxes = dcfg.worker_axes
    strides = {}
    acc = 1
    for a in reversed(waxes):
        strides[a] = acc
        acc *= sizes[a]

    def step_fn(state: TrainState, batch):
        sspec = state_specs(dcfg, state)
        bspec = jax.tree.map(lambda _: P(waxes), batch)

        def inner(st: TrainState, bt):
            wid = jnp.zeros((), jnp.int32)
            for a in waxes:
                wid = wid + jax.lax.axis_index(a) * strides[a]
            params, opt_state, art, loss, metrics, obs = sgd_core(
                st.params, st.opt_state, st.artemis, st.step, bt, wid)
            loss = jax.lax.pmean(loss, waxes)
            metrics = reduce_metrics(metrics, waxes)
            if telem:
                # totals over the worker ring (bytes moved, workers active,
                # payloads scrubbed, blowups injected this step)
                metrics = {**metrics,
                           "obs": jax.tree.map(
                               lambda x: jax.lax.psum(x, waxes), obs)}
            return (TrainState(params, opt_state, art, st.step + 1),
                    (loss, metrics))

        # check_vma=False: params/hbar stay replicated across workers by
        # construction (identically summed aggregate, shared downlink key),
        # which vma tracking cannot see; the loss and every metric (the
        # telemetry among them) leave reduced over the workers, replicated
        return jax.shard_map(inner, mesh=mesh, in_specs=(sspec, bspec),
                             out_specs=(sspec, P()),
                             axis_names=set(waxes),
                             check_vma=False)(state, batch)

    return init_state, step_fn
