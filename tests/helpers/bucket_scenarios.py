"""Bucketed-wire mesh scenarios (run in a subprocess with 8 fake CPU devices).

Unlike tests/helpers/dist_scenarios.py (which exercises the production
partial-manual mesh), these run on worker-only meshes, but for
``grad_specs_inert``.

Invoked by tests/test_bucketed.py as:
    python tests/helpers/bucket_scenarios.py <scenario>
Exits non-zero on assertion failure.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bucketing, dist, faults
from repro.launch import roofline
from repro.launch.mesh import make_host_mesh
from repro.models.toy import ToyMLP
from repro.optim import sgd

VARIANTS = list(dist.VARIANTS)


def _setup(variant="artemis", *, reduce_impl="pipelined",
           mesh_shape=(2, 2), axes=("p", "q"), p=1.0, s=3,
           bucket_bytes=4096, max_buckets=8, row=64, local_steps=1,
           error_feedback=False, fault_cfg=None,
           codec="squant", codec_kwargs=()):
    mesh = make_host_mesh(mesh_shape, axes)
    model = ToyMLP(n_layers=4, d=64)
    params = model.init(jax.random.PRNGKey(0))
    dcfg = dist.DistConfig(worker_axes=axes, variant=variant, s=s,
                           p_participation=p, reduce_impl=reduce_impl, bucket_bytes=bucket_bytes,
                           max_buckets=max_buckets, bucket_row=row,
                           local_steps=local_steps,
                           error_feedback=error_feedback, faults=fault_cfg,
                           codec=codec, codec_kwargs=tuple(codec_kwargs))
    init_state, step_fn = dist.make_train_step(model, sgd(0.05), dcfg, mesh)
    batch = model.batch(jax.random.PRNGKey(1), n=32)
    return mesh, model, params, dcfg, init_state, step_fn, batch


def _run(variant, steps=3, **kw):
    _, _, params, _, init_state, step_fn, batch = _setup(variant, **kw)
    state = init_state(params)
    jstep = jax.jit(step_fn)
    loss = None
    for _ in range(steps):
        state, (loss, _) = jstep(state, batch)
    return state, float(loss)


def scenario_ring_matches_psum():
    """Satellite: every variant's pipelined bucketed ring == jax.lax.psum of
    the dequantized payloads (the dense reference) to 1e-5 on a 2x2 mesh."""
    for variant in VARIANTS:
        out = {}
        for impl in ("pipelined", "psum"):
            state, loss = _run(variant, reduce_impl=impl)
            out[impl] = (jax.tree.map(np.asarray, state.params), loss)
        for pl, ps in zip(jax.tree.leaves(out["pipelined"][0]),
                          jax.tree.leaves(out["psum"][0])):
            np.testing.assert_allclose(pl, ps, atol=1e-5, err_msg=variant)
        assert abs(out["pipelined"][1] - out["psum"][1]) < 1e-5, variant


def scenario_ring_bitwise():
    """The pipelined scan ring (``bucket_ring_reduce``) matches the
    unpipelined reference (``bucket_ring_reduce_sequential``) BIT-FOR-BIT
    on the same payloads over 8 workers, inside a worker shard_map — both
    multi-bucket and the degenerate buckets=1 / bucket_bytes=inf layout."""
    from jax.sharding import PartitionSpec as P

    from repro.core import codec as wire
    n = 8
    mesh = make_host_mesh((n,), ("pod",))
    params = ToyMLP(n_layers=4, d=64).init(jax.random.PRNGKey(0))
    grids = [dict(bucket_bytes=4096, max_buckets=8),        # multi-bucket
             dict(bucket_bytes=1 << 40, max_buckets=1)]     # B=1, elems=all
    for kw in grids:
        lay = bucketing.make_layout(params, row=64, **kw)
        assert (lay.n_buckets > 1) == (kw["max_buckets"] > 1), lay
        wc = wire.make_codec("row_squant", lay.row, s=3)
        grads = jax.random.normal(jax.random.PRNGKey(1), (n,) + lay.shape)

        def both(g):
            key = jax.random.fold_in(jax.random.PRNGKey(2),
                                     jax.lax.axis_index("pod"))
            enc = bucketing.encode_buckets(wc, key, g[0])
            return (dist.bucket_ring_reduce(wc, enc, ("pod",), n)[None],
                    dist.bucket_ring_reduce_sequential(wc, enc, ("pod",),
                                                       n)[None],
                    dist.payload_decode(wc, enc)[None])

        ring, seq, dec = jax.jit(jax.shard_map(
            both, mesh=mesh, in_specs=P("pod"),
            out_specs=(P("pod"),) * 3, check_vma=False))(grads)
        ring, seq, dec = np.asarray(ring), np.asarray(seq), np.asarray(dec)
        assert np.any(ring != 0), kw
        np.testing.assert_array_equal(ring, seq, err_msg=str(kw))
        # the documented order: own payload first, then w-1, w-2, ...
        for w in range(n):
            want = dec[w]
            for k in range(1, n):
                want = want + dec[(w - k) % n]
            np.testing.assert_array_equal(ring[w], want, err_msg=str(kw))


def scenario_ef_pp_inactive_zero():
    """Satellite (EF + PP2 leak fix): a round where every worker is inactive
    must change params by EXACTLY zero and leave the EF buffers untouched —
    previously the inactive worker's e kept riding the compressed uplink
    (the fix is `scale *= active`)."""
    _, _, params, _, init_state, step_fn, batch = _setup("dore", p=1e-9)
    state = init_state(params)
    e0 = jax.tree.map(lambda e: jnp.full_like(e, 0.3), state.artemis.e)
    state = state._replace(artemis=state.artemis._replace(e=e0))
    new, (loss, _) = jax.jit(step_fn)(state, batch)
    assert np.isfinite(loss)
    for p0, p1 in zip(jax.tree.leaves(state.params),
                      jax.tree.leaves(new.params)):
        np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    for a, b in zip(jax.tree.leaves(e0), jax.tree.leaves(new.artemis.e)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(state.artemis.h),
                    jax.tree.leaves(new.artemis.h)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def scenario_hlo_wire_guard():
    """Satellite (CI wire-format guard): lower the bucketed train step on a
    4-worker mesh and pin the s8 collective-permute bytes to the roofline
    model within 10%."""
    mesh, model, params, dcfg, init_state, step_fn, batch = _setup(
        "artemis", mesh_shape=(4,), axes=("pod",))
    state = init_state(params)
    hlo = jax.jit(step_fn).lower(state, batch).compile().as_text()
    lay = dcfg.layout(params)
    model_b = roofline.bucketed_wire_model(
        n_workers=4, n_buckets=lay.n_buckets, rows=lay.rows, row=lay.row)
    res = roofline.wire_bytes_match(hlo, model_b)
    assert res["ok"], res
    # scales ride as f32 — present but small next to the s8 payload
    assert 0 < res["measured_scale_f32"] < res["measured_s8"], res


def scenario_bucketed_convergence():
    """All variants train finite on the bucketed wire; artemis converges;
    dore engages its EF buffer; the bucketed local (non-communicating) step
    compiles to ZERO collectives."""
    for variant in VARIANTS:
        state, loss = _run(variant, steps=1)
        assert np.isfinite(loss), variant

    _, _, params, _, init_state, step_fn, batch = _setup("artemis")
    state = init_state(params)
    jstep = jax.jit(step_fn)
    losses = []
    for _ in range(10):
        state, (loss, _) = jstep(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
    assert float(jnp.sum(jnp.square(state.artemis.h))) > 0

    state, _ = _run("dore", steps=5)
    assert float(jnp.sum(jnp.square(state.artemis.e))) > 0, "EF never engaged"

    mesh, model, params, dcfg, init_state, _, batch = _setup(
        "artemis", local_steps=4)
    local_fn = dist.make_local_step(model, dcfg, mesh)
    state = init_state(params)
    hlo = jax.jit(local_fn).lower(state, batch).compile().as_text()
    colls = re.findall(r"(all-reduce|all-gather|collective-permute|"
                       r"reduce-scatter|all-to-all)\(", hlo)
    assert not colls, f"bucketed local step must not communicate: {colls[:5]}"


def scenario_fault_zero_bitwise():
    """Zero-fault identity on the mesh backend: DistConfig(faults=
    FaultConfig()) — every rate zero, defenses off — produces bit-identical
    trajectories to faults=None.  The fault paths are all statically gated
    and their PRNG streams salted, so the config's mere presence must not
    move a bit."""
    out = {}
    for fc in (None, faults.FaultConfig()):
        state, loss = _run("artemis", p=0.5, fault_cfg=fc)
        out[fc is None] = (jax.tree.map(np.asarray, state.params), loss)
    for a, b in zip(jax.tree.leaves(out[True][0]),
                    jax.tree.leaves(out[False][0])):
        np.testing.assert_array_equal(a, b)
    assert out[True][1] == out[False][1]


def scenario_fault_matrix():
    """Fault matrix: wire bit-flips, NaN gradient blowups, and a straggler
    burst over sticky Markov participation — each with server scrubbing on
    — must keep training finite (corrupt => inactive via the PP2 zero-scale
    path)."""
    matrix = {
        "bitflip": faults.FaultConfig(bitflip_rate=0.02, scrub=True),
        "nan_blowup": faults.FaultConfig(blowup_rate=0.5, scrub=True),
        "dropout_burst": faults.FaultConfig(straggler_rate=0.5, p_stay=0.8,
                                            scrub=True),
    }
    for name, fc in matrix.items():
        state, loss = _run("artemis", p=0.5, steps=4, fault_cfg=fc)
        assert np.isfinite(loss), (name, loss)
        for leaf in jax.tree.leaves(state.params):
            assert np.all(np.isfinite(np.asarray(leaf))), name


def scenario_codec_sparsify():
    """Tentpole: a non-quantizer codec rides the SAME bucketed transport.
    ``codec="sparsify"`` ships (int32 indices, f32 values) payloads through
    the pipelined ring — training stays finite and converges, the pipelined
    ring matches psum of the decoded payloads, and the EF variant engages."""
    kw = dict(codec="sparsify", codec_kwargs=(("q", 0.5),))
    out = {}
    for impl in ("pipelined", "psum"):
        state, loss = _run("artemis", reduce_impl=impl, **kw)
        out[impl] = (jax.tree.map(np.asarray, state.params), loss)
        assert np.isfinite(loss), impl
    for pl, ps in zip(jax.tree.leaves(out["pipelined"][0]),
                      jax.tree.leaves(out["psum"][0])):
        np.testing.assert_allclose(pl, ps, atol=1e-5)

    _, _, params, _, init_state, step_fn, batch = _setup("artemis", **kw)
    state = init_state(params)
    jstep = jax.jit(step_fn)
    losses = []
    for _ in range(10):
        state, (loss, _) = jstep(state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses

    state, loss = _run("dore", steps=4, **kw)
    assert np.isfinite(loss)
    assert float(jnp.sum(jnp.square(state.artemis.e))) > 0, "EF never engaged"


def scenario_codec_wire_guard():
    """Tentpole (roofline from wire_bytes): for BOTH registered mesh codecs,
    lower the bucketed step on a 4-worker mesh and check every payload dtype's
    collective-permute bytes against the codec-derived roofline model."""
    from repro.core import codec as wire
    for name, kwargs in (("squant", (("s", 3),)), ("sparsify", (("q", 0.5),))):
        mesh, model, params, dcfg, init_state, step_fn, batch = _setup(
            "artemis", mesh_shape=(4,), axes=("pod",),
            codec=name, codec_kwargs=kwargs)
        state = init_state(params)
        hlo = jax.jit(step_fn).lower(state, batch).compile().as_text()
        lay = dcfg.layout(params)
        wc = dcfg.wire_codec(lay.row)
        model_b = roofline.bucketed_wire_model(
            n_workers=4, n_buckets=lay.n_buckets, rows=lay.rows, row=lay.row,
            codec=wc)
        res = roofline.wire_bytes_match(hlo, model_b)
        assert res["ok"], (name, res)


def scenario_obs_wire_telemetry():
    """Observability (repro.obs): DistConfig(telemetry=True) attaches a
    psum'd ``obs`` dict to the step metrics whose per-worker wire_bytes
    matches the codec-derived roofline model EXACTLY on the pipelined ring;
    the psum fallback reports its dense-f32 proxy; and telemetry is
    loss-neutral + absent from metrics when off.  The measured vs model
    numbers round-trip as schema-valid ``wire`` events."""
    import dataclasses
    import tempfile

    from repro.obs import events as obs_events
    from repro.optim import sgd as _sgd

    log_path = tempfile.mktemp(suffix=".jsonl")
    with obs_events.EventLog(log_path) as log:
        for impl in ("pipelined", "psum"):
            mesh, model, params, dcfg, init_state, step_fn, batch = _setup(
                "artemis", reduce_impl=impl, mesh_shape=(4,), axes=("pod",))
            dcfg_t = dataclasses.replace(dcfg, telemetry=True)
            init_t, step_t = dist.make_train_step(model, _sgd(0.05),
                                                  dcfg_t, mesh)
            state, (loss, m) = jax.jit(step_t)(init_t(params), batch)
            assert "obs" in m, (impl, sorted(m))
            obs = {k: float(v) for k, v in m["obs"].items()}
            assert obs["mesh_active"] == 4.0, obs
            # telemetry off: no obs key, identical loss
            _, (loss0, m0) = jax.jit(step_fn)(init_state(params), batch)
            assert "obs" not in m0, impl
            assert float(loss0) == float(loss), impl
            lay = dcfg.layout(params)
            wm = roofline.bucketed_wire_model(
                n_workers=4, n_buckets=lay.n_buckets, rows=lay.rows,
                row=lay.row, codec=dcfg.wire_codec(lay.row))
            per_worker = obs["wire_bytes"] / 4.0
            log.emit("wire", wire="bucketed", reduce_impl=impl,
                     measured_bytes=per_worker,
                     model_bytes=wm["wire_bytes_per_step"])
            if impl == "psum":          # dense all-reduce proxy, not a ring
                assert per_worker > wm["wire_bytes_per_step"], impl
            else:
                assert per_worker == wm["wire_bytes_per_step"], (
                    impl, per_worker, wm["wire_bytes_per_step"])
    evs = obs_events.read_events(log_path)
    assert len(evs) == 2
    assert obs_events.validate_events(evs) == []


def scenario_grad_specs_inert():
    """``make_train_step``'s ``grad_specs`` keyword is inert: on a (2, 2, 2)
    pod/data/model mesh with a reduced starcoder2 placed by the sharding
    policy, the step built with and without it lowers to the same HLO."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import configs
    from repro.launch import mesh as M
    from repro.models.model import build_model

    mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = configs.get_config("starcoder2-7b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pshard = M.params_shardings(mesh, params)
    gspecs = jax.tree.map(lambda ns: M.strip_axes(ns.spec, ("pod",)), pshard)
    dcfg = dist.DistConfig(worker_axes=("pod",), variant="artemis", s=3)
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (8, 128), 0, cfg.vocab),
        NamedSharding(mesh, P(("pod", "data"))))
    texts = []
    with jax.set_mesh(mesh):
        placed = jax.device_put(params, pshard)
        for gs in (None, gspecs):
            init_state, step_fn = dist.make_train_step(
                model, sgd(0.05), dcfg, mesh, grad_specs=gs)
            state = init_state(placed)
            texts.append(jax.jit(step_fn).lower(
                state, {"tokens": tokens}).as_text())
    assert "collective_permute" in texts[0]
    assert texts[0] == texts[1]


if __name__ == "__main__":
    name = sys.argv[1]
    globals()[f"scenario_{name}"]()
    print(f"scenario {name}: OK")
