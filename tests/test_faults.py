"""Fault-injection + self-healing tests (core/faults.py, DESIGN.md §8).

Pins, in order of importance:
  1. the zero-fault identity: an all-off ``FaultConfig`` is bitwise
     invisible — identical trajectories to ``faults=None`` on both the
     dense and Pallas sweep backends;
  2. the Markov availability chain reduces bitwise to the paper's i.i.d.
     Bernoulli sampling at ``p_stay = p`` and matches its stationary
     moments (mean p, lag-1 autocorrelation (p_stay-p)/(1-p)) otherwise;
  3. the defenses actually heal: scrubbing keeps NaN-blowup and bit-flip
     runs finite, the divergence sentinel rolls back and backs off;
  4. resumable sweeps restart bitwise mid-grid from a checkpoint, without
     retracing, and refuse foreign checkpoints;
  5. checkpointer saves are atomic and restores validate up front.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers.prop import given, settings, st

from repro.checkpoint import checkpointer
from repro.core import artemis as art
from repro.core import codec
from repro.core import faults
from repro.core import federated as fed
from repro.core import sweep as sw
from repro.kernels import ops

KEY = jax.random.PRNGKey(42)
N, D = 8, 16
BACKENDS = ["dense", "pallas"]


@pytest.fixture(scope="module")
def prob():
    p, _ = fed.make_lsr_problem(KEY, n_workers=N, n_per=50, d=D, noise=0.3)
    return p


def _cfg(fc=None, variant="artemis", p=0.7, s=1):
    cfg = art.variant_config(variant, D, N, s=s, p=p)
    return dataclasses.replace(cfg, faults=fc)


def _run(prob, cfg, iters=40, backend=None, **kw):
    return sw.run_sweep(prob, [cfg], [0.02], [0], iters=iters, batch=4,
                        backend=backend, **kw)


# ---------------------------------------------------------------------------
# 1. zero-fault identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_zero_fault_config_is_bitwise_identity(prob, backend):
    """FaultConfig() must not move a single bit vs faults=None: every fault
    branch is statically gated, and fault PRNG streams are salted side
    streams that are never drawn when rates are zero."""
    base = _run(prob, _cfg(None), backend=backend)
    zero = _run(prob, _cfg(faults.FaultConfig()), backend=backend)
    assert np.array_equal(base.losses, zero.losses)
    assert np.array_equal(base.bits, zero.bits)
    assert np.array_equal(base.dists, zero.dists)
    assert np.array_equal(base.w_final, zero.w_final)
    assert np.all(zero.rollbacks == 0) and np.all(zero.gamma_scale == 1.0)


@pytest.mark.parametrize("backend", BACKENDS)
def test_markov_p_stay_equals_p_is_bitwise_iid(prob, backend):
    """p_stay = p makes both Markov transition rows equal p, and the chain
    consumes the SAME uniform draw as the i.i.d. mask — bit-for-bit."""
    base = _run(prob, _cfg(None), backend=backend)
    mkv = _run(prob, _cfg(faults.FaultConfig(p_stay=0.7)), backend=backend)
    assert np.array_equal(base.losses, mkv.losses)
    assert np.array_equal(base.bits, mkv.bits)
    assert np.array_equal(base.w_final, mkv.w_final)


# ---------------------------------------------------------------------------
# 2. Markov availability moments
# ---------------------------------------------------------------------------

def _simulate_chain(fc, p, rounds, workers, seed=7):
    u = jax.random.uniform(jax.random.PRNGKey(seed), (rounds, workers))

    def step(prev, inp):
        k, uk = inp
        part = faults.participation(fc, p, uk, prev, k)
        return part, part

    _, series = jax.lax.scan(step, jnp.zeros((workers,)),
                             (jnp.arange(rounds), u))
    return np.asarray(series)


@given(st.floats(0.55, 0.95))
@settings(max_examples=5, deadline=None)
def test_markov_stationary_moments(p_stay):
    """Seeded moment check: stationary mean == p and lag-1 autocorrelation
    == (p_stay - p)/(1 - p), the closed form markov_autocorr() reports."""
    p = 0.5
    fc = faults.FaultConfig(p_stay=p_stay)
    x = _simulate_chain(fc, p, rounds=2000, workers=64)
    x = x[100:]                                   # burn-in to stationarity
    assert abs(x.mean() - p) < 0.02
    a, b = np.ravel(x[1:]), np.ravel(x[:-1])
    rho = np.corrcoef(a, b)[0, 1]
    want = faults.markov_autocorr(fc, p)
    assert want == pytest.approx((p_stay - p) / (1.0 - p))
    assert abs(rho - want) < 0.05


def test_markov_infeasible_chain_raises(prob):
    """p close to 1 with a sticky-off chain needs P(0->1) > 1: reject at
    config-build time, not with silent clamping inside the trace."""
    fc = faults.FaultConfig(p_stay=0.1)
    with pytest.raises(ValueError, match="infeasible"):
        faults.markov_rates(fc, 0.9)
    with pytest.raises(ValueError, match="infeasible"):
        _run(prob, _cfg(fc, p=0.9))


def test_fault_config_validation():
    with pytest.raises(ValueError):
        faults.FaultConfig(bitflip_rate=1.5)
    with pytest.raises(ValueError):
        faults.FaultConfig(p_stay=-0.1)
    with pytest.raises(ValueError):
        faults.FaultConfig(backoff=0.0)
    assert not faults.FaultConfig().enabled
    assert faults.FaultConfig(scrub=True).enabled


# ---------------------------------------------------------------------------
# 3. defenses heal injected faults
# ---------------------------------------------------------------------------

def test_straggler_drops_meter_fewer_bits(prob):
    """Stragglers never upload, so the metered uplink bits shrink while the
    run stays finite (they are just extra non-participants to PP2)."""
    base = _run(prob, _cfg(None))
    slow = _run(prob, _cfg(faults.FaultConfig(straggler_rate=0.5)))
    assert np.all(np.isfinite(slow.losses))
    assert slow.bits[0, 0, 0, -1] < base.bits[0, 0, 0, -1]


@pytest.mark.parametrize("backend", BACKENDS)
def test_nan_blowup_scrub_recovers(prob, backend):
    """NaN gradient blowups poison the unprotected dense run; with scrubbing
    the blown-up worker is masked inactive (PP2 zero-scale) and the sweep
    still converges.  (The Pallas wire survives even unprotected: its
    encode kernel clamps all-NaN tiles to scale 0, so the poisoned payload
    already decodes to zero — pinned separately below.)"""
    fc_bad = faults.FaultConfig(blowup_rate=0.25)
    bad = _run(prob, _cfg(fc_bad), backend=backend)
    if backend == "dense":
        assert not np.isfinite(bad.losses[0, 0, 0, -1])
    else:
        assert np.all(np.isfinite(bad.losses))

    fc_ok = faults.FaultConfig(blowup_rate=0.25, scrub=True)
    ok = _run(prob, _cfg(fc_ok), backend=backend)
    assert np.all(np.isfinite(ok.losses))
    assert ok.losses[0, 0, 0, -1] < ok.losses[0, 0, 0, 0]


@pytest.mark.parametrize("backend", BACKENDS)
def test_bitflip_scrub_sentinel_keeps_run_finite(prob, backend):
    """Wire bit-flips produce NaN/Inf scales (scrubbed as corrupt payloads)
    and occasionally huge-but-finite ones (caught by the divergence
    sentinel); together the run stays finite and converging."""
    fc = faults.FaultConfig(bitflip_rate=0.05, scrub=True, sentinel=1e4,
                            backoff=0.5)
    res = _run(prob, _cfg(fc), backend=backend)
    assert np.all(np.isfinite(res.losses))
    assert res.losses[0, 0, 0, -1] < res.losses[0, 0, 0, 0]


def test_sentinel_rolls_back_and_backs_off(prob):
    """Large finite blowups sail past the finite-scrubber by design; the
    sentinel catches them at the next eval, restores the last good carry,
    and shrinks gamma geometrically.  (1e15, not 1e30: a value whose square
    overflows f32 turns the payload non-finite and the scrubber would
    swallow it before the sentinel ever sees a bad loss.)"""
    fc = faults.FaultConfig(blowup_rate=0.1, blowup_value=1e15, scrub=True,
                            sentinel=1e3, backoff=0.5)
    res = _run(prob, _cfg(fc))
    assert np.all(np.isfinite(res.losses))
    rb = int(res.rollbacks[0, 0, 0])
    assert rb >= 1
    gs = float(res.gamma_scale[0, 0, 0])
    assert gs <= 0.5 ** 1 and gs == pytest.approx(0.5 ** rb)


def test_wire_scrubbed_stat_reported():
    """artemis_round reports how many payloads the server dropped."""
    cfg = _cfg(faults.FaultConfig(scrub=True), p=1.0)
    st0 = art.init_state(cfg)
    g = jax.random.normal(KEY, (N, D))
    g = g.at[2].set(jnp.nan)                       # one poisoned worker
    omega, _, stats = art.artemis_round(cfg, st0, g, KEY,
                                        jnp.ones((N,)), backend="dense")
    assert np.all(np.isfinite(np.asarray(omega)))
    assert float(stats["wire_scrubbed"]) == 1.0


# ---------------------------------------------------------------------------
# 4. resumable sweeps
# ---------------------------------------------------------------------------

def test_checkpointed_sweep_is_bitwise_plain(prob, tmp_path):
    """Segmented execution (same scan body, checkpoint barriers between
    segments) returns the bit-identical result of the whole-run program."""
    plain = _run(prob, _cfg(None), iters=40, eval_every=2)
    ck = _run(prob, _cfg(None), iters=40, eval_every=2,
              checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=10)
    for f in ("losses", "bits", "dists", "w_final", "w_avg", "w_tail_avg"):
        assert np.array_equal(getattr(plain, f), getattr(ck, f)), f


def test_resume_mid_grid_is_bitwise(prob, tmp_path):
    """Kill-and-restart: rewind LATEST to an early snapshot and resume; the
    completed result is bitwise the uninterrupted run, with zero retraces
    (the segment program is already in the compile cache)."""
    ckdir = str(tmp_path / "ck")
    full = _run(prob, _cfg(None), iters=40, eval_every=2,
                checkpoint_dir=ckdir, checkpoint_every=10)
    # simulate a crash after the first segment: LATEST points at snapshot 5
    # (5 evals = 10 rounds done); the later step dirs just become garbage
    with open(os.path.join(ckdir, "LATEST"), "w") as f:
        f.write("5")
    res = _run(prob, _cfg(None), iters=40, eval_every=2,
               checkpoint_dir=ckdir, checkpoint_every=10, resume=True)
    assert res.traces == 0
    for f_ in ("losses", "bits", "dists", "w_final"):
        assert np.array_equal(getattr(full, f_), getattr(res, f_)), f_


def test_resume_refuses_foreign_checkpoint(prob, tmp_path):
    """A checkpoint from a different sweep (here: different gamma) must be
    rejected by fingerprint, not silently restored into wrong cells."""
    ckdir = str(tmp_path / "ck")
    sw.run_sweep(prob, [_cfg(None)], [0.02], [0], iters=40, batch=4,
                 eval_every=2, checkpoint_dir=ckdir, checkpoint_every=20)
    with pytest.raises(ValueError, match="different sweep"):
        sw.run_sweep(prob, [_cfg(None)], [0.05], [0], iters=40, batch=4,
                     eval_every=2, checkpoint_dir=ckdir, checkpoint_every=20,
                     resume=True)


def test_checkpoint_arg_validation(prob, tmp_path):
    cfg = _cfg(None)
    with pytest.raises(ValueError, match="requires checkpoint_dir"):
        _run(prob, cfg, resume=True)
    with pytest.raises(ValueError, match="requires checkpoint_dir"):
        _run(prob, cfg, checkpoint_every=10)
    with pytest.raises(ValueError, match="group_by_variant"):
        _run(prob, cfg, checkpoint_dir=str(tmp_path), group_by_variant=True)
    with pytest.raises(ValueError, match="multiple"):
        _run(prob, cfg, iters=40, eval_every=2, checkpoint_every=3,
             checkpoint_dir=str(tmp_path))


# ---------------------------------------------------------------------------
# 5. checkpointer: atomic saves, validating restores
# ---------------------------------------------------------------------------

def _tree():
    return {"w": jnp.arange(6, dtype=jnp.float32),
            "step": jnp.zeros((), jnp.int32)}


def test_save_leaves_no_temp_files(tmp_path):
    d = checkpointer.save(str(tmp_path), 3, _tree())
    names = []
    for root, _, files in os.walk(tmp_path):
        names += files
    assert not [n for n in names if ".tmp." in n], names
    assert os.path.exists(os.path.join(d, "arrays.npz"))
    assert checkpointer.latest_step(str(tmp_path)) == 3


def test_restore_validates_keys_shapes_dtypes(tmp_path):
    checkpointer.save(str(tmp_path), 1, _tree())
    with pytest.raises(ValueError, match="missing keys"):
        checkpointer.restore(str(tmp_path), {**_tree(), "extra": jnp.ones(2)})
    with pytest.raises(ValueError, match="unexpected keys"):
        checkpointer.restore(str(tmp_path), {"w": jnp.zeros(6)})
    with pytest.raises(ValueError, match="shape"):
        checkpointer.restore(
            str(tmp_path), {"w": jnp.zeros(7), "step": jnp.zeros((), jnp.int32)})
    with pytest.raises(ValueError, match="dtype"):
        checkpointer.restore(
            str(tmp_path), {"w": jnp.zeros(6, jnp.int32),
                            "step": jnp.zeros((), jnp.int32)})


def test_read_manifest_round_trips_extra(tmp_path):
    checkpointer.save(str(tmp_path), 2, _tree(), extra={"fingerprint": "abc"})
    man = checkpointer.read_manifest(str(tmp_path))
    assert man["extra"]["fingerprint"] == "abc"
    with pytest.raises(FileNotFoundError):
        checkpointer.read_manifest(str(tmp_path / "nope"))


# ---------------------------------------------------------------------------
# 6. NaN-scale clamp regression (kernels + dist wire)
# ---------------------------------------------------------------------------

def test_nan_tile_decodes_to_finite_zero_kernels():
    """An all-NaN tile must ship a zero scale (not NaN) so dequantize is
    exactly 0 whatever the int8 levels hold — through the Pallas kernels."""
    x = jnp.full((64,), jnp.nan)
    out = ops.compress(KEY, x, s=1)
    assert np.array_equal(np.asarray(out), np.zeros((64,), np.float32))


def test_nan_row_decodes_to_finite_zero_dist():
    x = jnp.full((4, 8), jnp.nan)
    q, scale = codec.row_squant_encode(KEY, x, 1)
    assert np.all(np.asarray(scale) == 0.0)
    out = codec.row_squant_decode(q, scale)
    assert np.array_equal(np.asarray(out), np.zeros((4, 8), np.float32))


def test_nan_tree_compress_stays_finite():
    tree = {"a": jnp.full((3, 5), jnp.nan), "b": jnp.ones((4,))}
    out = ops.tree_compress(KEY, tree, s=1)
    assert np.all(np.isfinite(np.asarray(out["a"])))
    assert np.all(np.isfinite(np.asarray(out["b"])))
