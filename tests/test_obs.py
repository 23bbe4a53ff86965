"""repro.obs tests (DESIGN.md §11).

Pins, in order of importance:
  1. disabled telemetry is a no-op: ``telemetry=True`` vs ``False`` produce
     BITWISE-identical trajectories/bits/distances (the carry is appended,
     never mixed into the math), and ``telemetry=False`` — the default every
     pre-existing test runs under — leaves ``res.telemetry`` None;
  2. the counters mean what they claim: the bit-ledger counters reconcile
     exactly against ``res.bits``, participation counters against the
     availability draw, the error histogram against the round count, and
     rollback counts survive the sentinel's carry restore;
  3. the JSONL event log round-trips: write -> read -> validate (zero
     schema errors) -> summarize, including rollback events of a faulted
     run; the schema actually rejects malformed events;
  4. spans ledger + sink mirroring, exact per-name span totals past the
     record cap, ``train.main``'s ``train/*`` span events, and the mesh
     wire-byte reconciliation (subprocess, 8 fake CPU devices —
     tests/helpers/bucket_scenarios.py).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import artemis as art
from repro.core import faults
from repro.core import federated as fed
from repro.core import sweep as sw
from repro.obs import events, spans
from repro.obs import telemetry as T

KEY = jax.random.PRNGKey(42)
N, D = 8, 16


@pytest.fixture(scope="module")
def prob_star():
    prob, w_star = fed.make_lsr_problem(KEY, n_workers=N, n_per=50, d=D,
                                        noise=0.0)
    return prob, w_star


def _cfgs():
    plain = art.variant_config("artemis", D, N, s=1, p=1.0)
    pp = art.variant_config("artemis", D, N, s=1, p=0.5)
    return [plain, pp]


def _run(prob, cfgs, w_star=None, iters=40, eval_every=10, **kw):
    return sw.run_sweep(prob, cfgs, [0.02], [0, 1], iters=iters, batch=4,
                        eval_every=eval_every, w_star=w_star, **kw)


# ---------------------------------------------------------------------------
# 1. bitwise neutrality
# ---------------------------------------------------------------------------

def test_telemetry_off_by_default_and_none(prob_star):
    prob, w_star = prob_star
    res = _run(prob, _cfgs(), w_star)
    assert res.telemetry is None


def test_telemetry_is_bitwise_neutral(prob_star):
    """The tentpole acceptance bar: enabling telemetry changes NOTHING about
    the computation — losses, bits, distances, final iterates all bitwise
    equal to the telemetry-free program (which is itself the pre-obs
    program: the carry is statically absent when off)."""
    prob, w_star = prob_star
    cfgs = _cfgs()
    off = _run(prob, cfgs, w_star)
    on = _run(prob, cfgs, w_star, telemetry=True)
    np.testing.assert_array_equal(off.losses, on.losses)
    np.testing.assert_array_equal(off.bits, on.bits)
    np.testing.assert_array_equal(off.dists, on.dists)
    np.testing.assert_array_equal(off.w_final, on.w_final)
    assert on.telemetry is not None


def test_telemetry_neutral_under_faults_and_rollback(prob_star):
    """Same neutrality with the whole fault + sentinel machinery engaged
    (the telemetry carry must stay OUT of the rollback snapshot)."""
    prob, _ = prob_star
    fc = faults.FaultConfig(blowup_rate=0.1, blowup_value=1e15, scrub=True,
                            sentinel=1e3, backoff=0.5)
    cfg = dataclasses.replace(art.variant_config("artemis", D, N, s=1, p=0.7),
                              faults=fc)
    off = _run(prob, [cfg])
    on = _run(prob, [cfg], telemetry=True)
    np.testing.assert_array_equal(off.losses, on.losses)
    np.testing.assert_array_equal(off.w_final, on.w_final)
    np.testing.assert_array_equal(off.rollbacks, on.rollbacks)
    assert int(off.rollbacks.sum()) >= 1, "scenario never rolled back"


# ---------------------------------------------------------------------------
# 2. counter semantics
# ---------------------------------------------------------------------------

def test_bit_ledger_reconciles_exactly(prob_star):
    """uplink_bits + catchup_bits is the same ledger res.bits reports —
    counted independently inside the telemetry carry."""
    prob, w_star = prob_star
    res = _run(prob, _cfgs(), w_star, telemetry=True)
    tel = res.telemetry
    total = tel["uplink_bits"][..., -1] + tel["catchup_bits"][..., -1]
    np.testing.assert_allclose(total, res.bits[..., -1], rtol=1e-6)


def test_participation_and_hist_counts(prob_star):
    prob, w_star = prob_star
    iters = 40
    res = _run(prob, _cfgs(), w_star, iters=iters, telemetry=True)
    tel = res.telemetry
    # full participation: every worker available & active every round
    assert np.all(tel["avail"][0, ..., -1] == N * iters)
    assert np.all(tel["active"][0, ..., -1] == N * iters)
    # p=0.5 cell: strictly fewer, and avail == active (no faults configured)
    assert np.all(tel["avail"][1, ..., -1] < N * iters)
    np.testing.assert_array_equal(tel["avail"][1], tel["active"][1])
    # one histogram observation per round, cumulative across eval points
    hist = tel["err_up_hist"]
    np.testing.assert_allclose(hist[..., -1, :].sum(axis=-1), iters)
    # counters are monotone in the eval axis
    assert np.all(np.diff(tel["uplink_bits"], axis=-1) >= 0)


def test_rollback_counter_survives_restore(prob_star):
    """The sentinel restores the pre-divergence carry; the telemetry carry
    is outside that snapshot, so the rollback count (and the fault counters
    that caused it) persist."""
    prob, _ = prob_star
    fc = faults.FaultConfig(blowup_rate=0.1, blowup_value=1e15, scrub=True,
                            sentinel=1e3, backoff=0.5)
    cfg = dataclasses.replace(art.variant_config("artemis", D, N, s=1, p=0.7),
                              faults=fc)
    res = _run(prob, [cfg], telemetry=True)
    tel = res.telemetry
    rb = res.rollbacks[0]
    assert int(rb.sum()) >= 1
    np.testing.assert_array_equal(tel["rollbacks"][0, ..., -1], rb)
    assert np.all(tel["blowup_hits"][0, ..., -1] >= 1)


def test_memory_drift_shrinks_noiseless(prob_star):
    """Noiseless LSR: h_i -> grad F_i(w*), so the paper's memory-drift term
    must shrink over training (this is the quantity behind the linear-rate
    threshold — the reason the gauge exists)."""
    prob, w_star = prob_star
    res = _run(prob, _cfgs(), w_star, iters=200, eval_every=50,
               telemetry=True)
    drift = res.telemetry["mem_drift"][0, 0, 0]
    assert drift[-1] < 0.5 * drift[0], drift


# ---------------------------------------------------------------------------
# 3. JSONL round-trip + schema
# ---------------------------------------------------------------------------

def test_events_roundtrip_faulted_sweep(prob_star, tmp_path):
    prob, _ = prob_star
    fc = faults.FaultConfig(blowup_rate=0.1, blowup_value=1e15, scrub=True,
                            sentinel=1e3, backoff=0.5)
    cfg = dataclasses.replace(art.variant_config("artemis", D, N, s=1, p=0.7),
                              faults=fc)
    res = _run(prob, [cfg], telemetry=True)
    path = str(tmp_path / "events.jsonl")
    with events.EventLog(path) as log:
        log.start(config={"iters": 40}, fingerprint="test")
        n = events.record_sweep(log, res, cfgs=[cfg])
        log.end(status="ok", wall_s=0.0)
    assert n >= res.losses.size
    evs = events.read_events(path)
    assert events.validate_events(evs) == []
    s = events.summarize(evs)
    assert s["schema_errors"] == [] and s["status"] == "ok"
    # the faulted run's rollbacks surfaced as first-class events
    assert s["rollbacks"] == int(res.rollbacks.sum()) >= 1
    # per-cell final numbers match the arrays they came from
    for (v, g, sd), cell in ((tuple(map(int, k.split("/"))), c)
                             for k, c in s["cells"].items()):
        assert cell["loss"] == float(res.losses[v, g, sd, -1])
        assert cell["metrics"]["rollbacks"] == float(
            res.telemetry["rollbacks"][v, g, sd, -1])


def test_event_schema_rejects_malformed(tmp_path):
    log = events.EventLog(str(tmp_path / "e.jsonl"))
    with pytest.raises(ValueError, match="unknown event type"):
        log.emit("nonsense", x=1)
    with pytest.raises(ValueError, match="missing required field"):
        log.emit("eval", cell={}, iter=0, loss=1.0, bits=0.0)  # no dist
    with pytest.raises(ValueError, match="not in the catalogue"):
        log.emit("eval", cell={}, iter=0, loss=1.0, bits=0.0, dist=0.0,
                 metrics={"no_such_metric": 1.0})
    with pytest.raises(ValueError, match="must be a list"):
        log.emit("eval", cell={}, iter=0, loss=1.0, bits=0.0, dist=0.0,
                 metrics={"err_up_hist": 3.0})
    log.close()


def test_catalogue_is_closed_registry():
    names = {m.name for m in T.catalogue()}
    assert set(T.SWEEP_METRICS) <= names and set(T.MESH_METRICS) <= names
    with pytest.raises(ValueError, match="already registered differently"):
        T.register(T.Metric("err_up", "counter", "conflicting redefinition"))


# ---------------------------------------------------------------------------
# 4. spans + sink, train loop spans, mesh wire telemetry (subprocess)
# ---------------------------------------------------------------------------

def test_spans_ledger_and_sink(tmp_path):
    spans.reset()
    path = str(tmp_path / "s.jsonl")
    with events.EventLog(path) as log:
        spans.install_sink(log)
        try:
            with spans.span("outer"):
                with spans.span("inner"):
                    pass
        finally:
            spans.uninstall_sink()
    recs = spans.records()
    assert [r.name for r in recs[-2:]] == ["inner", "outer"]
    assert recs[-2].depth == 1 and recs[-1].depth == 0
    assert spans.total("outer") >= spans.total("inner") >= 0.0
    evs = events.read_events(path)
    assert [e["name"] for e in evs] == ["inner", "outer"]
    assert events.validate_events(evs) == []
    agg = {a["name"]: a for a in spans.summarize_spans(recs[-2:])}
    assert agg["outer"]["count"] == 1


def test_span_totals_exact_past_the_record_cap():
    """The record list drops its oldest half when full; the per-name count
    and total that ``total`` and ``summarize_spans()`` read do not."""
    spans.reset()
    n = spans._MAX_SPANS + 904
    for _ in range(n):
        with spans.span("tick"):
            pass
    assert len(spans.records()) < n
    agg, = spans.summarize_spans()
    assert agg["name"] == "tick" and agg["count"] == n
    assert agg["total_s"] == pytest.approx(spans.total("tick"))
    assert agg["total_s"] >= sum(r.dur_s for r in spans.records())
    assert agg["mean_s"] == pytest.approx(agg["total_s"] / n)
    spans.reset()
    assert spans.summarize_spans() == [] and spans.total("tick") == 0.0


def test_train_main_writes_loop_span_events(tmp_path, monkeypatch):
    """``train.main`` (one CPU device, 5 steps, loss read every 2) writes
    one ``span`` event per name, counted over the whole loop: an input
    batch per step, a dispatch per step after the compiling first one, and
    a loss read-back at steps 0, 2, 4."""
    from repro.launch import train
    # a cache directory named in the environment is JAX's to read at start:
    # main then leaves this process's compile cache as it is
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    path = str(tmp_path / "events.jsonl")
    train.main(["--arch", "whisper-tiny", "--reduced", "--dist", "artemis",
                "--steps", "5", "--batch", "2", "--seq", "16",
                "--log-every", "2", "--events", path])
    evs = events.read_events(path)
    assert events.validate_events(evs) == []
    got = {e["name"]: e for e in evs if e["ev"] == "span"}
    assert {n: got[n]["count"] for n in got} == {
        "train/batch": 5, "train/dispatch": 4, "train/loss_fetch": 3,
        "train/compile+first_step": 1, "train/steady_steps": 1}
    assert all(e["dur_s"] > 0 for e in got.values())
    s = events.summarize(evs)["spans"]
    assert s["train/batch"] == {"count": 5,
                                "total_s": got["train/batch"]["dur_s"]}


def test_mesh_wire_telemetry_subprocess():
    """wire_bytes matches the codec-derived roofline model on both mesh
    wires (8 fake CPU devices; see scenario_obs_wire_telemetry)."""
    helper = os.path.join(os.path.dirname(__file__), "helpers",
                          "bucket_scenarios.py")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, helper, "obs_wire_telemetry"],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, \
        f"\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"
    assert "scenario obs_wire_telemetry: OK" in proc.stdout


# ---------------------------------------------------------------------------
# CLI: summarize / validate / dashboard / bench round-trip
# ---------------------------------------------------------------------------

def test_cli_validate_and_summary(prob_star, tmp_path):
    prob, w_star = prob_star
    res = _run(prob, _cfgs(), w_star, telemetry=True)
    path = str(tmp_path / "events.jsonl")
    with events.EventLog(path) as log:
        log.start(config={}, fingerprint="cli-test")
        events.record_sweep(log, res, cfgs=_cfgs())
        log.end(status="ok", wall_s=1.0)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    for args in (["validate", path], ["summary", path, "--json"],
                 ["dashboard", path, "-o", str(tmp_path / "dash.md")]):
        proc = subprocess.run([sys.executable, "-m", "repro.obs", *args],
                              capture_output=True, text=True, timeout=300,
                              env=env)
        assert proc.returncode == 0, (args, proc.stdout, proc.stderr[-2000:])
    dash = open(tmp_path / "dash.md").read()
    assert "bits" in dash and "loss" in dash
    s = json.loads(subprocess.run(
        [sys.executable, "-m", "repro.obs", "summary", path, "--json"],
        capture_output=True, text=True, env=env).stdout)
    assert s["schema_errors"] == [] and s["cells"]
