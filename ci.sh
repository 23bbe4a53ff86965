#!/usr/bin/env bash
# Minimal CI gate. Stages:
#   0. static analysis  (repro.analysis --ci: ast lint incl. the
#      tracked-bytecode hygiene rule, compile-count trace audit, HLO
#      wire/donation/host-transfer checks; fails on any unsuppressed
#      finding) + codec conformance: the wire-codec registry suite and the
#      all-codec HLO wire-format guard (roofline wire_bytes vs measured
#      collective-permute bytes per dtype)
#   1. fast test tier   (tier-1: pytest default set, < 2 min budget)
#   2. slow test tier   (model-zoo smoke, XLA-compile bound)
#   3. benchmark smoke  (one grid cell per suite, printed as CSV rows; an
#      API-rot smoke on the CPU that writes no file and gates no number)
#   4. fault matrix     (self-healing smoke: inject NaN blowups / huge
#      finite blowups / wire bit-flips, assert scrubbing + sentinel recover)
#   5. observability    (instrumented sweep smoke: schema-valid JSONL event
#      log, Perfetto trace artifact, markdown dashboard — repro.obs)
set -euo pipefail
cd "$(dirname "$0")"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "=== stage 0: static analysis + codec conformance ==="
python -m repro.analysis --ci
python -m pytest -x -q tests/test_codec.py
python tests/helpers/bucket_scenarios.py codec_wire_guard

echo "=== stage 1: fast tests ==="
python -m pytest -x -q

echo "=== stage 2: slow tests (model zoo) ==="
python -m pytest -x -q -m slow

echo "=== stage 3: benchmark smoke (--fast) ==="
python benchmarks/run.py --fast

echo "=== stage 4: fault-matrix smoke ==="
python benchmarks/fault_bench.py --matrix

echo "=== stage 5: observability smoke ==="
# instrumented sweep: JSONL events + Perfetto trace + dashboard, then
# validate the log
python -m repro.obs smoke -o /tmp/repro_obs_ci
python -m repro.obs validate /tmp/repro_obs_ci/events.jsonl
echo "CI OK"
