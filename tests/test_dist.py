"""Distributed-Artemis tests. Each scenario runs in a subprocess with 8 fake
CPU devices (XLA device count is locked at first jax init, so it cannot be
set inside this pytest process)."""
import os
import subprocess
import sys

import pytest

HELPER = os.path.join(os.path.dirname(__file__), "helpers", "dist_scenarios.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCENARIOS = [
    "convergence",
    "sgd_variant_matches_baseline",
    "baseline_state_tree",
    "all_variants_lower",
    "partial_participation",
    "int8_ring_in_hlo",
    "mesh_policy",
    "pipeline_sharding",
    "dore_and_local_steps",
    "train_main_compiles_once",
]


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_scenario(scenario):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, HELPER, scenario],
                          capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, f"\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"
    assert f"scenario {scenario}: OK" in proc.stdout
