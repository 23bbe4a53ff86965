"""Bucketed-wire transport tests. Each scenario runs in a subprocess with 8
fake CPU devices (XLA device count is locked at first jax init)."""
import os
import subprocess
import sys

import pytest

HELPER = os.path.join(os.path.dirname(__file__), "helpers",
                      "bucket_scenarios.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCENARIOS = [
    "ring_matches_psum",
    "ring_bitwise",
    "ef_pp_inactive_zero",
    "hlo_wire_guard",
    "bucketed_convergence",
    "fault_zero_bitwise",
    "fault_matrix",
    "codec_sparsify",
    "codec_wire_guard",
    "grad_specs_inert",
]


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_scenario(scenario):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, HELPER, scenario],
                          capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, f"\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"
    assert f"scenario {scenario}: OK" in proc.stdout
