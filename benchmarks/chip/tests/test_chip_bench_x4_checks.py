"""The train comparison on four workers (b8's mix on a 4x1 mesh, as a
four-chip cell would run it), driven end to end on four virtual CPU
devices at a test size with b8's limits (``chip_cells``): a sound run is
correct; with the exchange between the workers left out (each worker's
ring returns its own payload alone) it is not."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))

SCRIPT = """
import json
import chip_cells as cells
from harness import spec
from repro.core import dist

driver = spec.load_driver("train")
cell = cells.train_cell("train.whisper-tiny.b8", workers=4, mesh="4x1")
out = {"sound": driver.run(cells.ctx(cell))}
dist.bucket_ring_reduce = (
    lambda codec, payload, axes, n, **kw: dist.payload_decode(codec, payload))
out["alone"] = driver.run(cells.ctx(cell))
print(json.dumps({k: {"correct": r["correct"], "checks": r["checks"],
                      "chips": r["rec"]["n_workers"]}
                  for k, r in out.items()}))
"""


def test_four_worker_run_correct_and_exchange_left_out_not():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [HERE, os.path.dirname(HERE), os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["sound"]["chips"] == 4
    assert res["sound"]["correct"], res["sound"]["checks"]
    assert not res["alone"]["correct"], res["alone"]["checks"]
