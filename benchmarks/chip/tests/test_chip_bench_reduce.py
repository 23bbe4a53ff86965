"""The yardstick's arithmetic on the CPU: the trace reduction against a
small hand-made trace, the HLO collective counter against a small HLO and
against the program's own wire telemetry, and the model FLOPs and kernel
bytes against hand-computed whisper-tiny numbers."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)

from harness import flops, hlo, spec  # noqa: E402
from harness import trace as TR  # noqa: E402

MS = 1e-3


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", "small_trace.pbtxt")) as f:
        pd = ProfileData.from_text_proto(f.read())
    with open(os.path.join(HERE, "data", "small_hlo.txt")) as f:
        scopes = TR.op_names(f.read())
    return TR.reduce_profile(pd, scopes)


def test_window_and_busy(reduced):
    assert reduced.window_s == pytest.approx(13 * MS)
    assert len(reduced.devices) == 2
    # device 0: [1, 3] + [5, 9] (a while holding two ops) + [11, 12] ms
    assert TR.busy_s(reduced.devices[0]) == pytest.approx(7 * MS)
    assert TR.busy_s(reduced.devices[1]) == pytest.approx(10 * MS)
    assert TR.mean_busy_s(reduced) == pytest.approx(8.5 * MS)


def test_scopes_and_self_time(reduced):
    d0 = reduced.devices[0]
    assert TR.scope_s(d0, "bucket_acc") == pytest.approx(1 * MS)
    assert TR.scope_s(d0, "ring_hop") == pytest.approx(2 * MS)
    assert TR.mean_scope_s(reduced, "bucket_acc") == pytest.approx(0.5 * MS)
    assert TR.count_ops(d0, "bucket_acc", "bucket_acc") == 1
    loop = next(o for o in d0 if o.name == "while.2")
    assert loop.self_s == pytest.approx(2 * MS)
    top = TR.top_ops(reduced, k=2)
    assert top[0][0].startswith("fusion.7") and top[0][1] == pytest.approx(
        5 * MS)


def test_idle_gaps_are_labelled_by_host_spans(reduced):
    gaps = TR.idle_gaps(reduced)
    assert [g[0] for g in gaps[:2]] == ["bench/batch", "bench/batch"]
    assert [g[1] for g in gaps] == pytest.approx([2 * MS, 2 * MS, 1.5 * MS,
                                                  0.5 * MS])
    assert gaps[2][0] == "host"


def test_hlo_counter_multiplies_loop_trips():
    with open(os.path.join(HERE, "data", "small_hlo.txt")) as f:
        got = hlo.collective_bytes(f.read())
    # s8[16] in a loop of known trip count 3, a started f32[4] permute, and
    # f32[2] in a loop counted by its condition (i < 2); two f32 sums
    assert got["collective-permute"] == 3 * 16 + 16 + 2 * 8
    assert got["all-reduce"] == 8
    assert sum(got.values()) == 88


def test_whisper_tiny_train_flops():
    cfg = spec.load_json(os.path.join(CHIP, "configs", "whisper-tiny.json"))
    d, f, s, ff, v = 384, 1500, 448, 1536, 51865
    front = 2 * f * d * d
    enc = 4 * (8 * f * d * d + 4 * f * f * d + 4 * f * d * ff)
    dec = 4 * (8 * s * d * d + 4 * s * s * d + 4 * s * d * d
               + 4 * f * d * d + 4 * s * f * d + 4 * s * d * ff)
    head = 2 * s * d * v
    per_row = 2 * front + 3 * (enc + dec + head)
    assert per_row == 208_491_134_976
    assert flops.encdec_train_flops(cfg, 8, 448) == 8 * per_row


def test_bucket_acc_bytes_at_whisper_tiny():
    d, ff, v = 384, 1536, 51865
    enc = 4 * (2 * d + 4 * d * d + 2 * d * ff)
    dec = 4 * (3 * d + 8 * d * d + 2 * d * ff)
    total = 2 * v * d + enc + dec + 2 * d + d * d
    assert total == 56_503_296
    # 16 equal buckets of whole 256-wide rows
    assert flops.bucket_layout(total) == (16, 13_795, 256)
    # read f32 acc + int8 levels + f32 row scales, write f32 acc
    assert flops.bucket_acc_bytes(16, 13_795, 256) == (
        9 * 56_504_320 + 4 * 16 * 13_795) == 509_421_760


SCENARIO = r'''
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1]]
import jax
from repro import configs
from repro.core import dist
from repro.launch import train
from repro.optim import adam
from harness import hlo
cfg = configs.get_config("whisper-tiny", reduced=True)
mesh = train.make_mesh("4x1")
dcfg = dist.DistConfig(worker_axes=("data",), variant="artemis",
                       telemetry=True)
model, gspecs, state, step = train.setup(cfg, mesh, dcfg, adam(3e-3))
batch = train.batch_source(cfg, mesh, 8, 16, "data")(0)
with jax.set_mesh(mesh):
    c = jax.jit(step).lower(state, batch).compile()
    _, (_, m) = c(state, batch)
got = hlo.collective_bytes(c.as_text())
tel = float(m["obs"]["wire_bytes"]) / 4      # psum'd over the 4 workers
print("permute", got["collective-permute"], "telemetry", tel)
assert got["collective-permute"] == tel, (got, tel)
print("OK")
'''


def test_hlo_counter_equals_wire_telemetry_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", SCENARIO, CHIP],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK" in proc.stdout
