"""The train step's phase scopes (``core/dist.py``) in its compiled HLO.

The bucketed Artemis step of reduced whisper-tiny is compiled on one CPU
device and on four virtual ones (a subprocess each: the device count is
fixed at JAX's start), and each instruction's ``op_name`` metadata is
checked: the model's matmuls sit under ``model``, the ring under
``wire/ring``, every wire sub-scope is there (the fused round's kernels
under ``wire/encode`` and ``wire/downlink``), and no instruction JAX
emitted outside the short allowlist below carries none of ``model``,
``wire``, ``optimizer``.  The chip benchmark's ``model_ms.train``,
``wire_ms.train`` and ``optimizer_ms.train`` read these scopes.
"""
import os
import re
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
PHASES = ("model", "wire", "optimizer")
# the Artemis round runs fused (``dist.fused_round``): its memory updates
# sit in the ``encode`` and ``downlink`` kernels, and ``memory`` is empty
WIRE_SUBS = ("bucketize", "encode", "ring", "downlink", "unbucketize")
FUSED = {"wire_uplink": "encode", "wire_server": "downlink"}

SCENARIO = r'''
import os, sys
n = int(sys.argv[1])
os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
import jax
from repro import configs
from repro.core import dist
from repro.launch import train
from repro.optim import adam
cfg = configs.get_config("whisper-tiny", reduced=True)
mesh = train.make_mesh(f"{n}x1")
dcfg = dist.DistConfig(worker_axes=("data",), variant="artemis")
_, _, state, step = train.setup(cfg, mesh, dcfg, adam(3e-3))
batch = train.batch_source(cfg, mesh, 2 * n, 16, "data")(0)
with jax.set_mesh(mesh):
    sys.stdout.write(jax.jit(step).lower(state, batch).compile().as_text())
'''

# Instructions outside the three phases, each with why.  An op_name here is
# the path below ``jit(step_fn)/`` (and ``shard_map/``).
ALLOWED = (
    # the shard_map edge: its constants and copies, and the fills the SPMD
    # partitioner makes there (named after the instruction they came from)
    r"shard_map|broadcast\.\d+",
    # the loss and metrics pmean after the step
    r"psum|div",
    # the train state's step counter
    r"add",
    # the rotary frequencies and the attention mask: built from positions
    # alone, they depend on no differentiated input, so JAX binds them
    # outside the ``model`` scope
    r"pow|jit\(_where\)/broadcast_in_dim",
)
# no work of their own: bookkeeping, layout and control flow
NO_WORK = {"parameter", "tuple", "get-tuple-element", "constant", "bitcast",
           "copy", "copy-start", "copy-done", "while", "call", "conditional",
           "after-all", "partition-id", "replica-id"}

_HEAD = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INST = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*.*?\s"
                   r"([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def parse(text):
    """[(computation, top_level, opcode, op_name parts, line)] per
    instruction;
    ``top_level`` is false inside fused and reducer computations (their
    instructions run as part of the fusion or reduction that calls them)."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _HEAD.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            continue
        m = _INST.match(line)
        if m and cur is not None:
            on = _OP_NAME.search(line)
            cur.append((m.group(2), on.group(1) if on else "", line))
    inner = set()
    for insts in comps.values():
        for op, _, line in insts:
            if op == "fusion":
                inner.update(re.findall(r"calls=%([\w.\-]+)", line))
            if op != "call":
                inner.update(re.findall(r"to_apply=%([\w.\-]+)", line))
    return [(c, c not in inner, op, on.split("/") if on else [], line)
            for c, insts in comps.items() for op, on, line in insts]


def under(parts, *path):
    """``path`` appears in ``parts`` in order."""
    it = iter(parts)
    return all(p in it for p in path)


@pytest.fixture(scope="module", params=[1, 4], ids=["1dev", "4dev"])
def insts(request):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.abspath(SRC) + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", SCENARIO,
                           str(request.param)], capture_output=True,
                          text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return request.param, parse(proc.stdout)


def check_matmuls_in_model(n, insts):
    mm = [(parts, line) for _, _, op, parts, line in insts
          if op in ("dot", "convolution")]
    assert mm
    for parts, line in mm:
        assert "model" in parts, line[:300]


def check_ring_in_wire(n, insts):
    permutes = [(parts, line) for _, _, op, parts, line in insts
                if op.startswith("collective-permute")]
    assert len(permutes) >= (1 if n > 1 else 0)
    for parts, line in permutes:
        assert under(parts, "wire", "ring", "ring_hop"), line[:300]
    folds = [(parts, line) for _, _, op, parts, line in insts
             if "bucket_acc" in parts or "tpu_custom_call" in line]
    assert folds
    for parts, line in folds:
        assert under(parts, "wire", "ring", "bucket_acc"), line[:300]


def check_wire_sub_scopes(n, insts):
    for sub in WIRE_SUBS:
        assert any(under(parts, "wire", sub) for *_, parts, _ in insts), sub
    assert not any(under(parts, "wire", "memory") for *_, parts, _ in insts)
    for kernel, sub in FUSED.items():
        ops = [parts for *_, parts, _ in insts if kernel in parts]
        assert ops, kernel
        assert all(under(parts, "wire", sub, kernel) for parts in ops), kernel


def check_no_unscoped_work(n, insts):
    stray = []
    for _, top, op, parts, line in insts:
        if not top or op in NO_WORK or not parts:
            # no op_name: XLA made it; no scope can label what JAX did not
            # emit (on the chip, what they cost shows as busy time outside
            # the three phases)
            continue
        if any(p in parts for p in PHASES):
            continue
        path = "/".join(p for p in parts[1:] if p != "shard_map")
        path = path or "shard_map"
        if not any(re.fullmatch(rx, path) for rx in ALLOWED):
            stray.append(f"{op} {path}")
    assert not stray, stray[:20]


@pytest.mark.parametrize("check", [check_matmuls_in_model,
                                   check_ring_in_wire,
                                   check_wire_sub_scopes,
                                   check_no_unscoped_work])
def test_phase_scopes(insts, check):
    n, parsed = insts
    check(n, parsed)
