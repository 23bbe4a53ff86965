"""The train step's phase readers (``model_ms.train``, ``wire_ms.train``,
``optimizer_ms.train``) against a hand-made trace of two steps on two chips
(``data/phase_trace.pbtxt``, scopes in ``data/phase_hlo.txt``), and without
a trace or without the scopes, as on a program that lacks them."""
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)

from harness import spec  # noqa: E402
from harness import trace as TR  # noqa: E402

STEPS = 2
# per chip, then the mean over the two chips, over two steps (ms):
# model: chip 0 [1, 9] (a matmul, then a loop whose body holds a matmul and
#   an op without metadata), chip 1 [1, 3]: (8 + 2) / 2 / 2
# wire: bucketize, encode, fold on chip 0 only: (1 + 1 + 0.5) / 2 / 2
# optimizer: 1 on each chip: (1 + 1) / 2 / 2
EXPECT = {"model_ms.train": 2.5, "wire_ms.train": 0.625,
          "optimizer_ms.train": 0.5}


def _data(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return f.read()


def _rec(hlo_text):
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(_data("phase_trace.pbtxt"))
    return {"trace": TR.reduce_profile(pd, TR.op_names(hlo_text)),
            "steps": STEPS}


@pytest.mark.parametrize("metric", sorted(EXPECT))
def test_phase_reader_ms_per_step(metric):
    rec = _rec(_data("phase_hlo.txt"))
    assert spec.load_reader(metric)(rec) == pytest.approx(EXPECT[metric])
    # the copy XLA made without metadata is busy time in no phase
    d0 = rec["trace"].devices[0]
    phases = TR.covered((o.start, o.end) for o in d0
                        if any(TR.in_scope(o.scope, p)
                               for p in ("model", "wire", "optimizer")))
    assert TR.busy_s(d0) - phases == pytest.approx(0.5e-3)


@pytest.mark.parametrize("metric", sorted(EXPECT))
def test_phase_reader_none_without_trace_or_scopes(metric):
    read = spec.load_reader(metric)
    assert read({"steps": STEPS}) is None
    assert read({"trace": None, "steps": STEPS}) is None
    # the same trace, the program's op_names without the phase scopes
    bare = re.sub(r"/(model|wire|optimizer)/", "/", _data("phase_hlo.txt"))
    assert read(_rec(bare)) is None
