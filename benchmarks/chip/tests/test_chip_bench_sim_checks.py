"""The sim cell's comparison, driven end to end on the CPU at a test size
with the cell's own limits: a sound run is correct; the reference at the
three-pass bfloat16 matmul precision put in the program's place, a round
that leaves the state unchanged, half the workers' gradients left out
(the mean taken over the rest), and one loss of one answer altered are
not."""
import numpy as np
import pytest

import chip_cells as cells
from harness import compare, spec

DRIVER = spec.load_driver("sim")


@pytest.fixture(scope="module")
def cell():
    return cells.sim_cell()


def test_sound_run_is_correct(cell):
    res = DRIVER.run(cells.ctx(cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert res["e2e"]["sim_cell_rounds_per_s"] > 0


def test_control_at_high_precision_is_not_correct(cell):
    (r,) = list(DRIVER.readings(cells.ctx(cell), [2**31 + 13],
                                control="high"))
    assert not compare.judge(r, cell.limits)["correct"], r


def _unchanged(monkeypatch):
    from repro.core import artemis as art
    inner = art.artemis_round

    def round_(*a, **k):
        omega, st, stats = inner(*a, **k)
        return omega * 0.0, st, stats
    monkeypatch.setattr(art, "artemis_round", round_)


def _half_workers(monkeypatch):
    from repro.core import federated as fed
    inner = fed.Problem.worker_grad

    def grad(self, w, idx):
        g = inner(self, w, idx)
        h = g.shape[0] // 2
        return g.at[h:2 * h].set(g[:h])
    monkeypatch.setattr(fed.Problem, "worker_grad", grad)


def _altered(monkeypatch):
    from repro.core import sweep as sw
    inner = sw.run_sweep

    def run(*a, **k):
        res = inner(*a, **k)
        losses = np.array(res.losses, copy=True)
        losses[0, 0, 0, -1] *= 1.01          # the sgd cell never rounds
        res.losses = losses
        return res
    monkeypatch.setattr(sw, "run_sweep", run)


@pytest.mark.parametrize("plant", [_unchanged, _half_workers, _altered],
                         ids=["unchanged", "half_workers", "altered"])
def test_planted_fault_is_not_correct(cell, plant, monkeypatch):
    plant(monkeypatch)
    res = DRIVER.run(cells.ctx(cell))
    assert not res["correct"], res["checks"]
