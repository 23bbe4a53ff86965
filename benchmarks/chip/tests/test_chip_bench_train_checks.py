"""The train cell's comparison, driven end to end on the CPU at a test
size with the cell's own limits: a sound run is correct; the reference in
float8 put in the program's place, a step that returns its state
unchanged, and half of each worker's batch left out are not."""
import pytest

import chip_cells as cells
from harness import compare, spec

DRIVER = spec.load_driver("train")


@pytest.fixture(scope="module")
def cell():
    return cells.train_cell()


def test_sound_run_is_correct(cell):
    res = DRIVER.run(cells.ctx(cell))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["e2e"]["train_tokens_per_s"] > 0


def test_control_in_float8_is_not_correct(cell):
    (r,) = list(DRIVER.readings(cells.ctx(cell), [2**31 + 11], control="fp8"))
    assert not compare.judge(r, cell.limits)["correct"], r


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_planted_fault_is_not_correct(cell, fault):
    res = DRIVER.run(cells.ctx(cell, fault=fault))
    assert not res["correct"], res["checks"]
