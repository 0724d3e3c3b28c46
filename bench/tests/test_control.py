"""The control (the plain reference in bfloat16, in the program's place)
fails at least one of each cell's limits, while the program passes."""

import pytest

from bench import harness
from bench.tests import tiny


@pytest.mark.parametrize("name", tiny.cells())
def test_bfloat16_control_fails_a_limit(name):
    cell = tiny.cell(name)
    cell.traffic = dict(cell.traffic, warmup_iters=0)
    algo, ws, rec, _ = harness.setup(cell, 12345)
    harness.stop(algo, ws, rec)
    program = harness.readings(cell, 12345, rec)
    control = harness.readings(cell, 12345, rec, control=True)
    assert all(program[k] <= lim for k, lim in cell.limits.items()), program
    assert any(control[k] > lim for k, lim in cell.limits.items()), control
