"""A run with the timed path broken underneath reads ``correct: false``,
once for each fault a cell can have; the sound run reads true.  Tiny sizes
on the CPU, the look for a chip skipped; the cells' own limits."""

import time

import pytest

from bench import faults, harness
from bench.tests import tiny

CELLS = tiny.cells()


def _run(name, fault=None, seed=2**33 + 7):
    cell = tiny.cell(name)
    return harness.run(cell, seed, 0.5, False, time.perf_counter(), allow_cpu=True, fault=fault)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert res["metrics"]["setup_s"]["value"] > 0


CASES = [(n, f) for n in CELLS for f in sorted(faults.FAULTS) if faults.applies(f, tiny.cell(n))]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_reads_incorrect(name, fault):
    res = _run(name, faults.FAULTS[fault])
    assert not res["correct"], res["checks"]
