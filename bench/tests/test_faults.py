"""A run with the timed path broken underneath reads ``correct: false``,
once for each fault a cell can have; the sound run reads true, also where
the learner step donates its optimizer state; and the program's device
state is freed before the reference runs.  Tiny sizes on the CPU, the look
for a chip skipped; the cells' own limits."""

import gc
import time
import weakref

import jax
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


def donating(monkeypatch, name):
    """The learner step re-jitted to donate the optimizer state as the
    workers are built, as the step of a configuration that fills the chip
    has to."""
    plan = tiny.cell(name).plan
    build = plan.build

    def build_donating(*a, **k):
        algo, ws = build(*a, **k)
        lw = ws.local_worker()
        lw._learn_jit = jax.jit(lw._learn, donate_argnums=(2,))
        return algo, ws

    monkeypatch.setattr(plan, "build", build_donating)


@pytest.mark.parametrize("name", ["ppo_lm.reason", "impala.cartpole"])
def test_sound_run_is_correct_with_a_donating_step(name, monkeypatch):
    donating(monkeypatch, name)
    res = _run(name)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("name", ["ppo_lm.reason", "impala.cartpole"])
def test_unchanged_state_survives_a_donating_step(name, monkeypatch):
    same = []
    donating(monkeypatch, name)

    def plant(ws, cell):
        faults.unchanged_state(ws, cell)
        lw = ws.local_worker()
        learn = lw._learn_jit

        def check(params, target_params, opt_state, batch, key):
            out = learn(params, target_params, opt_state, batch, key)
            same.append(out[0] is params and out[1] is opt_state)  # no copy made
            return out

        lw._learn_jit = check

    res = _run(name, plant)
    assert not res["correct"], res["checks"]
    assert same and all(same)


def test_learner_rows_are_counted_against_the_epochs():
    """The rows the compiled learner steps took, over set-up and window,
    against the plan's passes over what the program counts as trained: 4
    epochs with the last 3 skipped read 0.75 (a sound run reads 0, which
    every sound run's ``correct`` holds it to)."""
    res = _run("ppo_lm.chat", faults.one_epoch)
    assert res["checks"]["learner.rows_gap"] == {"value": 0.75, "limit": 0}


@pytest.mark.parametrize("name", CELLS)
def test_the_program_is_freed_before_the_reference(name, monkeypatch):
    """No array of the program's parameter or optimizer shapes made during
    the run is alive when the reference starts, nor after the run."""
    shapes = set()
    old = [weakref.ref(x) for x in jax.live_arrays()]

    def note(ws, cell):
        lw = ws.local_worker()
        shapes.update((x.shape, x.dtype) for x in jax.tree_util.tree_leaves(
            (lw.params, lw.opt_state)) if x.ndim)

    def program_arrays() -> int:
        gc.collect()
        before = {id(r()) for r in old if r() is not None}
        return sum(id(x) not in before and (x.shape, x.dtype) in shapes
                   for x in jax.live_arrays())

    left = {}
    readings = harness.readings

    def at_reference(*a, **k):
        left["at_reference"] = program_arrays()
        return readings(*a, **k)

    monkeypatch.setattr(harness, "readings", at_reference)
    _run(name, note)
    left["after_run"] = program_arrays()
    assert shapes and left == {"at_reference": 0, "after_run": 0}
