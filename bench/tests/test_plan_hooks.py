"""A PPO-LM plan for another architecture is a module of hooks
(``gqa_plan.py``): its CPU sizes, its faults, its policy and its reference
come from its own hooks, its sound run reads correct, and each fault that
applies to it reads incorrect.  Tiny sizes on the CPU, the look for a chip
skipped; ``ppo_lm.chat``'s traffic and ``ppo_lm.reason``'s limits."""

import time

import pytest

from bench import faults, harness
from bench.tests import gqa_plan, tiny

NAME = "gqa.chat"


def gqa_cell(shrink=True) -> harness.Cell:
    real = harness.load_json(harness.ROOT / "BENCHMARK.json")
    bench = dict(real, configs=[{"name": "gqa", "file": "-"}],
                 workloads=[{"name": NAME, "config": "gqa", "traffic": "chat", "chips": 1}])
    lm = harness.load_json(harness.BENCH / "configs" / "musicgen-large-lm.json")
    model = dict(num_hidden_layers=22, hidden_size=2048, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=64, intermediate_size=8192, vocab_size=32000,
                 rms_norm_eps=1e-5, rope_theta=10000.0, embed_init_std=0.02, value_head=[64],
                 loss=lm["loss"], optimizer=lm["optimizer"])
    cell = harness.Cell(bench, NAME, model=model,
                        traffic=harness.load_json(harness.BENCH / "traffic" / "chat.json"),
                        limits=harness.load_json(harness.BENCH / "limits" / "ppo_lm.reason.json"),
                        plan=gqa_plan)
    return tiny.shrink(cell) if shrink else cell


def _run(fault=None, seed=2**33 + 11):
    return harness.run(gqa_cell(), seed, 0.5, False, time.perf_counter(), allow_cpu=True,
                       fault=fault)


def test_cpu_sizes_come_from_the_plans_own_hook():
    full, cell = gqa_cell(shrink=False), gqa_cell()
    assert (cell.model, cell.traffic) == gqa_plan.tiny(full.model, full.traffic)
    assert cell.model["num_key_value_heads"] == 2 and "num_layers" not in cell.model
    assert cell.traffic["horizon"] == 8 and cell.traffic["sgd_epochs"] == 4


def test_faults_come_from_the_plans_own_hooks(monkeypatch):
    cell = gqa_cell()
    assert all(faults.applies(f, cell) for f in faults.FAULTS)
    monkeypatch.setattr(gqa_plan, "BOOTSTRAPS_WITH_CRITIC", False)
    assert not faults.applies("altered_bootstrap", cell)
    # A cut batch keeps whole units of the plan's rows: 4 of 8 rows in
    # PPO-LM's, 3 where the unit is 3.
    monkeypatch.setattr(gqa_plan, "batch_unit", lambda t: 3)
    rows = []

    def plant(ws, cell):
        lw = ws.local_worker()
        learn = lw._learn_jit

        def count(params, target_params, opt_state, batch, key):
            rows.append(len(batch["obs"]))
            return learn(params, target_params, opt_state, batch, key)

        lw._learn_jit = count
        faults.half_batch(ws, cell)

    algo, ws, rec, _ = harness.setup(cell, 5, plant)
    harness.stop(algo, ws, rec)
    assert cell.traffic["minibatch"] == 8 and rows and set(rows) == {3}, rows


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("fault", [f for f in sorted(faults.FAULTS)
                                   if faults.applies(f, gqa_cell())])
def test_fault_reads_incorrect(fault):
    res = _run(faults.FAULTS[fault])
    assert not res["correct"], res["checks"]
