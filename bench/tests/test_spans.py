"""The span readers: on synthetic intervals, and on one tiny traced CPU run of
each cell."""

import time

import pytest

from bench import harness, spans
from bench.tests import tiny

LO, HI = 0.0, 100.0


def _facts(units=2000):
    return {"trace": {"window_s": (HI - LO) * 1e-9}, "units": units,
            "cell": {"name": "synthetic"}}


@pytest.fixture
def synthetic(monkeypatch):
    """Driver line 0, learner line 1, broadcast line 2; device idle in
    [0, 10), [20, 35), [50, 60) and [90, 100)."""
    sp = {
        "window": (LO, HI),
        "idle": [(0.0, 10.0), (20.0, 35.0), (50.0, 60.0), (90.0, 100.0)],
        "driver": 0,
        "lines": [
            [(5.0, 40.0, "rollout.gather", None),
             (40.0, 80.0, "flow.TrainOneStep", None),
             (42.0, 70.0, "learner.train_one_step", None),
             (44.0, 55.0, "learner.learn", {"policy_lag": 0}),
             (52.0, 54.0, "learner.fetch", None),
             (70.0, 95.0, "weight_sync", None)],
            [(-5.0, 10.0, "learner.wait", None),
             (30.0, 45.0, "learner.wait", None),
             (40.0, 50.0, "learner.wait", None),
             (60.0, 62.0, "learner.learn", {"policy_lag": 3}),
             (62.0, 64.0, "learner.learn", {"policy_lag": 5}),
             (64.0, 66.0, "learner.learn", {})],
            [(85.0, 105.0, "weight_sync", None)],
        ],
    }
    monkeypatch.setattr(spans, "load", lambda facts: sp)
    return sp


def test_innermost_labels_each_instant():
    pieces = spans.innermost([(10.0, 50.0, "a", None), (20.0, 30.0, "b", None),
                              (30.0, 40.0, "c", None)], 0.0, 60.0)
    assert pieces == [(0.0, 10.0, None), (10.0, 20.0, "a"), (20.0, 30.0, "b"),
                      (30.0, 40.0, "c"), (40.0, 50.0, "a"), (50.0, 60.0, None)]


def test_idle_partitions_exactly(synthetic):
    by = spans.idle_by_layer(synthetic)
    # [0,5) no span -> flow; [5,10) and [20,35) gather -> rollout;
    # [50,52) and [54,55) learn, [52,54) fetch, [55,60) train_one_step -> learner;
    # [90,95) weight_sync, [95,100) no span -> flow.
    assert by == {"rollout": 20.0, "learner": 10.0, "flow": 15.0}
    total = sum(e - s for s, e in synthetic["idle"])
    assert sum(by.values()) == total
    f = _facts()
    read = {m: harness.reader(m).read(f)
            for m in ("rollout.idle_ms.lm", "learner.idle_ms.lm", "flow.idle_ms.lm")}
    # ns -> ms per 1000 of 2000 units
    assert read == pytest.approx({"rollout.idle_ms.lm": 20e-6 / 2,
                                  "learner.idle_ms.lm": 10e-6 / 2,
                                  "flow.idle_ms.lm": 15e-6 / 2})


def test_starved_share_weight_sync_and_mean_lag(synthetic):
    f = _facts()
    # learner.wait [0,10) + [30,50) (overlap counted once), over 100
    assert harness.reader("learner.starved_frac.env").read(f) == pytest.approx(0.30)
    # weight_sync [70,95) + [85,100) clipped: 40 ns, per 2 ksteps
    assert harness.reader("weight_sync.stall_ms.env").read(f) == pytest.approx(40e-6 / 2)
    # learner.learn stats 0, 3, 5 (one without a stat)
    assert harness.reader("learner.policy_lag.env").read(f) == pytest.approx(8 / 3)


def test_absent_spans_read_none(monkeypatch):
    sp = {"window": (LO, HI), "idle": [(LO, HI)], "driver": 0, "lines": [[]]}
    monkeypatch.setattr(spans, "load", lambda facts: sp)
    for m in ("rollout.idle_ms.lm", "learner.idle_ms.lm", "flow.idle_ms.lm",
              "learner.starved_frac.env", "weight_sync.stall_ms.env",
              "learner.policy_lag.env"):
        assert harness.reader(m).read(_facts()) is None, m
    monkeypatch.undo()
    assert spans.load({"trace": None}) is None  # untraced run


NEW = {"ppo_lm.reason": ("rollout.idle_ms.lm", "learner.idle_ms.lm", "flow.idle_ms.lm"),
       "impala.cartpole": ("learner.starved_frac.env", "weight_sync.stall_ms.env",
                           "learner.policy_lag.env")}


@pytest.mark.parametrize("name", sorted(NEW))
def test_tiny_traced_run_reads_every_span_metric(name):
    cell = tiny.cell(name)
    res = harness.run(cell, 2100000401, 1.0, True, time.perf_counter(), allow_cpu=True)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    for metric in NEW[name]:
        assert m.get(metric) is not None, metric
    if name == "ppo_lm.reason":
        # The three idle metrics partition device.idle_frac's idle time.
        idle_s = m["device.idle_frac.lm"] * res["device"]["window_s"]
        by = spans.idle_by_layer(spans.load({"trace": True, "cell": cell.spec, "chips": 1}))
        assert sum(by.values()) * 1e-9 == pytest.approx(idle_s, rel=1e-6)
    else:
        assert 0.0 <= m["learner.starved_frac.env"] <= 1.0
        assert m["learner.policy_lag.env"] >= 0.0
