"""Program spans (``repro.core.metrics.span``) on the profiler's trace, and
the policy lag the learner records at its boundary."""

import threading

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.core as c
from repro.core.metrics import MetricsContext, set_metrics_for_thread, span
from repro.flow import Algorithm
from repro.rl import ActorCriticPolicy, CartPole, RolloutWorker, SampleBatch


def _events(log_dir):
    """{line index: [(name, start, end, stats)]} of the host plane."""
    import glob
    import os

    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
               key=os.path.getmtime)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                out[i] = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats)) for ev in line.events]
    return out


def _find(lines, name):
    return [(i, ev) for i, evs in lines.items() for ev in evs if ev[0] == name]


@pytest.fixture
def ctx():
    m = MetricsContext()
    set_metrics_for_thread(m)
    yield m
    set_metrics_for_thread(None)


def test_span_on_a_cpu_trace(tmp_path, ctx):
    def waiter():
        with span("learner.wait"):
            threading.Event().wait(0.01)

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("learner.learn", policy_lag=2):
            with span("learner.step", timer="learn"):
                jax.numpy.ones(8).block_until_ready()
        t = threading.Thread(target=waiter)
        t.start()
        t.join(timeout=10)
    finally:
        jax.profiler.stop_trace()
    assert not t.is_alive()
    lines = _events(str(tmp_path))
    [(li, learn)], [(ls, step)], [(lw, _)] = (
        _find(lines, n) for n in ("learner.learn", "learner.step", "learner.wait"))
    assert learn[3] == {"policy_lag": 2}
    assert step[3] == {}
    assert li == ls and learn[1] <= step[1] and step[2] <= learn[2]  # nested, one thread
    assert lw != li  # the other thread's own line
    assert ctx.timers["learn"].count == 1 and ctx.timers["learn"].total > 0


def test_span_without_a_trace_times_and_reraises(ctx):
    with pytest.raises(ValueError):
        with span("flow.report", timer="t"):
            raise ValueError("boom")
    with span("flow.report"):
        pass
    assert ctx.timers["t"].count == 1
    assert list(ctx.timers) == ["t"]


def test_derived_batches_keep_the_oldest_weights_version():
    a = SampleBatch(x=np.arange(4))
    b = SampleBatch(x=np.arange(4))
    a.weights_version, b.weights_version = 3, 5
    both = SampleBatch.concat_samples([b, a])
    assert both.weights_version == 3
    assert both.slice(0, 2).weights_version == 3
    assert both.shuffle(np.random.default_rng(0)).weights_version == 3
    assert both.copy().weights_version == 3
    assert SampleBatch(x=np.arange(2)).weights_version is None


def _ws(algo, n=2):
    def mk(i):
        return RolloutWorker(
            CartPole(), ActorCriticPolicy(4, 2, loss_kind=algo, rollout_len=16), algo=algo,
            num_envs=2, rollout_len=16, seed=3, worker_index=i,
        )

    return c.WorkerSet.create(mk, n)


def test_policy_lag_is_zero_in_bulk_synchronous_ppo():
    ws = _ws("ppo")
    algo = Algorithm.from_plan("ppo", ws, train_batch_size=64, num_sgd_iter=2,
                               sgd_minibatch_size=32)
    try:
        for _ in range(3):
            res = algo.train()
    finally:
        algo.stop()
        ws.stop()
    lag = res["latencies"]["policy_lag"]
    assert lag["count"] == 3 * 2 * 2  # iterations x epochs x minibatches
    assert lag["mean"] == 0 and lag["p99"] == 0
    # The local worker's version counts its updates; samplers hold it.
    assert ws.local_worker().weights_version == 12
    # The existing timers still reach train() results.
    assert "learn" in res["timers"]
    assert any(k.startswith("gather/") for k in res["timers"])


def test_policy_lag_is_recorded_and_non_negative_in_impala():
    ws = _ws("vtrace")
    algo = Algorithm.from_plan("impala", ws, train_batch_size=64, num_async=2)
    try:
        for _ in range(6):
            res = algo.train()
    finally:
        algo.stop()
        ws.stop()
    lag = res["latencies"]["policy_lag"]
    assert lag["count"] >= 1
    assert lag["mean"] >= 0 and lag["p50"] >= 0


def test_a_traced_train_step_records_each_layers_spans(tmp_path):
    ws = _ws("ppo")
    algo = Algorithm.from_plan("ppo", ws, train_batch_size=64, num_sgd_iter=1,
                               sgd_minibatch_size=32)
    try:
        algo.train()
        jax.profiler.start_trace(str(tmp_path))
        try:
            algo.train()
        finally:
            jax.profiler.stop_trace()
    finally:
        algo.stop()
        ws.stop()
    names = {ev[0] for evs in _events(str(tmp_path)).values() for ev in evs}
    for want in ("rollout.gather", "rollout.sample", "flow.ConcatBatches(64)",
                 "flow.StandardizeFields", "flow.TrainOneStep", "learner.train_one_step",
                 "learner.learn", "learner.h2d", "learner.step", "learner.fetch",
                 "weight_sync", "flow.report"):
        assert want in names, want
