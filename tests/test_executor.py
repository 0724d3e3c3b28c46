"""Executor runtime: the rollout-worker matrix under every backend.

The same deterministic rollout-worker protocol suite (sampling in every
gather mode, weight sync, gradient paths, supervision, elasticity) runs
under ``ThreadBackend`` and ``ProcessBackend`` via a parametrized fixture
and must produce *identical* results (ISSUE 2 acceptance)."""

import time

import numpy as np
import pytest

import chaos
from conftest import BACKEND_MATRIX, make_backend
from repro.core import (
    ActorDiedError,
    FailurePolicy,
    ProcessBackend,
    ThreadBackend,
    VirtualActor,
    WorkerSet,
    resolve_backend,
)
from repro.core.metrics import (
    NUM_SAMPLES_DROPPED,
    NUM_SHARDS_DROPPED,
    NUM_WORKER_FAILURES,
    MetricsContext,
    set_metrics_for_thread,
)
from repro.core.operators import ParallelRollouts, par_compute_gradients

# thread / process+pickle / process+shm: the protocol suite must be
# transport-independent (ISSUE 3).
BACKENDS = BACKEND_MATRIX


@pytest.fixture(params=BACKENDS)
def backend(request):
    return make_backend(request.param)


def make_ws(backend, n=2, **supervision):
    return WorkerSet.create(chaos.make_stub_worker, n, backend=backend, **supervision)


def obs_bases(batches):
    """Map each StubWorker batch back to (worker_index, nth_sample)."""
    out = []
    for b in batches:
        first = int(np.asarray(b["obs"])[0])
        out.append((first // 10_000_000, (first % 10_000_000) // 100))
    return out


# ---------------------------------------------------------------- the matrix
def test_backend_resolution():
    assert isinstance(resolve_backend(None), ThreadBackend)
    assert isinstance(resolve_backend("process"), ProcessBackend)
    b = ProcessBackend()
    assert resolve_backend(b) is b
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_backend("gpu")


@pytest.mark.parametrize("mode", ["bulk_sync", "async", "raw_sync", "raw_batch"])
def test_rollout_matrix_identical_across_backends(mode):
    """Acceptance: every rollout mode yields the same stream under both
    backends (async mode modulo completion order)."""

    def run(backend):
        ws = make_ws(backend, n=2)
        try:
            if mode == "raw_sync":
                it = ParallelRollouts(ws, mode="raw").gather_sync()
                return [obs_bases([b])[0] for b in it.take(6)]
            if mode == "raw_batch":
                it = ParallelRollouts(ws, mode="raw").batch_across_shards()
                return [obs_bases(bs) for bs in it.take(3)]
            if mode == "bulk_sync":
                it = ParallelRollouts(ws, mode="bulk_sync")
                # Concatenated across shards per round: totals are exact.
                return [int(np.asarray(b["obs"]).sum()) for b in it.take(3)]
            it = ParallelRollouts(ws, mode="async", num_async=1)
            return obs_bases(it.take(6))
        finally:
            ws.stop()

    outs = [run(make_backend(p)) for p in BACKENDS]
    thread_out = outs[0]
    if mode != "async":
        for other in outs[1:]:
            assert thread_out == other
    else:
        # Async completion order is scheduling-dependent; the invariant
        # (identical under every backend/transport) is per-shard FIFO over
        # the same worker set with nothing lost or duplicated.
        for got in outs:
            assert len(got) == 6 and {w for w, _ in got} <= {1, 2}
            for w in (1, 2):
                seq = [k for wi, k in got if wi == w]
                assert seq == list(range(1, len(seq) + 1))


def test_rollout_matrix_expected_values(backend):
    """The stream is the *correct* deterministic stream, not just consistent:
    barrier gather round r yields workers 1..N each on their rth sample."""
    ws = make_ws(backend, n=2)
    it = ParallelRollouts(ws, mode="raw").gather_sync()
    assert obs_bases(it.take(6)) == [(1, 1), (2, 1), (1, 2), (2, 2), (1, 3), (2, 3)]
    ws.stop()


def test_weight_sync_roundtrip(backend):
    ws = make_ws(backend, n=2)
    ws.local_worker().set_weights(np.array([3.0, 4.0], np.float32))
    ws.sync_weights()
    for a in ws.remote_workers():
        np.testing.assert_array_equal(
            a.sync("get_weights"), np.array([3.0, 4.0], np.float32)
        )
    ws.stop()


def test_gradient_path(backend):
    """A2C-shaped path: per-worker grads -> barrier -> apply on local."""
    ws = make_ws(backend, n=2)
    rounds = par_compute_gradients(ws).batch_across_shards().take(2)
    for grads_infos in rounds:
        assert len(grads_infos) == 2
        for grads, info in grads_infos:
            ws.local_worker().apply_gradients(grads)
            assert info["batch_count"] == 8
    assert not np.array_equal(ws.local_worker().get_weights(), np.zeros(2))
    ws.stop()


def test_learn_on_batch_path(backend):
    ws = make_ws(backend, n=1)
    batch = ws.remote_workers()[0].sync("sample")
    info = ws.local_worker().learn_on_batch(batch)
    assert info["trained"] == 8
    ws.stop()


# ------------------------------------------------------------- supervision
def test_restart_policy_keeps_shard(backend):
    """A worker failing once under max_restarts keeps its shard: the item is
    lost, the stream continues, and the failure is counted."""
    factory = chaos.ChaosFactory(
        chaos.make_stub_worker, {1: [chaos.RaiseOnNth("sample", n=2)]}
    )
    # A restart rebuilds the injector (fresh counts), so every incarnation
    # fails on its 2nd sample; a large budget keeps the shard alive forever.
    ws = WorkerSet.create(
        factory, 2, backend=backend,
        max_restarts=100, backoff_base=0.0, failure_policy="restart",
    )
    metrics = MetricsContext()
    set_metrics_for_thread(metrics)
    it = ParallelRollouts(ws, mode="async", num_async=1)
    it.metrics = metrics
    got = obs_bases(it.take(8))
    # Both workers (re)join the stream after the injected failures; keep
    # pulling past spawn/restart latency until both have contributed and
    # worker 1's 2nd-call fault has actually fired.
    deadline = time.time() + 20
    while (
        {w for w, _ in got} != {1, 2} or metrics.counters[NUM_WORKER_FAILURES] == 0
    ) and time.time() < deadline:
        got += obs_bases(it.take(1))
    assert {w for w, _ in got} == {1, 2}
    assert metrics.counters[NUM_WORKER_FAILURES] >= 1
    assert metrics.counters[NUM_SHARDS_DROPPED] == 0
    [a1] = [a for a in ws.remote_workers() if a.name == "rollout-1"]
    assert a1.num_restarts >= 1 and a1.alive
    ws.stop()


def test_drop_shard_policy_shrinks_stream(backend):
    """A sticky failure under drop_shard removes the shard; survivors keep
    producing and the drop is recorded."""
    factory = chaos.ChaosFactory(
        chaos.make_stub_worker, {1: [chaos.RaiseOnNth("sample", n=3, sticky=True)]}
    )
    ws = WorkerSet.create(factory, 2, backend=backend, failure_policy="drop_shard")
    metrics = MetricsContext()
    set_metrics_for_thread(metrics)
    it = ParallelRollouts(ws, mode="async", num_async=1)
    it.metrics = metrics
    got = obs_bases(it.take(12))
    # Keep pulling past process-spawn latency until the sticky fault fires
    # and the shard is dropped.
    deadline = time.time() + 20
    while metrics.counters[NUM_SHARDS_DROPPED] == 0 and time.time() < deadline:
        got += obs_bases(it.take(1))
    assert metrics.counters[NUM_SHARDS_DROPPED] == 1
    assert metrics.counters[NUM_WORKER_FAILURES] >= 1
    # Worker 1 contributed at most its pre-fault samples; the tail is all
    # worker 2 (shard 1 gone for good).
    got += obs_bases(it.take(4))
    assert [w for w, _ in got].count(1) <= 2
    assert [w for w, _ in got][-4:] == [2, 2, 2, 2]
    ws.stop()


def test_raise_policy_propagates(backend):
    factory = chaos.ChaosFactory(
        chaos.make_stub_worker, {1: [chaos.RaiseOnNth("sample", n=1, exc=ValueError)]}
    )
    ws = WorkerSet.create(factory, 1, backend=backend)  # default: raise
    it = ParallelRollouts(ws, mode="async")
    with pytest.raises(ValueError, match="chaos"):
        it.take(2)
    ws.stop()


def test_restart_budget_exhaustion_drops_shard(backend):
    """Sticky fault + restart policy: the supervisor burns its budget, the
    actor dies, and the gather loop degrades to dropping the shard."""
    factory = chaos.ChaosFactory(
        chaos.make_stub_worker, {1: [chaos.RaiseOnNth("sample", n=1, sticky=True)]}
    )
    ws = WorkerSet.create(
        factory, 2, backend=backend,
        max_restarts=2, backoff_base=0.0, failure_policy="restart",
    )
    metrics = MetricsContext()
    set_metrics_for_thread(metrics)
    it = ParallelRollouts(ws, mode="async", num_async=1)
    it.metrics = metrics
    got = obs_bases(it.take(8))
    assert {w for w, _ in got} == {2}
    # Process restarts take real time: keep pulling until the supervisor
    # exhausts the budget and the gather loop drops the shard.
    deadline = time.time() + 20
    while metrics.counters[NUM_SHARDS_DROPPED] == 0 and time.time() < deadline:
        got += obs_bases(it.take(1))
    assert metrics.counters[NUM_SHARDS_DROPPED] == 1
    [a1] = [a for a in ws.remote_workers() if a.name == "rollout-1"]
    assert not a1.alive and a1.num_restarts == 2
    assert ws.num_healthy_workers() == 1
    ws.stop()


def test_recover_heals_dead_worker(backend):
    factory = chaos.ChaosFactory(
        chaos.make_stub_worker, {1: [chaos.RaiseOnNth("sample", n=1, sticky=True)]}
    )
    ws = WorkerSet.create(
        factory, 2, backend=backend,
        max_restarts=1, backoff_base=0.0, failure_policy="restart",
    )
    it = ParallelRollouts(ws, mode="async", num_async=1)
    it.take(6)
    deadline = time.time() + 20
    while ws.num_healthy_workers() == 2 and time.time() < deadline:
        it.take(1)
    assert ws.num_healthy_workers() == 1
    report = ws.recover()
    assert report["restarted"] or report["replaced"]
    assert ws.num_healthy_workers() == 2
    # The healed worker REJOINS the already-running stream (its "dead" drop
    # is pruned): it fails again on its fresh injector's 2nd call, burns the
    # budget again, dies again — proving it was actually re-dispatched.
    [a1] = [a for a in ws.remote_workers() if a.name == "rollout-1"]
    deadline = time.time() + 20
    while a1.alive and time.time() < deadline:
        it.take(1)
    assert not a1.alive, "recovered worker never rejoined the live stream"
    ws.stop()


def test_kill_and_dead_futures(backend):
    ws = make_ws(backend, n=2)
    victim = ws.remote_workers()[0]
    victim.kill()
    assert not victim.alive
    with pytest.raises(ActorDiedError):
        victim.call("sample").result(timeout=5)
    assert ws.num_healthy_workers() == 1
    # sync_weights skips the corpse instead of raising.
    ws.sync_weights()
    ws.stop()


# --------------------------------------------------------------- elasticity
def test_elastic_add_workers_mid_stream(backend):
    ws = make_ws(backend, n=2)
    it = ParallelRollouts(ws, mode="async", num_async=1)
    first = obs_bases(it.take(4))
    assert {w for w, _ in first} <= {1, 2}
    ws.add_workers(1)
    later = []
    deadline = time.time() + 20
    while 3 not in {w for w, _ in later} and time.time() < deadline:
        later += obs_bases(it.take(1))
    assert 3 in {w for w, _ in later}, "new worker never joined the stream"
    ws.stop()


def test_elastic_remove_workers_mid_stream(backend):
    ws = make_ws(backend, n=3)
    it = ParallelRollouts(ws, mode="async", num_async=1)
    it.take(6)
    removed = ws.remove_workers(1)
    assert removed == ["rollout-3"]
    tail = obs_bases(it.take(10))
    # Removed worker contributes at most its already-in-flight item.
    assert [w for w, _ in tail].count(3) <= 1
    assert {1, 2} <= {w for w, _ in tail}
    ws.stop()


def test_remove_workers_keeps_at_least_one(backend):
    ws = make_ws(backend, n=1)
    with pytest.raises(ValueError, match="at least one"):
        ws.remove_workers(1)
    ws.stop()


# ------------------------------------------------------------ misc plumbing
def test_enqueue_drop_counts_surface_in_metrics():
    """Satellite: Enqueue drops land in the shared metrics context."""
    import queue

    from repro.core import Enqueue

    metrics = MetricsContext()
    set_metrics_for_thread(metrics)
    q = queue.Queue(maxsize=1)
    enq = Enqueue(q, block=False)
    for i in range(3):
        assert enq(i) == i
    assert enq.num_dropped == 2
    assert metrics.counters[NUM_SAMPLES_DROPPED] == 2
    set_metrics_for_thread(None)


def test_virtual_actor_argument_validation():
    with pytest.raises(ValueError, match="exactly one"):
        VirtualActor()
    with pytest.raises(ValueError, match="exactly one"):
        VirtualActor(object(), factory=object)
    with pytest.raises(ValueError, match="factory"):
        VirtualActor(object(), max_restarts=1)
    with pytest.raises(ValueError, match="unknown failure policy"):
        VirtualActor(object(), failure_policy="retry")


def test_process_backend_requires_picklable_factory():
    with pytest.raises(Exception):
        VirtualActor(factory=lambda: object(), backend="process")


def test_failure_policy_validation():
    assert FailurePolicy.validate("drop_shard") == "drop_shard"
    with pytest.raises(ValueError):
        FailurePolicy.validate("explode")


# ------------------------------------------------------ one process per chip
class EnvProbe:
    """Reports the JAX platform setting its process was started with."""

    def platforms(self):
        import os

        return os.environ.get("JAX_PLATFORMS")


def test_actor_children_stay_off_the_chip(monkeypatch):
    """Actor children are CPU actors: a parent pinned to the CPU keeps the
    requested (or fast fork) start method, while a parent that may hold an
    accelerator always spawns them, with JAX_PLATFORMS=cpu in the child's
    environment and its own environment left as it was."""
    import os

    from repro.core import executor as ex

    monkeypatch.setattr(ex, "_parent_on_cpu", lambda: True)
    assert ex.child_start_method("forkserver") == "forkserver"
    assert ex.child_start_method() in ("fork", "spawn")
    monkeypatch.setattr(ex, "_parent_on_cpu", lambda: False)
    assert ex.child_start_method("fork") == "spawn"
    assert ex.child_start_method() == "spawn"

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    cell = ex.ProcessCell(target=EnvProbe(), start_method="fork", transport="pickle")
    try:
        assert cell._ctx.get_start_method() == "spawn"
        assert cell.rpc("platforms", (), {}) == "cpu"
        assert "JAX_PLATFORMS" not in os.environ
    finally:
        cell.stop()
