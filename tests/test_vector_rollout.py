"""Vectorized rollout engine unit suite (ISSUE 5 tentpole).

Covers the engine's load-bearing invariants directly:

  * ``VectorEnv`` lane semantics — auto-reset, per-lane key chains (lane i
    of an N-wide step is bit-identical to the same lane stepped alone),
    terminated/truncated split, episode counters;
  * fragment assembly — contiguous traces, unique monotone ``eps_id``,
    ``split_by_episode`` recovering fragments, dtype preservation;
  * truncation-aware GAE bootstrap — the fused_gae routing reproduces an
    explicit next-value GAE oracle at truncation boundaries;
  * decoupled inference — batched serving, credit gate, failure + recovery
    (weight re-sync) through the executor runtime;
  * flow lowering — ``vector=``/``inference=`` reach workers via
    ``ParallelRollouts`` and the builders, and non-vectorized workers fall
    back with a warning rather than an error.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.flow as flow
from repro.core.actor import VirtualActor
from repro.core.operators import ParallelRollouts, configure_vectorized_rollouts
from repro.core.workers import WorkerSet
from repro.rl import (
    ActorCriticPolicy,
    CartPole,
    CreditGate,
    DummyPolicy,
    InferenceActor,
    InferenceClient,
    InferenceUnavailable,
    StubEnv,
    VectorEnv,
)
from repro.rl.advantages import gae
from repro.rl.rollout_worker import (
    EPS_STRIDE,
    MAX_LANES,
    RolloutWorker,
    VectorizedRolloutWorker,
    assemble_fragments,
)


def make_vec_worker(i, cls=VectorizedRolloutWorker, policy=None, **kw):
    kw.setdefault("num_envs", 4)
    kw.setdefault("rollout_len", 8)
    kw.setdefault("seed", 21)
    kw.setdefault("algo", "pg")
    return cls(StubEnv(max_steps=6), policy or DummyPolicy(4, 2), worker_index=i, **kw)


# ----------------------------------------------------------------- VectorEnv
def test_vector_env_lane_parity_and_autoreset():
    """Lane i of an N-wide VectorEnv is bit-identical to the same lane run
    in a width-1 VectorEnv, and lanes auto-reset independently."""
    venv3 = VectorEnv(StubEnv(max_steps=5), 3)
    venv1 = VectorEnv(StubEnv(max_steps=5), 1)
    s3 = venv3.reset(jax.random.PRNGKey(11))
    lane = jax.tree_util.tree_map(lambda x: x[0:1], s3)
    for t in range(11):
        actions = jnp.asarray([t % 2, 1, 0])
        s3, out3 = venv3.step(s3, actions)
        lane, out1 = venv1.step(lane, actions[0:1])
        np.testing.assert_array_equal(np.asarray(s3.obs[0]), np.asarray(lane.obs[0]))
        np.testing.assert_array_equal(np.asarray(s3.rng[0]), np.asarray(lane.rng[0]))
        assert int(s3.eps_count[0]) == int(lane.eps_count[0])
    # 11 steps at horizon 5 -> every lane finished exactly 2 episodes.
    assert np.asarray(s3.eps_count).tolist() == [2, 2, 2]
    # Auto-reset zeroed the per-episode accounting at each boundary.
    assert np.all(np.asarray(s3.ep_len) == 1)


def test_vector_env_truncation_vs_termination():
    """StubEnv splits horizon cuts from env death; VectorEnv surfaces both
    and the true pre-reset successor obs."""
    env = StubEnv(max_steps=4, drift=0.0)  # never terminates: horizon only
    venv = VectorEnv(env, 2)
    s = venv.reset(jax.random.PRNGKey(0))
    truncs = []
    for _ in range(8):
        s, out = venv.step(s, jnp.asarray([1, 0]))
        truncs.append(np.asarray(out.truncated))
        assert not np.any(np.asarray(out.terminated))
        done = np.asarray(out.done)
        if done.any():
            # post-reset obs differs from the true successor on done lanes
            post = np.asarray(out.obs)[done]
            raw = np.asarray(out.next_obs)[done]
            assert not np.allclose(post, raw)
    assert np.sum(truncs) == 4  # 8 steps / horizon 4 * 2 lanes


def test_vector_env_legacy_step_fallback():
    """Envs without step_raw still vectorize (legacy auto-resetting step),
    with truncated == False and next_obs == post-reset obs."""

    from repro.rl.env import Env

    class LegacyEnv(Env):
        obs_dim = 4
        num_actions = 2

        def __init__(self):
            self._stub = StubEnv(max_steps=3)

        def reset(self, key):
            return self._stub.reset(key)

        def step(self, state, action, key):
            return self._stub.step(state, action, key)

    venv = VectorEnv(LegacyEnv(), 2)
    assert not venv._has_raw
    s = venv.reset(jax.random.PRNGKey(1))
    s, out = venv.step(s, jnp.asarray([0, 1]))
    np.testing.assert_array_equal(np.asarray(out.next_obs), np.asarray(out.obs))
    assert not np.any(np.asarray(out.truncated))


# ---------------------------------------------------------------- fragments
def test_fragment_assembly_invariants():
    w = make_vec_worker(2)
    batches = [w.sample() for _ in range(3)]
    for b in batches:
        eps = b["eps_id"]
        assert eps.dtype == np.int64
        T = w.rollout_len
        for lane in range(w.num_envs):
            trace = eps[lane * T : (lane + 1) * T]
            # Lane traces are contiguous: monotone episode ids from one lane.
            assert np.all(np.diff(trace) >= 0)
            assert np.all(trace // EPS_STRIDE == 2 * MAX_LANES + lane)
        # split_by_episode recovers fragments: one eps_id each, partition.
        frags = b.split_by_episode()
        assert sum(f.count for f in frags) == b.count
        for f in frags:
            assert len(np.unique(f["eps_id"])) == 1
    # Episode ids are monotone per lane across successive sample() calls:
    # only a lane's in-flight episode may straddle a batch boundary.
    T = w.rollout_len
    for lane in range(w.num_envs):
        prev_max = -1
        for b in batches:
            trace = b["eps_id"][lane * T : (lane + 1) * T]
            assert trace[0] >= prev_max
            prev_max = trace[-1]
    n_unique = len(np.unique(np.concatenate([b["eps_id"] for b in batches])))
    per_batch = [len(np.unique(b["eps_id"])) for b in batches]
    assert sum(per_batch) - 2 * w.num_envs <= n_unique <= sum(per_batch)


def test_assemble_fragments_rejects_bad_lane_base():
    cols = {
        "obs": np.zeros((4, 2, 3), np.float32),
        "eps_count": np.zeros((4, 2), np.int32),
    }
    with pytest.raises(ValueError, match="lane_base"):
        assemble_fragments(cols, np.arange(3))


def test_device_batch_excludes_eps_id():
    w = make_vec_worker(0)
    b = w.sample()
    dev = w._device_batch(b)
    assert "eps_id" not in dev and "obs" in dev


# ------------------------------------------------------- truncation bootstrap
def test_truncation_bootstrap_matches_explicit_next_value_gae():
    """The reward-folding trick through fused_gae == textbook GAE with an
    explicit next-value vector and proper truncation bootstrap."""
    w = make_vec_worker(
        0, policy=ActorCriticPolicy(4, 2, loss_kind="ppo"), algo="ppo",
        num_envs=3, rollout_len=12,
    )
    w.vstate, w.act_rng, w.lane_state, cols = w._vrollout_jit(
        w.params, w.vstate, w.act_rng, w.lane_state
    )
    out = w._postprocess_jit(w.params, cols)
    rewards = np.asarray(cols["rewards"], np.float64)
    values = np.asarray(cols["values"], np.float64)
    dones = np.asarray(cols["dones"], np.float64)
    trunc = np.asarray(cols["truncateds"], np.float64)
    v_next = np.asarray(w.policy.value(w.params, cols["next_obs"]), np.float64)
    T, B = rewards.shape
    adv_ref = np.zeros((T, B))
    gae_acc = np.zeros(B)
    for t in reversed(range(T)):
        # Bootstrap from the TRUE successor unless the env terminated.
        not_term = 1.0 - (dones[t] - trunc[t])
        delta = rewards[t] + w.gamma * v_next[t] * not_term - values[t]
        gae_acc = delta + w.gamma * w.lam * (1.0 - dones[t]) * gae_acc
        adv_ref[t] = gae_acc
    assert np.asarray(cols["truncateds"]).sum() > 0, "no truncations exercised"
    # values[t+1] (impl) vs V(next_obs[t]) (oracle) differ only in matmul
    # shape on non-done steps — same number, float32-rounded differently.
    np.testing.assert_allclose(
        np.asarray(out["advantages"]), adv_ref, rtol=1e-4, atol=1e-3
    )
    np.testing.assert_allclose(
        np.asarray(out["returns"]), adv_ref + values, rtol=1e-4, atol=1e-3
    )


def _truncations(case, T, B):
    trunc = np.zeros((T, B), np.float32)
    if case == "mid":
        trunc[2, 0] = trunc[5, [1, 2]] = 1.0
    elif case == "last":
        trunc[T - 1, 1] = 1.0
    elif case == "all":
        trunc[:] = 1.0
    return trunc


@pytest.mark.parametrize("case", ["none", "mid", "last", "all"])
def test_bootstrap_on_read_rows_matches_full_evaluation(case):
    """Evaluating the critic only on the last row and on rows with a
    truncation gives the advantages of evaluating it on all T x N
    successors, and counts the rows it evaluated."""
    w = make_vec_worker(
        0, policy=ActorCriticPolicy(4, 2, loss_kind="ppo"), algo="ppo",
        num_envs=3, rollout_len=8,
    )
    _, _, _, cols = w._vrollout_jit(w.params, w.vstate, w.act_rng, w.lane_state)
    T, B = cols["rewards"].shape
    trunc = _truncations(case, T, B)
    cols = dict(cols, truncateds=jnp.asarray(trunc),
                dones=jnp.maximum(cols["terminateds"], jnp.asarray(trunc)))
    out = w._postprocess_jit(w.params, cols)

    v_next = w.policy.value(w.params, cols["next_obs"])
    adv_ref, ret_ref = gae(
        cols["rewards"] + w.gamma * v_next * cols["truncateds"],
        cols["values"], cols["dones"], v_next[-1], w.gamma, w.lam,
    )
    np.testing.assert_allclose(np.asarray(out["advantages"]), np.asarray(adv_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out["returns"]), np.asarray(ret_ref),
                               rtol=1e-6, atol=1e-6)
    rows_read = 1 + int(trunc[:-1].any(axis=1).sum())
    assert int(out["bootstrap_rows"]) == B * rows_read


def test_lm_bootstrap_evaluates_only_last_row():
    """On the synchronous TokenEnv nothing truncates, so the LM critic's
    full forward sees only each fragment's last [N] row."""
    from repro.optim import adam
    from repro.rl import LMTokenPolicy, TokenEnv

    N, T = 4, 8
    policy = LMTokenPolicy(ctx=16, vocab_size=16, d_model=16, n_layers=1)
    w = VectorizedRolloutWorker(
        TokenEnv(vocab_size=16, ctx=16, horizon=6), policy, algo="ppo",
        num_envs=N, rollout_len=T, optimizer=adam(1e-3), seed=0, decode="cache",
    )
    shapes = []
    orig = policy.value

    def value(params, obs):
        shapes.append(obs.shape)
        return orig(params, obs)

    policy.value = value
    for _ in range(3):
        w.sample()
    assert shapes and set(shapes) == {(N, policy.obs_dim)}
    stats = w.episode_stats()
    assert stats["bootstrap_rows"] == 3 * N  # N per fragment of T * N rows


# -------------------------------------------------------- decoupled inference
def ac_factory():
    return ActorCriticPolicy(4, 2, loss_kind="ppo")


def test_inference_actor_serves_and_counts():
    target = InferenceActor(ac_factory, algo="ppo", seed=3)
    obs = np.zeros((4, 4), np.float32)
    keys = np.stack([np.asarray(jax.random.PRNGKey(i)) for i in range(4)])
    a, logp, v = target.compute_actions(obs, keys)
    assert a.shape == (4,) and logp.shape == (4,) and v.shape == (4,)
    stats = target.stats()
    assert stats["num_requests"] == 1 and stats["num_lane_steps"] == 4
    # Continuous batching defaults to unbounded admission: a whole-batch
    # request is one admit step + one jitted dispatch (bit-parity anchor).
    assert stats["num_dispatches"] == 1 and stats["stateful"] is False
    assert stats["queue"]["num_completed"] == 4.0
    assert stats["queue"]["occupancy_peak"] == 4.0
    vals = target.compute_values(obs)
    np.testing.assert_allclose(vals, v, atol=1e-5)


def test_credit_gate_bounds_and_counts_stalls():
    gate = CreditGate(1)
    gate.acquire()
    import threading
    import time

    acquired = threading.Event()

    def second():
        gate.acquire()
        acquired.set()
        gate.release()

    t = threading.Thread(target=second)
    t.start()
    time.sleep(0.05)
    assert not acquired.is_set()  # blocked: only 1 credit
    gate.release()
    t.join(timeout=5)
    assert acquired.is_set() and gate.stalls == 1 and gate.stall_time_s > 0
    with pytest.raises(ValueError):
        CreditGate(0)


def test_server_mode_bit_matches_local_mode():
    """Decoupled inference is the same batched computation as local mode:
    identical weights + key chains => identical SampleBatch streams."""
    actor = VirtualActor(
        factory=lambda: InferenceActor(ac_factory, algo="ppo", seed=3),
        name="inf", max_restarts=1, backoff_base=0.0,
    )
    client = InferenceClient(actor, credits=CreditGate(2))
    w_srv = make_vec_worker(
        1, policy=ac_factory(), algo="ppo",
        inference="server", inference_client=client,
    )
    client.sync_weights(w_srv.get_weights())
    w_loc = make_vec_worker(1, policy=ac_factory(), algo="ppo")
    w_loc.set_weights(w_srv.get_weights())
    try:
        for _ in range(2):
            b_srv, b_loc = w_srv.sample(), w_loc.sample()
            assert set(b_srv.keys()) == set(b_loc.keys())
            for k in b_srv:
                np.testing.assert_array_equal(b_srv[k], b_loc[k], err_msg=k)
    finally:
        actor.stop()


def test_inference_failure_drops_fragment_and_recovers():
    actor = VirtualActor(
        factory=lambda: InferenceActor(ac_factory, algo="ppo", seed=3),
        name="inf2", max_restarts=1, backoff_base=0.0,
    )
    client = InferenceClient(
        actor, credits=CreditGate(2), weights_provider=lambda: canonical[0]
    )
    w = make_vec_worker(
        1, policy=ac_factory(), algo="ppo",
        inference="server", inference_client=client,
    )
    canonical = [w.get_weights()]
    client.sync_weights()
    try:
        w.sample()
        actor.kill()
        b = w.sample()  # drops the in-flight fragment, recovers, resamples
        assert b.count == w.num_envs * w.rollout_len
        assert w.num_fragments_dropped == 1
        assert client.num_recoveries == 1
        # Recovery re-synced canonical weights into the fresh target.
        srv = jax.tree_util.tree_leaves(actor.sync("get_weights"))
        ref = jax.tree_util.tree_leaves(canonical[0])
        for a, b_ in zip(srv, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    finally:
        actor.stop()


def test_inference_unavailable_after_retry_budget():
    class DeadTarget:
        def compute_actions(self, obs, keys):
            raise RuntimeError("down")

        def set_weights(self, w):
            pass

    w = make_vec_worker(
        1, policy=ac_factory(), algo="ppo",
        inference="server", inference_client=InferenceClient(DeadTarget()),
        max_inference_retries=1,
    )
    with pytest.raises(InferenceUnavailable):
        w.sample()
    assert w.num_fragments_dropped == 2  # initial attempt + one retry


# ------------------------------------------------------------- flow lowering
def test_parallel_rollouts_configures_vector():
    ws = WorkerSet.create(make_vec_worker, 2)
    try:
        it = ParallelRollouts(ws, mode="bulk_sync", vector=6)
        b = next(iter(it))
        assert b.count == 2 * 6 * 8  # workers x lanes x rollout_len
        acks = [a.sync("configure_vectorization") for a in ws.remote_workers()]
        assert all(a["vector"] == 6 for a in acks)
    finally:
        ws.stop()


def test_configure_falls_back_on_plain_workers(caplog):
    def plain(i):
        return RolloutWorker(
            CartPole(), DummyPolicy(4, 2), algo="pg", num_envs=2,
            rollout_len=4, seed=1, worker_index=i,
        )

    ws = WorkerSet.create(plain, 2)
    try:
        with caplog.at_level(logging.WARNING):
            acks = configure_vectorized_rollouts(ws, vector=8)
        assert acks == []
        assert "do not support" in caplog.text
        # The stream still runs on the legacy path.
        b = next(iter(ParallelRollouts(ws, mode="bulk_sync", vector=8)))
        assert b.count == 2 * 2 * 4
    finally:
        ws.stop()


def test_ppo_builder_vector_annotation_renders_and_lowers():
    ws = WorkerSet.create(make_vec_worker, 2)
    try:
        algo = flow.Algorithm.from_plan(
            "ppo", ws, train_batch_size=64, num_sgd_iter=1,
            vector=2, inference="server",
        )
        dot = algo.to_dot()
        assert "vector=2" in dot and "inference=server" in dot
        res = algo.train()
        assert res["counters"]["num_steps_trained"] > 0
        assert len(algo.compiled._inference_actors) == 1
        actor = algo.compiled._inference_actors[0]
        assert actor.sync("stats")["num_requests"] > 0
        algo.stop()
        assert not actor.alive  # flow teardown owns the server
    finally:
        ws.stop()


def test_impala_builder_vector_lowers():
    ws = WorkerSet.create(make_vec_worker, 2)
    algo = flow.Algorithm.from_plan(
        "impala", ws, train_batch_size=64, vector=2,
    )
    try:
        res = algo.train()
        deadline_rounds = 20
        while res["counters"].get("num_steps_trained", 0) == 0 and deadline_rounds:
            res = algo.train()
            deadline_rounds -= 1
        assert res["counters"]["num_steps_trained"] > 0
        acks = [a.sync("configure_vectorization") for a in ws.remote_workers()]
        assert all(a["vector"] == 2 for a in acks)
    finally:
        algo.stop()


def test_set_state_adopts_checkpoint_lane_count():
    """A state saved at vector=8 restores into a vector=4 worker: the lane
    plumbing (VectorEnv, lane_base, jits) follows the checkpoint."""
    w8 = make_vec_worker(1, num_envs=8)
    w8.sample()
    state = w8.get_state()
    ref = w8.sample()
    w4 = make_vec_worker(1, num_envs=4)
    w4.set_state(state)
    assert w4.num_envs == 8
    got = w4.sample()
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k], err_msg=k)


def test_flow_stop_unregisters_weight_sink():
    """A shared WorkerSet outlives any one flow: stopping a server-inference
    flow must remove its weight sink, or later broadcasts from other flows
    would keep RPCing the stopped actor."""
    ws = WorkerSet.create(make_vec_worker, 2)
    try:
        algo = flow.Algorithm.from_plan(
            "ppo", ws, train_batch_size=64, num_sgd_iter=1,
            inference="server", own_workers=False,
        )
        algo.train()
        assert len(ws._weight_sinks) == 1
        algo.stop()
        assert ws._weight_sinks == []
        ws.sync_weights()  # no stopped-actor sink left behind
    finally:
        ws.stop()


@pytest.mark.timeout(180)
def test_server_inference_falls_back_on_process_workers(caplog):
    """Actor handles don't pickle across the RPC boundary: process-backed
    workers keep vectorization but fall back to local inference, loudly."""
    import repro.core as c
    from repro.rl import InferenceActor

    ws = WorkerSet.create(
        make_vec_worker, 1,
        backend=c.ProcessBackend(transport="pickle", start_method="spawn"),
    )
    try:
        client = InferenceClient(InferenceActor(lambda: DummyPolicy(4, 2)))
        with caplog.at_level(logging.WARNING):
            acks = configure_vectorized_rollouts(
                ws, vector=2, inference="server", inference_clients=[client]
            )
        assert acks == [{"vector": 2, "inference": "local", "decode": "forward"}]
        assert "fall back to local inference" in caplog.text
        b = next(iter(ParallelRollouts(ws, mode="bulk_sync")))
        assert b.count == 2 * 8  # vectorization still applied
    finally:
        ws.stop()


def test_vector_validation_errors():
    spec = flow.FlowSpec("bad")
    with pytest.raises(ValueError, match="vector"):
        spec.rollouts(None, vector=0)
    with pytest.raises(ValueError, match="inference mode"):
        spec.rollouts(None, inference="gpu")
    with pytest.raises(ValueError, match="inference_credits"):
        spec.rollouts(None, inference="server", inference_credits=0)
    with pytest.raises(ValueError, match="unknown inference mode"):
        make_vec_worker(0, inference="weird")
