"""PPO-on-LM workload: TokenEnv semantics, KV-cache decode rollouts through
the flow runtime, decode/forward parity gates, and the build_ppo_lm plan
training end-to-end (the RLHF-shaped acceptance path)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import flow
from repro.configs.base import LayerSpec, ModelConfig
from repro.core.workers import WorkerSet
from repro.launch.rlhf import make_rlhf_worker
from repro.models.transformer import Model
from repro.rl import (
    EOS,
    PAD,
    ActorCriticPolicy,
    LMTokenPolicy,
    TokenEnv,
    TransformerPolicy,
    VectorizedRolloutWorker,
    make_obs,
    split_obs,
    target_token_reward,
)


# ------------------------------------------------------------------ TokenEnv
def test_token_env_obs_layout_roundtrip():
    env = TokenEnv(vocab_size=11, ctx=24, horizon=16)
    st, obs = env.reset(jax.random.PRNGKey(0))
    assert obs.shape == (env.obs_dim,) and obs.dtype == jnp.float32
    tokens, length, t = split_obs(obs[None], env.ctx)
    np.testing.assert_array_equal(np.asarray(tokens[0]), np.asarray(st.tokens))
    assert int(length[0]) == int(st.length) == int(st.prompt_len)
    assert int(t[0]) == 0
    back = make_obs(tokens[0], length[0], t[0])
    np.testing.assert_array_equal(np.asarray(back), np.asarray(obs))
    # Prompt tokens avoid the PAD/EOS codepoints.
    prompt = np.asarray(st.tokens[: int(st.prompt_len)])
    assert (prompt >= 2).all()


def test_token_env_sync_absorbing_eos():
    """sync mode: EOS absorbs (PAD-stepping) and every lane terminates at the
    shared horizon — the invariant the once-per-episode prefill relies on."""
    env = TokenEnv(vocab_size=11, ctx=24, horizon=6, sync=True)
    st, _ = env.reset(jax.random.PRNGKey(1))
    key = jax.random.PRNGKey(2)
    st, _, r, term, trunc = env.step_raw(st, jnp.asarray(EOS), key)
    assert not bool(term) and not bool(trunc) and bool(st.finished)
    for i in range(1, env.horizon):
        st, _, r, term, trunc = env.step_raw(st, jnp.asarray(5), key)
        # Post-EOS actions are absorbed into PAD.
        assert int(st.tokens[int(st.length) - 1]) == PAD
    assert bool(term) and not bool(trunc)
    assert float(r) == 0.0  # no non-PAD generated tokens -> reward 0


def test_token_env_nonsync_eos_terminates():
    env = TokenEnv(vocab_size=11, ctx=24, horizon=6, sync=False)
    st, _ = env.reset(jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(4)
    st, _, _, term, trunc = env.step_raw(st, jnp.asarray(7), key)
    assert not bool(term) and not bool(trunc)
    st, _, _, term, trunc = env.step_raw(st, jnp.asarray(EOS), key)
    assert bool(term) and not bool(trunc)
    # Horizon truncates when EOS never comes.
    st, _ = env.reset(jax.random.PRNGKey(5))
    for _ in range(env.horizon):
        st, _, _, term, trunc = env.step_raw(st, jnp.asarray(7), key)
    assert not bool(term) and bool(trunc)


def test_token_env_reward_is_target_fraction():
    env = TokenEnv(vocab_size=11, ctx=24, horizon=4, sync=True,
                   reward_fn=target_token_reward(target=3))
    st, _ = env.reset(jax.random.PRNGKey(6))
    key = jax.random.PRNGKey(7)
    for a in (3, 5, 3):
        st, _, r, term, _ = env.step_raw(st, jnp.asarray(a), key)
        assert float(r) == 0.0 and not bool(term)
    st, _, r, term, _ = env.step_raw(st, jnp.asarray(3), key)
    assert bool(term)
    assert float(r) == pytest.approx(3 / 4)


def test_token_env_ctx_guard():
    with pytest.raises(ValueError, match="overrun"):
        TokenEnv(ctx=16, max_prompt=8, horizon=16)


# ------------------------------------- prefill -> decode chain (model level)
def _chain_cfg(heads, kv, d_model=32, layers=2):
    return ModelConfig(
        name="chain-test", arch_type="dense", num_layers=layers,
        d_model=d_model, num_heads=heads, num_kv_heads=kv, d_ff=64,
        vocab_size=32, head_dim=d_model // heads,
        block_pattern=(LayerSpec(kind="attn", mlp="dense"),),
        dtype="float32",
    )


@pytest.mark.parametrize("heads,kv", [(4, 4), (4, 2), (4, 1)])
def test_prefill_decode_chain_matches_forward(heads, kv):
    """Multi-step generation through the KV cache must track the no-cache
    forward at every step, across dense MHA / GQA / MQA head layouts."""
    model = Model(_chain_cfg(heads, kv))
    key = jax.random.PRNGKey(8)
    params = model.init_params(key)
    B, S, T = 2, 10, 6
    tokens = jax.random.randint(key, (B, S + T), 0, model.cfg.vocab_size)
    _, cache = model.prefill(params, tokens[:, :S], window=S + T)
    for k in range(S, S + T):
        dec, cache = model.decode_step(params, cache, tokens[:, k : k + 1])
        x, _ = model.forward(params, tokens[:, : k + 1])
        full = model._head(params, x[:, -1:])
        a = np.asarray(full[:, 0], np.float32)
        b = np.asarray(dec[:, 0], np.float32)
        rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
        assert rel < 2e-3, (k, rel)


def test_prefill_window_clamp_then_decode():
    """_fit_window edge: prompt longer than the cache window.  Prefill keeps
    the last W tokens (ring-rotated); decode after the clamp must match the
    sliding-window forward at the new position."""
    model = Model(_chain_cfg(4, 2))
    key = jax.random.PRNGKey(9)
    params = model.init_params(key)
    B, S, W = 2, 24, 16
    tokens = jax.random.randint(key, (B, S + 1), 0, model.cfg.vocab_size)
    _, cache = model.prefill(params, tokens[:, :S], window=W)
    assert cache["blocks"]["0"]["k"].shape[2] == W  # [blocks, B, W, KV, D]
    dec, _ = model.decode_step(params, cache, tokens[:, S : S + 1])
    x, _ = model.forward(params, tokens, window=W)
    full = model._head(params, x[:, -1:])
    a = np.asarray(full[:, 0], np.float32)
    b = np.asarray(dec[:, 0], np.float32)
    rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-9)
    assert rel < 2e-3, rel


def test_prefill_with_hidden_shapes():
    model = Model(_chain_cfg(4, 4))
    params = model.init_params(jax.random.PRNGKey(10))
    tokens = jnp.zeros((2, 8), jnp.int32)
    logits, cache, h = model.prefill(params, tokens, window=8, with_hidden=True)
    assert h.shape == (2, 8, model.cfg.d_model)
    dec, _, h1 = model.decode_step(params, cache, tokens[:, :1], with_hidden=True)
    assert h1.shape == (2, 1, model.cfg.d_model)


# ------------------------------------------------------------- LMTokenPolicy
def test_lm_policy_stateful_matches_forward_over_episode():
    """Decode-path value/logp must track the no-cache forward on every step
    of a live episode, including the prefill step and mid-episode decodes."""
    env = TokenEnv(vocab_size=11, ctx=16, min_prompt=3, max_prompt=6, horizon=8)
    policy = LMTokenPolicy(ctx=16, vocab_size=11, d_model=16, n_layers=1)
    B = 3
    params = policy.init_params(jax.random.PRNGKey(11))
    reset = jax.vmap(env.reset)
    step = jax.vmap(env.step_raw)
    sts, obs = reset(jax.random.split(jax.random.PRNGKey(12), B))
    state = policy.init_lane_state(B)
    for i in range(env.horizon):
        keys = jax.random.split(jax.random.PRNGKey(100 + i), B)
        a, lp, v, state = policy.compute_actions_stateful(params, obs, keys, state)
        logits_f, v_f = policy.logits_value(params, obs)
        np.testing.assert_allclose(np.asarray(v), np.asarray(v_f), atol=1e-4)
        lp_f = jnp.take_along_axis(
            jax.nn.log_softmax(logits_f), a[:, None], axis=-1
        )[:, 0]
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lp_f), atol=1e-4)
        sts, obs, _, term, _ = step(sts, a, keys)
    assert bool(term.all())  # sync horizon
    gap = float(policy.decode_parity_gap(params, obs, state))
    assert gap < 1e-4, gap


def test_lm_policy_self_heals_after_state_loss():
    """A desynced cache (restore from an older checkpoint, lane migration)
    must be rebuilt by re-prefill, not silently trusted."""
    env = TokenEnv(vocab_size=11, ctx=16, min_prompt=3, max_prompt=6, horizon=8)
    policy = LMTokenPolicy(ctx=16, vocab_size=11, d_model=16, n_layers=1)
    B = 2
    params = policy.init_params(jax.random.PRNGKey(13))
    sts, obs = jax.vmap(env.reset)(jax.random.split(jax.random.PRNGKey(14), B))
    keys = jax.random.split(jax.random.PRNGKey(15), B)
    state = policy.init_lane_state(B)
    a, _, _, state = policy.compute_actions_stateful(params, obs, keys, state)
    sts, obs, _, _, _ = jax.vmap(env.step_raw)(sts, a, keys)
    # Fresh (wrong) state mid-episode: pos=0 disagrees with length-1.
    stale = policy.init_lane_state(B)
    _, _, v_stale, _ = policy.compute_actions_stateful(params, obs, keys, stale)
    _, v_f = policy.logits_value(params, obs)
    np.testing.assert_allclose(np.asarray(v_stale), np.asarray(v_f), atol=1e-4)


# --------------------------------------- TransformerPolicy current contract
def test_transformer_policy_contract():
    policy = TransformerPolicy(4, 2, d_model=16, n_layers=1)
    params = policy.init_params(jax.random.PRNGKey(16))
    obs = jax.random.normal(jax.random.PRNGKey(17), (5, 4))
    keys = jax.random.split(jax.random.PRNGKey(18), 5)
    a, lp, v, lg = policy.compute_actions(params, obs, keys)
    assert a.shape == (5,) and lg.shape == (5, 2)
    np.testing.assert_allclose(np.asarray(v), np.asarray(policy.value(params, obs)))
    # Lane i of the batched dispatch reproduces the legacy batched act on
    # that lane's row with that lane's key.
    for i in (0, 3):
        a1, lp1, v1, lg1 = policy.act(params, obs[i : i + 1], keys[i])
        np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(a1[0]))
        np.testing.assert_allclose(np.asarray(lg[i]), np.asarray(lg1[0]), atol=1e-6)
    # Stateful protocol: acts identically, state is a counted pytree.
    st = policy.init_lane_state(5)
    a2, lp2, v2, st2 = policy.compute_actions_stateful(params, obs, keys, st)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a2))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v2), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(st2["steps"]), 1)


# ------------------------------------------------- worker decode='cache' path
def test_worker_cache_decode_sample_columns():
    w = make_rlhf_worker(0, num_envs=4, rollout_len=8, d_model=16, n_layers=1)
    assert w.decode == "cache"
    b = w.sample()
    assert b.count == 4 * 8
    for col in ("actions", "advantages", "logp", "values", "returns"):
        assert col in b, col
    stats = w.episode_stats()
    assert stats["episodes"] >= 0


def test_zero_return_episodes_are_counted():
    """A sparse reward is often exactly 0: such episodes still complete and
    must enter the episode count and mean (they used to be dropped as if
    the lane had not finished)."""
    from repro.optim import adam

    env = TokenEnv(vocab_size=16, ctx=16, horizon=4, reward_fn=lambda *a: jnp.float32(0.0))
    policy = LMTokenPolicy(ctx=16, vocab_size=16, d_model=16, n_layers=1)
    w = VectorizedRolloutWorker(
        env, policy, algo="ppo", num_envs=4, rollout_len=8,
        optimizer=adam(1e-3), seed=0, worker_index=1, decode="cache",
    )
    w.sample()
    stats = w.episode_stats()
    assert stats["episodes"] == 8  # 4 lanes x two horizon-4 episodes
    assert stats["episode_reward_mean"] == 0.0


def test_worker_cache_decode_state_roundtrip():
    w1 = make_rlhf_worker(0, num_envs=4, rollout_len=8, d_model=16, n_layers=1)
    w1.sample()
    state = w1.get_state()
    assert "lane_state" in state
    ref = w1.sample()
    w2 = make_rlhf_worker(0, num_envs=4, rollout_len=8, d_model=16, n_layers=1)
    w2.set_state(state)
    got = w2.sample()
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(ref[k]), np.asarray(got[k]), atol=1e-5, err_msg=k
        )


def test_worker_decode_reconfigure_and_fallback():
    w = make_rlhf_worker(0, num_envs=4, rollout_len=8, d_model=16, n_layers=1)
    ack = w.configure_vectorization(decode="forward")
    assert ack["decode"] == "forward"
    w.sample()
    ack = w.configure_vectorization(decode="cache")
    assert ack["decode"] == "cache"
    w.sample()
    with pytest.raises(ValueError, match="decode"):
        w.configure_vectorization(decode="bogus")
    # A policy without the stateful protocol cannot construct in cache mode...
    from repro.rl import StubEnv

    with pytest.raises(ValueError, match="stateful"):
        VectorizedRolloutWorker(
            StubEnv(max_steps=6), ActorCriticPolicy(4, 2), algo="pg",
            num_envs=2, rollout_len=4, decode="cache",
        )
    # ...and reconfiguring one onto cache falls back to forward.
    plain = VectorizedRolloutWorker(
        StubEnv(max_steps=6), ActorCriticPolicy(4, 2), algo="pg",
        num_envs=2, rollout_len=4,
    )
    ack = plain.configure_vectorization(decode="cache")
    assert ack["decode"] == "forward"


# ------------------------------------------------------------ flow-level plan
def test_decode_annotation_validation():
    def mk(i):
        return make_rlhf_worker(i, num_envs=2, rollout_len=4, d_model=16, n_layers=1)

    ws = WorkerSet.create(mk, 1)
    try:
        with pytest.raises(ValueError, match="decode"):
            flow.build_ppo_lm(ws, decode="bogus")
        spec = flow.build_ppo_lm(ws)
        # A hand-mutated annotation is caught by the static analyzer.
        src = next(n for n in spec.nodes.values() if n.kind == "rollouts")
        src.annotations["decode"] = "bogus"
        diags = flow.analyze(spec, rules=["annotation-lowering"])
        assert any(
            d.severity == flow.Severity.ERROR and "decode" in str(d.message)
            for d in diags
        )
    finally:
        ws.stop()


def test_rlhf_launch_dot_smoke(monkeypatch, capsys):
    import sys

    from repro.launch import rlhf

    monkeypatch.setattr(
        sys, "argv",
        ["rlhf", "--dot", "--workers", "1", "--num-envs", "2",
         "--rollout-len", "4", "--d-model", "16", "--layers", "1"],
    )
    rlhf.main()
    out = capsys.readouterr().out
    assert "digraph" in out and "decode=cache" in out


def test_build_ppo_lm_trains_reward_rises():
    """Acceptance: the PPO-LM plan trains >=3 iterations through the normal
    Algorithm facade, on the KV-cache decode path, and the stub reward
    (fraction of target tokens) rises."""

    def mk(i):
        return make_rlhf_worker(
            i, num_envs=4, rollout_len=16, d_model=16, n_layers=1,
            seed=3, lr=1e-2,
        )

    ws = WorkerSet.create(mk, 2)
    algo = flow.Algorithm.from_plan(
        "ppo_lm", ws, train_batch_size=128, num_sgd_iter=2,
        sgd_minibatch_size=64,
    )
    try:
        dot = algo.to_dot()
        assert "decode=cache" in dot
        rewards = []
        for _ in range(4):
            res = algo.train()
            rewards.append(res["episodes"]["episode_reward_mean"])
        assert res["counters"]["num_steps_trained"] >= 3 * 128
        assert rewards[-1] > rewards[0], rewards
    finally:
        algo.stop()
        ws.stop()
