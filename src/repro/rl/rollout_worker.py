"""RolloutWorker: the actor target used by every dataflow plan.

Owns: a vectorized JAX env, a policy, params (+ target params for off-policy
algos), optimizer state, and RNG.  The entire T-step × B-env rollout compiles
to a single ``lax.scan`` XLA program; ``learn_on_batch`` is likewise one jitted
update.  The dataflow layer composes these via the worker protocol
(sample / get_weights / set_weights / compute_gradients / apply_gradients /
learn_on_batch / update_target).
"""

from __future__ import annotations

import functools
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import fused_gae as gae
from repro.optim import Optimizer, adam
from repro.rl.env import Env, VectorEnv, VectorEnvState
from repro.rl.sample_batch import MultiAgentBatch, SampleBatch

PyTree = Any

__all__ = [
    "RolloutWorker",
    "MultiAgentRolloutWorker",
    "VectorizedRolloutWorker",
    "PerEnvRolloutWorker",
    "assemble_fragments",
]

# Episode-id layout: eps_id = (worker_index * MAX_LANES + lane) * EPS_STRIDE
# + per-lane episode counter.  int64 gives ~2^43 worker-lanes' headroom.
MAX_LANES = 4096
EPS_STRIDE = 1 << 20


def _to_numpy_batch(cols: Dict[str, jax.Array]) -> SampleBatch:
    """[T, B, ...] device arrays -> batch-major flattened numpy SampleBatch.

    Batch-major flattening keeps each env's length-T trace contiguous, which
    the v-trace loss relies on to reshape back to time-major.
    """
    out = {}
    for k, v in cols.items():
        v = np.asarray(v)
        v = v.swapaxes(0, 1)  # [B, T, ...]
        out[k] = v.reshape((-1,) + v.shape[2:])
    return SampleBatch(out)


def assemble_fragments(cols: Dict[str, Any], lane_base: np.ndarray) -> SampleBatch:
    """[T, B, ...] rollout columns -> one batch-major SampleBatch whose rows
    carry globally unique int64 ``eps_id`` episode-fragment labels.

    The ``eps_count`` column (each step's per-lane episode index, int32) is
    consumed and replaced by ``eps_id = lane_base[lane] * EPS_STRIDE +
    eps_count``; ``lane_base`` must be globally unique per (worker, lane)
    (see ``MAX_LANES``).  Row order is batch-major, so every lane's length-T
    trace stays contiguous and, within a lane, episode fragments are
    contiguous runs — ``SampleBatch.split_by_episode()`` recovers exactly
    the per-episode fragments, and any slice/concat/shard that respects
    lane boundaries preserves fragment boundaries.
    """
    cols = dict(cols)
    eps_count = np.asarray(cols.pop("eps_count"))  # [T, B]
    batch = _to_numpy_batch(cols)
    lane_base = np.asarray(lane_base, np.int64)
    if lane_base.shape != (eps_count.shape[1],):
        raise ValueError(
            f"lane_base shape {lane_base.shape} != (num_lanes,)={eps_count.shape[1:2]}"
        )
    eps_id = lane_base[:, None] * EPS_STRIDE + eps_count.T.astype(np.int64)  # [B, T]
    batch["eps_id"] = eps_id.reshape(-1)
    return batch


class RolloutWorker:
    def __init__(
        self,
        env: Env,
        policy: Any,
        algo: str = "pg",  # pg | ppo | vtrace | dqn | sac
        num_envs: int = 4,
        rollout_len: int = 64,
        optimizer: Optional[Optimizer] = None,
        gamma: float = 0.99,
        lam: float = 0.95,
        epsilon: float = 0.1,
        target_polyak: float = 0.0,  # 0 -> hard target copy
        seed: int = 0,
        worker_index: int = 0,
    ):
        self.env = env
        self.policy = policy
        self.algo = algo
        self.num_envs = num_envs
        self.rollout_len = rollout_len
        self.gamma = gamma
        self.lam = lam
        self.epsilon = epsilon
        self.target_polyak = target_polyak
        self.worker_index = worker_index

        self._key = jax.random.PRNGKey(seed * 10007 + worker_index)
        self._key, pk, ek = jax.random.split(self._key, 3)
        self.params = policy.init_params(pk)
        self.target_params = jax.tree_util.tree_map(jnp.array, self.params)
        self.optimizer = optimizer or adam(3e-4)
        self.opt_state = self.optimizer.init(self.params)

        self._completed: deque = deque(maxlen=100)
        self._init_env_state(ek)

        self._learn_jit = jax.jit(self._learn)
        self._grad_jit = jax.jit(self._grads)
        self._apply_jit = jax.jit(self._apply)

    def _init_env_state(self, ek: jax.Array) -> None:
        """Build the worker's env-side state (subclass hook: the vectorized
        engine replaces the flat vmapped state with a ``VectorEnv``)."""
        env_keys = jax.random.split(ek, self.num_envs)
        self.env_state, self.obs = jax.vmap(self.env.reset)(env_keys)
        self._ep_returns = jnp.zeros((self.num_envs,), jnp.float32)
        self._rollout_jit = jax.jit(self._rollout)

    # --------------------------------------------------------------- rollout
    def _act(self, params: PyTree, obs: jax.Array, key: jax.Array):
        if self.algo == "dqn":
            return self.policy.act(params, obs, key, jnp.asarray(self.epsilon))
        return self.policy.act(params, obs, key)

    def _rollout(self, params: PyTree, env_state: Any, obs: jax.Array, ep_ret: jax.Array, key: jax.Array):
        def step_fn(carry, key_t):
            env_state, obs, ep_ret = carry
            k_act, k_env = jax.random.split(key_t)
            action, logp, value, _ = self._act(params, obs, k_act)
            env_keys = jax.random.split(k_env, self.num_envs)
            env_state, next_obs, reward, done = jax.vmap(self.env.step)(
                env_state, action, env_keys
            )
            new_ret = ep_ret + reward
            completed = jnp.where(done, new_ret, 0.0)
            ep_ret = jnp.where(done, 0.0, new_ret)
            out = {
                "obs": obs,
                "actions": action,
                "rewards": reward,
                "dones": done.astype(jnp.float32),
                "logp": logp,
                "values": value,
                "next_obs": next_obs,
                "completed": completed,
            }
            return (env_state, next_obs, ep_ret), out

        keys = jax.random.split(key, self.rollout_len)
        (env_state, obs, ep_ret), cols = jax.lax.scan(step_fn, (env_state, obs, ep_ret), keys)

        if self.algo in ("pg", "ppo"):
            _, _, last_value, _ = self._act(params, obs, keys[-1])
            adv, ret = gae(
                cols["rewards"], cols["values"], cols["dones"], last_value, self.gamma, self.lam
            )
            cols["advantages"] = adv
            cols["returns"] = ret
        return env_state, obs, ep_ret, cols

    def sample(self) -> SampleBatch:
        self._key, k = jax.random.split(self._key)
        self.env_state, self.obs, self._ep_returns, cols = self._rollout_jit(
            self.params, self.env_state, self.obs, self._ep_returns, k
        )
        # Select by the done mask, not by a nonzero return: an episode whose
        # return is exactly 0 (a sparse reward) is still a completed episode.
        completed = np.asarray(cols.pop("completed"))
        for r in completed[np.asarray(cols["dones"]) != 0.0]:
            self._completed.append(float(r))
        if self.algo in ("dqn", "sac"):
            for k_ in ("logp", "values"):
                cols.pop(k_, None)
        return _to_numpy_batch(cols)

    def sample_with_count(self) -> Tuple[SampleBatch, int]:
        b = self.sample()
        return b, b.count

    # ----------------------------------------------------------------- learn
    # NOTE: target_params must be an explicit argument (never closed over) or
    # jit would bake the trace-time snapshot in as a constant.
    def _loss_for(self, params: PyTree, target_params: PyTree, batch: Dict[str, jax.Array], key: jax.Array):
        if self.algo == "dqn":
            return self.policy.loss(params, target_params, batch)
        if self.algo == "sac":
            return self.policy.loss(params, target_params, batch, key)
        return self.policy.loss(params, batch)

    def _grads(self, params: PyTree, target_params: PyTree, batch: Dict[str, jax.Array], key: jax.Array):
        (loss, aux), grads = jax.value_and_grad(self._loss_for, has_aux=True)(
            params, target_params, batch, key
        )
        return grads, loss, aux

    def _apply(self, params: PyTree, opt_state: PyTree, grads: PyTree):
        return self.optimizer.apply(params, grads, opt_state)

    def _learn(self, params: PyTree, target_params: PyTree, opt_state: PyTree, batch: Dict[str, jax.Array], key: jax.Array):
        (loss, aux), grads = jax.value_and_grad(self._loss_for, has_aux=True)(
            params, target_params, batch, key
        )
        params, opt_state = self.optimizer.apply(params, grads, opt_state)
        return params, opt_state, loss, aux

    # Host-side metadata columns that never enter jitted losses (eps_id is
    # int64, which JAX would silently truncate without x64 mode).
    _HOST_COLUMNS = frozenset({"batch_indices", "eps_id"})

    @classmethod
    def _device_batch(cls, batch: SampleBatch) -> Dict[str, jax.Array]:
        return {
            k: jnp.asarray(v) for k, v in batch.items() if k not in cls._HOST_COLUMNS
        }

    def learn_on_batch(self, batch: SampleBatch, policy_id: Optional[str] = None) -> Dict[str, Any]:
        self._key, k = jax.random.split(self._key)
        self.params, self.opt_state, loss, aux = self._learn_jit(
            self.params, self.target_params, self.opt_state, self._device_batch(batch), k
        )
        info = {"loss": float(loss)}
        for name, v in aux.items():
            if name == "td_error":
                info["td_error"] = np.asarray(v)
            else:
                info[name] = float(v)
        self._post_update()
        return info

    def _post_update(self) -> None:
        """Per-update side effects beyond the optimizer step (single hook so
        sharded learner groups replay the exact same behaviour): SAC tracks
        its target network by polyak averaging."""
        if self.algo == "sac" and self.target_polyak > 0:
            tau = self.target_polyak
            self.target_params = jax.tree_util.tree_map(
                lambda t, p: (1 - tau) * t + tau * p, self.target_params, self.params
            )

    def compute_gradients(self, batch: SampleBatch) -> Tuple[PyTree, Dict[str, Any]]:
        self._key, k = jax.random.split(self._key)
        grads, loss, aux = self._grad_jit(
            self.params, self.target_params, self._device_batch(batch), k
        )
        info = {"loss": float(loss), "batch_count": batch.count}
        return grads, info

    def apply_gradients(self, grads: PyTree) -> None:
        self.params, self.opt_state = self._apply_jit(self.params, self.opt_state, grads)

    # ------------------------------------------------------------- messaging
    def get_weights(self) -> PyTree:
        return self.params

    def set_weights(self, weights: PyTree) -> None:
        self.params = weights

    def update_target(self) -> None:
        self.target_params = jax.tree_util.tree_map(jnp.array, self.params)

    def episode_stats(self) -> Dict[str, float]:
        if not self._completed:
            return {"episode_reward_mean": float("nan"), "episodes": 0}
        return {
            "episode_reward_mean": float(np.mean(self._completed)),
            "episodes": len(self._completed),
        }

    # ------------------------------------------------------------ durability
    def get_state(self) -> Dict[str, Any]:
        """Resumable rollout-side state (weights are checkpointed separately
        by ``Algorithm.save``): env auto-reset state, RNG, episode stats."""
        return {
            "key": np.asarray(self._key),
            "env_state": jax.tree_util.tree_map(np.asarray, self.env_state),
            "obs": np.asarray(self.obs),
            "ep_returns": np.asarray(self._ep_returns),
            "completed": list(self._completed),
        }

    def set_state(self, state: Dict[str, Any]) -> None:
        self._key = jnp.asarray(state["key"])
        self.env_state = jax.tree_util.tree_map(jnp.asarray, state["env_state"])
        self.obs = jnp.asarray(state["obs"])
        self._ep_returns = jnp.asarray(state["ep_returns"])
        self._completed = deque(state["completed"], maxlen=100)

    # --------------------------------------------------------------- MAML
    def inner_adapt(self, batch: SampleBatch) -> None:
        """One inner-loop PG step on worker-local params (first-order MAML)."""
        self.learn_on_batch(batch)

    def reset_inner(self) -> None:
        # Meta-params were just broadcast via set_weights; nothing else to do
        # because inner adaptation mutated self.params in place.
        pass


class VectorizedRolloutWorker(RolloutWorker):
    """Vectorized rollout engine: a ``VectorEnv`` stepped with one batched
    policy dispatch per step (``policy.compute_actions``, per-lane RNG).

    Differences from the base worker:

      * the whole T×N rollout is still one jitted ``lax.scan``, but env
        auto-reset, per-lane key chains, and episode accounting live in an
        explicit ``VectorEnvState`` — checkpointable (``get_state``) and
        reconfigurable at lowering time (``configure_vectorization``);
      * batches are assembled as per-episode *fragments*: every row carries
        a globally unique int64 ``eps_id``, plus ``terminateds``/
        ``truncateds`` split so consumers can tell env death from horizon
        cuts;
      * GAE routes through ``repro.kernels.ops.fused_gae`` with truncation-
        aware bootstrap: at a truncated step the successor value (from the
        TRUE pre-reset next obs) is folded into the reward, so advantage
        math is correct across artificial horizons;
      * optional decoupled inference (``inference='server'``): actions come
        from an ``InferenceActor`` via an ``InferenceClient`` (batched
        request per step, credit-bounded in flight).  If the server fails
        mid-rollout the in-flight fragment is dropped
        (``num_fragments_dropped``), the client's recovery path restarts
        the actor and re-syncs weights, and sampling resumes from the live
        env state;
      * optional cached decode (``decode='cache'``): a policy implementing
        the stateful-policy protocol (``init_lane_state`` /
        ``compute_actions_stateful``) carries per-lane model state — e.g.
        an LM's KV cache — through the rollout scan, so acting is one
        decode step per token instead of a full forward (the RLHF fast
        path; parity-gated in tests/bench_rlhf).
    """

    def __init__(
        self,
        env: Env,
        policy: Any,
        algo: str = "pg",
        num_envs: int = 8,
        rollout_len: int = 64,
        inference: str = "local",
        inference_client: Any = None,
        max_inference_retries: int = 3,
        decode: str = "forward",
        **kwargs: Any,
    ):
        if inference not in ("local", "server"):
            raise ValueError(f"unknown inference mode {inference!r}")
        if decode not in ("forward", "cache"):
            raise ValueError(f"unknown decode mode {decode!r}")
        if decode == "cache" and not hasattr(policy, "init_lane_state"):
            raise ValueError(
                "decode='cache' needs a stateful policy "
                "(init_lane_state/compute_actions_stateful)"
            )
        self.inference = inference
        self.inference_client = inference_client
        self.max_inference_retries = max_inference_retries
        self.num_fragments_dropped = 0
        self.decode = decode
        super().__init__(
            env, policy, algo=algo, num_envs=num_envs, rollout_len=rollout_len, **kwargs
        )

    # ------------------------------------------------------------ state init
    def _rebuild_plumbing(self) -> None:
        """(Re)derive everything that depends on ``self.num_envs``: the
        VectorEnv, lane-id bases, and the jitted entry points.  Called at
        init, on ``configure_vectorization(vector=...)`` resizes, and when
        ``set_state`` adopts a checkpoint taken at a different lane count."""
        if self.num_envs > MAX_LANES:
            raise ValueError(f"num_envs {self.num_envs} > MAX_LANES {MAX_LANES}")
        self.venv = VectorEnv(self.env, self.num_envs)
        self._lane_base = (
            self.worker_index * MAX_LANES + np.arange(self.num_envs, dtype=np.int64)
        )
        self._vrollout_jit = jax.jit(self._vrollout)
        self._postprocess_jit = jax.jit(self._postprocess_cols)
        self._vstep_jit = jax.jit(self.venv.step)
        self._act1_jit = jax.jit(self._act)

    def _init_env_state(self, ek: jax.Array) -> None:
        self._rebuild_plumbing()
        k_env, k_act = jax.random.split(ek)
        self.vstate = self.venv.reset(k_env)
        self.act_rng = jax.vmap(lambda i: jax.random.fold_in(k_act, i))(
            jnp.arange(self.num_envs)
        )
        self._reset_lane_state()

    def _reset_lane_state(self) -> None:
        """Fresh per-lane model state for the cached-decode path (an empty
        pytree when decode='forward', so the scan carry shape is uniform)."""
        self.lane_state = (
            self.policy.init_lane_state(self.num_envs) if self.decode == "cache" else {}
        )

    # -------------------------------------------------------------- lowering
    def configure_vectorization(
        self,
        vector: Optional[int] = None,
        inference: Optional[str] = None,
        client: Any = None,
        decode: Optional[str] = None,
    ) -> Dict[str, Any]:
        """Reconfigure lanes / inference mode / decode path (FlowSpec
        annotation lowering).

        Resizing rebuilds the ``VectorEnv`` with fresh per-lane key chains
        derived from the worker's RNG; switching to ``'server'`` without a
        client falls back to local inference (flagged in the ack), and
        ``decode='cache'`` on a policy without the stateful protocol falls
        back to ``'forward'`` likewise.
        """
        if vector is not None and int(vector) != self.num_envs:
            self.num_envs = int(vector)
            self._key, ek = jax.random.split(self._key)
            self._init_env_state(ek)
        if inference is not None:
            if inference not in ("local", "server"):
                raise ValueError(f"unknown inference mode {inference!r}")
            if client is not None:
                self.inference_client = client
            if inference == "server" and self.inference_client is None:
                inference = "local"
            self.inference = inference
        if decode is not None:
            if decode not in ("forward", "cache"):
                raise ValueError(f"unknown decode mode {decode!r}")
            if decode == "cache" and not hasattr(self.policy, "init_lane_state"):
                decode = "forward"
            if decode != self.decode:
                self.decode = decode
                self._reset_lane_state()
                self._vrollout_jit = jax.jit(self._vrollout)
        return {"vector": self.num_envs, "inference": self.inference, "decode": self.decode}

    # --------------------------------------------------------------- rollout
    def _compute_actions(self, params: PyTree, obs: jax.Array, keys: jax.Array):
        if self.algo == "dqn":
            return self.policy.compute_actions(
                params, obs, keys, jnp.asarray(self.epsilon)
            )
        return self.policy.compute_actions(params, obs, keys)

    def _vrollout(
        self, params: PyTree, vstate: VectorEnvState, act_rng: jax.Array, lane_state: PyTree
    ):
        stateful = self.decode == "cache"

        def step_fn(carry, _):
            vstate, act_rng, lstate = carry
            act_rng, k_act = VectorEnv._split_lanes(act_rng)
            obs = vstate.obs
            if stateful:
                action, logp, value, lstate = self.policy.compute_actions_stateful(
                    params, obs, k_act, lstate
                )
            else:
                action, logp, value, _ = self._compute_actions(params, obs, k_act)
            vstate, out = self.venv.step(vstate, action)
            cols = {
                "obs": obs,
                "actions": action,
                "rewards": out.reward,
                "dones": out.done.astype(jnp.float32),
                "terminateds": out.terminated.astype(jnp.float32),
                "truncateds": out.truncated.astype(jnp.float32),
                "logp": logp,
                "values": value,
                "next_obs": out.next_obs,
                "completed": out.completed_return,
                "eps_count": out.eps_count,
            }
            return (vstate, act_rng, lstate), cols

        (vstate, act_rng, lane_state), cols = jax.lax.scan(
            step_fn, (vstate, act_rng, lane_state), None, length=self.rollout_len
        )
        return vstate, act_rng, lane_state, cols

    def _postprocess_cols(self, params: PyTree, cols: Dict[str, jax.Array]):
        """Advantage columns over assembled [T, B] rollout columns.

        Shared verbatim by the vectorized and per-env paths (one jitted
        function object), so the two engines are bit-comparable downstream
        of acting.  Truncation bootstrap: the successor value (true
        pre-reset next obs) is folded into the reward at truncated steps,
        then the standard ``fused_gae`` runs with ``dones`` as the
        accumulation mask — identical math to explicit next-value GAE, but
        expressed through the existing kernel dispatch.
        """
        cols = dict(cols)
        if self.algo in ("pg", "ppo"):
            v_next = self.policy.value(params, cols["next_obs"])
            rewards_adj = cols["rewards"] + self.gamma * v_next * cols["truncateds"]
            adv, ret = gae(
                rewards_adj,
                cols["values"],
                cols["dones"],
                v_next[-1],
                self.gamma,
                self.lam,
            )
            cols["advantages"] = adv
            cols["returns"] = ret
        return cols

    def _record_completed(self, completed: np.ndarray, dones: np.ndarray) -> None:
        # Lane-major; zero-return episodes count (see RolloutWorker.sample).
        for r in completed.T.reshape(-1)[dones.T.reshape(-1) != 0.0]:
            self._completed.append(float(r))

    def _emit(self, cols: Dict[str, Any]) -> SampleBatch:
        """Post-scan host path shared by all inference modes."""
        cols = dict(self._postprocess_jit(self.params, cols))
        self._record_completed(np.asarray(cols.pop("completed")), np.asarray(cols["dones"]))
        if self.algo in ("dqn", "sac"):
            for k_ in ("logp", "values"):
                cols.pop(k_, None)
        return assemble_fragments(cols, self._lane_base)

    def sample(self) -> SampleBatch:
        if self.inference == "server":
            return self._sample_server()
        self.vstate, self.act_rng, self.lane_state, cols = self._vrollout_jit(
            self.params, self.vstate, self.act_rng, self.lane_state
        )
        return self._emit(cols)

    # ---------------------------------------------------- decoupled inference
    def _sample_server(self) -> SampleBatch:
        from repro.rl.inference import InferenceUnavailable

        attempts = 0
        while True:
            try:
                cols = self._server_rollout()
                return self._emit(cols)
            except InferenceUnavailable:
                # Drop ONLY the in-flight fragment: env state has advanced
                # to wherever acting stopped; collected step columns are
                # discarded, completed batches are untouched.
                self.num_fragments_dropped += 1
                attempts += 1
                if attempts > self.max_inference_retries:
                    raise
                self.inference_client.recover()

    def _server_rollout(self) -> Dict[str, np.ndarray]:
        # Routing clients (InferenceRouter) want the global lane ids so
        # stateful policies can be sticky-routed; plain clients/bare targets
        # keep the two-argument call (legacy fakes in the chaos suite).
        send_lanes = bool(getattr(self.inference_client, "wants_lanes", False))
        lanes = np.asarray(self._lane_base) if send_lanes else None
        steps: List[Dict[str, np.ndarray]] = []
        for _ in range(self.rollout_len):
            self.act_rng, k_act = VectorEnv._split_lanes(self.act_rng)
            obs = np.asarray(self.vstate.obs)
            if lanes is not None:
                action, logp, value = self.inference_client.compute_actions(
                    obs, np.asarray(k_act), lanes
                )
            else:
                action, logp, value = self.inference_client.compute_actions(
                    obs, np.asarray(k_act)
                )
            self.vstate, out = self._vstep_jit(self.vstate, jnp.asarray(action))
            steps.append(
                {
                    "obs": obs,
                    "actions": action,
                    "rewards": np.asarray(out.reward),
                    "dones": np.asarray(out.done, np.float32),
                    "terminateds": np.asarray(out.terminated, np.float32),
                    "truncateds": np.asarray(out.truncated, np.float32),
                    "logp": logp,
                    "values": value,
                    "next_obs": np.asarray(out.next_obs),
                    "completed": np.asarray(out.completed_return),
                    "eps_count": np.asarray(out.eps_count),
                }
            )
        return {k: np.stack([s[k] for s in steps]) for k in steps[0]}

    # ------------------------------------------------------------ durability
    def get_state(self) -> Dict[str, Any]:
        state = {
            "key": np.asarray(self._key),
            "vstate": VectorEnv.state_to_numpy(self.vstate),
            "act_rng": np.asarray(self.act_rng),
            "completed": list(self._completed),
            "num_fragments_dropped": self.num_fragments_dropped,
        }
        if self.decode == "cache":
            state["lane_state"] = jax.tree_util.tree_map(np.asarray, self.lane_state)
        return state

    def set_state(self, state: Dict[str, Any]) -> None:
        self._key = jnp.asarray(state["key"])
        self.vstate = VectorEnv.state_from_numpy(state["vstate"])
        self.act_rng = jnp.asarray(state["act_rng"])
        self._completed = deque(state["completed"], maxlen=100)
        self.num_fragments_dropped = int(state.get("num_fragments_dropped", 0))
        # Adopt the checkpoint's lane count: a state saved at vector=8
        # restored into a worker configured vector=4 must not leave stale
        # lane plumbing behind (the next sample would crash in assembly).
        lanes = int(self.act_rng.shape[0])
        if lanes != self.num_envs:
            self.num_envs = lanes
            self._rebuild_plumbing()
        if self.decode == "cache":
            ls = state.get("lane_state")
            # A checkpoint without lane state (taken under decode='forward')
            # restores to fresh caches; stale caches self-heal anyway — the
            # stateful policy re-prefills any lane whose cache position
            # disagrees with its observation.
            self.lane_state = (
                jax.tree_util.tree_map(jnp.asarray, ls)
                if ls is not None
                else self.policy.init_lane_state(self.num_envs)
            )

    def episode_stats(self) -> Dict[str, float]:
        stats = super().episode_stats()
        stats["fragments_dropped"] = float(self.num_fragments_dropped)
        return stats


class PerEnvRolloutWorker(VectorizedRolloutWorker):
    """The per-env reference loop: one policy dispatch *per env per step*.

    Identical key chains, env stepping, and fragment assembly as
    ``VectorizedRolloutWorker`` — only the inference dispatch differs (N
    single-obs calls instead of one batched call).  For elementwise envs/
    policies (``StubEnv`` + ``DummyPolicy``) the two engines are
    bit-identical; the determinism regression suite pins that down, and
    ``benchmarks/bench_rollout.py`` measures what the batching is worth.
    """

    def _rebuild_plumbing(self) -> None:
        super()._rebuild_plumbing()
        # Per-lane stepping uses an N=1 VectorEnv over lane slices: vmap
        # over one lane is elementwise-identical to lane i of the N-wide
        # step, so the env key chains match the vectorized engine exactly.
        self._venv1 = VectorEnv(self.env, 1)
        self._lane_step_jit = jax.jit(self._venv1.step)

    @staticmethod
    def _lane(tree: Any, i: int) -> Any:
        return jax.tree_util.tree_map(lambda x: x[i : i + 1], tree)

    def sample(self) -> SampleBatch:
        if self.inference == "server":
            return super().sample()
        B, T = self.num_envs, self.rollout_len
        lanes = [self._lane(self.vstate, i) for i in range(B)]
        act_rng = self.act_rng
        steps: List[Dict[str, np.ndarray]] = []
        for _ in range(T):
            act_rng, k_act = VectorEnv._split_lanes(act_rng)
            per_lane: List[Dict[str, np.ndarray]] = []
            for i in range(B):
                obs_i = lanes[i].obs[0]
                a, logp, value, _ = self._act1_jit(self.params, obs_i, k_act[i])
                lanes[i], out = self._lane_step_jit(lanes[i], a[None])
                per_lane.append(
                    {
                        "obs": np.asarray(obs_i),
                        "actions": np.asarray(a),
                        "rewards": np.asarray(out.reward[0]),
                        "dones": np.asarray(out.done[0], np.float32),
                        "terminateds": np.asarray(out.terminated[0], np.float32),
                        "truncateds": np.asarray(out.truncated[0], np.float32),
                        "logp": np.asarray(logp),
                        "values": np.asarray(value),
                        "next_obs": np.asarray(out.next_obs[0]),
                        "completed": np.asarray(out.completed_return[0]),
                        "eps_count": np.asarray(out.eps_count[0]),
                    }
                )
            steps.append(
                {k: np.stack([p[k] for p in per_lane]) for k in per_lane[0]}
            )
        self.act_rng = act_rng
        self.vstate = jax.tree_util.tree_map(
            lambda *xs: jnp.concatenate(xs, axis=0), *lanes
        )
        cols = {k: np.stack([s[k] for s in steps]) for k in steps[0]}
        return self._emit(cols)


class MultiAgentRolloutWorker:
    """Multi-policy rollouts for the PPO+DQN composition (paper §5.3).

    Each agent index is mapped to a policy id; per-policy experiences are
    returned as a MultiAgentBatch.  Policies may use different algorithms
    (PPO and DQN here), which is exactly the composition the paper enables.
    """

    def __init__(
        self,
        env: Any,  # MultiAgentCartPole
        policy_specs: Dict[str, Dict[str, Any]],
        agent_to_policy: Dict[int, str],
        rollout_len: int = 32,
        gamma: float = 0.99,
        lam: float = 0.95,
        epsilon: float = 0.1,
        seed: int = 0,
        worker_index: int = 0,
    ):
        self.env = env
        self.rollout_len = rollout_len
        self.gamma = gamma
        self.lam = lam
        self.epsilon = epsilon
        self.agent_to_policy = dict(agent_to_policy)
        self._key = jax.random.PRNGKey(seed * 7919 + worker_index)

        self.policies: Dict[str, Any] = {}
        self.params: Dict[str, PyTree] = {}
        self.target_params: Dict[str, PyTree] = {}
        self.optimizers: Dict[str, Optimizer] = {}
        self.opt_states: Dict[str, PyTree] = {}
        self.algos: Dict[str, str] = {}
        for pid, spec in policy_specs.items():
            self._key, k = jax.random.split(self._key)
            self.policies[pid] = spec["policy"]
            self.algos[pid] = spec.get("algo", "ppo")
            self.params[pid] = spec["policy"].init_params(k)
            self.target_params[pid] = jax.tree_util.tree_map(jnp.array, self.params[pid])
            self.optimizers[pid] = spec.get("optimizer") or adam(3e-4)
            self.opt_states[pid] = self.optimizers[pid].init(self.params[pid])

        self._key, ek = jax.random.split(self._key)
        self.env_state, self.obs = env.reset(ek)
        self._ep_returns = jnp.zeros((env.num_agents,), jnp.float32)
        self._completed: deque = deque(maxlen=100)
        self._rollout_jit = jax.jit(self._rollout)
        self._learn_jits: Dict[str, Callable] = {
            pid: jax.jit(functools.partial(self._learn, pid)) for pid in self.policies
        }

    # Agents grouped by policy for vectorized acting.
    def _agents_of(self, pid: str):
        return np.array([a for a, p in self.agent_to_policy.items() if p == pid])

    def _rollout(self, params: Dict[str, PyTree], env_state, obs, ep_ret, key):
        A = self.env.num_agents

        def step_fn(carry, key_t):
            env_state, obs, ep_ret = carry
            k_act, k_env = jax.random.split(key_t)
            actions = jnp.zeros((A,), jnp.int32)
            logps = jnp.zeros((A,), jnp.float32)
            values = jnp.zeros((A,), jnp.float32)
            for pid, pol in self.policies.items():
                idx = self._agents_of(pid)
                o = obs[idx]
                if self.algos[pid] == "dqn":
                    a, lp, v, _ = pol.act(params[pid], o, k_act, jnp.asarray(self.epsilon))
                else:
                    a, lp, v, _ = pol.act(params[pid], o, k_act)
                actions = actions.at[idx].set(a.astype(jnp.int32))
                logps = logps.at[idx].set(lp)
                values = values.at[idx].set(v)
            env_state, next_obs, reward, done = self.env.step(env_state, actions, k_env)
            new_ret = ep_ret + reward
            completed = jnp.where(done, new_ret, 0.0)
            ep_ret = jnp.where(done, 0.0, new_ret)
            out = {
                "obs": obs,
                "actions": actions,
                "rewards": reward,
                "dones": done.astype(jnp.float32),
                "logp": logps,
                "values": values,
                "next_obs": next_obs,
                "completed": completed,
            }
            return (env_state, next_obs, ep_ret), out

        keys = jax.random.split(key, self.rollout_len)
        (env_state, obs, ep_ret), cols = jax.lax.scan(step_fn, (env_state, obs, ep_ret), keys)
        adv, ret = gae(
            cols["rewards"], cols["values"], cols["dones"],
            jnp.zeros_like(ep_ret), self.gamma, self.lam,
        )
        cols["advantages"] = adv
        cols["returns"] = ret
        return env_state, obs, ep_ret, cols

    def sample(self) -> MultiAgentBatch:
        self._key, k = jax.random.split(self._key)
        self.env_state, self.obs, self._ep_returns, cols = self._rollout_jit(
            self.params, self.env_state, self.obs, self._ep_returns, k
        )
        completed = np.asarray(cols.pop("completed"))
        for r in completed[completed != 0.0]:
            self._completed.append(float(r))
        # Split per policy: columns are [T, A, ...].
        batches = {}
        for pid in self.policies:
            idx = self._agents_of(pid)
            sub = {k_: np.asarray(v)[:, idx] for k_, v in cols.items()}
            if self.algos[pid] == "dqn":
                sub.pop("logp", None)
                sub.pop("values", None)
                sub.pop("advantages", None)
                sub.pop("returns", None)
            batches[pid] = _to_numpy_batch(sub)
        return MultiAgentBatch(batches)

    def _learn(self, pid: str, params, target_params, opt_state, batch, key):
        pol = self.policies[pid]
        if self.algos[pid] == "dqn":
            loss_fn = lambda p: pol.loss(p, target_params, batch)
        else:
            loss_fn = lambda p: pol.loss(p, batch)
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = self.optimizers[pid].apply(params, grads, opt_state)
        return params, opt_state, loss, aux

    def learn_on_batch(self, batch: SampleBatch, policy_id: str = "ppo_policy") -> Dict[str, Any]:
        dev = {k: jnp.asarray(v) for k, v in batch.items() if k != "batch_indices"}
        self._key, k = jax.random.split(self._key)
        self.params[policy_id], self.opt_states[policy_id], loss, aux = self._learn_jits[
            policy_id
        ](self.params[policy_id], self.target_params[policy_id], self.opt_states[policy_id], dev, k)
        info: Dict[str, Any] = {"loss": float(loss)}
        if "td_error" in aux:
            info["td_error"] = np.asarray(aux["td_error"])
        return info

    def update_target(self) -> None:
        for pid in self.policies:
            if self.algos[pid] == "dqn":
                self.target_params[pid] = jax.tree_util.tree_map(
                    jnp.array, self.params[pid]
                )

    def get_weights(self) -> Dict[str, PyTree]:
        return dict(self.params)

    def set_weights(self, weights: Dict[str, PyTree]) -> None:
        self.params.update(weights)

    def episode_stats(self) -> Dict[str, float]:
        if not self._completed:
            return {"episode_reward_mean": float("nan"), "episodes": 0}
        return {
            "episode_reward_mean": float(np.mean(self._completed)),
            "episodes": len(self._completed),
        }
