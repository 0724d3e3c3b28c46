"""LM token policy: the transformer model zoo as an RL actor-critic, with
KV-cache decode as the rollout fast path.

``LMTokenPolicy`` acts on ``TokenEnv`` observations (token window + length +
step, see ``rl/token_env.py``) with a real ``models/transformer.Model`` trunk:

  * **Learner path** — ``logits_value``/``loss`` run the full no-cache
    ``forward`` (flash-attention forward/backward via ``ops.flash_attention``)
    and read logits + value at each sequence's own last position.  This is
    what ``ShardedLearnerGroup`` fine-tunes.
  * **Decode path** — the PR 9 stateful-policy protocol
    (``init_lane_state``/``compute_actions_stateful``) carries a per-lane KV
    cache: prefill once when a lane starts an episode, then one
    ``decode_step`` per action via ``ops.decode_attention`` — O(1) work per
    token instead of re-running the O(S) forward.  The same surface serves
    both the vectorized rollout scan (``decode='cache'``) and the sticky
    serving tier (cache as server-side lane state).

The two paths are parity-gated: decode logits must match forward logits (see
``decode_parity_gap`` and tests/bench).  The prefill-or-decode choice is a
single ``lax.cond`` on "any lane fresh": with the sync ``TokenEnv`` all lanes
reset together so prefill runs exactly once per episode; with ragged resets
(or after a restore that lost lane state) re-prefilling *all* lanes from
their obs windows rebuilds byte-equivalent caches — correctness never
depends on the episodes being synchronized, only the speedup does.

Lane-state layout: every leaf carries the lane axis leading (the serving
tier gathers/scatters per-lane rows with ``tree_map``), so the model's
scan-stacked block caches ``[num_blocks, B, ...]`` are transposed to
``[B, num_blocks, ...]`` at the protocol boundary and back inside.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models.transformer import Model
from repro.rl.policy import mlp_apply, mlp_init
from repro.rl.token_env import split_obs

PyTree = Any

__all__ = ["LMTokenPolicy"]


def _lm_cfg(
    vocab_size: int, d_model: int, n_layers: int, num_heads: int, num_kv_heads: int
) -> ModelConfig:
    return ModelConfig(
        name="rl-lm",
        arch_type="dense",
        num_layers=n_layers,
        d_model=d_model,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        d_ff=d_model * 4,
        vocab_size=vocab_size,
        head_dim=d_model // num_heads,
        block_pattern=(LayerSpec(kind="attn", mlp="dense"),),
        dtype="float32",
    )


class LMTokenPolicy:
    """Discrete actor-critic over a causal LM; actions are vocabulary tokens."""

    def __init__(
        self,
        ctx: int,
        vocab_size: int,
        d_model: int = 32,
        n_layers: int = 2,
        num_heads: int = 2,
        num_kv_heads: int = 0,
        loss_kind: str = "ppo",
        vf_coef: float = 0.5,
        ent_coef: float = 0.01,
        clip_eps: float = 0.2,
    ):
        self.ctx = ctx
        self.vocab_size = vocab_size
        self.obs_dim = ctx + 2
        self.num_actions = vocab_size
        self.cfg = _lm_cfg(vocab_size, d_model, n_layers, num_heads, num_kv_heads or num_heads)
        self.model = Model(self.cfg)
        self.loss_kind = loss_kind
        self.vf_coef = vf_coef
        self.ent_coef = ent_coef
        self.clip_eps = clip_eps

    def init_params(self, key: jax.Array) -> PyTree:
        k1, k2 = jax.random.split(key)
        return {
            "lm": self.model.init_params(k1),
            "vf": mlp_init(k2, (self.cfg.d_model, 64, 1), scale_last=1.0),
        }

    # ------------------------------------------------------------ forward path
    def _heads(self, params: PyTree, h_last: jax.Array):
        """(logits [B,V], value [B]) from the last-position hidden [B,d]."""
        logits = self.model._head(params["lm"], h_last)
        value = mlp_apply(params["vf"], h_last)[..., 0]
        return logits, value

    def logits_value(self, params: PyTree, obs: jax.Array):
        """No-cache forward: full-sequence attention, read at length-1.

        Accepts any leading batch shape: acting passes [N, D], the GAE
        bootstrap one time row [N, D] at a time, the learner [rows, D].
        """
        lead = obs.shape[:-1]
        tokens, length, _ = split_obs(obs.reshape(-1, obs.shape[-1]), self.ctx)
        h, _ = self.model.forward(params["lm"], tokens)
        idx = jnp.clip(length - 1, 0, self.ctx - 1)
        h_last = h[jnp.arange(h.shape[0]), idx]
        logits, value = self._heads(params, h_last)
        return logits.reshape(lead + (self.vocab_size,)), value.reshape(lead)

    def value(self, params: PyTree, obs: jax.Array) -> jax.Array:
        """Critic value only: the GAE bootstrap's successor values, on one
        [N, D] time row at a time (a fragment's last row and rows with a
        truncation)."""
        return self.logits_value(params, obs)[1]

    def compute_actions(self, params: PyTree, obs: jax.Array, keys: jax.Array):
        """Batched acting with per-lane RNG keys (no cache — the slow path)."""
        logits, value = self.logits_value(params, obs)
        action = jax.vmap(jax.random.categorical)(keys, logits)
        logp_all = jax.nn.log_softmax(logits)
        logp = jnp.take_along_axis(logp_all, action[:, None], axis=-1)[:, 0]
        return action, logp, value, logits

    def act(self, params: PyTree, obs: jax.Array, key: jax.Array):
        """Single-obs acting (legacy per-env contract)."""
        a, lp, v, lg = self.compute_actions(params, obs[None], key[None])
        return a[0], lp[0], v[0], lg[0]

    # ------------------------------------------------ stateful-policy protocol
    def init_lane_state(self, n: int) -> PyTree:
        """Fresh per-lane KV cache (lane axis leading on every leaf)."""
        cache = self.model.init_cache(n, self.ctx)
        cache["pos"] = jnp.zeros((n,), jnp.int32)
        return self._to_lane_layout(cache)

    @staticmethod
    def _to_lane_layout(cache: PyTree) -> PyTree:
        out = dict(cache)
        out["blocks"] = jax.tree_util.tree_map(lambda x: jnp.moveaxis(x, 0, 1), cache["blocks"])
        return out

    @staticmethod
    def _to_model_layout(state: PyTree) -> PyTree:
        out = dict(state)
        out["blocks"] = jax.tree_util.tree_map(lambda x: jnp.moveaxis(x, 1, 0), state["blocks"])
        return out

    def compute_actions_stateful(
        self, params: PyTree, obs: jax.Array, keys: jax.Array, state: PyTree
    ) -> Tuple[jax.Array, jax.Array, jax.Array, PyTree]:
        """One generation step against the per-lane KV cache."""
        # Coerce eager numpy inputs (serving tier, scripts): indexing a
        # numpy array with a tracer inside lax.cond branches fails.
        obs = jnp.asarray(obs)
        B = obs.shape[0]
        tokens, length, t = split_obs(obs, self.ctx)
        cache = self._to_model_layout(state)
        idx = jnp.clip(length - 1, 0, self.ctx - 1)
        # A lane is fresh at episode start (t == 0) or whenever its cache
        # position disagrees with the sequence (state lost/restored/desynced):
        # either way a full re-prefill from the obs window rebuilds it.
        fresh = (t == 0) | (cache["pos"] != length - 1)

        def do_prefill(_):
            _, new_cache, h = self.model.prefill(
                params["lm"], tokens, window=self.ctx, with_hidden=True
            )
            new_cache["pos"] = length
            return h[jnp.arange(B), idx], new_cache

        def do_decode(_):
            last_tok = tokens[jnp.arange(B), idx][:, None]
            _, new_cache, h = self.model.decode_step(
                params["lm"], cache, last_tok, with_hidden=True
            )
            return h[:, 0], new_cache

        h_last, new_cache = jax.lax.cond(jnp.any(fresh), do_prefill, do_decode, None)
        logits, value = self._heads(params, h_last)
        action = jax.vmap(jax.random.categorical)(keys, logits)
        logp_all = jax.nn.log_softmax(logits)
        logp = jnp.take_along_axis(logp_all, action[:, None], axis=-1)[:, 0]
        return action, logp, value, self._to_lane_layout(new_cache)

    # ------------------------------------------------------------ parity gate
    def decode_parity_gap(self, params: PyTree, obs: jax.Array, state: PyTree) -> jax.Array:
        """Max |decode-path logits - forward-path logits| over a batch — the
        number the cache rollout is gated on (tests and bench_rlhf)."""
        tokens, length, _ = split_obs(obs, self.ctx)
        cache = self._to_model_layout(state)
        idx = jnp.clip(length - 1, 0, self.ctx - 1)
        last_tok = tokens[jnp.arange(obs.shape[0]), idx][:, None]
        dec_logits, _ = self.model.decode_step(params["lm"], cache, last_tok)
        fwd_logits, _ = self.logits_value(params, obs)
        return jnp.max(jnp.abs(dec_logits[:, 0] - fwd_logits))

    # ----------------------------------------------------------------- loss
    def loss(self, params: PyTree, batch: Dict[str, jax.Array]):
        from repro.rl.policy import ActorCriticPolicy

        proxy = ActorCriticPolicy.__new__(ActorCriticPolicy)
        proxy.loss_kind = self.loss_kind
        proxy.vf_coef = self.vf_coef
        proxy.ent_coef = self.ent_coef
        proxy.clip_eps = self.clip_eps
        proxy.gamma = 0.99
        proxy.rollout_len = 0
        proxy.logits_value = lambda p, o: self.logits_value(p, o)
        if self.loss_kind == "ppo":
            return proxy._ppo_loss(params, batch)
        return proxy._pg_loss(params, batch)
