"""IMPALA, the paper's actor-learner dataflow: ``Algorithm.from_plan("impala")``
with asynchronous vectorized samplers, a learner thread, V-trace and a weight
broadcast after every update.

``build`` makes the system under test from a configuration (the actor-critic
towers) and a traffic mix (samplers, lanes, fragment length, batch, requests
in flight).  ``Reference`` is the plain float32 model and V-trace loss.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import mlp as ref_mlp
from bench.reference import rl as ref_rl


# V-trace runs in the learner; the postprocess bootstraps nothing.
BOOTSTRAPS_WITH_CRITIC = False

# CPU test sizes (``tiny``): the configuration's own widths, fewer lanes.
TINY_TRAFFIC = dict(sampling_workers=2, num_envs=16, rollout_len=8, train_batch=128,
                    warmup_iters=3)


def tiny(m: dict, t: dict):
    """(configuration, traffic) cut to sizes a CPU test run can hold."""
    return m, dict(t, **TINY_TRAFFIC)


def batch_unit(t: dict) -> int:
    """Rows that a cut of the learner's batch keeps together: V-trace reads
    whole length-``rollout_len`` traces."""
    return t["rollout_len"]


def learner_passes(t: dict) -> None:
    """The learner thread steps on whatever batches have arrived, so no
    number of learner rows belongs to one ``train()``."""
    return None


def _worker(m: dict, t: dict, seed: int, index: int):
    from repro.optim import adam
    from repro.rl import ActorCriticPolicy, VectorizedRolloutWorker
    from repro.rl import env as envs

    c, o = m["loss"], m["optimizer"]
    policy = ActorCriticPolicy(
        m["obs_dim"], m["num_actions"], hidden=tuple(m["hidden"]), loss_kind="vtrace",
        vf_coef=c["vf_coef"], ent_coef=c["ent_coef"], gamma=c["gamma"],
        rollout_len=t["rollout_len"],
    )
    return VectorizedRolloutWorker(
        getattr(envs, m["env"])(), policy, algo="vtrace", num_envs=t["num_envs"],
        rollout_len=t["rollout_len"], gamma=c["gamma"], seed=seed, worker_index=index,
        optimizer=adam(t["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"]),
    )


def check_config(m: dict) -> None:
    fixed = {"activation": "tanh", "dtype": "float32", "loss": dict(m["loss"], rho_clip=1.0, c_clip=1.0)}
    bad = {k: (m[k], v) for k, v in fixed.items() if m[k] != v}
    if bad:
        raise SystemExit(f"configuration differs from what ActorCriticPolicy builds: {bad}")


def build(m: dict, t: dict, seed: int):
    from repro import flow
    from repro.core.workers import WorkerSet

    check_config(m)
    ws = WorkerSet.create(functools.partial(_worker, m, t, seed), t["sampling_workers"])
    algo = flow.Algorithm.from_plan(
        "impala", ws, train_batch_size=t["train_batch"], num_async=t["num_async"],
        broadcast_interval=t["broadcast_interval"],
    )
    return algo, ws


def check_fragments(t: dict) -> int:
    """Fragments of each sampler that set-up records for the comparison."""
    return 1


def cycle_iterations(t: dict) -> int:
    """Lanes reset on their own, so any whole iteration ends a cycle."""
    return 1


def row_stats(batch) -> dict:
    return {}


class Reference:
    def __init__(self, m: dict, t: dict, seed: int, dtype=jnp.float32):
        self.m, self.t, self.seed, self.dtype = m, t, seed, dtype
        self._lv = jax.jit(self._logits_value)
        self._vg = jax.jit(jax.value_and_grad(self._loss))

    def cast(self, p):
        return jax.tree_util.tree_map(lambda x: x.astype(self.dtype), p)

    def init(self, worker: int):
        return ref_mlp.init_params(self.m, ref_rl.worker_key(self.seed, worker))

    def _logits_value(self, p, obs):
        return ref_mlp.logits_value(self.m, self.cast(p), obs)

    def _loss(self, p, batch):
        logits, values = ref_mlp.logits_value(self.m, self.cast(p), batch["obs"])
        return ref_rl.vtrace_loss(logits, values, batch, self.m["loss"], self.t["rollout_len"])

    def loss_and_grad(self, p, batch):
        return self._vg(p, {k: jnp.asarray(v) for k, v in batch.items()})

    def rollout(self, p, frags):
        """(log-probs of the sampled actions, values, None) of a sampler's
        fragments, concatenated; V-trace runs in the learner, not here."""
        lg, v = self._lv(p, jnp.asarray(np.concatenate([f["obs"] for f in frags])))
        actions = np.concatenate([f["actions"] for f in frags])
        return ref_rl.log_softmax_at(lg, actions), np.asarray(v), None
