"""PPO on a language model: ``Algorithm.from_plan("ppo_lm")`` over
``TokenEnv`` and ``LMTokenPolicy`` workers with KV-cache decode rollouts.

``build`` makes the system under test from a configuration (``LMTokenPolicy``
sizes) and a traffic mix (prompt lengths, horizon, lanes, fragment length,
learner schedule).  ``Reference`` is the plain float32 model of the same
policy and losses (``bench/reference``), built from the seed alone.

A plan for another policy architecture is a short module of its own: it
supplies ``make_policy(m, t)`` and a ``build`` that passes it to this one's,
subclasses ``Reference`` with its own ``net`` (a plain reference module),
gives its own CPU test sizes (``tiny``), and takes ``check_fragments``,
``cycle_iterations``, ``row_stats``, ``covers``, ``learner_passes`` and the
fault hooks (``BOOTSTRAPS_WITH_CRITIC``, ``batch_unit``) from here (PERF.md,
section 3).

Every lane of a sampler starts and ends its episodes together (``sync``), so
``horizon / rollout_len`` fragments make one whole episode: set-up records
that many of each sampler for the comparison, so that every sequence length
the window decodes at is compared, and the window runs whole episodes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import lm as ref_lm
from bench.reference import rl as ref_rl


# The postprocess bootstraps GAE with the critic's values: ``altered_bootstrap``
# applies.
BOOTSTRAPS_WITH_CRITIC = True

# CPU test sizes (``tiny``).  d_model 512 keeps the logits as spread as at the
# cell's width (a token altered where it is produced must change its
# log-probability visibly).
TINY_MODEL = dict(num_layers=1, d_model=512, num_heads=8, num_kv_heads=8, head_dim=64,
                  d_ff=2048, vocab_size=64)
TINY_TRAFFIC = dict(ctx=32, min_prompt=4, max_prompt=8, horizon=16, num_envs=4,
                    rollout_len=8, train_batch=32, minibatch=8, warmup_iters=1)


def tiny(m: dict, t: dict):
    """(configuration, traffic) cut to sizes a CPU test run can hold."""
    return dict(m, **TINY_MODEL), dict(t, **TINY_TRAFFIC)


def batch_unit(t: dict) -> int:
    """Rows that a cut of the learner's batch keeps together: PPO's loss
    reads each row alone."""
    return 1


def learner_passes(t: dict) -> int:
    """Times the learner steps over each row that one ``train()`` trains:
    PPO's epochs (``learner.rows_gap``, the ``one_epoch`` fault)."""
    return int(t["sgd_epochs"])


def make_policy(m: dict, t: dict):
    """``LMTokenPolicy`` at the configuration's sizes, once ``check_config``
    has passed."""
    from repro.rl import LMTokenPolicy

    check_config(m)
    c = m["loss"]
    return LMTokenPolicy(
        ctx=t["ctx"], vocab_size=m["vocab_size"], d_model=m["d_model"],
        n_layers=m["num_layers"], num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        clip_eps=c["clip_eps"], vf_coef=c["vf_coef"], ent_coef=c["ent_coef"],
    )


def _worker(m: dict, t: dict, seed: int, make_policy, index: int):
    from repro.optim import adam
    from repro.rl import TokenEnv, VectorizedRolloutWorker

    env = TokenEnv(
        vocab_size=m["vocab_size"], ctx=t["ctx"], min_prompt=t["min_prompt"],
        max_prompt=t["max_prompt"], horizon=t["horizon"], sync=t["sync"],
    )
    policy = make_policy(m, t)
    c, o = m["loss"], m["optimizer"]
    return VectorizedRolloutWorker(
        env, policy, algo="ppo", num_envs=t["num_envs"], rollout_len=t["rollout_len"],
        gamma=c["gamma"], lam=c["lam"], seed=seed, worker_index=index, decode="cache",
        optimizer=adam(t["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"]),
    )


def check_config(m: dict) -> None:
    """The program builds a fixed layout; refuse a file that states another."""
    fixed = {"head_dim": m["d_model"] // m["num_heads"], "d_ff": 4 * m["d_model"],
             "activation": "silu", "dtype": "float32", "num_codebooks": 1,
             "norm": "rms", "position_encoding": "rope", "cross_attention": False,
             "value_head": [64], "norm_eps": 1e-5, "rope_theta": 10000.0}
    bad = {k: (m[k], v) for k, v in fixed.items() if m[k] != v}
    if bad:
        raise SystemExit(f"configuration differs from what LMTokenPolicy builds: {bad}")


def build(m: dict, t: dict, seed: int, make_policy=make_policy):
    """(algo, workers) of the cell; ``make_policy(m, t)`` builds each
    worker's policy (a plan of another architecture passes its own)."""
    from repro import flow
    from repro.core.workers import WorkerSet

    ws = WorkerSet.create(functools.partial(_worker, m, t, seed, make_policy),
                          t["sampling_workers"])
    algo = flow.Algorithm.from_plan(
        "ppo_lm", ws, train_batch_size=t["train_batch"], num_sgd_iter=t["sgd_epochs"],
        sgd_minibatch_size=t["minibatch"], num_learners=t["num_learners"], decode="cache",
    )
    return algo, ws


def _whole(a: int, b: int, what: str) -> int:
    n, r = divmod(a, b)
    if r or not n:
        raise SystemExit(f"traffic: {what} ({a} / {b}) is not a whole number")
    return n


def check_fragments(t: dict) -> int:
    """Fragments of each sampler that set-up records for the comparison:
    one whole episode, prompt prefill to the horizon."""
    return _whole(t["horizon"], t["rollout_len"], "fragments per episode")


def cycle_iterations(t: dict) -> int:
    """``train()`` iterations in which every sampler runs one whole episode:
    the window ends on a multiple of it, so each phase of an episode (the
    prefill, each decode length) weighs the same in every run."""
    per_episode = t["horizon"] * t["num_envs"] * t["sampling_workers"]
    return _whole(per_episode, t["train_batch"], "iterations per episode")


def row_stats(batch) -> dict:
    """Sequence length and decode step of each sampled row (for the kernels'
    required work): read from the TokenEnv observation's trailing scalars."""
    obs = np.asarray(batch["obs"])
    return {"length": obs[:, -2].astype(np.int64), "t": obs[:, -1].astype(np.int64)}


def covers(tokens: np.ndarray, length: np.ndarray):
    """(cover rows, which): the rows ``cover`` that hold the longest sequences,
    and for each row the index into ``cover`` of one whose first ``length``
    tokens are the row's own.  Under a causal mask position i reads nothing
    after i, so one forward of a cover row serves every row it covers."""
    order = np.argsort(-length, kind="stable")
    cover, which = [], np.empty(len(length), np.int64)
    for i in order:
        n = int(length[i])
        for j, c in enumerate(cover):
            if np.array_equal(tokens[c, :n], tokens[i, :n]):
                which[i] = j
                break
        else:
            which[i] = len(cover)
            cover.append(i)
    return np.asarray(cover, np.int64), which


class Reference:
    """The plain model and losses, in float32 at full matmul precision, or
    in bfloat16 (the control)."""

    # The plain model: init_params(m, key), hidden(m, p, tokens), heads(p, h)
    # and logits_value(m, p, obs).  A plan of another architecture subclasses
    # this class with its own.
    net = ref_lm
    seqs_per_block = 8    # sequences per forward
    rows_per_block = 512  # rows per read of the heads

    def __init__(self, m: dict, t: dict, seed: int, dtype=jnp.float32):
        self.m, self.t, self.seed, self.dtype = m, t, seed, dtype
        self._hidden = jax.jit(lambda p, tok: self.net.hidden(m, self.cast(p), tok))
        self._heads = jax.jit(lambda p, h, idx: self.net.heads(self.cast(p), h[idx]))
        self._vg = jax.jit(jax.value_and_grad(self._loss))

    def cast(self, p):
        return jax.tree_util.tree_map(lambda x: x.astype(self.dtype), p)

    def init(self, worker: int):
        return self.net.init_params(self.m, ref_rl.worker_key(self.seed, worker))

    def _loss(self, p, batch):
        logits, values = self.net.logits_value(self.m, self.cast(p), batch["obs"])
        return ref_rl.ppo_loss(logits, values, batch, self.m["loss"])

    def logits_value(self, p, obs):
        """(logits, values) of TokenEnv observations [R, ctx + 2], each read at
        its own last position: one causal forward per cover row, in blocks."""
        ctx = obs.shape[1] - 2
        tokens = obs[:, :ctx].astype(np.int32)
        length = obs[:, ctx].astype(np.int64)
        pos = np.clip(length - 1, 0, ctx - 1)
        cover, which = covers(tokens, length)
        S, R = self.seqs_per_block, self.rows_per_block
        logits = np.empty((len(obs), self.m["vocab_size"]), np.float32)
        values = np.empty(len(obs), np.float32)
        for b in range(0, len(cover), S):
            tok = np.zeros((S, ctx), np.int32)
            tok[:len(cover[b:b + S])] = tokens[cover[b:b + S]]
            h = self._hidden(p, jnp.asarray(tok))
            h = h.reshape(S * ctx, h.shape[-1])
            rows = np.nonzero((which >= b) & (which < b + S))[0]
            flat = (which[rows] - b) * ctx + pos[rows]
            for r in range(0, len(rows), R):
                idx = np.zeros(R, np.int32)
                idx[:len(flat[r:r + R])] = flat[r:r + R]
                lg, v = self._heads(p, h, jnp.asarray(idx))
                k = len(rows[r:r + R])
                logits[rows[r:r + R]] = np.asarray(lg)[:k]
                values[rows[r:r + R]] = np.asarray(v)[:k]
            del h
        return logits, values

    def loss_and_grad(self, p, batch):
        return self._vg(p, {k: jnp.asarray(v) for k, v in batch.items()})

    def rollout(self, p, frags):
        """(log-probs of the sampled tokens, values, (advantages, returns)) of
        a sampler's fragments [N*T rows each, batch-major], concatenated.  The
        advantages come from each fragment's own rewards, done flags and
        rollout values, bootstrapped with this reference's values of the true
        successor observations."""
        c, T = self.m["loss"], self.t["rollout_len"]
        n = sum(len(f["obs"]) for f in frags)
        lg, v = self.logits_value(p, np.concatenate([f[k] for k in ("obs", "next_obs")
                                                     for f in frags]))
        logp = ref_rl.log_softmax_at(lg[:n], np.concatenate([f["actions"] for f in frags]))
        del lg

        def tm(x):
            return jnp.asarray(np.asarray(x, np.float32).reshape(-1, T).T)

        adv, ret, off = [], [], n
        for f in frags:
            v_next = tm(v[off:off + len(f["obs"])])
            off += len(f["obs"])
            rewards = tm(f["rewards"]) + c["gamma"] * v_next * tm(f["truncateds"])
            a, r = ref_rl.gae(rewards, tm(f["values"]), tm(f["dones"]), v_next[-1],
                              c["gamma"], c["lam"])
            adv.append(np.asarray(a.T).reshape(-1))
            ret.append(np.asarray(r.T).reshape(-1))
        return logp, v[:n], (np.concatenate(adv), np.concatenate(ret))
