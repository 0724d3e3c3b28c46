"""Plain references of the RL mathematics the learners and the postprocess
run: GAE, V-trace, the PPO and V-trace losses, and Adam.  Time-major
``[T, N]`` arrays; straight Python loops over time, no kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def worker_key(seed: int, worker: int) -> jax.Array:
    """The parameter key of rollout worker ``worker`` built from ``seed``
    (the recipe every worker of the system under test follows)."""
    key = jax.random.PRNGKey(seed * 10007 + worker)
    _, pk, _ = jax.random.split(key, 3)
    return pk


def log_softmax_at(logits, actions) -> np.ndarray:
    """log pi(action) of each row of ``logits`` [R, A], in float64 on the host."""
    lg = np.asarray(logits, np.float64)
    mx = lg.max(-1, keepdims=True)
    lse = mx[:, 0] + np.log(np.exp(lg - mx).sum(-1))
    return lg[np.arange(lg.shape[0]), np.asarray(actions, np.int64)] - lse


def gae(rewards, values, dones, last_value, gamma, lam):
    """(advantages, returns) by the GAE recursion, time-major [T, N]."""
    T = rewards.shape[0]
    nxt = jnp.concatenate([values[1:], last_value[None]], 0)
    delta = rewards + gamma * (1.0 - dones) * nxt - values
    adv, acc = [], jnp.zeros_like(last_value)
    for t in reversed(range(T)):
        acc = delta[t] + gamma * lam * (1.0 - dones[t]) * acc
        adv.append(acc)
    adv = jnp.stack(adv[::-1])
    return adv, adv + values


def vtrace(blogp, tlogp, rewards, values, dones, last_value, gamma, rho_clip, c_clip):
    """(vs, pg_advantages), Espeholt et al. 2018, time-major [T, N]."""
    T = rewards.shape[0]
    rho = jnp.exp(tlogp - blogp)
    crho, cs = jnp.minimum(rho_clip, rho), jnp.minimum(c_clip, rho)
    disc = gamma * (1.0 - dones)
    nxt = jnp.concatenate([values[1:], last_value[None]], 0)
    delta = crho * (rewards + disc * nxt - values)
    out, acc = [], jnp.zeros_like(last_value)
    for t in reversed(range(T)):
        acc = delta[t] + disc[t] * cs[t] * acc
        out.append(acc)
    vs = jnp.stack(out[::-1]) + values
    nvs = jnp.concatenate([vs[1:], last_value[None]], 0)
    return vs, crho * (rewards + disc * nvs - values)


def _logp_entropy(logits, actions):
    lp_all = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    logp = jnp.take_along_axis(lp_all, actions.astype(jnp.int32)[:, None], -1)[:, 0]
    return logp, -jnp.sum(jnp.exp(lp_all) * lp_all, -1)


def ppo_loss(logits, values, batch, c):
    """Clipped-surrogate PPO loss over a minibatch of rows."""
    logp, ent = _logp_entropy(logits, batch["actions"])
    ratio = jnp.exp(logp - batch["logp"])
    adv = batch["advantages"]
    pg = -jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - c["clip_eps"], 1 + c["clip_eps"]) * adv)
    vf = jnp.square(values - batch["returns"])
    return jnp.mean(pg) + c["vf_coef"] * jnp.mean(vf) - c["ent_coef"] * jnp.mean(ent)


def vtrace_loss(logits, values, batch, c, T):
    """IMPALA loss over batch-major rows [N * T] holding whole length-T traces."""
    logp, ent = _logp_entropy(logits, batch["actions"])

    def tm(x):
        return x.reshape((-1, T) + x.shape[1:]).swapaxes(0, 1)

    sg = jax.lax.stop_gradient
    vs, pg_adv = vtrace(
        tm(batch["logp"]), sg(tm(logp)), tm(batch["rewards"]), sg(tm(values)),
        tm(batch["dones"]), sg(tm(values)[-1]), c["gamma"], c["rho_clip"], c["c_clip"],
    )
    pg = -jnp.mean(tm(logp) * pg_adv)
    vf = jnp.mean(jnp.square(tm(values) - vs))
    return pg + c["vf_coef"] * vf - c["ent_coef"] * jnp.mean(ent)


def adam_init(params):
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"step": 0, "mu": z, "nu": jax.tree_util.tree_map(jnp.zeros_like, params)}


def adam_step(params, grads, state, lr, o):
    """One Adam step with bias correction; returns (params, state)."""
    step = state["step"] + 1
    b1, b2, eps = o["b1"], o["b2"], o["eps"]
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"], grads)
    c1, c2 = 1.0 / (1 - b1 ** step), 1.0 / (1 - b2 ** step)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m * c1) / (jnp.sqrt(v * c2) + eps), params, mu, nu
    )
    return params, {"step": step, "mu": mu, "nu": nu}
