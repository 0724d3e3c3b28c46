"""Plain float32 reference of the CartPole actor-critic, in jax.numpy.

Two separate tanh towers, ``obs -> hidden... -> num_actions`` (policy) and
``obs -> hidden... -> 1`` (value), as RLlib's fully connected default with
``vf_share_layers=False``.  Weights are made here from the seed by the recipe
the configuration implies (He-scaled normal hidden layers, a 0.01-scaled
policy output and a unit-scaled value output, zero biases).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _tower(key, sizes, scale_last):
    keys = jax.random.split(key, len(sizes) - 1)
    out = []
    for i, (din, dout) in enumerate(zip(sizes[:-1], sizes[1:])):
        s = scale_last if i == len(sizes) - 2 else math.sqrt(2.0 / din)
        out.append({
            "w": jax.random.normal(keys[i], (din, dout), jnp.float32) * s,
            "b": jnp.zeros((dout,), jnp.float32),
        })
    return out


def init_params(m: dict, key: jax.Array) -> dict:
    k_pi, k_vf = jax.random.split(key)
    hid = tuple(m["hidden"])
    return {
        "pi": _tower(k_pi, (m["obs_dim"], *hid, m["num_actions"]), 0.01),
        "vf": _tower(k_vf, (m["obs_dim"], *hid, 1), 1.0),
    }


def _apply(tower, x):
    for i, lay in enumerate(tower):
        x = x @ lay["w"] + lay["b"]
        if i < len(tower) - 1:
            x = jnp.tanh(x)
    return x


def logits_value(m: dict, p: dict, obs: jax.Array):
    x = obs.astype(p["pi"][0]["w"].dtype)
    return _apply(p["pi"], x).astype(jnp.float32), _apply(p["vf"], x)[:, 0].astype(jnp.float32)
