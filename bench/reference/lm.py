"""Plain float32 reference of the PPO-LM policy, in straightforward jax.numpy.

A decoder-only transformer as the configuration file states it: token
embedding, per layer a pre-norm (RMS) causal multi-head attention with rotary
position embedding (rotate-half, theta from the file) and a pre-norm SwiGLU
MLP, a final RMS norm, an untied output head, and a tanh MLP value head on the
last hidden state.  No kernels, no cache, no batching tricks: every row runs
the whole window with a causal mask and is read at its own last position.

The weights are made here from the seed by the recipe the configuration
implies (the key splits of a rollout worker of index ``worker``), so nothing
the program made is taken.  Given bfloat16 parameters, the same code runs
with activations in bfloat16 (norms, rotary angles and softmax computed in
float32 and rounded back): the lower-precision control.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def init_params(m: dict, key: jax.Array) -> dict:
    """Parameters [num_layers-stacked blocks] from ``key`` (float32)."""
    d, V, L = m["d_model"], m["vocab_size"], m["num_layers"]
    H, KV, hd, F = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["d_ff"]
    k_lm, k_vf = jax.random.split(key)
    keys = jax.random.split(k_lm, 4)
    std = m["embed_init_std"]

    def layer(k):
        (k,) = jax.random.split(k, 1)
        ka, km = jax.random.split(k)
        ks = jax.random.split(ka, 6)
        kms = jax.random.split(km, 3)
        return {
            "norm1": jnp.ones((d,), jnp.float32),
            "attn": {
                "wq": _normal(ks[0], (d, H * hd), 1.0 / math.sqrt(d)),
                "wk": _normal(ks[1], (d, KV * hd), 1.0 / math.sqrt(d)),
                "wv": _normal(ks[2], (d, KV * hd), 1.0 / math.sqrt(d)),
                "wo": _normal(ks[3], (H * hd, d), 1.0 / math.sqrt(H * hd)),
            },
            "norm2": jnp.ones((d,), jnp.float32),
            "mlp": {
                "up": _normal(kms[0], (d, F), 1.0 / math.sqrt(d)),
                "down": _normal(kms[1], (F, d), 1.0 / math.sqrt(F)),
                "gate": _normal(kms[2], (d, F), 1.0 / math.sqrt(d)),
            },
        }

    layers = [layer(k) for k in jax.random.split(keys[2], L)]
    blocks = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    sizes = (d, *m["value_head"], 1)
    vkeys = jax.random.split(k_vf, len(sizes) - 1)
    vf = []
    for i, (din, dout) in enumerate(zip(sizes[:-1], sizes[1:])):
        s = 1.0 if i == len(sizes) - 2 else math.sqrt(2.0 / din)
        vf.append({"w": _normal(vkeys[i], (din, dout), s), "b": jnp.zeros((dout,), jnp.float32)})
    return {
        "lm": {
            "embed": _normal(keys[0], (V, d), std),
            "lm_head": _normal(keys[1], (d, V), std),
            "final_norm": jnp.ones((d,), jnp.float32),
            "blocks": {"0": blocks},
        },
        "vf": vf,
    }


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x [B, S, H, D]; position = index along S."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def hidden(m: dict, p: dict, tokens: jax.Array) -> jax.Array:
    """Final-norm hidden states [B, S, d] of token rows [B, S]."""
    B, S = tokens.shape
    H, KV, hd, eps = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["norm_eps"]
    lm = p["lm"]
    x = lm["embed"][tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))
    for i in range(m["num_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], lm["blocks"]["0"])
        h = _rms(x, lp["norm1"], eps)
        q = _rope((h @ lp["attn"]["wq"]).reshape(B, S, H, hd), m["rope_theta"])
        k = _rope((h @ lp["attn"]["wk"]).reshape(B, S, KV, hd), m["rope_theta"])
        v = (h @ lp["attn"]["wv"]).reshape(B, S, KV, hd)
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, H * hd)
        x = x + o @ lp["attn"]["wo"]
        h = _rms(x, lp["norm2"], eps)
        x = x + (jax.nn.silu(h @ lp["mlp"]["gate"]) * (h @ lp["mlp"]["up"])) @ lp["mlp"]["down"]
    return _rms(x, lm["final_norm"], eps)


def logits_value(m: dict, p: dict, obs: jax.Array):
    """(logits [B, V] float32, value [B] float32) of TokenEnv observations
    [B, ctx + 2] (token window, length, step), read at position length - 1."""
    ctx = obs.shape[-1] - 2
    tokens = obs[:, :ctx].astype(jnp.int32)
    last = jnp.clip(obs[:, ctx].astype(jnp.int32) - 1, 0, ctx - 1)
    h = hidden(m, p, tokens)
    return heads(p, h[jnp.arange(h.shape[0]), last])


def heads(p: dict, h: jax.Array):
    """(logits [R, V] float32, value [R] float32) of final hidden rows [R, d]."""
    logits = (h @ p["lm"]["lm_head"]).astype(jnp.float32)
    v = h
    for i, lay in enumerate(p["vf"]):
        v = v @ lay["w"] + lay["b"]
        if i < len(p["vf"]) - 1:
            v = jnp.tanh(v)
    return logits, v[:, 0].astype(jnp.float32)
