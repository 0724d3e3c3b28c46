"""Pallas TPU kernel for the fused PPO surrogate loss.

The PPO learn step's elementwise hot loop — log-softmax, action gather,
ratio = exp(logp - logp_old), the clipped-surrogate min, the value-function
square error, and the entropy bonus — is a chain of small XLA ops that each
stream the [B]-row batch through HBM.  This kernel fuses the whole chain
into one pass over lane-aligned batch panels: logits live as an [A, block_b]
panel (A = num_actions on the sublane dim, batch on the lanes), every
intermediate stays in VMEM/VREGs, and HBM traffic is exactly the six input
streams plus the four per-row output terms.

The kernel emits *per-row* terms (pg_i, vf_i, ent_i, kl_i); the batch-mean
reductions and the ``pg + vf_coef*vf - ent_coef*ent`` combination happen in
the dispatch wrapper (``repro.kernels.ops.fused_ppo_loss``) so padding rows
are sliced off before any reduction and the scalar epilogue is shared
bit-for-bit with the CPU reference path.

``pallas_call`` has no transpose rule, but the surrogate loss *must* be
differentiable (it is the training objective), so the op is wrapped in
``jax.custom_vjp`` with a hand-written backward that is itself a Pallas
kernel over the same panels.  The backward mirrors JAX's subgradient
conventions exactly — ``lax.min``/``max`` split ties 0.5/0.5 (the
"balanced_eq" rule), which matters here because ``min(ratio*adv,
clip(ratio)*adv)`` ties *identically* whenever the ratio is inside the clip
band — so gradients match ``jax.grad`` of the jnp oracle to float rounding
(parity-tested to 1e-5 in ``tests/test_kernel_surrogate.py``).

On CPU (this container) the kernels run under ``interpret=True``; the
dispatch layer selects the jnp reference on CPU and this kernel on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ppo_surrogate_pallas"]

_BLOCK_B = 128  # lane dimension of one batch panel


def _softmax_terms(logits, onehot):
    """Shared fwd/bwd recompute: (logp_all, p, logp, entropy) from an
    [A, Bb] logits panel.  Same max-shift as ``jax.nn.log_softmax``."""
    m = jnp.max(logits, axis=0, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m), axis=0, keepdims=True))
    logp_all = logits - lse  # [A, Bb]
    p = jnp.exp(logp_all)
    logp = jnp.sum(onehot * logp_all, axis=0, keepdims=True)  # [1, Bb]
    entropy = -jnp.sum(p * logp_all, axis=0, keepdims=True)
    return logp_all, p, logp, entropy


def _fwd_kernel(
    logits_ref, onehot_ref, v_ref, blp_ref, adv_ref, ret_ref,
    pg_ref, vf_ref, ent_ref, kl_ref, *, clip_eps,
):
    logits = logits_ref[...].astype(jnp.float32)  # [A, Bb]
    onehot = onehot_ref[...].astype(jnp.float32)
    values = v_ref[...].astype(jnp.float32)  # [1, Bb]
    blp = blp_ref[...].astype(jnp.float32)
    adv = adv_ref[...].astype(jnp.float32)
    ret = ret_ref[...].astype(jnp.float32)

    _, _, logp, entropy = _softmax_terms(logits, onehot)
    ratio = jnp.exp(logp - blp)
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps) * adv
    pg_ref[...] = (-jnp.minimum(unclipped, clipped)).astype(pg_ref.dtype)
    vf_ref[...] = jnp.square(values - ret).astype(vf_ref.dtype)
    ent_ref[...] = entropy.astype(ent_ref.dtype)
    kl_ref[...] = (blp - logp).astype(kl_ref.dtype)


def _balanced(x, z, y):
    """d/dx of min/max(x, y) evaluated at result z, matching JAX's
    ``_balanced_eq`` JVP rule: full gradient off-tie, 0.5 on a tie."""
    return jnp.where(x == z, jnp.where(y == z, 0.5, 1.0), 0.0)


def _bwd_kernel(
    logits_ref, onehot_ref, v_ref, blp_ref, adv_ref, ret_ref,
    gpg_ref, gvf_ref, gent_ref, gkl_ref,
    dlogits_ref, donehot_ref, dv_ref, dblp_ref, dadv_ref, dret_ref,
    *, clip_eps,
):
    logits = logits_ref[...].astype(jnp.float32)
    onehot = onehot_ref[...].astype(jnp.float32)
    values = v_ref[...].astype(jnp.float32)
    blp = blp_ref[...].astype(jnp.float32)
    adv = adv_ref[...].astype(jnp.float32)
    ret = ret_ref[...].astype(jnp.float32)
    gpg = gpg_ref[...].astype(jnp.float32)
    gvf = gvf_ref[...].astype(jnp.float32)
    gent = gent_ref[...].astype(jnp.float32)
    gkl = gkl_ref[...].astype(jnp.float32)

    logp_all, p, logp, _ = _softmax_terms(logits, onehot)
    ratio = jnp.exp(logp - blp)
    lo, hi = 1.0 - clip_eps, 1.0 + clip_eps
    mx = jnp.maximum(ratio, lo)
    rc = jnp.minimum(mx, hi)  # == clip(ratio, lo, hi)
    u = ratio * adv
    c = rc * adv
    mn = jnp.minimum(u, c)

    du = _balanced(u, mn, c)
    dc = _balanced(c, mn, u)
    # d clip/d ratio through max-then-min, each with the balanced tie rule.
    dcl = _balanced(ratio, mx, jnp.full_like(ratio, lo)) * _balanced(
        mx, rc, jnp.full_like(ratio, hi)
    )
    g_ratio = -gpg * (du * adv + dc * adv * dcl)
    g_logp = g_ratio * ratio - gkl

    # Cotangent into logp_all: the action gather plus the entropy term
    # dH/dlp_j = -p_j (lp_j + 1); then the log-softmax VJP t - p * sum(t).
    t = g_logp * onehot - gent * p * (logp_all + 1.0)
    dlogits = t - p * jnp.sum(t, axis=0, keepdims=True)

    dlogits_ref[...] = dlogits.astype(dlogits_ref.dtype)
    donehot_ref[...] = (g_logp * logp_all).astype(donehot_ref.dtype)
    dv_ref[...] = (gvf * 2.0 * (values - ret)).astype(dv_ref.dtype)
    dblp_ref[...] = (-g_ratio * ratio + gkl).astype(dblp_ref.dtype)
    dadv_ref[...] = (-gpg * (du * ratio + dc * rc)).astype(dadv_ref.dtype)
    dret_ref[...] = (-gvf * 2.0 * (values - ret)).astype(dret_ref.dtype)


def _pad_b(x: jax.Array, block: int) -> jax.Array:
    pad = (-x.shape[1]) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x


def _panel_call(kernel, inputs, out_rows, B, dtype, interpret, block_b):
    """Grid over lane-aligned batch panels; inputs/outputs are [rows_i, B]
    with per-array row counts (A for logits panels, 1 for flat rows)."""
    block_b = min(block_b, max(B, 1))
    padded = [_pad_b(x, block_b) for x in inputs]
    Bp = padded[0].shape[1]
    nb = Bp // block_b

    def _spec(rows: int) -> pl.BlockSpec:
        return pl.BlockSpec((rows, block_b), lambda b: (0, b))

    outs = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[_spec(x.shape[0]) for x in padded],
        out_specs=[_spec(r) for r in out_rows],
        out_shape=[jax.ShapeDtypeStruct((r, Bp), dtype) for r in out_rows],
        interpret=interpret,
        name="surrogate",
    )(*padded)
    return [o[:, :B] for o in outs]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _surrogate_terms(clip_eps, block_b, interpret, logits_t, onehot_t, values, blp, adv, ret):
    """Per-row surrogate terms (pg_i, vf_i, ent_i, kl_i), each [B].

    logits_t/onehot_t are [A, B] (batch on lanes); the rest are [B].
    """
    A, B = logits_t.shape
    rows = [values[None, :], blp[None, :], adv[None, :], ret[None, :]]
    kernel = functools.partial(_fwd_kernel, clip_eps=clip_eps)
    outs = _panel_call(
        kernel, [logits_t, onehot_t] + rows, [1, 1, 1, 1],
        B, logits_t.dtype, interpret, block_b,
    )
    return tuple(o[0] for o in outs)


def _surrogate_terms_fwd(clip_eps, block_b, interpret, logits_t, onehot_t, values, blp, adv, ret):
    out = _surrogate_terms(
        clip_eps, block_b, interpret, logits_t, onehot_t, values, blp, adv, ret
    )
    return out, (logits_t, onehot_t, values, blp, adv, ret)


def _surrogate_terms_bwd(clip_eps, block_b, interpret, res, g):
    logits_t, onehot_t, values, blp, adv, ret = res
    gpg, gvf, gent, gkl = g
    A, B = logits_t.shape
    rows = [values, blp, adv, ret, gpg, gvf, gent, gkl]
    kernel = functools.partial(_bwd_kernel, clip_eps=clip_eps)
    outs = _panel_call(
        kernel,
        [logits_t, onehot_t] + [x[None, :] for x in rows],
        [A, A, 1, 1, 1, 1],
        B, logits_t.dtype, interpret, block_b,
    )
    dlogits_t, donehot_t = outs[0], outs[1]
    dv, dblp, dadv, dret = (o[0] for o in outs[2:])
    return dlogits_t, donehot_t, dv, dblp, dadv, dret


_surrogate_terms.defvjp(_surrogate_terms_fwd, _surrogate_terms_bwd)


def ppo_surrogate_pallas(
    logits: jax.Array,          # [B, A]
    values: jax.Array,          # [B]
    actions: jax.Array,         # [B] int
    behaviour_logp: jax.Array,  # [B]
    advantages: jax.Array,      # [B]
    returns: jax.Array,         # [B]
    clip_eps: float = 0.2,
    block_b: int = _BLOCK_B,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused per-row PPO surrogate terms; same math as the jnp reference
    (``repro.kernels.ref.ppo_surrogate_ref``).  Returns (pg, vf, ent, kl),
    each [B]; differentiable via a hand-written Pallas backward."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # The action gather becomes a one-hot contraction inside the kernel;
    # built outside so the custom_vjp surface is all-float (the int actions
    # would otherwise need a float0 cotangent).
    onehot = jax.nn.one_hot(
        actions.astype(jnp.int32), logits.shape[-1], dtype=logits.dtype
    )
    return _surrogate_terms(
        float(clip_eps), int(block_b), bool(interpret),
        logits.T, onehot.T, values, behaviour_logp, advantages, returns,
    )
