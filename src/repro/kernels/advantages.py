"""Pallas TPU kernels for fused advantage estimation (GAE / V-trace).

The reverse-time recurrences in ``repro.rl.advantages`` are sequential in T
but embarrassingly parallel in the batch dimension.  The ``lax.scan``
references materialize the ``next_values``/``deltas`` intermediates in HBM
and dispatch one tiny elementwise op per time step; these kernels instead
grid over batch blocks and keep the whole [T, block_b] column panel resident
in VMEM: the delta computation, the reverse recurrence, and the value-target
epilogue fuse into one reverse loop over rows (ref-indexed row loads and
stores), so HBM traffic is exactly the input streams plus the two outputs.

Layout: all inputs are time-major [T, B] (the same layout the scan
references take), ``last_value`` is [B].  The wrappers flatten arbitrary
trailing dims into B, pad B up to the lane-aligned block size (padded rows
are independent garbage, sliced off on return), and leave T unpadded — T is
the sublane dim and the boundary row (bootstrap ``last_value``) is handled
in-kernel, never by padding.

On CPU (this container) the kernels run under ``interpret=True`` and are
parity-tested against the scan references to 1e-5
(``tests/test_kernel_advantages.py``); the dispatch layer
(``repro.kernels.ops.fused_gae`` / ``fused_vtrace``) selects the scan
reference on CPU and the Pallas kernel on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gae_pallas", "vtrace_pallas"]

_BLOCK_B = 128  # lane dimension of one batch panel


def _row(ref, t):
    """Row ``t`` of a [T, Bb] VMEM panel as [1, Bb] float32 (a dynamic
    sublane load; value-level dynamic slices do not lower on TPU)."""
    return ref[pl.ds(t, 1), :].astype(jnp.float32)


def _gae_kernel(r_ref, v_ref, d_ref, last_ref, adv_ref, ret_ref, *, gamma, lam, T):
    # Reverse loop over rows: the bootstrap boundary (V(s_T) = last) enters
    # as the initial next-value carry, never by padding T.
    def step(i, carry):
        acc, nv = carry
        t = T - 1 - i
        v = _row(v_ref, t)
        nd = 1.0 - _row(d_ref, t)
        delta = _row(r_ref, t) + gamma * nd * nv - v
        acc = delta + gamma * lam * nd * acc
        adv_ref[pl.ds(t, 1), :] = acc.astype(adv_ref.dtype)
        ret_ref[pl.ds(t, 1), :] = (acc + v).astype(ret_ref.dtype)
        return acc, v

    last = last_ref[...].astype(jnp.float32)  # [1, Bb]
    jax.lax.fori_loop(0, T, step, (jnp.zeros_like(last), last))


def _vtrace_kernel(
    blp_ref, tlp_ref, r_ref, v_ref, d_ref, last_ref, vs_ref, pg_ref,
    *, gamma, rho_clip, c_clip, T,
):
    # Carries: acc = vs_t - v_t, the next value V(s_{t+1}) and the next
    # target vs_{t+1}; both start at the bootstrap value.
    def step(i, carry):
        acc, nv, nvs = carry
        t = T - 1 - i
        v = _row(v_ref, t)
        r = _row(r_ref, t)
        rhos = jnp.exp(_row(tlp_ref, t) - _row(blp_ref, t))
        clipped_rhos = jnp.minimum(rho_clip, rhos)
        cs = jnp.minimum(c_clip, rhos)
        discount = gamma * (1.0 - _row(d_ref, t))
        delta = clipped_rhos * (r + discount * nv - v)
        acc = delta + discount * cs * acc
        vs = acc + v
        vs_ref[pl.ds(t, 1), :] = vs.astype(vs_ref.dtype)
        pg = clipped_rhos * (r + discount * nvs - v)
        pg_ref[pl.ds(t, 1), :] = pg.astype(pg_ref.dtype)
        return acc, v, vs

    last = last_ref[...].astype(jnp.float32)  # [1, Bb]
    jax.lax.fori_loop(0, T, step, (jnp.zeros_like(last), last, last))


def _flatten_tm(x: jax.Array) -> jax.Array:
    """[T, ...] -> [T, B] (B = product of trailing dims; B=1 when none)."""
    T = x.shape[0]
    return x.reshape(T, -1) if x.ndim != 1 else x.reshape(T, 1)


def _pad_b(x: jax.Array, block: int) -> jax.Array:
    B = x.shape[1]
    pad = (-B) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x


def _panel_call(kernel, inputs, T, B, dtype, num_outputs, interpret, block_b):
    """Shared pallas_call plumbing: grid over lane-aligned batch panels."""
    block_b = min(block_b, max(B, 1))
    padded = [_pad_b(x, block_b) for x in inputs]
    Bp = padded[0].shape[1]
    nb = Bp // block_b
    spec_tb = pl.BlockSpec((T, block_b), lambda b: (0, b))
    spec_last = pl.BlockSpec((1, block_b), lambda b: (0, b))
    outs = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[spec_tb] * (len(inputs) - 1) + [spec_last],
        out_specs=[spec_tb] * num_outputs,
        out_shape=[jax.ShapeDtypeStruct((T, Bp), dtype)] * num_outputs,
        interpret=interpret,
        name="advantages",
    )(*padded)
    return [o[:, :B] for o in outs]


def gae_pallas(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    last_value: jax.Array,
    gamma: float = 0.99,
    lam: float = 0.95,
    block_b: int = _BLOCK_B,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused GAE; same contract as ``repro.rl.advantages.gae``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T = rewards.shape[0]
    shape, dtype = rewards.shape, rewards.dtype
    r, v, d = map(_flatten_tm, (rewards, values, dones.astype(rewards.dtype)))
    last = last_value.reshape(1, -1).astype(dtype)
    B = r.shape[1]
    kernel = functools.partial(_gae_kernel, gamma=gamma, lam=lam, T=T)
    adv, ret = _panel_call(kernel, [r, v, d, last], T, B, dtype, 2, interpret, block_b)
    return adv.reshape(shape), ret.reshape(shape)


def vtrace_pallas(
    behaviour_logp: jax.Array,
    target_logp: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    last_value: jax.Array,
    gamma: float = 0.99,
    rho_clip: float = 1.0,
    c_clip: float = 1.0,
    block_b: int = _BLOCK_B,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused V-trace; same contract as ``repro.rl.advantages.vtrace``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    T = rewards.shape[0]
    shape, dtype = rewards.shape, rewards.dtype
    blp, tlp, r, v, d = map(
        _flatten_tm,
        (behaviour_logp, target_logp, rewards, values, dones.astype(rewards.dtype)),
    )
    last = last_value.reshape(1, -1).astype(dtype)
    B = r.shape[1]
    kernel = functools.partial(
        _vtrace_kernel, gamma=gamma, rho_clip=rho_clip, c_clip=c_clip, T=T
    )
    vs, pg = _panel_call(
        kernel, [blp, tlp, r, v, d, last], T, B, dtype, 2, interpret, block_b
    )
    return vs.reshape(shape), pg.reshape(shape)
