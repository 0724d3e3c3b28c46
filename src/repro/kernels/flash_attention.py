"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax tiling: grid (B, H, num_q_blocks, num_k_blocks) with the K
dimension innermost (sequential on TPU), carrying the running max / sum /
accumulator in VMEM scratch.  BlockSpecs stream one (block_q x D) Q tile and
(block_k x D) K/V tiles through VMEM; D and block sizes are MXU-aligned
(multiples of 128 for the matmul dims).  GQA is expressed in the K/V index
maps (query head h reads kv head h // group).

``pallas_call`` has no autodiff rule, and the learner differentiates
through attention, so the op is a ``jax.custom_vjp``: the forward is the
Pallas kernel and the backward recomputes attention with the jnp oracle
``ref.chunked_attention`` (per-chunk rematerialized) and takes its VJP.
Gradients therefore cost one extra attention forward in XLA; no Pallas
backward kernel exists yet.

Validated against kernels/ref.py oracles in interpret mode on CPU (forward
and gradients, ``tests/test_kernels.py``); compiled for a v5e chip in
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import chunked_attention

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, 1, block_q, D]
    k_ref,  # [1, 1, block_k, D]
    v_ref,  # [1, 1, block_k, D]
    o_ref,  # [1, 1, block_q, D]
    m_scr,  # [block_q]   running max
    l_scr,  # [block_q]   running sum
    acc_scr,  # [block_q, D] accumulator
    *,
    causal: bool,
    window: int,
    scale: float,
    block_q: int,
    block_k: int,
    num_k_blocks: int,
    q_offset: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= k_pos > q_pos - window

    # Skip fully-masked K blocks (beyond the causal frontier / window).
    block_needed = jnp.logical_or(not causal, ki * block_k <= q_offset + (qi + 1) * block_q - 1)
    if window:
        block_needed = jnp.logical_and(
            block_needed, (ki + 1) * block_k - 1 > q_offset + qi * block_q - window
        )

    @pl.when(block_needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _flash_forward(q, k, v, causal, window, q_offset, block_q, block_k, interpret):
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, "pad seq to block multiples"
    nq, nk = Sq // block_q, Sk // block_k

    # [B, H, S, D] layout so the last two dims tile the MXU.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        window=window,
        scale=1.0 / math.sqrt(D),
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=nk,
        q_offset=q_offset,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, q_offset, block_q, block_k, interpret):
    return _flash_forward(q, k, v, causal, window, q_offset, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, window, q_offset, block_q, block_k, interpret):
    out = _flash_forward(q, k, v, causal, window, q_offset, block_q, block_k, interpret)
    return out, (q, k, v)


def _flash_bwd(causal, window, q_offset, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: chunked_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset
        ),
        q, k, v,
    )
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_pallas(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, KV, D]
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Differentiable flash attention; backward = VJP of the jnp oracle."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _flash(
        q, k, v, bool(causal), int(window), int(q_offset),
        int(block_q), int(block_k), bool(interpret),
    )
