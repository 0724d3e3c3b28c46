"""Single-token decode attention Pallas TPU kernel.

One query token per sequence against a long KV cache: the compute is tiny,
the HBM traffic (streaming the cache) dominates — so the kernel's job is to
stream each cache window tile through VMEM exactly once while carrying the
online-softmax state of every query head in VMEM scratch.

Grid: (B, num_w_blocks) with the cache-window dim innermost/sequential.
The cache is viewed as [B, W, KV*D] (a free reshape of [B, W, KV, D]), so
one K/V block is a ``(block_w, KV*D)`` tile holding every KV head — the
last two block dims are then (multiple of 8, full) and tile on TPU, where a
per-head ``(1, D)`` block would not.  All H query heads are scored in one
MXU matmul against that tile: each head's query is spread into its own KV
head's D-lane slot of an otherwise-zero [H, KV*D] row (block-diagonal), so
``q_bd @ K^T`` is exactly the per-head q.k (the zeros add nothing).  The
[H, KV*D] accumulator is folded back to [H, D] once, at the last tile.
The mask is int32 [B, 1, W] so its ``(1, block_w)`` blocks tile as well.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_pallas"]

NEG_INF = -1e30
# VMEM budget for the double-buffered K and V tiles (4 tiles in flight).
_KV_TILE_BUDGET = 8 * 1024 * 1024


def _head_masks(H: int, g: int, D: int, KVD: int):
    """spread [D, KV*D] (copies a D-vector into every head slot) and
    own [H, KV*D] (query head r keeps only KV head r // g's slot)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (D, KVD), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (D, KVD), 1)
    spread = (col % D == row).astype(jnp.float32)
    hrow = jax.lax.broadcasted_iota(jnp.int32, (H, KVD), 0)
    hcol = jax.lax.broadcasted_iota(jnp.int32, (H, KVD), 1)
    own = (hcol // D == hrow // g).astype(jnp.float32)
    return spread, own


def _decode_kernel(
    q_ref,      # [1, H, D]
    k_ref,      # [1, block_w, KV*D]
    v_ref,      # [1, block_w, KV*D]
    valid_ref,  # [1, 1, block_w] int32 (per-sequence row of the mask)
    o_ref,      # [1, H, D]
    m_scr,      # [H, 1]
    l_scr,      # [H, 1]
    acc_scr,    # [H, KV*D]
    *,
    scale: float,
    g: int,
    num_w_blocks: int,
):
    wi = pl.program_id(1)
    H, D = q_ref.shape[1], q_ref.shape[2]
    KVD = k_ref.shape[2]
    spread, own = _head_masks(H, g, D, KVD)
    exact = jax.lax.Precision.HIGHEST  # 0/1 selection matmuls must not round

    @pl.when(wi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                    # [H, D]
    q_bd = jax.lax.dot_general(
        q, spread, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=exact,
    ) * own                                             # [H, KV*D]
    k = k_ref[0].astype(jnp.float32)                    # [block_w, KV*D]
    v = v_ref[0].astype(jnp.float32)
    vmask = valid_ref[0] != 0                           # [1, block_w]
    s = jax.lax.dot_general(
        q_bd, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                           # [H, block_w]
    s = jnp.where(vmask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # Mask the probabilities, not just the scores: in an all-invalid block
    # every score is NEG_INF, so exp(s - m_new) would be a uniform 1.0 and
    # the row normalizer l would count phantom mass (the empty-cache bug).
    p = jnp.where(vmask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(wi == num_w_blocks - 1)
    def _finalize():
        # Keep each head's own KV slot and fold the KV*D lanes back to D.
        o = jax.lax.dot_general(
            acc_scr[...] * own, spread, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=exact,
        )                                               # [H, D]
        # l == 0 iff no cache slot was valid: attention over an empty cache
        # is defined as zeros, not a uniform average of garbage.
        l = l_scr[...]
        o = o / jnp.maximum(l, 1e-30)
        o_ref[0] = jnp.where(l > 0.0, o, 0.0).astype(o_ref.dtype)


def _pick_block_w(block_w: int, W: int, row_bytes: int) -> int:
    block_w = min(block_w, W)
    while block_w % 256 == 0 and 4 * block_w * row_bytes > _KV_TILE_BUDGET:
        block_w //= 2
    return block_w


def decode_attention_pallas(
    q: jax.Array,        # [B, 1, H, D]
    k_cache: jax.Array,  # [B, W, KV, D]
    v_cache: jax.Array,
    valid: jax.Array,    # [W] or [B, W] bool (per-sequence occupancy)
    block_w: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, _, H, D = q.shape
    W, KV = k_cache.shape[1], k_cache.shape[2]
    g = H // KV
    if valid.ndim == 1:
        valid = jnp.broadcast_to(valid[None], (B, W))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    KVD = KV * D
    block_w = _pick_block_w(block_w, W, KVD * k_cache.dtype.itemsize)
    assert W % block_w == 0, "pad cache window to block multiple"
    nw = W // block_w

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(D), g=g, num_w_blocks=nw
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, nw),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, wi: (b, 0, 0)),
            pl.BlockSpec((1, block_w, KVD), lambda b, wi: (b, wi, 0)),
            pl.BlockSpec((1, block_w, KVD), lambda b, wi: (b, wi, 0)),
            pl.BlockSpec((1, 1, block_w), lambda b, wi: (b, 0, wi)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, wi: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, KVD), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attention",
    )(
        q.reshape(B, H, D),
        k_cache.reshape(B, W, KVD),
        v_cache.reshape(B, W, KVD),
        valid.astype(jnp.int32).reshape(B, 1, W),
    )
    return out.reshape(B, 1, H, D)
