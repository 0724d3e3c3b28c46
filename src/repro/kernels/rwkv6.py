"""RWKV6 WKV recurrence Pallas TPU kernel.

The recurrence

    o_t = r_t . (S_{t-1} + u * k_t^T v_t)
    S_t = diag(w_t) S_{t-1} + k_t^T v_t

is sequential in t, but the [N, N] per-head state never needs to leave VMEM:
the kernel walks time chunks on the innermost (sequential) grid dimension,
carrying S in VMEM scratch, so HBM traffic is O(T*N) for the r/k/v/w/o
streams instead of O(T*N^2) for materialized states.  This is the TPU-native
restatement of the CUDA wkv kernels shipped with RWKV (DESIGN.md §5).

Grid: (B, H, T // chunk); within a chunk a fori_loop runs the exact
step-by-step recurrence on VREG-resident [N] rows.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_pallas"]


def _wkv_kernel(
    r_ref,  # [1, 1, chunk, N]
    k_ref,
    v_ref,
    w_ref,
    u_ref,  # [1, N]
    o_ref,  # [1, 1, chunk, N]
    s_out_ref,  # [1, 1, N, N]
    state_scr,  # [N, N] f32
    *,
    chunk: int,
    num_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0, 0].astype(jnp.float32)  # [chunk, N]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # [N]

    def step(t, carry):
        S, out = carry
        kv = k[t][:, None] * v[t][None, :]          # [N, N]
        o_t = (r[t][:, None] * (S + u[:, None] * kv)).sum(axis=0)  # [N]
        S = w[t][:, None] * S + kv
        out = jax.lax.dynamic_update_slice(out, o_t[None, :], (t, 0))
        return S, out

    S0 = state_scr[...]
    out0 = jnp.zeros((chunk, r.shape[-1]), jnp.float32)
    S, out = jax.lax.fori_loop(0, chunk, step, (S0, out0))
    state_scr[...] = S
    o_ref[0, 0] = out.astype(o_ref.dtype)

    @pl.when(ci == num_chunks - 1)
    def _write_state():
        s_out_ref[0, 0] = state_scr[...]


def rwkv6_pallas(
    r: jax.Array,  # [B, T, H, N]
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # decay in (0, 1]
    u: jax.Array,  # [H, N]
    state: Optional[jax.Array] = None,
    chunk: int = 64,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    B, T, H, N = r.shape
    if state is not None:
        # The kernel's VMEM state scratch is zero-initialized on the first
        # chunk; a nonzero initial state would need an extra input stream.
        # Checked *before* any compute — callers needing stateful resume go
        # through ``ops.rwkv6``, which routes them to the exact reference.
        raise NotImplementedError("rwkv6_pallas starts from zero state")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    chunk = min(chunk, T)
    assert T % chunk == 0, "pad T to chunk multiple"
    nc = T // chunk
    tm = lambda x: x.transpose(0, 2, 1, 3)  # [B, H, T, N]

    kernel = functools.partial(_wkv_kernel, chunk=chunk, num_chunks=nc)
    out, s_out = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, N), lambda b, h, ci: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, ci: (b, h, ci, 0)),
            pl.BlockSpec((1, 1, N, N), lambda b, h, ci: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, N), r.dtype),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
        name="rwkv6",
    )(tm(r), tm(k), tm(v), tm(w), u)
    out = out.transpose(0, 2, 1, 3)
    return out, s_out
