"""Grouped matmul (MoE expert FFN) Pallas TPU kernel.

After sort-by-expert dispatch, tokens form contiguous per-expert groups.
Each (block_m x D) row tile belongs to exactly one expert (groups are padded
to block_m multiples, as in MegaBlocks); the expert id per tile is computed
on the host and passed as a scalar-prefetch argument so the weight BlockSpec
index map can select w[eid] — no gather of weight matrices through HBM.

Grid: (num_row_tiles, F // block_n).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_gmm_pallas"]


def _gmm_kernel(eid_ref, x_ref, w_ref, o_ref):
    # x: [block_m, D]; w: [1, D, block_n] (expert slice); o: [block_m, block_n]
    o_ref[...] = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32),
        w_ref[0].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


def moe_gmm_pallas(
    x: jax.Array,            # [T, D] rows sorted/padded by expert
    w: jax.Array,            # [E, D, F]
    group_sizes: jax.Array,  # [E] rows per expert (sum == T, block_m-aligned)
    block_m: int = 128,
    block_n: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    T, D = x.shape
    E, _, F = w.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_m = min(block_m, T)
    block_n = min(block_n, F)
    assert T % block_m == 0 and F % block_n == 0
    nm, nn = T // block_m, F // block_n

    # Expert id per row tile (host-side; groups padded to block_m multiples).
    ends = jnp.cumsum(group_sizes)
    tile_starts = jnp.arange(nm, dtype=jnp.int32) * block_m
    eids = jnp.sum(tile_starts[:, None] >= ends[None, :], axis=-1).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nm, nn),
        in_specs=[
            pl.BlockSpec((block_m, D), lambda mi, ni, eids: (mi, 0)),
            pl.BlockSpec((1, D, block_n), lambda mi, ni, eids: (eids[mi], 0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda mi, ni, eids: (mi, ni)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, F), x.dtype),
        interpret=interpret,
        name="moe_gmm",
    )(eids, x, w)
