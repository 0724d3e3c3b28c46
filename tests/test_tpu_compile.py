"""Compile the PPO-on-LM main-path Pallas kernels for a described TPU v5e.

Interpret mode (``tests/test_kernels.py`` and friends) checks the math but
not what the chip's compiler refuses: untileable block shapes, primitives
Mosaic cannot lower, VMEM overruns.  These tests lower each kernel at the
widths ``chip_smoke.py`` runs (musicgen-large: d_model 2048, 32 heads of
64, vocabulary 2048) and compile it against a ``v5e:2x2`` topology that is
described, not attached.  Nothing runs; a pass says only that the chip's
compiler accepts the program and that it contains the Mosaic custom call.

The topology is described inside a module fixture (never at import time):
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.advantages import gae_pallas, vtrace_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.surrogate import ppo_surrogate_pallas

# Smoke widths (chip_smoke.py): MHA 32 x 64 over a 256-token window.
H, KV, D, CTX, VOCAB = 32, 32, 64, 256, 2048
LANES, T = 16, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the compiled program"


def _s(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_forward_compiles(one_chip, dtype):
    qkv = [_s(one_chip, (4, CTX, H, D), dtype)] * 3
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False), *qkv)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grad_compiles(one_chip, dtype):
    def loss(q, k, v):
        out = flash_attention_pallas(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    qkv = [_s(one_chip, (4, CTX, H, D), dtype)] * 3
    _compile(lambda q, k, v: jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v), *qkv)


@pytest.mark.parametrize("per_lane", [False, True])
def test_decode_attention_compiles(one_chip, per_lane):
    q = _s(one_chip, (LANES, 1, H, D))
    cache = _s(one_chip, (LANES, CTX, KV, D))
    valid = _s(one_chip, (LANES, CTX) if per_lane else (CTX,), jnp.bool_)
    _compile(
        lambda q, k, v, m: decode_attention_pallas(q, k, v, m, interpret=False),
        q, cache, cache, valid,
    )


def test_gae_compiles(one_chip):
    cols = [_s(one_chip, (T, LANES))] * 3
    _compile(
        lambda r, v, d, last: gae_pallas(r, v, d, last, interpret=False),
        *cols, _s(one_chip, (LANES,)),
    )


def test_vtrace_compiles(one_chip):
    cols = [_s(one_chip, (T, LANES))] * 5
    _compile(
        lambda *a: vtrace_pallas(*a, interpret=False),
        *cols, _s(one_chip, (LANES,)),
    )


def test_ppo_surrogate_fwd_bwd_compiles(one_chip):
    B = 256

    def fwd_bwd(logits, values, actions, blp, adv, ret):
        def loss(logits, values):
            terms = ppo_surrogate_pallas(
                logits, values, actions, blp, adv, ret, interpret=False
            )
            return sum(jnp.mean(t) for t in terms)

        return jax.value_and_grad(loss, argnums=(0, 1))(logits, values)

    rows = [_s(one_chip, (B,))]
    _compile(
        fwd_bwd,
        _s(one_chip, (B, VOCAB)), rows[0], _s(one_chip, (B,), jnp.int32),
        rows[0], rows[0], rows[0],
    )
