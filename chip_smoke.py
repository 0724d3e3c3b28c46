#!/usr/bin/env python3
"""Bring-up smoke run: PPO on a language model, end to end on a TPU chip.

Runs the system's main path through the entry points a user calls, at the
published widths of the registry model ``musicgen-large``
(``src/repro/configs/musicgen_large.py``) with random weights from a seed,
and checks what comes out.  Every Pallas kernel on the path runs compiled
for the chip; there is no CPU fallback and no interpreter.

    python chip_smoke.py              # one chip (the phases below)
    python chip_smoke.py --chips 4    # four chips: sharded learner parity only

One chip:
  * preflight  -- the first device is a TPU, kernels dispatch to Pallas.
  * kernels    -- each main-path kernel, compiled, against its jnp oracle
                  on a small input.
  * ppo_lm     -- ``launch/rlhf.make_rlhf_worker`` workers under
                  ``Algorithm.from_plan("ppo_lm", decode="cache")`` for a few
                  ``train()`` iterations: KV-cache rollouts, fused GAE, PPO
                  learner.  Checks trained steps rise, finite loss and
                  reward, decode-vs-forward logits gap, and that the compiled
                  rollout and learner programs hold Mosaic kernels.
  * trainer    -- ``launch/train.train`` over the full musicgen-large config
                  (bf16, 4 codebooks, gelu), depth cut, a few steps.
Four chips:
  * sharded    -- one PPO-LM batch through ``ShardedLearnerGroup`` with 4
                  learners against 1: loss and updated parameters agree, and
                  the batch columns live on 4 distinct devices.

Cuts from musicgen-large for the PPO-LM policy (``LMTokenPolicy`` builds a
fixed dense config): depth 48 -> ``PPO_LM["n_layers"]``, one codebook
instead of 4, SwiGLU (silu) instead of gelu, float32 instead of bf16.
Widths are as published: d_model 2048, 32 heads of 64 (MHA), d_ff 8192,
vocabulary 2048.

Every line before the last reports a phase; the last line is one JSON
object ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Any
failure raises, and the script exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# musicgen-large widths as LMTokenPolicy expresses them; depth, lanes and
# fragment length sized for one v5e chip (16 GB).  Two workers (learner and
# sampler) each hold params, target params and Adam moments (4 x 0.53 GiB at
# 2 layers), and the value bootstrap over all T x N fragment rows compiles
# to ~7.3 GiB of temporaries whatever the depth.  On a TPU v5e this size
# peaks at 8.64 GiB (54% of the chip).
PPO_LM = dict(
    d_model=2048, n_layers=2, num_heads=32, num_kv_heads=32, vocab_size=2048,
    ctx=256, horizon=128, num_envs=4, rollout_len=64,
)
# PPO learner: one fragment per iteration, minibatches of whole rows.
LEARN = dict(train_batch=256, minibatch=16, sgd_iters=1)
PPO_ITERS = 3
# Registry trainer: musicgen-large with only num_layers cut.
TRAINER = dict(num_layers=4, batch=8, seq=256, steps=3)
# Sharded learner parity (four chips): rows in the one batch compared.
SHARDED_ROWS = 32
PARITY_TOL = 1e-3  # decode vs forward logits, float32
KERNEL_TOL = 1e-4  # compiled kernel vs jnp oracle, float32
SHARD_TOL = 1e-4   # 4 learners vs 1: loss and updated params


def _log(msg: str) -> None:
    print(msg, flush=True)


class _CompileClock:
    """Sums XLA backend compile seconds reported by JAX's monitoring."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: object) -> None:
        if event == self.EVENT:
            self.seconds += duration

    def lap(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s


def kernel_calls(fn, *args) -> int:
    """How many Mosaic kernels the compiled program of ``fn(*args)`` holds."""
    return fn.lower(*args).compile().as_text().count("tpu_custom_call")


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def preflight() -> dict:
    """Refuse anything but a TPU with the Pallas kernels switched on."""
    import jax

    from repro.kernels import ops
    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
            f"({dev.device_kind}); there is no CPU fallback"
        )
    if ops.FORCE_MODE is not None or not ops.use_pallas():
        raise SystemExit(
            f"chip_smoke: kernels do not dispatch to Pallas "
            f"(FORCE_MODE={ops.FORCE_MODE!r})"
        )
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    _log(f"preflight device_kind={dev.device_kind} count={device['count']} "
         f"compile_cache={cache_dir or 'none'}")
    return device


def kernel_parity_phase(seed: int = 0) -> dict:
    """Each main-path kernel, compiled, against its jnp oracle on a small
    input (float32 matmuls at full precision on both sides)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.advantages import gae_pallas, vtrace_pallas
    from repro.kernels.decode_attention import decode_attention_pallas
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.surrogate import ppo_surrogate_pallas
    from repro.rl.advantages import gae, vtrace

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def rand(shape, scale=1.0):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    def attn_grads(attn, ct):
        return lambda q, k, v: jax.grad(
            lambda *a: jnp.sum(attn(*a) * ct), argnums=(0, 1, 2)
        )(q, k, v)

    def surrogate(terms, *rows):
        return lambda logits, values: jax.value_and_grad(
            lambda lg, v: sum(jnp.mean(t) for t in terms(lg, v, *rows)),
            argnums=(0, 1),
        )(logits, values)

    q, k, v = rand((2, 256, 4, 64)), rand((2, 256, 2, 64)), rand((2, 256, 2, 64))
    ct = rand((2, 256, 4, 64))
    qd, kc, vc = rand((4, 1, 32, 64)), rand((4, 256, 32, 64)), rand((4, 256, 32, 64))
    valid = jnp.arange(256)[None, :] < jnp.array([0, 1, 100, 256])[:, None]
    T, B = 64, 16
    r, val, last = rand((T, B)), rand((T, B)), rand((B,))
    dones = (jax.random.uniform(next(keys), (T, B)) < 0.1).astype(jnp.float32)
    blp, tlp = rand((T, B), 0.1), rand((T, B), 0.1)
    logits, values = rand((256, 2048)), rand((256,))
    actions = jax.random.randint(next(keys), (256,), 0, 2048)
    rows = (actions, rand((256,)) - 8.0, rand((256,)), rand((256,)))
    cases = {
        "flash_attention": (flash_attention_pallas,
                            lambda *a: ref.naive_attention(*a, causal=True), (q, k, v)),
        "flash_attention_grad": (attn_grads(flash_attention_pallas, ct),
                                 attn_grads(lambda *a: ref.naive_attention(*a, causal=True), ct),
                                 (q, k, v)),
        "decode_attention": (decode_attention_pallas, ref.decode_attention_ref,
                             (qd, kc, vc, valid)),
        "gae": (lambda *a: gae_pallas(*a, 0.99, 0.95), lambda *a: gae(*a, 0.99, 0.95),
                (r, val, dones, last)),
        "vtrace": (lambda *a: vtrace_pallas(*a, gamma=0.99), lambda *a: vtrace(*a, gamma=0.99),
                   (blp, tlp, r, val, dones, last)),
        "ppo_surrogate_fwd_bwd": (surrogate(ppo_surrogate_pallas, *rows),
                                  surrogate(ref.ppo_surrogate_ref, *rows), (logits, values)),
    }
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, (kernel, oracle, args) in cases.items():
            got = jax.tree_util.tree_leaves(jax.jit(kernel)(*args))
            want = jax.tree_util.tree_leaves(jax.jit(oracle)(*args))
            out[name] = max(
                float(np.max(np.abs(np.asarray(a) - np.asarray(b)))) for a, b in zip(got, want)
            )
    _log("kernels max_abs_err " + " ".join(f"{n} {e:.3e}" for n, e in out.items()))
    bad = {n: e for n, e in out.items() if not e <= KERNEL_TOL}
    if bad:
        raise AssertionError(f"kernels off their oracles by more than {KERNEL_TOL}: {bad}")
    return out


def _rlhf_factory(sizes: dict, seed: int, lr: float):
    import functools

    from repro.launch.rlhf import make_rlhf_worker

    return functools.partial(
        make_rlhf_worker,
        num_envs=sizes["num_envs"], rollout_len=sizes["rollout_len"],
        vocab_size=sizes["vocab_size"], ctx=sizes["ctx"], horizon=sizes["horizon"],
        d_model=sizes["d_model"], n_layers=sizes["n_layers"],
        num_heads=sizes["num_heads"], num_kv_heads=sizes["num_kv_heads"],
        decode="cache", seed=seed, lr=lr,
    )


def ppo_lm_phase(sizes: dict, learn: dict, iters: int, seed: int = 0, lr: float = 1e-5) -> dict:
    """PPO-LM through the ppo_lm plan; returns what the checks read."""
    from repro import flow
    from repro.core.workers import WorkerSet
    from repro.launch.rlhf import decode_parity_gap

    clock = _CompileClock()
    ws = WorkerSet.create(_rlhf_factory(sizes, seed, lr), 1)
    algo = flow.Algorithm.from_plan(
        "ppo_lm", ws,
        train_batch_size=learn["train_batch"], num_sgd_iter=learn["sgd_iters"],
        sgd_minibatch_size=learn["minibatch"], decode="cache",
    )
    out: dict = {"steps": [], "loss": [], "reward": [], "episodes": 0}
    try:
        for it in range(iters):
            res = algo.train()
            steps = int(res["counters"].get("num_steps_trained", 0))
            loss = float(res["info"].get("loss", float("nan")))
            reward = float(res["episodes"]["episode_reward_mean"])
            out["steps"].append(steps)
            out["loss"].append(loss)
            out["reward"].append(reward)
            out["episodes"] = int(res["episodes"]["episodes"])
            _log(f"ppo_lm iter {it} num_steps_trained {steps} loss {loss:.6g} "
                 f"reward {reward:.6g} episodes {out['episodes']} "
                 f"compile_s {clock.lap():.3f}")
        lw = ws.local_worker()
        rw = ws.remote_workers().actors[0].target  # the thread actor sampling
        out["parity_gap"] = decode_parity_gap(rw)
        # The programs the iterations ran, lowered again at the same shapes
        # (the persistent cache serves the compile): the rollout scan, its
        # postprocess (value bootstrap + GAE) and the learner's step.
        _, _, _, cols = rw._vrollout_jit(rw.params, rw.vstate, rw.act_rng, rw.lane_state)
        out["rollout_kernels"] = kernel_calls(
            rw._vrollout_jit, rw.params, rw.vstate, rw.act_rng, rw.lane_state
        ) + kernel_calls(rw._postprocess_jit, rw.params, cols)
        mb = lw._device_batch(rw._emit(cols).slice(0, learn["minibatch"]))
        out["learner_kernels"] = kernel_calls(
            lw._learn_jit, lw.params, lw.target_params, lw.opt_state, mb, lw._key
        )
        del cols, mb
        out["compile_s_checks"] = clock.lap()
        out["peak_bytes"] = peak_bytes()
    finally:
        algo.stop()
        ws.stop()
    _log(f"ppo_lm parity_gap {out['parity_gap']:.3e} rollout_kernels {out['rollout_kernels']} "
         f"learner_kernels {out['learner_kernels']} peak_bytes {out['peak_bytes']} "
         f"compile_s {out['compile_s_checks']:.3f}")
    steps = out["steps"]
    if any(b <= a for a, b in zip([0] + steps, steps)):
        raise AssertionError(f"num_steps_trained did not rise every iteration: {steps}")
    if not all(math.isfinite(x) for x in out["loss"]):
        raise AssertionError(f"non-finite PPO loss: {out['loss']}")
    if out["episodes"] == 0 or not math.isfinite(out["reward"][-1]):
        raise AssertionError(f"no finite episode reward: {out['reward']}")
    if not out["parity_gap"] <= PARITY_TOL:
        raise AssertionError(f"decode parity gap {out['parity_gap']} > {PARITY_TOL}")
    return out


def trainer_phase(cfg, steps: int, batch: int, seq: int) -> dict:
    """LM pretraining flow (``launch/train``) over a registry config."""
    from repro.launch.train import train

    clock = _CompileClock()
    losses, _ = train(cfg, steps, batch, seq)
    for i, loss in enumerate(losses):
        _log(f"trainer step {i} loss {loss:.6g}")
    out = {"loss": losses, "compile_s": clock.lap(), "peak_bytes": peak_bytes()}
    _log(f"trainer {cfg.name} layers {cfg.num_layers} dtype {cfg.dtype} "
         f"compile_s {out['compile_s']:.3f} peak_bytes {out['peak_bytes']}")
    if len(losses) < steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"registry trainer losses: {losses}")
    return out


def sharded_phase(sizes: dict, rows: int, num_learners: int = 4, seed: int = 0) -> dict:
    """One PPO-LM batch: ``num_learners`` data-parallel learners vs one."""
    import jax
    import numpy as np

    from repro.rl.learner_group import ShardedLearnerGroup

    if jax.device_count() < num_learners:
        raise AssertionError(
            f"{num_learners} learners need {num_learners} devices, "
            f"found {jax.device_count()}"
        )
    make = _rlhf_factory(sizes, seed, lr=1e-5)
    w_one = make(0)
    batch = w_one.sample().slice(0, rows)
    one = ShardedLearnerGroup(w_one, num_learners=1)
    info_one = one.learn_on_batch(batch)

    w_many = make(0)
    many = ShardedLearnerGroup(w_many, num_learners=num_learners)
    if many.num_learners != num_learners:
        raise AssertionError(f"learner group clamped to {many.num_learners}")
    cols, _ = many.shard_batch(batch)
    devices = {d for col in cols.values() for d in col.sharding.device_set}
    info_many = many.learn_on_batch(batch)

    diff = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(
            jax.tree_util.tree_leaves(w_one.params),
            jax.tree_util.tree_leaves(w_many.params),
        )
    )
    out = {
        "loss_1": info_one["loss"], "loss_n": info_many["loss"],
        "param_diff": diff, "devices": len(devices), "peak_bytes": peak_bytes(),
    }
    _log(f"sharded learners {num_learners} vs 1: loss {out['loss_n']:.6g} vs "
         f"{out['loss_1']:.6g} max_param_diff {diff:.3e} batch_devices {len(devices)} "
         f"peak_bytes {out['peak_bytes']}")
    if len(devices) != num_learners:
        raise AssertionError(f"batch columns on {len(devices)} devices, want {num_learners}")
    if abs(out["loss_1"] - out["loss_n"]) > SHARD_TOL or diff > SHARD_TOL:
        raise AssertionError(f"sharded learner parity off: {out}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repro package under {SRC}; run from a checkout")
    sys.path.insert(0, str(SRC))

    device = preflight()
    if device["count"] < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but {device['count']} devices")
    if args.chips == 4:
        sharded_phase(PPO_LM, SHARDED_ROWS)
    else:
        from repro.configs import get_config

        kernel_parity_phase()
        t = time.perf_counter()
        ppo = ppo_lm_phase(PPO_LM, LEARN, PPO_ITERS)
        if not (ppo["rollout_kernels"] > 0 and ppo["learner_kernels"] > 0):
            raise AssertionError(f"no Mosaic kernel in a compiled program: {ppo}")
        _log(f"ppo_lm phase done in {time.perf_counter() - t:.1f} s")
        cfg = dataclasses.replace(
            get_config("musicgen-large"), num_layers=TRAINER["num_layers"]
        )
        t = time.perf_counter()
        trainer_phase(cfg, TRAINER["steps"], TRAINER["batch"], TRAINER["seq"])
        _log(f"trainer phase done in {time.perf_counter() - t:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
