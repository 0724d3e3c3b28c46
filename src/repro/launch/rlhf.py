"""RLHF-style driver: PPO on a language model through the flow runtime.

The workload the serving + learner tiers were built for, end to end:

    TokenEnv (prompts as resets, one action = one token)
      -> VectorizedRolloutWorker(decode='cache')   KV-cache generation
      -> build_ppo_lm FlowSpec                     same graph as build_ppo
      -> Algorithm.train()                         fine-tunes the LM policy

Rollouts generate through the per-lane KV cache (prefill once per episode,
then one ``ops.decode_attention`` step per token); the learner path runs the
full flash-attention forward/backward.  The two paths are parity-gated
(``--parity`` prints the max logits gap).  The stub reward is programmatic
(fraction of generated tokens equal to a target token), so PPO has a clean
rising signal without a learned reward model.

Usage:
  PYTHONPATH=src python -m repro.launch.rlhf --iters 5
  PYTHONPATH=src python -m repro.launch.rlhf --decode forward   # no-cache A/B
  PYTHONPATH=src python -m repro.launch.rlhf --dot              # graph only
"""

from __future__ import annotations

import argparse
import time


def make_rlhf_worker(
    worker_index: int,
    num_envs: int = 8,
    rollout_len: int = 16,
    vocab_size: int = 17,
    ctx: int = 32,
    horizon: int = 16,
    d_model: int = 32,
    n_layers: int = 2,
    num_heads: int = 2,
    num_kv_heads: int = 0,
    decode: str = "cache",
    seed: int = 0,
    lr: float = 3e-3,
):
    """One vectorized LM rollout worker over TokenEnv (shared with tests).

    ``num_kv_heads=0`` means multi-head attention (as many KV heads as
    query heads)."""
    from repro.optim import adam
    from repro.rl import LMTokenPolicy, TokenEnv, VectorizedRolloutWorker

    env = TokenEnv(vocab_size=vocab_size, ctx=ctx, horizon=horizon)
    policy = LMTokenPolicy(
        ctx=ctx, vocab_size=vocab_size, d_model=d_model, n_layers=n_layers,
        num_heads=num_heads, num_kv_heads=num_kv_heads,
    )
    return VectorizedRolloutWorker(
        env, policy, algo="ppo", num_envs=num_envs, rollout_len=rollout_len,
        seed=seed, worker_index=worker_index, decode=decode,
        optimizer=adam(lr),
    )


def decode_parity_gap(worker) -> float:
    """Max |decode logits - forward logits| on the worker's live lanes.

    Prefills a cache holding tokens 0..L-2 of each lane (the newest token
    dropped, t forced to 0), then compares one true ``decode_step`` against
    the no-cache forward over the full window.

    Both paths run with float32 matmuls at full precision: on a TPU the
    default f32 matmul (XLA and Pallas alike) is one bfloat16 pass, whose
    rounding (~1e-2 on these logits) would swamp any cache-path error."""
    import jax
    import numpy as np

    policy = worker.policy
    obs = np.asarray(worker.vstate.obs)
    prev = obs.copy()
    prev[:, policy.ctx] -= 1
    prev[:, policy.ctx + 1] = 0
    state = policy.init_lane_state(obs.shape[0])
    with jax.default_matmul_precision("highest"):
        _, _, _, state = jax.jit(policy.compute_actions_stateful)(
            worker.params, prev,
            jax.random.split(jax.random.PRNGKey(0), obs.shape[0]),
            state,
        )
        return float(jax.jit(policy.decode_parity_gap)(worker.params, obs, state))


def main() -> None:
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--num-envs", type=int, default=8)
    ap.add_argument("--rollout-len", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=17)
    ap.add_argument("--ctx", type=int, default=32)
    ap.add_argument("--horizon", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--kv-heads", type=int, default=0, help="0 = as many as --heads")
    ap.add_argument("--train-batch", type=int, default=256)
    ap.add_argument("--sgd-iters", type=int, default=4)
    ap.add_argument("--minibatch", type=int, default=64)
    ap.add_argument("--num-learners", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--decode", default="cache", choices=("cache", "forward"),
        help="rollout path: per-lane KV cache vs full re-forward",
    )
    ap.add_argument(
        "--parity", action="store_true",
        help="print the decode-vs-forward max logits gap each iteration",
    )
    ap.add_argument("--dot", action="store_true", help="print the flow graph and exit")
    args = ap.parse_args()

    from repro import flow
    from repro.core.workers import WorkerSet

    def factory(i: int):
        return make_rlhf_worker(
            i, num_envs=args.num_envs, rollout_len=args.rollout_len,
            vocab_size=args.vocab, ctx=args.ctx, horizon=args.horizon,
            d_model=args.d_model, n_layers=args.layers, num_heads=args.heads,
            num_kv_heads=args.kv_heads, decode=args.decode,
            seed=args.seed, lr=args.lr,
        )

    ws = WorkerSet.create(factory, args.workers)
    algo = flow.Algorithm.from_plan(
        "ppo_lm", ws,
        train_batch_size=args.train_batch, num_sgd_iter=args.sgd_iters,
        sgd_minibatch_size=args.minibatch, num_learners=args.num_learners,
        decode=args.decode,
    )
    if args.dot:
        print(algo.to_dot())
        algo.stop()
        ws.stop()
        return

    t0 = time.time()
    tokens_per_iter = args.workers * args.num_envs * args.rollout_len
    try:
        for it in range(args.iters):
            res = algo.train()
            ep = res["episodes"]
            line = (
                f"iter {it:3d} reward {ep['episode_reward_mean']:.3f} "
                f"episodes {ep['episodes']:4d} "
                f"trained {res['counters'].get('num_steps_trained', 0):6d} "
                f"({tokens_per_iter / ((time.time() - t0) / (it + 1)):.0f} tok/s)"
            )
            if args.parity:
                line += f" parity_gap {decode_parity_gap(ws.local_worker()):.2e}"
            print(line, flush=True)
    finally:
        algo.stop()
        ws.stop()


if __name__ == "__main__":
    main()
