"""LM training driver: the paper's dataflow model driving pjit SPMD steps.

The training loop IS a dataflow graph (ppo-shaped, minus the RL loss),
declared as a ``FlowSpec`` and run through the ``Algorithm`` facade:

    data actors -> par_source -> batch_across_shards -> merge
                -> SPMD train step (pjit-fused synchronous fragment)
                -> report

Data pipeline shards are actors (one per host in production; N virtual
actors here); the learner's ``learn_on_batch`` is the pjit-fused synchronous
fragment (core/spmd.py).  On this CPU container use --smoke for a reduced
config; the same flags drive the full configs on a real pod.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --smoke --steps 20
"""

from __future__ import annotations

import argparse
import time
from typing import Callable, List, Optional

import numpy as np


def build_lm_flow(workers, pipes):
    """The LM pretrain dataflow as a declarative graph."""
    from repro.core.metrics import get_metrics
    from repro.flow import FlowSpec, pure

    spec = FlowSpec("lm_pretrain")

    def _merge(shards):
        return {
            k: np.concatenate([s[k] for s in shards], axis=0) for k in shards[0]
        }

    @pure
    def _train(batch):  # dict batches (no .count/.minibatches)
        info = workers.local_worker().learn_on_batch(batch)
        get_metrics().counters["num_steps_trained"] += batch["tokens"].shape[0]
        return batch, info

    data_op = (
        spec.par_source(pipes, lambda p: p.sample(), name="TokenPipeline")
        .batch_across_shards()
        .for_each(pure(_merge), label="MergeShards")
    )
    spec.set_output(data_op.for_each(_train, label="SPMDTrainStep").report())
    return spec


def build_trainer(cfg, batch: int, seq: int, data_shards: int, lr: float, steps: int):
    """(workers, pipes, learner) for ``build_lm_flow`` on this host's mesh:
    one SPMD learner over an ``(n, 1)`` data x model mesh of the n local
    devices, fed by ``data_shards`` token-pipeline actors."""
    from repro.configs.base import InputShape
    from repro.core.actor import ActorPool
    from repro.core.spmd import SPMDLearnerWorker, SPMDTrainContext
    from repro.core.workers import WorkerSet
    from repro.data import TokenPipeline
    from repro.launch.mesh import make_local_mesh
    from repro.optim import adamw, chain_clip_by_global_norm, linear_warmup_cosine

    shape = InputShape("train", seq, batch, "train")
    opt = chain_clip_by_global_norm(
        adamw(linear_warmup_cosine(lr, 20, max(steps, 100)), weight_decay=0.1),
        max_norm=1.0,
    )
    learner = SPMDLearnerWorker(SPMDTrainContext(cfg, opt, make_local_mesh()))
    pipes = ActorPool.from_targets(
        [
            TokenPipeline(cfg, shape, seed=0, host_id=i, num_hosts=data_shards)
            for i in range(data_shards)
        ],
        name="data",
    )
    return WorkerSet(learner, pipes), pipes, learner


def train(
    cfg,
    steps: int,
    batch: int,
    seq: int,
    data_shards: int = 2,
    lr: float = 3e-4,
    on_step: Optional[Callable[[int, float], None]] = None,
):
    """Run ``steps`` iterations of the LM flow; returns (losses, learner)."""
    from repro.flow import Algorithm

    workers, pipes, learner = build_trainer(cfg, batch, seq, data_shards, lr, steps)
    losses: List[float] = []
    with Algorithm.from_plan(build_lm_flow(workers, pipes), workers) as algo:
        for step in range(steps):
            losses.append(algo.train()["info"].get("loss", float("nan")))
            if on_step is not None:
                on_step(step, losses[-1])
    return losses, learner


def main() -> None:
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-shards", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--checkpoint", default="")
    ap.add_argument("--dot", action="store_true", help="print the flow graph and exit")
    args = ap.parse_args()

    from repro.checkpoint import save_pytree
    from repro.configs import get_config, reduced_config

    cfg = reduced_config(args.arch) if args.smoke else get_config(args.arch)
    if args.dot:
        workers, pipes, _ = build_trainer(
            cfg, args.batch, args.seq, args.data_shards, args.lr, args.steps
        )
        print(build_lm_flow(workers, pipes).to_dot())
        return

    t0 = time.time()

    def report(step: int, loss: float) -> None:
        if step % max(1, args.steps // 10) == 0 or step == args.steps - 1:
            print(
                f"step {step:4d} loss {loss:.4f} "
                f"({(time.time() - t0) / (step + 1):.2f}s/step)",
                flush=True,
            )

    _, learner = train(
        cfg, args.steps, args.batch, args.seq, args.data_shards, args.lr, on_step=report
    )
    if args.checkpoint:
        save_pytree(args.checkpoint, learner.params)
        print(f"saved checkpoint to {args.checkpoint}")


if __name__ == "__main__":
    main()
