"""Sharded-learner throughput + parity benchmark (ISSUE 4).

Measures ``learn_on_batch`` row throughput for three execution mappings of
the same PPO update — single device, 4-device data-parallel mesh, 4-device
mesh with 4-way gradient microbatch accumulation — and checks loss parity
between them.  The 4 CPU devices are simulated: the measurement runs in a
child process launched with ``XLA_FLAGS=--xla_force_host_platform_device_
count=4`` (the flag must precede JAX initialization, so it cannot be set in
the already-running driver).

Rows (``name,value,derived``):

  * ``learner_rows_per_s_1dev``      — single-device update throughput
  * ``learner_rows_per_s_4dev``      — 4-device sharded throughput
  * ``learner_rows_per_s_4dev_mb4``  — 4-device + microbatch(4) throughput
  * ``learner_shard_speedup``        — 4dev / 1dev ratio (recorded, not
                                       gated: simulated CPU devices share
                                       the same cores, so the ratio shows
                                       overhead, not the real-mesh win)
  * ``learner_parity_ok``            — 1.0 iff all three mappings produce
                                       the same loss to 1e-4 (**gated**:
                                       deterministic, machine-independent)

The gated parity bit is what the regression harness protects: a change that
breaks SPMD/microbatch numerical equivalence fails ``scripts/tier1.sh
--bench`` even if every test file forgot to cover the new code path.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Tuple

GATED: Dict[str, Dict[str, float]] = {
    # Loss parity across 1-dev / 4-dev / microbatched mappings at equal
    # global batch — a correctness ratio, exactly reproducible anywhere.
    "learner_parity_ok": {"min": 1.0, "value": 1.0},
}

_DEVICES = 4
_MICROBATCH = 4
_ROWS = 2048


# ------------------------------------------------------------------- child
def _child(iters: int) -> None:
    import jax
    import numpy as np

    from repro.rl import ActorCriticPolicy, CartPole, RolloutWorker, ShardedLearnerGroup
    from repro.rl.sample_batch import SampleBatch

    def make_worker():
        return RolloutWorker(
            CartPole(),
            ActorCriticPolicy(4, 2, hidden=(256, 256), loss_kind="ppo"),
            algo="ppo", num_envs=2, rollout_len=8, seed=5, worker_index=0,
        )

    rng = np.random.default_rng(0)
    batch = SampleBatch(
        obs=rng.standard_normal((_ROWS, 4)).astype(np.float32),
        actions=rng.integers(0, 2, _ROWS).astype(np.int32),
        logp=(-np.abs(rng.standard_normal(_ROWS))).astype(np.float32),
        advantages=rng.standard_normal(_ROWS).astype(np.float32),
        returns=rng.standard_normal(_ROWS).astype(np.float32),
        rewards=rng.standard_normal(_ROWS).astype(np.float32),
        dones=np.zeros(_ROWS, np.float32),
    )

    def measure(num_learners: int, microbatch: int) -> Tuple[float, float]:
        group = ShardedLearnerGroup(
            make_worker(), num_learners=num_learners, microbatch=microbatch
        )
        first = group.learn_on_batch(batch)["loss"]  # warm-up = compile
        group.learn_on_batch(batch)
        t0 = time.perf_counter()
        for _ in range(iters):
            group.learn_on_batch(batch)
        dt = time.perf_counter() - t0
        return _ROWS * iters / dt, first

    rows_1, loss_1 = measure(1, 1)
    rows_4, loss_4 = measure(_DEVICES, 1)
    rows_mb, loss_mb = measure(_DEVICES, _MICROBATCH)
    parity = float(
        abs(loss_1 - loss_4) < 1e-4 and abs(loss_1 - loss_mb) < 1e-4
    )
    print(json.dumps({
        "devices": jax.device_count(),
        "rows_1dev": rows_1,
        "rows_4dev": rows_4,
        "rows_4dev_mb4": rows_mb,
        "parity_ok": parity,
    }))


# ------------------------------------------------------------------ driver
def run(iters: int = 20) -> List[Tuple[str, float, str]]:
    env = dict(os.environ)
    # The child simulates its devices on the host CPU; it must never try to
    # open a chip this parent process may hold.
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_DEVICES} "
        + env.get("XLA_FLAGS", "")
    ).strip()
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_learner", "--child",
         "--iters", str(iters)],
        env=env, capture_output=True, text=True, timeout=900,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    if proc.returncode != 0:
        raise RuntimeError(f"bench_learner child failed:\n{proc.stderr[-2000:]}")
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    return [
        ("learner_rows_per_s_1dev", round(row["rows_1dev"], 1), ""),
        ("learner_rows_per_s_4dev", round(row["rows_4dev"], 1), ""),
        ("learner_rows_per_s_4dev_mb4", round(row["rows_4dev_mb4"], 1), ""),
        ("learner_shard_speedup",
         round(row["rows_4dev"] / max(row["rows_1dev"], 1e-9), 3),
         "simulated devices share cores; recorded for trend only"),
        ("learner_parity_ok", row["parity_ok"],
         "1-dev vs 4-dev vs microbatch loss parity at 1e-4"),
    ]


if __name__ == "__main__":
    if "--child" in sys.argv:
        iters = int(sys.argv[sys.argv.index("--iters") + 1]) if "--iters" in sys.argv else 20
        _child(iters)
    else:
        print("name,value,derived")
        for r in run():
            print(",".join(str(x) for x in r))
