"""Cells of BENCHMARK.json at sizes a CPU test run can hold: the same plans,
references, limits and harness, with widths, lanes and lengths cut down."""

from bench import harness

# d_model 512 keeps the logits as spread as at the cell's width (a token
# altered where it is produced must change its log-probability visibly).
LM_MODEL = dict(num_layers=1, d_model=512, num_heads=8, num_kv_heads=8, head_dim=64,
                d_ff=2048, vocab_size=64)
LM_TRAFFIC = dict(ctx=32, min_prompt=4, max_prompt=8, horizon=16, num_envs=4,
                  rollout_len=8, train_batch=32, minibatch=8, warmup_iters=1)
IMPALA_TRAFFIC = dict(sampling_workers=2, num_envs=16, rollout_len=8, train_batch=128,
                      warmup_iters=3)


def cell(name: str) -> harness.Cell:
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    base = harness.Cell(bench, name)
    if base.traffic["plan"] == "ppo_lm":
        return harness.Cell(bench, name, model=dict(base.model, **LM_MODEL),
                            traffic=dict(base.traffic, **LM_TRAFFIC))
    return harness.Cell(bench, name, traffic=dict(base.traffic, **IMPALA_TRAFFIC))


def cells():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    return [w["name"] for w in bench["workloads"]]
