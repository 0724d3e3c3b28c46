"""Sharding rules, spec derivation, data pipeline, checkpoint."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import restore_pytree, save_pytree
from repro.configs import INPUT_SHAPES, get_config, reduced_config
from repro.data import TokenPipeline, make_batch
from repro.distributed.hlo_cost import analyze_hlo
from repro.distributed.sharding import DEFAULT_RULES, AxisRules
from repro.distributed.specs import param_specs
from repro.launch.input_specs import decode_window_for, input_specs
from repro.launch.mesh import make_local_mesh


class FakeMesh:
    """Stand-in exposing axis_names/devices.shape without jax devices."""

    def __init__(self, shape, names):
        self.axis_names = names
        import numpy as _np

        self.devices = _np.zeros(shape)


def test_axis_rules_divisibility_drop():
    rules = AxisRules(DEFAULT_RULES, FakeMesh((16, 16), ("data", "model")))
    # 40 heads do not divide the 16-way model axis -> replicated.
    assert rules.resolve(["heads"], shape=[40]) == P(None)
    assert rules.resolve(["heads"], shape=[32]) == P("model")
    # batch maps to data (pod absent on single-pod mesh)
    assert rules.resolve(["batch"], shape=[256]) == P("data")


def test_axis_rules_multi_pod_batch():
    rules = AxisRules(DEFAULT_RULES, FakeMesh((2, 16, 16), ("pod", "data", "model")))
    spec = rules.resolve(["batch"], shape=[256])
    assert spec == P(("pod", "data"))
    # batch=1 (long_500k): nothing divides -> replicated
    assert rules.resolve(["batch"], shape=[1]) == P(None)


def test_axis_rules_no_double_axis_use():
    rules = AxisRules(DEFAULT_RULES, FakeMesh((16, 16), ("data", "model")))
    spec = rules.resolve(["d_ff", "vocab"], shape=[1024, 512])
    # 'model' can only be used once per spec.
    assert spec == P("model", None)


def test_param_specs_cover_all_leaves():
    from repro.models import Model

    for arch in ["qwen3-14b", "deepseek-v2-lite-16b", "jamba-v0.1-52b", "rwkv6-7b", "musicgen-large"]:
        cfg = reduced_config(arch)
        model = Model(cfg)
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        rules = AxisRules(DEFAULT_RULES, FakeMesh((16, 16), ("data", "model")))
        specs = param_specs(shapes, rules)
        n_leaves = len(jax.tree_util.tree_leaves(shapes))
        n_specs = len(jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P)))
        assert n_leaves == n_specs


def test_input_specs_shapes():
    cfg = get_config("llava-next-34b")
    spec = input_specs(cfg, INPUT_SHAPES["train_4k"])
    assert spec["tokens"].shape == (256, 4096 - cfg.num_media_tokens)
    assert spec["media_emb"].shape == (256, cfg.num_media_tokens, cfg.d_model)
    aud = input_specs(get_config("musicgen-large"), INPUT_SHAPES["decode_32k"])
    assert aud["tokens"].shape == (128, 1, 4)


def test_decode_window_policy():
    assert decode_window_for(get_config("qwen3-14b"), INPUT_SHAPES["decode_32k"]) == 32768
    assert decode_window_for(get_config("qwen3-14b"), INPUT_SHAPES["long_500k"]) == 8192
    assert decode_window_for(get_config("rwkv6-7b"), INPUT_SHAPES["long_500k"]) == 1


def test_pipeline_determinism_and_host_sharding():
    cfg = get_config("qwen3-14b")
    shape = INPUT_SHAPES["train_4k"]
    b1 = make_batch(cfg, shape, seed=0, step=3, host_id=1, num_hosts=16)
    b2 = make_batch(cfg, shape, seed=0, step=3, host_id=1, num_hosts=16)
    b3 = make_batch(cfg, shape, seed=0, step=3, host_id=2, num_hosts=16)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    assert b1["tokens"].shape == (256 // 16, 4096)
    assert (b1["labels"][:, :-1] == b1["tokens"][:, 1:]).all()


def test_pipeline_iterator_protocol():
    cfg = reduced_config("qwen3-14b")
    pipe = TokenPipeline(cfg, INPUT_SHAPES["train_4k"])
    a = pipe.sample()
    b = pipe.sample()
    assert not np.array_equal(a["tokens"], b["tokens"])  # step advances


def test_checkpoint_roundtrip():
    import tempfile, os

    tree = {"a": jnp.arange(6).reshape(2, 3), "b": [jnp.ones(4), {"c": jnp.zeros(())}]}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        save_pytree(path, tree)
        out = restore_pytree(path, tree)
    assert np.array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    assert np.array_equal(np.asarray(out["b"][0]), np.ones(4))


def test_hlo_cost_walker_scan_trip_count():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(s, s).compile()
    cost = analyze_hlo(compiled.as_text())
    assert cost.flops == pytest.approx(2 * 64**3 * 7, rel=0.01)


def test_local_mesh_train_step_runs():
    """End-to-end: reduced model under a real (1,1) mesh with shardings."""
    from repro.distributed.sharding import axis_rules_context
    from repro.models import Model, make_train_step
    from repro.optim import adam

    cfg = reduced_config("qwen3-14b")
    model = Model(cfg)
    mesh = make_local_mesh()
    rules = AxisRules(DEFAULT_RULES, mesh)
    with mesh, axis_rules_context(rules):
        params = model.init_params(jax.random.PRNGKey(0))
        opt = adam(1e-4)
        step = jax.jit(make_train_step(model, opt))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        p2, o2, m = step(params, opt.init(params), {"tokens": tokens, "labels": tokens})
        assert np.isfinite(float(m["loss"]))


@pytest.mark.timeout(300)
def test_train_mesh_spans_every_local_device():
    """``launch/train`` builds an ``(n, 1)`` data x model mesh from the
    devices present: on a 4-device host (simulated, in a child process so
    the device count is fixed before JAX starts) all four chips hold the
    data-parallel learner, and the LM flow trains."""
    import json
    import os
    import subprocess
    import sys

    code = (
        "import json, math\n"
        "from repro.configs import reduced_config\n"
        "from repro.launch.train import train\n"
        "losses, learner = train(reduced_config('qwen3-14b', d_model=64), steps=2, batch=4, seq=16)\n"
        "print(json.dumps({'mesh': dict(learner.ctx.mesh.shape),\n"
        "                  'finite': all(math.isfinite(x) for x in losses)}))\n"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=280)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row == {"mesh": {"data": 4, "model": 1}, "finite": True}
