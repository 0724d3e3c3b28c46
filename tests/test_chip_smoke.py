"""Rehearsal of ``chip_smoke.py`` on the CPU.

The script's entry point refuses anything but a TPU; its phase functions
are plain functions, so here they run end to end at a tiny size with the
Pallas kernels forced on (interpret mode on the CPU).  The four-chip phase
runs on four virtual CPU devices in a child process (the device count must
be fixed before JAX starts).
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(
    d_model=32, n_layers=1, num_heads=2, num_kv_heads=2, vocab_size=16,
    ctx=16, horizon=8, num_envs=4, rollout_len=8,
)
TINY_LEARN = dict(train_batch=32, minibatch=8, sgd_iters=1)


@pytest.fixture
def pallas_interpret(monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "FORCE_MODE", "pallas")


def _run_script(cwd, env_extra, *args):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_entry_point_refuses_the_cpu():
    proc = _run_script(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_entry_point_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run_script(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.timeout(600)
def test_ppo_lm_phase_rehearsal(pallas_interpret):
    out = chip_smoke.ppo_lm_phase(TINY, TINY_LEARN, iters=3)
    assert len(out["steps"]) == 3
    assert out["steps"] == sorted(set(out["steps"]))
    assert out["parity_gap"] <= chip_smoke.PARITY_TOL
    # The interpreter lowers kernels to plain HLO: no Mosaic call on CPU.
    assert out["rollout_kernels"] == 0 and out["learner_kernels"] == 0


@pytest.mark.timeout(600)
def test_trainer_phase_rehearsal(pallas_interpret):
    from repro.configs import get_config, reduced_config

    cfg = reduced_config("musicgen-large", num_layers=1, d_model=64)
    # Same family as the chip run: codebooks, activation and dtype kept.
    full = get_config("musicgen-large")
    assert (cfg.num_codebooks, cfg.activation) == (full.num_codebooks, full.activation)
    cfg = dataclasses.replace(cfg, vocab_size=64)
    out = chip_smoke.trainer_phase(cfg, steps=3, batch=2, seq=16)
    assert len(out["loss"]) == 3


@pytest.mark.timeout(600)
def test_sharded_phase_rehearsal_on_four_devices():
    code = (
        "import json, chip_smoke\n"
        "from repro.kernels import ops\n"
        "ops.FORCE_MODE = 'pallas'\n"
        f"out = chip_smoke.sharded_phase({TINY!r}, rows=16)\n"
        "print(json.dumps(out))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=540,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["devices"] == 4
    assert row["param_diff"] <= chip_smoke.SHARD_TOL


@pytest.mark.timeout(600)
def test_kernel_parity_phase_rehearsal():
    out = chip_smoke.kernel_parity_phase()
    assert set(out) == {
        "flash_attention", "flash_attention_grad", "decode_attention",
        "gae", "vtrace", "ppo_surrogate_fwd_bwd",
    }


def test_compile_cache_directory(monkeypatch):
    """The environment's cache directory wins; otherwise an accelerator
    process caches in <checkout>/.jax_cache, and a CPU process in none."""
    import jax

    from repro.launch import cache

    assert cache.DEFAULT_DIR == __import__("pathlib").Path(ROOT) / ".jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cache.enable_compile_cache() == "/elsewhere"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cache.enable_compile_cache() == ""
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        assert cache.enable_compile_cache() == str(cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(cache.DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
