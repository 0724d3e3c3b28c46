"""The trace reduction, on intervals and on a small trace recorded on the CPU."""

import jax
import jax.numpy as jnp
import pytest

from bench import trace as tr


def test_union_counts_overlap_once():
    assert tr.union_length([(0, 10), (5, 15), (20, 25), (21, 22)]) == 20
    assert tr.union_length([]) == 0


def test_gaps_are_the_complement_in_the_window():
    assert tr.gaps([(2, 4), (3, 6), (8, 9)], 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    def _vrollout(x):
        return jnp.tanh(x @ x).sum()

    def _learn(x):
        return jnp.sin(x @ x.T).mean()

    f, g = jax.jit(_vrollout), jax.jit(_learn)
    x = jnp.ones((128, 128))
    f(x).block_until_ready(), g(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            f(x).block_until_ready()
            g(x).block_until_ready()
    jax.profiler.stop_trace()
    return tr.reduce_trace(tr.find_xplane(d))


def test_modules_found_by_name_and_absent_is_none(reduced):
    assert tr.module_seconds(reduced, "_vrollout") > 0
    assert tr.module_seconds(reduced, "_learn") > 0
    assert tr.module_seconds(reduced, "_postprocess_cols") is None
    assert tr.kernel_seconds(reduced, "_no_such_kernel") is None


def test_busy_is_a_union_within_the_window(reduced):
    total_op_s = sum(r["s"] for r in reduced["ops"].values())
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["busy_s"] <= total_op_s + 1e-9
    assert all(s > 0 for _, s in reduced["idle_gaps"])


def test_kernel_found_by_op_name(reduced):
    rec = next(iter(reduced["ops"].values()))
    s, calls = tr.kernel_seconds(reduced, rec["text"])
    assert s > 0 and calls >= 1


def test_operations_are_kept_apart_by_module(reduced):
    mods = {r["module"] for r in reduced["ops"].values()}
    assert {"jit__vrollout", "jit__learn"} <= mods
    for key, r in reduced["ops"].items():
        assert key.startswith(r["module"] + "/")
    assert tr.in_module({"module": "jit__learn"}, "_learn")
    assert not tr.in_module({"module": "jit__learn"}, "_vrollout")
