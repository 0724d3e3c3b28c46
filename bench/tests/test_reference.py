"""The PPO-LM reference's shared forward: rows that extend one token
sequence read the same numbers as a forward of each row alone."""

import jax
import numpy as np
import pytest

from bench.plans import ppo_lm
from bench.reference import lm as ref_lm
from bench.tests import tiny


def test_covers_groups_rows_by_shared_prefix():
    tokens = np.array([[5, 6, 7, 0], [5, 6, 0, 0], [5, 9, 0, 0], [5, 6, 7, 8], [5, 0, 0, 0]])
    length = np.array([3, 2, 2, 4, 1])
    cover, which = ppo_lm.covers(tokens, length)
    assert list(cover) == [3, 2]  # the longest of each sequence
    assert list(which) == [0, 0, 1, 0, 0]  # row 4 ([5]) is a prefix of the first cover


def _episode_obs(rng, n_lanes, ctx, prompt, steps, vocab):
    rows = []
    for _ in range(n_lanes):
        seq = rng.integers(2, vocab, ctx)
        for t in range(steps):
            n = prompt + t
            tok = np.where(np.arange(ctx) < n, seq, 0)
            rows.append(np.concatenate([tok, [n, t]]).astype(np.float32))
    return np.stack(rows)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_shared_forward_matches_a_forward_per_row(dtype):
    cell = tiny.cell("ppo_lm.reason")
    m, t = cell.model, cell.traffic
    ref = ppo_lm.Reference(m, t, 7, dtype=getattr(jax.numpy, dtype))
    ref.seqs_per_block, ref.rows_per_block = 2, 8  # several blocks of each kind
    p = ref.init(0)
    obs = _episode_obs(np.random.default_rng(0), 3, t["ctx"], 5, 9, m["vocab_size"])
    obs = obs[np.random.default_rng(1).permutation(len(obs))]
    with jax.default_matmul_precision("highest"):
        lg, v = ref.logits_value(p, obs)
        want_lg, want_v = ref_lm.logits_value(m, ref.cast(p), jax.numpy.asarray(obs))
    tol = 1e-4 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(lg, np.asarray(want_lg, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(v, np.asarray(want_v, np.float32), atol=tol, rtol=tol)
