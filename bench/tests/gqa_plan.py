"""A second PPO-LM plan made from the hooks of ``bench/plans/ppo_lm.py``
alone, as a plan for another policy architecture would be: a configuration
in Hugging Face's keys with grouped-query attention (8 query heads over 2
key-value heads), its own ``make_policy``, a ``Reference`` subclass with its
own plain model, and its own CPU sizes.  ``test_plan_hooks.py`` runs it as a
cell; ``ppo_lm``'s own ``make_policy`` and reference could not read its file.
"""

from bench.plans import ppo_lm
from bench.plans.ppo_lm import (  # noqa: F401  (the plan's hooks)
    BOOTSTRAPS_WITH_CRITIC, batch_unit, check_fragments, covers, cycle_iterations,
    learner_passes, row_stats,
)
from bench.reference import lm as ref_lm

TINY_MODEL = dict(num_hidden_layers=1, hidden_size=512, num_attention_heads=8,
                  num_key_value_heads=2, head_dim=64, intermediate_size=2048, vocab_size=64)
TINY_TRAFFIC = dict(ctx=32, min_prompt=12, max_prompt=20, horizon=8, num_envs=4,
                    rollout_len=4, train_batch=32, minibatch=8, warmup_iters=1)


def tiny(m: dict, t: dict):
    return dict(m, **TINY_MODEL), dict(t, **TINY_TRAFFIC)


def _lm(m: dict) -> dict:
    """The configuration in the keys of ``bench.reference.lm``."""
    return dict(m, num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
                num_heads=m["num_attention_heads"], num_kv_heads=m["num_key_value_heads"],
                d_ff=m["intermediate_size"], norm_eps=m["rms_norm_eps"])


def make_policy(m: dict, t: dict):
    from repro.rl import LMTokenPolicy

    d, h = m["hidden_size"], m["num_attention_heads"]
    if m["intermediate_size"] != 4 * d or m["head_dim"] != d // h:
        raise SystemExit("LMTokenPolicy builds d_ff = 4 x d_model and head_dim = d_model / heads")
    c = m["loss"]
    return LMTokenPolicy(
        ctx=t["ctx"], vocab_size=m["vocab_size"], d_model=d, n_layers=m["num_hidden_layers"],
        num_heads=h, num_kv_heads=m["num_key_value_heads"],
        clip_eps=c["clip_eps"], vf_coef=c["vf_coef"], ent_coef=c["ent_coef"],
    )


def build(m: dict, t: dict, seed: int):
    return ppo_lm.build(m, t, seed, make_policy)


class Net:
    """The plain model of ``bench.reference.lm``, read from this file's keys."""

    @staticmethod
    def init_params(m, key):
        return ref_lm.init_params(_lm(m), key)

    @staticmethod
    def hidden(m, p, tokens):
        return ref_lm.hidden(_lm(m), p, tokens)

    heads = staticmethod(ref_lm.heads)

    @staticmethod
    def logits_value(m, p, obs):
        return ref_lm.logits_value(_lm(m), p, obs)


class Reference(ppo_lm.Reference):
    net = Net
