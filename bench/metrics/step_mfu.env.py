"""Whole IMPALA step's share of the chip's bf16 peak: the acting forward of
both towers plus the learner's forward and backward (3x) per trained env
step x env steps trained per second / (chips x peak)."""

from bench.metrics import _count as c


def read(facts):
    if facts["units"] <= 0 or facts["window_s"] <= 0:
        return None
    per_step = 4.0 * c.mlp_forward_flops(facts["model"])
    return 100.0 * per_step * facts["units_per_s"] / (facts["chips"] * facts["peak"]["bf16_flops"])
