"""Whole PPO-LM step's share of the chip's bf16 peak: FLOPs the algorithm
requires per trained token (one generation forward, plus forward and
backward, 3x, per SGD epoch) x tokens trained per second in the traced run
/ (chips x peak).  The learner's and the bootstrap's re-forwards of the whole
window per row are not required work and are not counted."""

import numpy as np

from bench.metrics import _count as c


def read(facts):
    rows = c.rows(facts)
    if rows is None or facts["units"] <= 0 or facts["window_s"] <= 0:
        return None
    m = facts["model"]
    per_token = (1 + 3 * facts["traffic"]["sgd_epochs"]) * float(
        np.mean(c.lm_token_forward_flops(m, rows["length"])))
    achieved = per_token * facts["units"] / facts["window_s"]
    return 100.0 * achieved / (facts["chips"] * facts["peak"]["bf16_flops"])
