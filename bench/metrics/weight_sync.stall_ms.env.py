"""Milliseconds the weight broadcast takes in the traced window, from the
first ``set_weights`` dispatch to the last acknowledgement (summed
``weight_sync`` spans), per 1000 trained env steps (``bench/spans.py``)."""

from bench import spans


def read(facts):
    got = spans.in_window(facts, "weight_sync")
    if got is None:
        return None
    return spans.ms_per_kunit(facts, sum(e - s for s, e, _, _ in got[1]) * 1e-9)
