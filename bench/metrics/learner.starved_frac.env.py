"""Share of the traced window in which the learner thread waits for a batch:
the union of its ``learner.wait`` spans over the window (``bench/spans.py``)."""

from bench import spans
from bench import trace as tr


def read(facts):
    got = spans.in_window(facts, "learner.wait")
    if got is None:
        return None
    sp, waits = got
    lo, hi = sp["window"]
    return tr.union_length((s, e) for s, e, _, _ in waits) / (hi - lo)
