"""Mean policy lag of the learner updates in the traced window: learner
updates between the weights a batch was sampled with and the update that
consumes it, the ``policy_lag`` stat of each ``learner.learn`` span
(``bench/spans.py``)."""

from bench import spans


def read(facts):
    got = spans.in_window(facts, "learner.learn")
    if got is None:
        return None
    lags = [st["policy_lag"] for _, _, _, st in got[1] if st and "policy_lag" in st]
    return sum(lags) / len(lags) if lags else None
