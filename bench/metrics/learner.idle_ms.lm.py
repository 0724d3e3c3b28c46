"""Device-idle milliseconds of the traced window per 1000 trained tokens
during which the driver thread's innermost program span is ``learner.*``
(``bench/spans.py``)."""

from bench import spans


def read(facts):
    return spans.idle_ms(facts, "learner")
