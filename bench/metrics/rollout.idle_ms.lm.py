"""Device-idle milliseconds of the traced window per 1000 trained tokens
during which the driver thread's innermost program span is ``rollout.*`` or
``postprocess.*`` (``bench/spans.py``).  With ``learner.idle_ms.lm`` and
``flow.idle_ms.lm`` it partitions the window's idle time."""

from bench import spans


def read(facts):
    return spans.idle_ms(facts, "rollout")
