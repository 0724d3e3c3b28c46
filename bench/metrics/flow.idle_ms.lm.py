"""Device-idle milliseconds of the traced window per 1000 trained tokens
charged to the flow runtime: the driver thread under a ``flow.*`` or
``weight_sync`` span, or under no program span (``bench/spans.py``).  The
three ``*.idle_ms.lm`` metrics sum to ``device.idle_frac.lm`` x window s per
1000 tokens."""

from bench import spans


def read(facts):
    return spans.idle_ms(facts, "flow")
