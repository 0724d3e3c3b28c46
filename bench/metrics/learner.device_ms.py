"""Device time of the XLA module(s) compiled from ``_learn`` in the traced
window, in milliseconds per 1000 trained units (tokens, env steps).  Read as
``learner.device_ms.<family>`` for each family of cells."""

from bench import trace as tr


def read(facts):
    return tr.ms_per_kilo_unit(facts, "_learn")
