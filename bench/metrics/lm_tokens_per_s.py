"""Tokens trained per second: increase of ``num_steps_trained`` over the
window, over the window's wall seconds (the window ends when an iteration
does).  Host clock."""


def read(facts):
    return facts["units_per_s"]
