"""Process start to window start: imports, building the workers and their
weights, compiles or cache loads, and the warm-up iterations.  Host clock."""


def read(facts):
    return facts["setup_s"]
