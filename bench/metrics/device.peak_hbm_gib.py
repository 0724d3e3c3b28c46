"""Peak device memory in use over the run (the fullest chip), in GiB, from
``memory_stats()["peak_bytes_in_use"]`` after the window."""


def read(facts):
    b = facts["memory_peak_bytes"]
    return b / 2**30 if b else None
