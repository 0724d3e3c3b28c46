"""Share of the traced window in which no operation ran on the device:
1 - union of the busy intervals / window (mean over the cell's chips).  Read as
``device.idle_frac.<family>`` for each family of cells."""


def read(facts):
    tr = facts["trace"]
    if not tr or tr["window_s"] <= 0 or tr["busy_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
