"""mine_s: the measured window over the whole mines in it, in seconds —
the time to the iceberg lattice (host clock; the window ends with a mine)."""


def read(run):
    return run.window_s / len(run.units) if run.units else None
