"""mine.host_blocked_ms: the program's EngineStats.host_blocked_s (host
time blocked on device results) per mine in the window, in ms."""

from harness.record import per_unit


def read(run):
    v = per_unit(run, "host_blocked_s")
    return None if v is None else v * 1e3
