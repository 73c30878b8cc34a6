"""serve.gen_lag_ms: the load generator's worst lateness, submission time
against schedule, over the window, in ms (a starved generator, not a fast
server, would show here)."""


def read(run):
    lag = run.counters.get("max_lag_s")
    return None if lag is None else lag * 1e3
