"""query_p95_ms: the exact 95th percentile, over every request offered in
the window, of the time from its scheduled arrival to its answer on the
host, in ms; a shed or unanswered request counts as over every limit."""

from harness.record import percentile


def read(run):
    return percentile(run.latencies_s, 95) * 1e3 if run.latencies_s else None
