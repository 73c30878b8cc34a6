"""serve.query_p99_ms: the exact 99th percentile, over every request
offered in the window, of the time from its scheduled arrival to its
answer on the host, in ms; a shed or unanswered request counts as over
every limit.  One host stall of about 120 ms, which came in about two
10 s windows of five on a TPU v5e host, holds some 400 requests past
50 ms and so decides it; ``serve.gen_lag_ms`` shows such a stall."""

from harness.record import percentile


def read(run):
    return percentile(run.latencies_s, 99) * 1e3 if run.latencies_s else None
