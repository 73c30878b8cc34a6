"""serve.service_ms: mean host time of one micro-batch dispatch (from its
start to its answers, the admission queue's own clock), in ms."""


def read(run):
    s = run.services_s
    return sum(s) / len(s) * 1e3 if s else None
