"""The open-loop load of a serving cell (copied from ``repro.serve.loadgen``).

Kept here so that no change to the program can move the yardstick.  What
is kept of the original: the burst process, the thinned-row query
payloads, and the open-loop driver, which submits each request when it
is due whatever the server is doing, backdates its arrival to the
schedule when the host ran late (so queueing delay is charged to the
latency, with no coordinated omission), and records how late it ran.

What differs: a run offers exactly ``round(qps · seconds)`` requests, and
exactly the mix's share of each kind, whatever the seed.  The seed draws
only where they fall and in which order (a Poisson process conditioned on
its count puts its arrivals uniformly), so every seed gives the same work.
"""

from __future__ import annotations

import time

import numpy as np


def poisson_arrivals(n: int, duration_s: float, rng) -> np.ndarray:
    """``n`` sorted arrival offsets of a Poisson process over
    ``[0, duration_s)``, conditioned on its count."""
    return np.sort(rng.uniform(0.0, duration_s, size=n))


def burst_arrivals(
    n: int, duration_s: float, rng, *, period_s: float = 1.0,
    duty: float = 0.25, factor: float = 4.0,
) -> np.ndarray:
    """``n`` sorted arrivals of a Poisson process whose rate is ``factor``
    times higher in the first ``duty`` of each period than in the rest
    (the period is stretched so that whole periods fill the run)."""
    if factor < 1.0 or not 0.0 < duty < 1.0:
        raise ValueError("burst needs factor ≥ 1 and 0 < duty < 1")
    periods = max(1, round(duration_s / period_s))
    n_hi = int(round(n * duty * factor / (duty * factor + 1.0 - duty)))
    phase = np.concatenate([
        rng.uniform(0.0, duty, size=n_hi), rng.uniform(duty, 1.0, size=n - n_hi)
    ])
    start = rng.integers(0, periods, size=n)
    return np.sort((start + phase) * (duration_s / periods))


ARRIVALS = {"poisson": poisson_arrivals, "burst": burst_arrivals}


def make_requests(rows: np.ndarray, n: int, mix: dict, rng):
    """``n`` ``(kind, payload)`` requests: exactly ``round(n · share)`` of
    each kind (the last kind takes the remainder), in a seeded order.
    Payloads are context rows with about a quarter of their attributes
    kept (packed, as the program takes them), so queries land in
    populated parts of the lattice; lookups send the raw thinned rows, so
    misses are part of the traffic."""
    kinds = sorted(mix)
    total = sum(mix.values())
    counts = [int(round(n * mix[k] / total)) for k in kinds[:-1]]
    counts.append(n - sum(counts))
    labels = np.repeat(np.arange(len(kinds)), counts)
    rng.shuffle(labels)
    picks = rng.integers(0, rows.shape[0], size=n)
    keep = rng.random((n, rows.shape[1])) < 0.25
    return [kinds[k] for k in labels], rows[picks] & keep


def run_load(queue, arrivals, kinds, payloads, *, clock=time.monotonic,
             sleep=time.sleep):
    """Submit request ``i`` to ``queue`` at ``arrivals[i]`` seconds after
    the start; poll deadlines in between; flush at the end.

    Returns ``(tickets, max_lag_s, wall_s)``: one ticket per request, the
    worst lateness of a submission against its schedule, and the time from
    the first scheduled arrival to the last answer."""
    tickets = []
    max_lag = 0.0
    n = len(arrivals)
    t0 = clock()
    i = 0
    while i < n:
        now = clock() - t0
        while i < n and arrivals[i] <= now:
            sched = float(arrivals[i])
            max_lag = max(max_lag, now - sched)
            tickets.append(queue.submit(kinds[i], payloads[i], arrival_s=t0 + sched))
            i += 1
        queue.poll()
        if i < n:
            now = clock() - t0
            wait = min(arrivals[i] - now, queue.next_deadline_in(clock()))
            if wait > 0:
                # the floor keeps a wait that rounds to ~1e-17 from spinning
                sleep(min(max(wait, 1e-5), 0.002))
    queue.poll()
    queue.flush()
    return tickets, max_lag, clock() - t0
