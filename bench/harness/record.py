"""What one run hands to the metric readers, and the arithmetic they share."""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class Run:
    """One run of one cell.

    ``units`` holds one dict per timed unit of work (a whole mine); a
    serving run fills ``latencies_s`` with one entry per request offered
    in the window (``inf`` for one that was shed or never answered) and
    ``services_s`` with one host time per micro-batch dispatch.
    ``counters`` holds what the program counted in the window, and
    ``device`` the reduction of the profiler trace (``--trace 1`` only).
    """

    cell: dict
    config: dict
    traffic: dict
    seed: int
    traced: bool
    setup_s: float = 0.0
    window_s: float = 0.0
    units: list = dataclasses.field(default_factory=list)
    latencies_s: list = dataclasses.field(default_factory=list)
    services_s: list = dataclasses.field(default_factory=list)
    counters: dict = dataclasses.field(default_factory=dict)
    device: object = None
    checks: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0


def percentile(values, q: float) -> float:
    """The exact nearest-rank ``q``-th percentile (0 < q ≤ 100): the
    smallest value with at least ``q`` % of the values at or below it.
    ``inf`` entries sort last, so shed requests count as over any limit."""
    if not values:
        raise ValueError("percentile of no values")
    ordered = sorted(values)
    rank = math.ceil(q / 100.0 * len(ordered))
    return ordered[max(rank, 1) - 1]


def per_unit(run: Run, key: str) -> float | None:
    """Mean of ``key`` over the run's units; None where there are none."""
    vals = [u[key] for u in run.units if u.get(key) is not None]
    return sum(vals) / len(vals) if vals else None
