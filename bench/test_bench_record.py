"""The arithmetic the end-to-end metrics rest on."""

import math
import pathlib

import pytest

from harness.layout import Layout
from harness.record import Run, per_unit, percentile

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _read(metric, run):
    return Layout(ROOT).reader(metric)(run)


@pytest.mark.parametrize("q, want", [(50, 50), (99, 99), (100, 100), (1, 1), (0.5, 1)])
def test_percentile_is_exact_nearest_rank(q, want):
    values = list(range(100, 0, -1))  # order must not matter
    assert percentile(values, q) == want


def test_percentile_counts_shed_requests_as_over_every_limit():
    values = [0.001] * 98 + [float("inf")] * 2
    assert percentile(values, 99) == math.inf
    assert percentile(values, 98) == 0.001


def test_query_p99_ms_reads_every_request():
    run = Run(cell={}, config={}, traffic={}, seed=0, traced=False)
    run.latencies_s = [i / 1000 for i in range(1, 201)]  # 1..200 ms
    assert _read("serve.query_p99_ms", run) == pytest.approx(198.0)


def test_query_p95_ms_reads_every_request():
    run = Run(cell={}, config={}, traffic={}, seed=0, traced=False)
    run.latencies_s = [i / 1000 for i in range(200, 0, -1)]  # 200..1 ms
    assert _read("query_p95_ms", run) == pytest.approx(190.0)
    run.latencies_s = [0.001] * 94 + [float("inf")] * 6
    assert _read("query_p95_ms", run) == math.inf
    run.latencies_s = []
    assert _read("query_p95_ms", run) is None


def test_mine_s_is_window_over_whole_mines():
    run = Run(cell={}, config={}, traffic={}, seed=0, traced=False)
    run.window_s = 31.5
    run.units = [{"wall_s": 2.0}] * 9
    assert _read("mine_s", run) == pytest.approx(3.5)
    run.units = []
    assert _read("mine_s", run) is None


def test_per_unit_means_and_milliseconds():
    run = Run(cell={}, config={}, traffic={}, seed=0, traced=False)
    run.units = [{"compile_s": 0.1, "host_blocked_s": 1.0}, {"compile_s": 0.3, "host_blocked_s": 2.0}]
    assert per_unit(run, "compile_s") == pytest.approx(0.2)
    assert _read("mine.compile_ms", run) == pytest.approx(200.0)
    assert _read("mine.host_blocked_ms", run) == pytest.approx(1500.0)


@pytest.mark.parametrize("process", ["poisson", "burst"])
def test_arrivals_fix_the_count_and_keep_the_window(process):
    import numpy as np

    from harness import context, loadgen

    times = loadgen.ARRIVALS[process](3000, 6.0, context.run_rng(2**31 + 3, 1))
    assert times.shape == (3000,) and np.all(np.diff(times) >= 0)
    assert 0.0 <= times[0] and times[-1] < 6.0
    if process == "burst":  # factor 4 in the first quarter of each second
        high = ((times % 1.0) < 0.25).mean()
        assert high == pytest.approx(4 * 0.25 / (4 * 0.25 + 0.75), abs=0.005)


def test_requests_keep_the_mix_exact_whatever_the_seed():
    import numpy as np

    from harness import context, loadgen

    rows = np.ones((50, 20), bool)
    mix = {"closure": 0.6, "topk": 0.3, "lookup": 0.1}
    for seed in (1, 2**31 + 11):
        kinds, payloads = loadgen.make_requests(rows, 1000, mix, context.run_rng(seed, 2))
        assert {k: kinds.count(k) for k in mix} == {"closure": 600, "topk": 300, "lookup": 100}
        assert payloads.shape == (1000, 20)
