"""The reduction from a profiler trace to the device metrics."""

import json
import pathlib

import numpy as np
import pytest

from harness import devtrace
from harness.layout import Layout
from harness.record import Run

ROOT = pathlib.Path(__file__).resolve().parents[1]
MS = 1_000_000  # ns


def _trace():
    """Two chips; chip 0 runs a kernel and an overlapping copy, then a
    collective after a gap under a host round span."""
    dev0 = [
        ["tpu_custom_call (u32[8,4])", 0 * MS, 4 * MS],
        ["copy u32[8,4]", 2 * MS, 4 * MS],  # overlaps: busy 0..6
        ["all-gather u32[16,4]", 10 * MS, 2 * MS],  # gap 6..10 under the round span
        ["fusion s32[8]", 15 * MS, 1 * MS],  # gap 12..15 under no span
    ]
    dev1 = [["tpu_custom_call (u32[8,4])", 0 * MS, 2 * MS]]
    host = [
        ["bench/mine", 0, 13 * MS],
        ["mine/round[3]/allreduce", 5 * MS, 6 * MS],
        ["$python_frame", 5 * MS, 1 * MS],  # not a span the reduction reads
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": dev0}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": dev1}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
    ]}


def test_busy_is_the_union_of_op_intervals_per_chip():
    s = devtrace.reduce(_trace())
    assert s.chips == 2
    assert s.busy_s == pytest.approx((9e-3 + 2e-3) / 2)


def test_op_seconds_sum_over_chips_per_chip():
    s = devtrace.reduce(_trace())
    assert s.op_seconds(r"^tpu_custom_call ") == pytest.approx((4e-3 + 2e-3) / 2)
    assert s.op_seconds(r"^all-gather") == pytest.approx(1e-3)
    assert s.op_seconds(r"^no-such-op") is None


def test_idle_gaps_go_to_the_innermost_host_span():
    s = devtrace.reduce(_trace())
    assert s.idle_by_span == pytest.approx(
        {"mine/round/allreduce": 4e-3, "(no span)": 3e-3}
    )
    b = s.breakdown()
    assert b["device_ops"][0][0] in {"tpu_custom_call (u32[8,4])", "copy u32[8,4]"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_ops_reduces_to_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert devtrace.reduce(trace) is None


def test_device_readers():
    layout = Layout(ROOT)
    run = Run(cell={}, config={}, traffic={}, seed=0, traced=True)
    run.device = devtrace.reduce(_trace())
    run.window_s = 0.02
    run.units = [{}, {}]
    assert layout.reader("idle_share.mine")(run) == pytest.approx(100 * (1 - 5.5e-3 / 0.02))
    assert layout.reader("mine.kernel_ms")(run) == pytest.approx(3e-3 / 2 * 1e3)
    # chip 0's all-gather, 2 ms over two chips and two mines
    assert layout.reader("mine.collective_ms")(run) == pytest.approx(2e-3 / 2 / 2 * 1e3)
    trace = _trace()
    dev1 = trace["planes"][1]["lines"][0]["events"]
    dev1 += [["all-to-all u32[2048,5]", 3 * MS, 3 * MS],
             ["all-gather-start (u32[512,5], u32[2048,5])", 7 * MS, 1 * MS],
             ["fusion u32[2048,5]", 9 * MS, 5 * MS]]
    run.device = devtrace.reduce(trace)
    assert layout.reader("mine.collective_ms")(run) == pytest.approx(
        (2e-3 + 3e-3 + 1e-3) / 2 / 2 * 1e3
    )
    no_collective = _trace()
    no_collective["planes"][0]["lines"][0]["events"].pop(2)
    run.device = devtrace.reduce(no_collective)
    assert layout.reader("mine.collective_ms")(run) is None
    run.device = None
    assert layout.reader("idle_share.mine")(run) is None
    assert layout.reader("mine.kernel_ms")(run) is None
    assert layout.reader("mine.collective_ms")(run) is None


@pytest.mark.parametrize("hlo, key", [
    ('%fusion.2 = s32[8192]{0:T(1024)} fusion(s32[8192]{0:T(1024)S(1)} %a), kind=kCustom',
     "fusion s32[8192]"),
    ('%branch_0_fun.1 = (u32[512,4]{1,0:T(8,128)S(1)}, s32[512,1]{1,0:T(8,128)}) '
     'custom-call(s32[4]{0:T(128)S(1)} %p), custom_call_target="tpu_custom_call", x',
     "tpu_custom_call (u32[512,4], s32[512,1])"),
    ('%all-gather-start.1 = (u32[8,5]{1,0}, u32[32,5]{1,0}) all-gather-start(u32[8,5]{1,0} %x)',
     "all-gather-start (u32[8,5], u32[32,5])"),
    ("not an instruction", "not an instruction"),
])
def test_op_key_names_ops_by_kind_and_type(hlo, key):
    assert devtrace.op_key(hlo) == key


def test_recorded_chip_trace():
    """400 ms of one mushroom MRCbo mine traced on a TPU v5e (device ops
    of chip 0, the program's host spans), reduced: busy time equals an
    independent count on a 1 µs grid, the fused kernels are the largest
    item, and each idle stretch goes to a program span."""
    trace = json.loads((ROOT / "bench" / "fixtures" / "trace_mushroom_mine.json").read_text())
    s = devtrace.reduce(trace)
    ops = [e for p in trace["planes"] if p["name"] == "/device:TPU:0"
           for line in p["lines"] for e in line["events"]]
    grid = np.zeros(max(st + d for _, st, d in ops) // 1000 + 2, bool)
    for _, st, d in ops:
        grid[st // 1000 : -(-(st + d) // 1000)] = True
    assert s.chips == 1
    assert s.busy_s == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    kernel = s.op_seconds(r"^tpu_custom_call ")
    assert kernel > 0.5 * s.busy_s
    assert set(s.idle_by_span) <= {"mine/mrcbo", "mine/round", "mine/round/expand",
                                   "mine/round/dispatch", "mine/round/allreduce",
                                   "mine/round/filter", "bench/mine", "(no span)"}
    span = (max(st + d for _, st, d in ops) - min(st for _, st, _ in ops)) * 1e-9
    assert 0 < sum(s.idle_by_span.values()) < span
