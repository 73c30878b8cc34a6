"""The profiler trace of a ``--trace 1`` window, reduced to numbers.

:func:`capture` runs a function under ``jax.profiler`` and returns the
trace in a plain form: planes, their lines, and events as
``[name, start_ns, duration_ns]``.  :func:`reduce` turns that form into a
:class:`DeviceSummary`; it reads nothing else, so a recorded trace in the
same form (``bench/fixtures``) checks it on the CPU.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` and ``Async XLA
Ops`` lines hold one event per operation that ran, named by its HLO text,
which :func:`op_key` shortens to the operation's kind (the opcode, or a
custom call's target: ``tpu_custom_call`` for a Pallas kernel) and its
result type.  A chip is busy where any operation runs (the union of
those intervals).  Host planes carry the program's and the
harness's spans (``jax.profiler.TraceAnnotation``); an idle stretch of
chip 0 is put down to the innermost such span around its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINES = ("XLA Ops", "Async XLA Ops")
HOST_PLANE = "/host:CPU"
# host span names the idle gaps are put down to: the program's spans
# (mine/…, query/…, serve/…) and the harness's own (bench/…)
SPAN = re.compile(r"^(bench|mine|query|serve|stream)/")
INDEX = re.compile(r"\[\d+\]")
HLO = re.compile(r"^%?\S+ = (.*?) ([a-z][a-z0-9-]*)\(")
TARGET = re.compile(r'custom_call_target="([^"]+)"')
LAYOUT = re.compile(r"\{[^{}]*\}")


def op_key(hlo: str) -> str:
    """``"%fusion.2 = s32[8192]{0:T(1024)} fusion(...)"`` → ``"fusion s32[8192]"``;
    a custom call is named by its target.  Text that is no HLO instruction
    is kept as it is."""
    m = HLO.match(hlo)
    if not m:
        return hlo
    kind = m.group(2)
    if kind == "custom-call":
        t = TARGET.search(hlo)
        kind = t.group(1) if t else kind
    return f"{kind} {LAYOUT.sub('', LAYOUT.sub('', m.group(1)))}"


def capture(fn, chips: int):
    """``fn()`` under the profiler → (fn's result, plain trace)."""
    import jax

    out_dir = tempfile.mkdtemp(prefix="bench_trace_")
    # no Python function events (hundreds of thousands a second) and no
    # runtime internals: device ops and the spans' TraceAnnotations only
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False
    try:
        jax.profiler.start_trace(out_dir, profiler_options=options)
        try:
            result = fn()
        finally:
            jax.profiler.stop_trace()
        return result, load(out_dir, chips)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def load(out_dir: str, chips: int) -> dict:
    """The plain form of the ``.xplane.pb`` under ``out_dir``: the first
    ``chips`` device planes' op lines and the host plane's span lines."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb under {out_dir}, found {paths}")
    planes = []
    for plane in ProfileData.from_file(paths[0]).planes:
        dev = DEVICE_PLANE.match(plane.name)
        if dev and int(dev.group(1)) < chips:
            keep = lambda line: line.name in OPS_LINES  # noqa: E731
            name = op_key
        elif plane.name == HOST_PLANE:
            keep = lambda line: True  # noqa: E731
            name = lambda n: n if SPAN.match(n) else None  # noqa: E731
        else:
            continue
        lines = []
        for line in plane.lines:
            if not keep(line):
                continue
            events = []
            for ev in line.events:
                key = name(ev.name)
                if key is not None:
                    events.append([key, int(ev.start_ns), int(ev.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


@dataclasses.dataclass
class DeviceSummary:
    """What the trace says about the chips a cell used."""

    chips: int
    busy_s: float  # union of op intervals, mean over the chips
    op_s: dict  # op name -> seconds, summed over the chips
    idle_by_span: dict  # host span -> idle seconds of chip 0 under it

    def op_seconds(self, pattern: str) -> float | None:
        """Seconds per chip of the ops whose name matches ``pattern``;
        None where no op matches."""
        rx = re.compile(pattern)
        hits = [s for name, s in self.op_s.items() if rx.search(name)]
        return sum(hits) / self.chips if hits else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[n, s / self.chips] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps],
        }


def _union(intervals):
    """Sorted, merged ``(start, end)`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce(trace: dict) -> DeviceSummary | None:
    """The plain trace → :class:`DeviceSummary`; None where no operation
    ran on a device."""
    devices, host = {}, []
    for plane in trace["planes"]:
        dev = DEVICE_PLANE.match(plane["name"])
        for line in plane["lines"]:
            if dev and line["name"] in OPS_LINES:
                devices.setdefault(int(dev.group(1)), []).extend(line["events"])
            elif plane["name"] == HOST_PLANE:
                host.extend(ev for ev in line["events"] if SPAN.match(ev[0]))
    devices = {d: evs for d, evs in devices.items() if evs}
    if not devices:
        return None
    busy = 0.0
    op_s: dict = {}
    merged0 = None
    for d in sorted(devices):
        evs = devices[d]
        merged = _union((s, s + dur) for _, s, dur in evs)
        busy += sum(e - s for s, e in merged) * 1e-9
        for name, _, dur in evs:
            op_s[name] = op_s.get(name, 0.0) + dur * 1e-9
        if merged0 is None:
            merged0 = merged
    n = len(devices)
    return DeviceSummary(
        chips=n,
        busy_s=busy / n,
        op_s=op_s,
        idle_by_span=_idle_by_span(merged0, host),
    )


def _idle_by_span(merged, host) -> dict:
    """Idle stretches between chip 0's busy intervals, summed by the
    innermost host span (index-free name) around each one's midpoint."""
    host = sorted(host, key=lambda ev: ev[1])
    out: dict = {}
    active, i = [], 0
    for (_, a), (b, _) in zip(merged, merged[1:]):  # midpoints ascend
        mid = (a + b) / 2
        while i < len(host) and host[i][1] <= mid:
            active.append(host[i])
            i += 1
        active = [ev for ev in active if ev[1] + ev[2] >= mid]
        best = min(active, key=lambda ev: ev[2], default=None)
        key = INDEX.sub("", best[0]) if best else "(no span)"
        out[key] = out.get(key, 0.0) + (b - a) * 1e-9
    return out
