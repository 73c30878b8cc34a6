"""Run one cell of the chip benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by name
(BENCHMARK.json and the files under ``bench/``, see ``harness/layout.py``).
The run generates the context from the configuration and ``--seed``, sets
up (context on the device, one whole unit of the cell's work, which
compiles or loads every program the window uses), measures for
``--seconds``, then compares what the window produced with the plain
reference.  ``--trace 1`` runs the window under the profiler and reports
the per-layer metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` when
traced), and last ``checks``, each compared number beside its limit; the
same numbers are the last lines of standard error.  Where JAX finds no
TPU, or fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

from harness import context, devtrace  # noqa: E402
from harness.clock import CompileClock  # noqa: E402
from harness.layout import Layout  # noqa: E402
from harness.mine import MineJob  # noqa: E402
from harness.record import Run  # noqa: E402
from harness.serve import ServeJob  # noqa: E402

JOBS = {"mine": MineJob, "serve": ServeJob}


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True, help="cell name in BENCHMARK.json")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def find_devices(chips: int, *, require_tpu: bool = True) -> list:
    """The devices the cell runs on; exits where they are not TPUs or are
    fewer than ``chips``."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def enable_compile_cache() -> None:
    """The program's fixed compile cache in the checkout (or
    ``$JAX_COMPILATION_CACHE_DIR``), with every program written to it, so
    that only a cell's first run in a checkout compiles."""
    import jax

    from repro.launch import fca

    fca.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def measure(layout: Layout, args, *, require_tpu: bool = True) -> dict:
    """One run of one cell → its result object."""
    cell = layout.cell(args.workload)
    traced = bool(args.trace)
    run = Run(
        cell=cell,
        config=layout.config(cell["config"]),
        traffic=layout.traffic(cell["traffic"]),
        seed=args.seed,
        traced=traced,
    )
    metrics = layout.metrics(cell["name"], traced=traced)
    readers = {m["name"]: layout.reader(m["name"]) for m in metrics}

    import jax

    devs = find_devices(cell["chips"], require_tpu=require_tpu)[: cell["chips"]]
    kind = devs[0].device_kind
    if require_tpu:
        layout.peaks(kind)
    enable_compile_cache()
    clock = CompileClock()

    dense = context.make_context(run.config, args.seed)
    job = JOBS[run.traffic["kind"]](run, dense, clock)
    job.setup()
    run.setup_s = time.perf_counter() - T_START
    c0, x0 = clock.reading()
    if traced:
        from repro.obs import Tracer, use_tracer

        with use_tracer(Tracer(jax_annotations=True)):
            _, trace = devtrace.capture(lambda: job.window(args.seconds), len(devs))
        run.device = devtrace.reduce(trace)
    else:
        job.window(args.seconds)
    c1, x1 = clock.reading()
    run.counters.update(window_compile_s=c1 - c0, window_xla_compiles=x1 - x0)

    device = {
        "platform": devs[0].platform,
        "kind": kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": memory_peak(devs),
    }
    if traced:
        device["busy_s"] = run.device.busy_s if run.device else 0.0
        device["window_s"] = run.window_s
    job.release()
    t_ref = time.perf_counter()
    run.checks = job.check()
    run.counters["reference_s"] = time.perf_counter() - t_ref

    values = {}
    for m in metrics:
        value = readers[m["name"]](run)
        if value is not None:
            values[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": all(v <= lim for v, lim in run.checks.values()),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": values,
        "device": device,
    }
    if traced and run.device is not None:
        result["breakdown"] = run.device.breakdown()
    result["counters"] = run.counters
    result["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in run.checks.items()}
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    result = measure(Layout(ROOT), args)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
