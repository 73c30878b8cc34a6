"""Find the rate a serving cell's system sustains: one set-up, then the
cell's traffic at each offered rate in turn.

    python3 bench/sweep.py --workload mushroom.serve --seconds 5 \
        --qps 1000 2000 4000 8000 16000

One JSON line per rate: offered and answered requests, shed, the exact
p50/p99 latency, the generator's worst lag, and the p50 and p99 of the
first and the last quarter of the arrivals (a backlog that grows over the window
shows as a last quarter far above the first).  A rate is sustained where
nothing is shed and the last quarter's p99 stays near the first's.  The
cell's traffic file then takes about 0.8 of the highest such rate.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

import run as bench  # noqa: E402
from harness import context  # noqa: E402
from harness.clock import CompileClock  # noqa: E402
from harness.layout import Layout  # noqa: E402
from harness.record import Run, percentile  # noqa: E402
from harness.serve import ServeJob  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--qps", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    layout = Layout(ROOT)
    cell = layout.cell(args.workload)
    bench.find_devices(cell["chips"])
    bench.enable_compile_cache()
    run = Run(cell=cell, config=layout.config(cell["config"]),
              traffic=layout.traffic(cell["traffic"]), seed=args.seed, traced=False)
    job = ServeJob(run, context.make_context(run.config, args.seed), CompileClock())
    job.setup()
    for qps in args.qps:
        run.traffic["qps"] = qps
        t0 = time.perf_counter()
        job.window(args.seconds)
        lat = run.latencies_s
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "qps": qps,
            "offered": len(lat),
            "answered": sum(x != float("inf") for x in lat),
            "shed": sum(tk.shed for tk in job.tickets),
            "p50_ms": percentile(lat, 50) * 1e3,
            "p99_ms": percentile(lat, 99) * 1e3,
            "p50_first_quarter_ms": percentile(lat[:q], 50) * 1e3,
            "p50_last_quarter_ms": percentile(lat[-q:], 50) * 1e3,
            "p99_first_quarter_ms": percentile(lat[:q], 99) * 1e3,
            "p99_last_quarter_ms": percentile(lat[-q:], 99) * 1e3,
            "max_lag_ms": run.counters["max_lag_s"] * 1e3,
            "wall_s": time.perf_counter() - t0,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
