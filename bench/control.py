"""The control of a cell: the reference, counting in bfloat16, put in the
program's place, and the cell's own comparison run on what it answers.

    python3 bench/control.py --workload census.mine --seeds 1 2 3 [--seconds 30]

A mining cell's control mines the iceberg lattice of the cell's context
with every count accumulated in bfloat16; a serving cell's control
answers the requests of one run at the cell's load that way.  Each seed
prints one JSON line with the numbers the comparison read and whether
the run would have been correct; a sound comparison reads not correct on
every seed.  The benchmark's own runs never run this.
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]

from harness import context, reference  # noqa: E402
from harness.layout import Layout  # noqa: E402
from harness.mine import MineJob  # noqa: E402
from harness.record import Run  # noqa: E402
from harness.serve import ServeJob  # noqa: E402


class _Ticket:
    """A request answered by the control instead of the program."""

    def __init__(self, kind, result):
        self.kind, self.result, self.shed = kind, result, False


def control_checks(layout: Layout, cell_name: str, seed: int, seconds: float) -> dict:
    cell = layout.cell(cell_name)
    run = Run(cell=cell, config=layout.config(cell["config"]),
              traffic=layout.traffic(cell["traffic"]), seed=seed, traced=False)
    dense = context.make_context(run.config, seed)
    ctrl = reference.Reference(dense, acc=reference.CONTROL)
    if run.traffic["kind"] == "mine":
        job = MineJob(run, dense, clock=None)
        intents, _ = ctrl.iceberg(job.min_support)
        run.units = [{"intents": reference.pack(intents)}]
        return job.check()
    job = ServeJob(run, dense, clock=None)
    _, job.kinds, job.payloads = job.requests(seconds)
    answers = reference.StoreAnswers(ctrl, job.store_support)
    job.k = run.traffic["topk_k"]
    m = dense.shape[1]
    job.tickets = [None] * len(job.kinds)
    for kind in set(job.kinds):
        idx = [i for i, k in enumerate(job.kinds) if k == kind]
        sets = reference.unpack(job.payloads[idx], m)
        if kind == "closure":
            closed, sup, ids = answers.closure(sets)
            rows = zip(reference.pack(closed), sup, ids)
        elif kind == "topk":
            rows = zip(*answers.topk(sets, job.k))
        else:
            rows = answers.lookup(sets)
        for i, row in zip(idx, rows):
            job.tickets[i] = _Ticket(kind, row)
    return job.check()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=10.0,
                   help="serving cells: the length of the run whose requests are answered")
    args = p.parse_args(argv)
    layout = Layout(ROOT)
    for seed in args.seeds:
        checks = control_checks(layout, args.workload, seed, args.seconds)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": all(v <= lim for v, lim in checks.values()),
            "checks": {k: v for k, (v, _) in checks.items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
