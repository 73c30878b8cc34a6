"""A mining cell: whole iceberg mines of the cell's context, back to back.

One unit of work is what ``python -m repro.launch.fca mine`` does with the
traffic's arguments: build the run's ShardPlan, a fresh ClosureEngine
that places the context on the device, and mine (``fca._mine``).  The
first mine is set-up (it compiles, or loads from the compile cache, every
frontier bucket the later ones use).  The window runs mines until
``seconds`` have passed and ends with the mine that crosses that line.

A cell on one chip runs the traffic's ``parts`` as a plan simulated on that
chip.  A cell on more than one chip mines over a real mesh (``--mesh``):
one part on each of the cell's chips, with the AND-allreduce and the
support psum between them.  ``fca.build_plan`` spans every device JAX
sees, so a run whose plan is not a mesh of exactly the cell's chips exits.

Every mine in the window is compared with the reference's iceberg lattice
of the same context: the program returns intents only, and with the
threshold applied a set of intents fixes their supports.
"""

from __future__ import annotations

import time

import numpy as np

from harness import context, reference

# what a comparison may read; every count is exact, so each limit is 0
LIMITS = {"missing_concepts": 0, "extra_concepts": 0, "duplicate_concepts": 0}


def argv(traffic: dict, min_support: int, chips: int) -> list[str]:
    """The ``fca mine`` arguments of a mining traffic file for a cell on
    ``chips`` chips; exits where a cell on more than one chip does not put
    one part on each."""
    parts = traffic.get("parts", 1)
    if chips > 1 and parts != chips:
        raise SystemExit(
            f"bench: a cell on {chips} chips mines one part on each, "
            f"its traffic has parts {parts}"
        )
    out = [
        "mine",
        "--algorithm", traffic["algorithm"],
        "--parts", str(parts),
        "--backend", traffic["backend"],
        "--rounds", traffic.get("rounds", "sync"),
        "--min-support", str(min_support),
    ]
    if traffic.get("local_prune"):
        out.append("--local-prune")
    if chips > 1:
        out.append("--mesh")
    return out


def mesh_devices(plan, chips: int) -> int:
    """The devices ``plan`` mines on; exits where a cell on more than one
    chip would not mine on a mesh of exactly its chips, one part each."""
    mode = plan.describe()["mode"]
    devices = 1 if plan.mesh is None else plan.mesh.devices.size
    if chips > 1 and (mode != "mesh" or plan.n_parts != chips or devices != chips):
        raise SystemExit(
            f"bench: a cell on {chips} chips mines on a mesh of them, one part "
            f"each; the plan is {mode} with {plan.n_parts} parts on {devices} devices"
        )
    return devices


class MineJob:
    def __init__(self, run, dense: np.ndarray, clock):
        from repro.core.context import FormalContext
        from repro.launch import fca

        self.run, self.dense, self.clock, self.fca = run, dense, clock, fca
        self.ctx = FormalContext.from_dense(dense)
        self.min_support = context.resolve_min_support(
            run.traffic["min_support"], self.ctx.n_objects
        )
        self.chips = run.cell["chips"]
        self.args = fca.parse_args(argv(run.traffic, self.min_support, self.chips))

    def _mine(self) -> dict:
        import jax

        fca, clock = self.fca, self.clock
        c0, x0 = clock.reading()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/mine"):
            plan = fca.build_plan(self.args)
            self.run.counters["mesh_devices"] = mesh_devices(plan, self.chips)
            eng, res = fca._mine(
                self.args, self.ctx, plan, self.run.traffic["backend"],
                self.min_support,
            )
        wall = time.perf_counter() - t0
        c1, x1 = clock.reading()
        st = eng.stats
        return {
            "wall_s": wall,
            "compile_s": c1 - c0,
            "xla_compiles": x1 - x0,
            "host_blocked_s": st.host_blocked_s,
            "dispatch_s": st.dispatch_s,
            "fused_steps": st.fused_steps,
            "rounds": res.n_iterations,
            "concepts": res.n_concepts,
            "intents": np.asarray(res.intents, np.uint32).reshape(-1, self.ctx.W),
        }

    def setup(self) -> None:
        self.warm = self._mine()

    def window(self, seconds: float) -> None:
        t0 = time.perf_counter()
        units = []
        while not units or time.perf_counter() - t0 < seconds:
            units.append(self._mine())
        self.run.window_s = time.perf_counter() - t0
        self.run.units = units
        self.run.attempted = len(units)

    def release(self) -> None:
        """Nothing to drop: each mine's engine is gone with its mine."""

    def check(self) -> dict:
        """The window's mines against the reference lattice: summed counts
        of concepts missing, extra and repeated."""
        ref = reference.Reference(self.dense)
        intents, _ = ref.iceberg(self.min_support)
        want = set(reference.row_keys(reference.pack(intents)))
        totals = dict.fromkeys(LIMITS, 0)
        failed = 0
        for unit in self.run.units:
            keys = reference.row_keys(unit.pop("intents"))
            got = set(keys)
            counts = {
                "missing_concepts": len(want - got),
                "extra_concepts": len(got - want),
                "duplicate_concepts": len(keys) - len(got),
            }
            failed += any(counts.values())
            for k, v in counts.items():
                totals[k] += v
        self.run.failed = failed
        self.run.counters["reference_concepts"] = len(want)
        return {k: (v, LIMITS[k]) for k, v in totals.items()}

