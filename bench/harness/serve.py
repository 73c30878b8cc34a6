"""A serving cell: an open-loop mix of queries against one concept store.

Set-up mines the store's iceberg lattice through ``fca._mine``, builds the
program's ``ConceptStore``, ``QueryEngine`` and ``AdmissionQueue`` with
the traffic's settings, and sends one full micro-batch of each query kind
through the engine, which compiles every step the window uses.  Set-up
ends as a long-running server starts: one full garbage collection, then
``gc.freeze()``, so that the 170,000 or so objects set-up leaves tracked
are not scanned again by a full collection inside the window (on a TPU
v5e host a 55-85 ms stall, about 5 s into a 10 s window).  The
window offers the traffic's requests at their scheduled times
(:mod:`harness.loadgen`) and ends when the last one is answered.

Every answered query is then compared with what a store over the
reference's lattice answers (:class:`harness.reference.StoreAnswers`).
"""

from __future__ import annotations

import gc

import numpy as np

from harness import context, loadgen, reference

LIMITS = {
    "wrong_closures": 0, "wrong_topk": 0, "wrong_lookups": 0, "unanswered": 0,
}


class ServeJob:
    def __init__(self, run, dense: np.ndarray, clock):
        from repro.core.context import FormalContext

        self.run, self.dense = run, dense
        self.ctx = FormalContext.from_dense(dense)
        t = run.traffic
        self.store_support = context.resolve_min_support(
            t["store"]["min_support"], self.ctx.n_objects
        )

    def setup(self) -> None:
        from repro.launch import fca
        from repro.query import ConceptStore, QueryEngine
        from repro.query.engine import QueryConfig
        from repro.serve import AdmissionConfig, AdmissionQueue

        t, ctx = self.run.traffic, self.ctx
        store_argv = [
            "mine", "--algorithm", t["store"]["algorithm"], "--parts", "1",
            "--backend", t["backend"], "--min-support", str(self.store_support),
        ]
        args = fca.parse_args(store_argv)
        plan = fca.build_plan(args)
        _, res = fca._mine(args, ctx, plan, t["backend"], self.store_support)
        store = ConceptStore.build(ctx, res.intents, plan=plan)
        self.engine = QueryEngine(
            store, QueryConfig(slots=t["slots"], backend=t["backend"])
        )
        self.k = t["topk_k"]
        self.queue = AdmissionQueue(self.engine, AdmissionConfig(
            max_wait_s=t["max_wait_ms"] / 1000.0, depth=t["queue_depth"],
            topk_k=self.k,
        ))
        self.run.counters["store_concepts"] = store.snapshot.n_concepts
        self.run.counters["store_cap"] = store.snapshot.cap
        warm = reference.pack(self.dense[: t["slots"]])
        for kind in sorted(t["mix"]):
            if kind == "closure":
                self.engine.closure_batch(warm)
            elif kind == "topk":
                self.engine.topk_batch(warm, k=self.k)
            elif kind == "lookup":
                self.engine.lookup_batch(warm)
            else:
                raise ValueError(f"serving kind {kind!r} has no warm-up here")
        gc.collect()
        gc.freeze()

    def requests(self, seconds: float):
        """The run's schedule: arrival offsets, kinds, packed payloads."""
        t, seed = self.run.traffic, self.run.seed
        n = int(round(t["qps"] * seconds))
        arrival = dict(t["arrival"])
        process = arrival.pop("process")
        times = loadgen.ARRIVALS[process](n, seconds, context.run_rng(seed, 1), **arrival)
        kinds, payloads = loadgen.make_requests(
            self.dense, n, t["mix"], context.run_rng(seed, 2)
        )
        return times, kinds, reference.pack(payloads)

    def window(self, seconds: float) -> None:
        import jax

        times, self.kinds, self.payloads = self.requests(seconds)
        with jax.profiler.TraceAnnotation("bench/load"):
            tickets, max_lag, wall = loadgen.run_load(
                self.queue, times, self.kinds, self.payloads
            )
        self.tickets = tickets
        run = self.run
        run.window_s = wall
        run.attempted = len(tickets)
        run.latencies_s = [
            tk.done_s - tk.arrival_s if tk.done_s is not None else float("inf")
            for tk in tickets
        ]
        dispatches = {}
        for tk in tickets:
            if tk.done_s is not None:
                dispatches[(tk.kind, tk.dispatch_s)] = tk.done_s - tk.dispatch_s
        run.services_s = list(dispatches.values())
        st = self.queue.stats
        run.counters.update(
            max_lag_s=max_lag, shed=st.shed, dispatches=st.dispatches,
            occupancy_mean=st.occupancy_mean,
        )
        run.failed = sum(tk.done_s is None for tk in tickets)

    def release(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.engine = self.queue = None
        gc.unfreeze()

    def check(self) -> dict:
        """Every answered query against the reference store's answer."""
        m = self.ctx.n_attrs
        answers = reference.StoreAnswers(reference.Reference(self.dense), self.store_support)
        by_kind = {}
        for i, tk in enumerate(self.tickets):
            if tk.result is not None:
                by_kind.setdefault(tk.kind, []).append(i)
        counts = dict.fromkeys(LIMITS, 0)
        counts["unanswered"] = sum(
            tk.result is None and not tk.shed for tk in self.tickets
        )
        idx = by_kind.get("closure", [])
        if idx:
            got = [self.tickets[i].result for i in idx]
            closed, sup, ids = answers.closure(reference.unpack(self.payloads[idx], m))
            bad = (
                np.any(np.stack([g[0] for g in got]) != reference.pack(closed), axis=1)
                | (np.array([g[1] for g in got]) != sup)
                | (np.array([g[2] for g in got]) != ids)
            )
            counts["wrong_closures"] = int(bad.sum())
        idx = by_kind.get("topk", [])
        if idx:
            got = [self.tickets[i].result for i in idx]
            ids, sups = answers.topk(reference.unpack(self.payloads[idx], m), self.k)
            bad = np.any(np.stack([g[0] for g in got]) != ids, axis=1) | np.any(
                np.stack([g[1] for g in got]) != sups, axis=1
            )
            counts["wrong_topk"] = int(bad.sum())
        idx = by_kind.get("lookup", [])
        if idx:
            got = np.array([int(self.tickets[i].result) for i in idx])
            ids = answers.lookup(reference.unpack(self.payloads[idx], m))
            counts["wrong_lookups"] = int((got != ids).sum())
        self.run.counters["answered"] = sum(len(v) for v in by_kind.values())
        return {k: (v, LIMITS[k]) for k, v in counts.items()}
