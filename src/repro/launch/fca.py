"""Distributed FCA launcher — the paper's system as a production CLI.

    # mine (default subcommand)
    python -m repro.launch.fca --dataset mushroom --scale 0.05 \
        --algorithm mrganter+ --parts 8 --reduce rsag --local-prune

    # mine → build the device-resident concept store → serve a mixed
    # query/update batch (repro.query)
    python -m repro.launch.fca serve --dataset mushroom --scale 0.02 \
        --parts 4 --reduce auto --queries 256 --topk 32 --updates 8

    # serve under sustained load: open-loop Poisson arrivals through the
    # continuous admission queue, live /metrics endpoint, saved
    # OpenMetrics exposition (repro.serve)
    python -m repro.launch.fca serve --dataset mushroom --scale 0.02 \
        --parts 4 --load-qps 200 --load-seconds 5 --arrival burst \
        --max-wait-ms 2 --queue-depth 512 \
        --mix closure=0.5,topk=0.3,lookup=0.1,update=0.1 \
        --metrics-port 0 --metrics-dump metrics.txt

    # iceberg-mine → extract implication/association-rule bases → answer
    # a rule-query batch (repro.rules)
    python -m repro.launch.fca rules --dataset census-income --scale 0.002 \
        --parts 8 --min-support 0.05 --min-conf 0.5 --rule-queries 128

With a real multi-device runtime pass ``--mesh`` to shard the context over
the device mesh (objects over the pod×data axes the ShardPlan picks up);
otherwise partitions are simulated on one device with bit-identical
arithmetic.  Either way the run executes through one
:class:`repro.dist.ShardPlan` — the CLI only chooses its geometry.
``--reduce auto`` lets the plan pick allgather-vs-rsag per round from the
measured batch size (the per-round record lands in the printed stats);
``--calibrate-hops`` replaces the model's 4096 B latency default with a
measured interconnect probe.  ``--min-support`` takes an absolute object
count (≥ 1) or a fraction of |O| (in (0, 1)); the resolved count is echoed
in the JSON stats.

Observability (all subcommands): ``--trace out.json`` records every round
/ speculative dispatch+reconcile / query micro-batch / stream commit as a
Chrome/Perfetto timeline (open at https://ui.perfetto.dev, validate with
``python -m repro.obs out.json``) and adds a per-span latency rollup to
the printed stats; ``--stats-json`` writes those stats to a file.  Query
stats carry HDR-histogram p50/p95/p99 micro-batch latencies
(``latency_percentiles``); mining stats carry per-round ones.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import pathlib
import sys
import time

import numpy as np

from repro.core import ClosureEngine, bitset, mrcbo, mrganter, mrganter_plus
from repro.core.engine import BACKENDS
from repro.core.mr import PIPELINES, ROUNDS
from repro.data import fca_datasets
from repro.dist.collectives import IMPLS
from repro.dist.shardplan import ShardPlan
from repro.obs import (
    Tracer,
    span_rollup,
    start_device_trace,
    stop_device_trace,
    use_tracer,
)


# Where the persistent compile cache lives when JAX_COMPILATION_CACHE_DIR
# is unset: one fixed path inside the checkout (listed in .gitignore), so
# every run from this checkout finds what an earlier run compiled.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return jax.config.jax_compilation_cache_dir


def concepts_digest(intents) -> str:
    """Order-free fingerprint of a concept set (its packed intents): two
    runs found the same concepts iff their digests match."""
    rows = np.unique(np.asarray(intents, np.uint32), axis=0)
    return hashlib.sha256(rows.tobytes()).hexdigest()[:16]


def build_plan(args) -> ShardPlan:
    """The run's ShardPlan from CLI geometry flags."""
    calibrate = getattr(args, "calibrate_hops", False)
    cand = getattr(args, "cand_shards", 1)
    if args.mesh:
        from repro.launch.mesh import make_local_mesh

        mesh = make_local_mesh(model=1, pod=args.pod, cand=cand)
        return ShardPlan.over_mesh(
            mesh, reduce_impl=args.reduce, calibrate_hops=calibrate
        )
    return ShardPlan.simulated(
        args.parts,
        cand_parts=cand,
        reduce_impl=args.reduce,
        calibrate_hops=calibrate,
    )


def _resolved_min_support(args, ctx) -> int | None:
    if args.min_support is None:
        return None
    from repro.rules import resolve_min_support

    return resolve_min_support(args.min_support, ctx.n_objects)


def _mine(args, ctx, plan, backend, min_support=None):
    eng = ClosureEngine(ctx, plan=plan, backend=backend)
    algo = {"mrganter": mrganter, "mrganter+": mrganter_plus, "mrcbo": mrcbo}[
        args.algorithm
    ]
    kw = {
        "pipeline": args.pipeline,
        "rounds": getattr(args, "rounds", "sync"),
        "min_support": min_support,
    }
    if args.algorithm == "mrganter+":
        kw["local_prune"] = args.local_prune
    res = algo(ctx, eng, max_iterations=args.max_iterations, **kw)
    return eng, res


def cmd_mine(args, ctx, spec, plan, backend):
    eng, res = _mine(args, ctx, plan, backend, _resolved_min_support(args, ctx))
    return {
        "dataset": spec.name,
        "objects": spec.n_objects,
        "attributes": spec.n_attrs,
        "density": round(spec.density, 4),
        "synthetic": spec.synthetic,
        "plan": plan.describe(),
        "backend": backend,
        "pipeline": args.pipeline,
        "rounds": args.rounds,
        "algorithm": res.algorithm,
        "min_support_resolved": res.min_support,
        "concepts": res.n_concepts,
        "concepts_digest": concepts_digest(res.intents),
        "iterations": res.n_iterations,
        "closures_computed": res.n_closures_computed,
        "fused_steps": eng.stats.fused_steps,
        "modeled_comm_bytes": res.modeled_comm_bytes,
        "modeled_dispatch_bytes": eng.stats.modeled_dispatch_bytes,
        "modeled_collective_bytes": eng.stats.modeled_collective_bytes,
        "reduce_rounds": eng.stats.reduce_rounds,
        "dispatch_s": round(eng.stats.dispatch_s, 4),
        "host_blocked_s": round(eng.stats.host_blocked_s, 4),
        "spec_rounds": eng.stats.spec_rounds,
        "spec_fallbacks": eng.stats.spec_fallbacks,
        "spec_discarded": eng.stats.spec_discarded,
        "wall_time_s": round(res.wall_time_s, 3),
    }


def cmd_serve(args, ctx, spec, plan, backend):
    """mine → build store → serve one mixed query/update batch."""
    from repro.query import ConceptStore, QueryEngine, StreamUpdater
    from repro.query.engine import QueryConfig

    eng, res = _mine(args, ctx, plan, backend, _resolved_min_support(args, ctx))

    t0 = time.perf_counter()
    store = ConceptStore.build(ctx, res.intents, plan=plan)
    build_s = time.perf_counter() - t0
    qe = QueryEngine(
        store, QueryConfig(slots=args.slots, backend=backend)
    )

    rng = np.random.default_rng(args.seed)
    # query attrsets: real rows with ~25% of their bits kept, so closures
    # hit populated regions of the lattice
    base = ctx.rows[rng.integers(0, ctx.n_objects, size=args.queries)]
    keep = bitset.pack_bool(
        rng.random((args.queries, ctx.n_attrs)) < 0.25, ctx.W
    )
    queries = base & keep

    t0 = time.perf_counter()
    closures, supports, ids = qe.closure_batch(queries)
    tops, top_supports = qe.topk_batch(queries[: args.topk], k=5)
    hit_ids = ids[ids >= 0]
    trav = qe.children(hit_ids[:8]) if hit_ids.size else []
    query_s = time.perf_counter() - t0

    # streaming update: synthetic rows matched to the context density.
    # Skipped for iceberg serves: the Godin grow formula maintains the
    # FULL intent family, so streaming onto an iceberg store would drift
    # to neither the full nor the iceberg lattice of the grown context
    # (re-mine, or serve rules, after updates instead).
    receipt, update_s, post_ids = None, None, ids
    if res.min_support is None:
        upd = StreamUpdater(store)
        new_rows = bitset.pack_bool(
            rng.random((args.updates, ctx.n_attrs)) < max(0.05, spec.density),
            ctx.W,
        )
        t0 = time.perf_counter()
        receipt = upd.stage(new_rows)
        upd.commit()
        update_s = time.perf_counter() - t0
        post_ids = qe.lookup_batch(closures)  # same intents, new snapshot
    elif args.updates:
        print(
            "serve --min-support: skipping the streaming-update phase "
            "(Godin insertion maintains the full family, not an iceberg)",
            file=sys.stderr,
        )

    n_q = args.queries + min(args.queries, args.topk)
    out = {
        "dataset": spec.name,
        "plan": plan.describe(),
        "backend": backend,
        "algorithm": res.algorithm,
        "min_support_resolved": res.min_support,
        "concepts": res.n_concepts,
        "concepts_digest": concepts_digest(res.intents),
        "mine_iterations": res.n_iterations,
        "mine_fused_steps": eng.stats.fused_steps,
        "mine_wall_s": round(res.wall_time_s, 3),
        "store": store.describe(),
        "store_build_s": round(build_s, 3),
        "slots": args.slots,
        "queries": int(n_q),
        "query_wall_s": round(query_s, 4),
        "queries_per_s": round(n_q / max(query_s, 1e-9), 1),
        "closure_hit_rate": (
            round(float((ids >= 0).mean()), 4) if ids.size else None
        ),
        "traversal_children_sample": [len(t) for t in trav],
        "top_support_max": (
            int(top_supports.max()) if top_supports.size else None
        ),
        "update": None if receipt is None else dataclass_dict(receipt),
        "update_commit_s": None if update_s is None else round(update_s, 4),
        "post_update_version": store.snapshot.version,
        "post_update_hit_rate": (
            round(float((post_ids >= 0).mean()), 4) if post_ids.size else None
        ),
        "query_stats": qe.describe()["stats"],
    }
    if args.load_qps:
        out["serve_load"] = _serve_load_phase(
            args, ctx, spec, res, store, qe, plan
        )
    return out


def _parse_mix(s: str) -> dict[str, float]:
    """``"closure=0.6,topk=0.3,update=0.1"`` → weighted workload mix."""
    mix = {}
    for part in s.split(","):
        kind, eq, w = part.partition("=")
        if not eq:
            raise SystemExit(f"--mix: expected kind=weight, got {part!r}")
        try:
            mix[kind.strip()] = float(w)
        except ValueError:
            raise SystemExit(f"--mix: non-numeric weight in {part!r}")
    return mix


def _serve_load_phase(args, ctx, spec, res, store, qe, plan):
    """``fca serve --load-qps N``: sustained open-loop load through the
    continuous admission queue, with optional live ``/metrics`` scraping
    (``--metrics-port``) and a saved exposition (``--metrics-dump``)."""
    from repro.obs import MetricsServer, to_openmetrics
    from repro.obs.slo import SLO
    from repro.query import StreamUpdater
    from repro.serve import (
        ARRIVALS,
        AdmissionConfig,
        AdmissionQueue,
        make_workload,
        run_load,
    )

    mix = _parse_mix(args.mix)
    if "update" in mix and res.min_support is not None:
        # same constraint as the one-shot update phase: Godin insertion
        # maintains the full intent family, never an iceberg's
        print("serve --min-support: dropping 'update' from the load mix",
              file=sys.stderr)
        mix.pop("update")
    rules_index = None
    if "rules" in mix:
        from repro.rules import RuleIndex, extract_bases

        rules_index = RuleIndex.build(
            extract_bases(store, min_conf=args.min_conf), plan=plan
        )
    cfg = AdmissionConfig(
        max_wait_s=args.max_wait_ms / 1000.0,
        depth=args.queue_depth,
        rules_k=args.topk_rules,
        rules_min_conf=args.min_conf,
        rules_rank_by=args.rank_by,
    )
    queue = AdmissionQueue(qe, cfg, rules_index=rules_index)
    updater = StreamUpdater(store) if "update" in mix else None

    rng = np.random.default_rng(args.seed + 1)
    # warm each kind's jit cache: the measured window should show steady
    # state, not first-call compilation
    warm = ctx.rows[rng.integers(0, ctx.n_objects, size=qe.cfg.slots)]
    for kind in sorted(set(mix) - {"update"}):
        if kind == "closure":
            qe.closure_batch(warm)
        elif kind == "topk":
            qe.topk_batch(warm, k=cfg.topk_k)
        elif kind == "lookup":
            qe.lookup_batch(warm)
        elif kind == "rules":
            qe.rules_batch(rules_index, warm, k=cfg.rules_k,
                           min_conf=cfg.rules_min_conf,
                           rank_by=cfg.rules_rank_by)

    kwargs = {"factor": args.burst_factor} if args.arrival == "burst" else {}
    arrivals = ARRIVALS[args.arrival](
        args.load_qps, args.load_seconds, rng, **kwargs
    )
    events = make_workload(
        ctx, len(arrivals), rng, mix=mix, density=spec.density
    )
    server = None
    if args.metrics_port is not None:
        server = MetricsServer(lambda: queue.registry, port=args.metrics_port)
        print(f"serving metrics at {server.url}", file=sys.stderr)
    try:
        rep = run_load(queue, arrivals, events, updater=updater, slo=SLO())
    finally:
        if args.metrics_dump:
            with open(args.metrics_dump, "w") as fh:
                fh.write(to_openmetrics(queue.registry))
        if server is not None:
            server.close()
    out = rep.describe()
    out["arrival"] = args.arrival
    out["mix"] = mix
    out["queue"] = queue.describe()
    return out


def cmd_rules(args, ctx, spec, plan, backend):
    """iceberg-mine → store → extract DG + Luxenburger bases → serve a
    rule-query batch through the QueryEngine's fixed-slot rule ops."""
    from repro.query import ConceptStore, QueryEngine
    from repro.query.engine import QueryConfig
    from repro.rules import RuleIndex, extract_bases
    from repro.rules.index import rule_query_mix

    min_support = _resolved_min_support(args, ctx)
    if min_support is None:  # rules without a threshold = iceberg at 1
        min_support = 1
    eng, res = _mine(args, ctx, plan, backend, min_support)

    t0 = time.perf_counter()
    store = ConceptStore.build(ctx, res.intents, plan=plan)
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    basis = extract_bases(store, min_conf=args.min_conf)
    index = RuleIndex.build(basis, plan=plan)
    basis_s = time.perf_counter() - t0

    qe = QueryEngine(store, QueryConfig(slots=args.slots, backend=backend))
    rng = np.random.default_rng(args.seed)
    n_q = args.rule_queries
    queries = rule_query_mix(ctx, index, n_q, rng)

    t0 = time.perf_counter()
    ids, scores, consequents = qe.rules_batch(
        index, queries, k=args.topk_rules, min_conf=args.min_conf,
        rank_by=args.rank_by,
    )
    query_s = time.perf_counter() - t0
    hits = ids[:, 0] >= 0

    return {
        "dataset": spec.name,
        "plan": plan.describe(),
        "backend": backend,
        "algorithm": res.algorithm,
        "min_support_resolved": min_support,
        "min_conf": args.min_conf,
        "iceberg_concepts": res.n_concepts,
        "concepts_digest": concepts_digest(res.intents),
        "mine_iterations": res.n_iterations,
        "mine_fused_steps": eng.stats.fused_steps,
        "mine_wall_s": round(res.wall_time_s, 3),
        "store_build_s": round(build_s, 3),
        "basis": basis.describe(),
        "rule_index": index.describe(),
        "basis_extract_s": round(basis_s, 3),
        "rule_queries": int(n_q),
        "rank_by": args.rank_by,
        "rule_query_wall_s": round(query_s, 4),
        "rule_queries_per_s": round(n_q / max(query_s, 1e-9), 1),
        "rule_hit_rate": round(float(hits.mean()), 4) if n_q else None,
        "top_score_max": float(scores.max()) if scores.size else None,
        "consequent_bits_mean": (
            round(float(bitset.popcount(consequents).mean()), 2)
            if n_q
            else None
        ),
        "reduce_rounds": eng.stats.reduce_rounds,
        "query_stats": qe.describe()["stats"],
    }


def dataclass_dict(obj):
    import dataclasses

    return dataclasses.asdict(obj)


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser()
    p.add_argument("command", nargs="?", default="mine",
                   choices=["mine", "serve", "rules"],
                   help="mine (default): run an MR* miner; serve: mine, "
                        "build the repro.query concept store, then run a "
                        "mixed query/update batch; rules: iceberg-mine, "
                        "extract the DG/Luxenburger bases, answer a "
                        "rule-query batch")
    p.add_argument("--dataset", default="mushroom",
                   choices=list(fca_datasets.PAPER_DATASETS))
    p.add_argument("--scale", type=float, default=0.05)
    p.add_argument("--algorithm", default="mrganter+",
                   choices=["mrganter", "mrganter+", "mrcbo"])
    p.add_argument("--parts", type=int, default=8)
    p.add_argument("--cand-shards", type=int, default=1,
                   help="2-D decomposition: block the candidate/frontier "
                        "axis over this many devices (--mesh: a 'cand' mesh "
                        "axis) or simulated lanes; one round then absorbs "
                        "cand-shards × max_batch candidates at the same "
                        "per-device footprint")
    p.add_argument("--reduce", default="rsag",
                   choices=list(IMPLS) + ["auto"],
                   help="AND-allreduce schedule the plan's reduce phase "
                        "runs; 'auto' picks allgather-vs-rsag per round "
                        "from the batch size")
    p.add_argument("--mesh", action="store_true",
                   help="shard over the jax device mesh (needs >1 device)")
    p.add_argument("--pod", type=int, default=1,
                   help="pod axis size for --mesh (>1 builds a pod×data mesh)")
    p.add_argument("--backend", default=None, choices=list(BACKENDS),
                   help="closure map backend (default: kernel — fused "
                        "Pallas frontier steps: closure, support and "
                        "driver filter in one VMEM-resident pass; "
                        "serving kernels route with it)")
    p.add_argument("--no-kernel", action="store_true",
                   help="deprecated: use --backend jnp")
    p.add_argument("--pipeline", default="device", choices=list(PIPELINES),
                   help="device-resident frontier pipeline vs host oracle loop")
    p.add_argument("--rounds", default="sync", choices=list(ROUNDS),
                   help="sync = blocking oracle rounds; async = speculative "
                        "double-buffered scheduler (device pipeline only)")
    p.add_argument("--local-prune", action="store_true",
                   help="mrganter+: per-partition seed dedupe before the "
                        "reduce (pruned candidates never cross the wire)")
    p.add_argument("--max-iterations", type=int, default=None)
    p.add_argument("--data-dir", default=None,
                   help="directory with real UCI .data files (else synthetic)")
    p.add_argument("--min-support", type=float, default=None,
                   help="iceberg threshold: absolute object count (≥1) or "
                        "fraction of |O| (in (0,1)); fused in-round for "
                        "every driver, resolved count echoed in the stats")
    p.add_argument("--calibrate-hops", action="store_true",
                   help="measure the interconnect's per-ring-step latency "
                        "(tiny allgather probe, cached) instead of the "
                        "4096 B auto_hop_bytes default")
    # serve-only knobs
    p.add_argument("--queries", type=int, default=256,
                   help="serve: closure queries in the mixed batch")
    p.add_argument("--topk", type=int, default=32,
                   help="serve: top-k queries in the mixed batch")
    p.add_argument("--updates", type=int, default=8,
                   help="serve: streamed new objects in the update batch")
    p.add_argument("--slots", type=int, default=64,
                   help="serve/rules: fixed micro-batch slot width")
    p.add_argument("--seed", type=int, default=0)
    # serve: sustained-load phase (continuous admission queue)
    p.add_argument("--load-qps", type=float, default=None,
                   help="serve: also run an open-loop sustained-load phase "
                        "at this offered QPS through the continuous "
                        "admission queue (deadline-or-full micro-batch "
                        "dispatch); results land under 'serve_load' with "
                        "p50/p95/p99 e2e latency, shed rate, and an SLO "
                        "verdict")
    p.add_argument("--load-seconds", type=float, default=3.0,
                   help="serve: duration of the --load-qps phase")
    p.add_argument("--arrival", default="poisson",
                   choices=["poisson", "burst"],
                   help="serve: arrival process for --load-qps (burst = "
                        "square-wave-modulated Poisson, mean held at the "
                        "target rate)")
    p.add_argument("--burst-factor", type=float, default=4.0,
                   help="serve: peak/trough rate ratio for --arrival burst")
    p.add_argument("--max-wait-ms", type=float, default=2.0,
                   help="serve: admission deadline — a partial micro-batch "
                        "dispatches once its oldest request has waited this "
                        "long (full batches dispatch immediately)")
    p.add_argument("--queue-depth", type=int, default=512,
                   help="serve: per-kind admission bound; arrivals beyond "
                        "it are shed (counted, never queued)")
    p.add_argument("--mix", default="closure=0.6,topk=0.3,lookup=0.1",
                   help="serve: weighted workload mix for --load-qps, "
                        "kind=weight CSV over closure/topk/lookup/rules/"
                        "update (update streams objects through the store "
                        "— snapshot swaps between micro-batches)")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve: expose the live registry as OpenMetrics "
                        "text on http://127.0.0.1:PORT/metrics during the "
                        "load phase (0 = ephemeral port, echoed to stderr)")
    p.add_argument("--metrics-dump", metavar="PATH", default=None,
                   help="serve: write the end-of-run OpenMetrics "
                        "exposition to PATH (validate with "
                        "`python -m repro.obs.export PATH`)")
    # rules-only knobs
    p.add_argument("--min-conf", type=float, default=0.5,
                   help="rules: Luxenburger basis + query confidence floor")
    p.add_argument("--rule-queries", type=int, default=128,
                   help="rules: rule-query batch size")
    p.add_argument("--topk-rules", type=int, default=5,
                   help="rules: top-k rules returned per query")
    p.add_argument("--rank-by", default="confidence",
                   choices=["confidence", "lift"],
                   help="rules: top-k rank metric")
    # observability (all subcommands)
    p.add_argument("--trace", metavar="PATH", default=None,
                   help="write a Chrome/Perfetto trace_event JSON timeline "
                        "of the run (every mining round with its dispatch/"
                        "allreduce/filter phases, speculative dispatch+"
                        "reconcile windows, serving micro-batches, stream "
                        "stage/commit) to PATH; load in ui.perfetto.dev or "
                        "validate with `python -m repro.obs.trace PATH`")
    p.add_argument("--stats-json", metavar="PATH", default=None,
                   help="also write the run's JSON stats to PATH (with "
                        "--trace they gain a per-span latency rollup)")
    p.add_argument("--device-trace", metavar="DIR", default=None,
                   help="pass-through to jax.profiler.start_trace(DIR): "
                        "capture the XLA device timeline alongside --trace")
    return p.parse_args(argv)


def run(args: argparse.Namespace) -> dict:
    """Run one ``fca`` subcommand in this process; return its JSON stats."""
    backend = args.backend
    if backend is None:
        backend = "jnp" if args.no_kernel else "kernel"
    elif args.no_kernel:
        print("--no-kernel is deprecated and ignored when --backend is given",
              file=sys.stderr)

    ctx, spec = fca_datasets.load(args.dataset, scale=args.scale,
                                  data_dir=args.data_dir)
    plan = build_plan(args)
    cmd = {"mine": cmd_mine, "serve": cmd_serve, "rules": cmd_rules}[
        args.command
    ]
    tracer = Tracer() if args.trace else None
    if args.device_trace:
        start_device_trace(args.device_trace)
    try:
        if tracer is not None:
            with use_tracer(tracer):
                out = cmd(args, ctx, spec, plan, backend)
        else:
            out = cmd(args, ctx, spec, plan, backend)
    finally:
        if args.device_trace:
            stop_device_trace()
    if tracer is not None:
        tracer.save(args.trace)
        out["trace_path"] = args.trace
        out["span_rollup"] = span_rollup(tracer.to_dict()["traceEvents"])
    if args.stats_json:
        with open(args.stats_json, "w") as fh:
            json.dump(out, fh, indent=2)
    return out


def main(argv=None):
    enable_compile_cache()
    print(json.dumps(run(parse_args(argv)), indent=2))


if __name__ == "__main__":
    main()
