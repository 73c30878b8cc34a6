"""Chip smoke run: the FCA system's main path, compiled, on a TPU.

    python chip_smoke.py              # one chip: mine → store → serve → rules
    python chip_smoke.py --chips 4    # four chips: the mesh path only

One chip.  Full-size Table-7 mushroom (8,124 objects × 125 attributes at
17.4 % density, generated from a seed) goes through the functions behind
``python -m repro.launch.fca``, in this process:

  mine-mrganter+  MRGanter+ --local-prune on the simulated 8-part plan
                  (vmapped ``map_closure_call`` + ``filter_call`` kernels)
  mine-mrcbo      MRCbo on one part (the single-shard ``fused_closure_call``)
  mine-census     MRCbo on full census-income (103,950 × 133, 104,192 ×
                  5-word rows: the widest context the kernels see)
  serve           iceberg mine → concept store → query batch → 2 s of
                  open-loop closure/topk/lookup load (``contains_topk_call``)
  rules           iceberg mine → DG/Luxenburger bases → rule queries
                  (``rules_topk_call``)
  serve-kernels   the serving kernels against the jnp steps on one store

Each ``fca`` phase runs twice (cold, then warm) and must find the concept
set of the host oracle (``--pipeline host --backend jnp``) for the same
context and threshold.  The mining must have dispatched the fused Pallas
steps and the serving must have taken the fused kernels, which
:mod:`repro.kernels.mosaic` compiles on a TPU and never interprets there.

Four chips.  ``fca mine --mesh`` at object × candidate splits 4×1 and 2×2
against a simulated plan of the same geometry: the concept sets and
iteration counts must match, and the context rows and the step outputs
must sit on all four devices.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Where JAX finds no TPU, or any phase fails, the script exits non-zero and
prints no result.  It starts no other process.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Fraction of the paper's dataset sizes; 1.0 is the full Table-7 context.
SCALE = 1.0
# Iceberg thresholds (fractions of |O|): the full mushroom lattice is far
# too large to mine whole, so every phase mines an iceberg.  At 0.1 the
# rule basis has 5,340 rules, past the rules kernel's VMEM bound (the jnp
# step would serve it), so the rules phase mines at 0.2 (945 rules).
MINE_SUPPORT = "0.05"
SERVE_SUPPORT = "0.1"
RULES_SUPPORT = "0.2"
CENSUS_SUPPORT = "0.05"
MIN_CONF = "0.5"
# The four-chip path compiles every step once per placement (mesh and
# simulated, two geometries), so it mines the smaller serve iceberg.
MESH_SUPPORT = SERVE_SUPPORT


def device_info() -> dict:
    """The devices JAX runs on; exits non-zero where it finds no TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {devs[0].platform!r})"
        )
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


class CompileClock:
    """Seconds jax spends tracing, lowering and compiling (its own
    ``/jax/core/compile/*`` duration events)."""

    def __init__(self):
        import jax

        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += secs


def dataset(name: str = "mushroom") -> list[str]:
    return ["--dataset", name, "--scale", str(SCALE)]


def fca_run(argv: list[str]) -> dict:
    from repro.launch import fca

    return fca.run(fca.parse_args(argv))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


_ORACLE: dict = {}


def oracle_digest(name: str, min_support: str) -> str:
    """Concept-set digest of the host MR loop with jnp closures."""
    key = (name, min_support)
    if key not in _ORACLE:
        out = fca_run(
            ["mine", *dataset(name), "--min-support", min_support,
             "--pipeline", "host", "--backend", "jnp", "--parts", "1"]
        )
        _ORACLE[key] = out["concepts_digest"]
    return _ORACLE[key]


def run_phase(name: str, argv: list[str], min_support: str, clock,
              data: str = "mushroom") -> dict:
    """Run one ``fca`` command on ``data`` cold and warm; check both
    against the oracle; return the warm output and print the phase's
    JSON line."""
    runs = []
    for _ in range(2):
        c0, t0 = clock.total, time.perf_counter()
        out = fca_run(argv)
        runs.append((out, time.perf_counter() - t0, clock.total - c0))
    want = oracle_digest(data, min_support)
    for out, _, _ in runs:
        check(out["concepts_digest"] == want,
              f"{name}: concept set {out['concepts_digest']} != oracle {want}")
    (cold, cold_s, cold_c), (warm, warm_s, warm_c) = runs
    line = {
        "phase": name,
        "argv": argv,
        "concepts": warm.get("concepts", warm.get("iceberg_concepts")),
        "iterations": warm.get("iterations", warm.get("mine_iterations")),
        "concepts_digest": warm["concepts_digest"],
        "oracle_digest": want,
        "cold_wall_s": cold_s,
        "cold_compile_s": cold_c,
        "warm_wall_s": warm_s,
        "warm_compile_s": warm_c,
        "fused_steps": warm.get("fused_steps", warm.get("mine_fused_steps")),
    }
    check(line["fused_steps"] > 0, f"{name}: no fused Pallas frontier step ran")
    stats = warm.get("query_stats")
    if stats is not None:
        line["serve_paths"] = stats["serve_paths"]
    print(json.dumps(line), flush=True)
    return warm


def host_store(min_support: str):
    """(context, concept store) of the host-oracle iceberg mine."""
    from repro.data import fca_datasets
    from repro.launch import fca
    from repro.query import ConceptStore

    args = fca.parse_args(
        ["mine", *dataset(), "--min-support", min_support,
         "--parts", "1", "--pipeline", "host", "--backend", "jnp"]
    )
    ctx, _ = fca_datasets.load(args.dataset, scale=args.scale)
    plan = fca.build_plan(args)
    _, res = fca._mine(
        args, ctx, plan, "jnp", fca._resolved_min_support(args, ctx)
    )
    return ctx, ConceptStore.build(ctx, res.intents, plan=plan)


def check_serve_kernels(clock) -> None:
    """The fused serving kernels answer exactly as the jnp steps do, on
    the serve and rules phases' tables and one query batch (the chip's
    compiled kernels against the oracle the tests hold them to)."""
    import numpy as np

    from repro.core import bitset
    from repro.query import QueryEngine
    from repro.query.engine import QueryConfig
    from repro.rules import RuleIndex, extract_bases
    from repro.rules.index import rule_query_mix

    t0, c0 = time.perf_counter(), clock.total
    ctx, store = host_store(SERVE_SUPPORT)
    _, rules_store = host_store(RULES_SUPPORT)
    index = RuleIndex.build(
        extract_bases(rules_store, min_conf=float(MIN_CONF)), plan=store.plan
    )
    rng = np.random.default_rng(0)
    queries = ctx.rows[rng.integers(0, ctx.n_objects, 256)] & bitset.pack_bool(
        rng.random((256, ctx.n_attrs)) < 0.25, ctx.W
    )
    rule_queries = rule_query_mix(ctx, index, 256, rng)
    answers, paths = {}, {}
    for backend in ("kernel", "jnp"):
        qe = QueryEngine(store, QueryConfig(slots=64, backend=backend))
        answers[backend] = (
            *qe.topk_batch(queries, k=5),
            *qe.rules_batch(index, rule_queries, k=5,
                            min_conf=float(MIN_CONF)),
        )
        paths[backend] = qe.stats.serve_paths
    for got, want in zip(answers["kernel"], answers["jnp"]):
        check(np.array_equal(got, want), "serve kernels differ from jnp steps")
    check(set(paths["kernel"]) == {"topk/kernel", "rules/kernel"},
          f"serve kernels not taken: {paths['kernel']}")
    print(json.dumps({
        "phase": "serve-kernels",
        "concepts": store.snapshot.n_concepts,
        "rules": index.n_rules,
        "table_caps": {"topk": store.snapshot.cap, "rules": index.cap},
        "serve_paths": paths,
        "identical": True,
        "wall_s": time.perf_counter() - t0,
        "compile_s": clock.total - c0,
    }), flush=True)


def run_one_chip(clock) -> None:
    base = ["--min-support", MINE_SUPPORT]
    run_phase("mine-mrganter+",
              ["mine", *dataset(), *base, "--algorithm", "mrganter+",
               "--local-prune", "--parts", "8"], MINE_SUPPORT, clock)
    run_phase("mine-mrcbo",
              ["mine", *dataset(), *base, "--algorithm", "mrcbo",
               "--parts", "1"], MINE_SUPPORT, clock)
    run_phase("mine-census",
              ["mine", *dataset("census-income"), "--min-support",
               CENSUS_SUPPORT, "--algorithm", "mrcbo", "--parts", "1"],
              CENSUS_SUPPORT, clock, data="census-income")
    out = run_phase(
        "serve",
        ["serve", *dataset(), "--min-support", SERVE_SUPPORT,
         "--local-prune", "--parts", "8", "--load-qps", "200",
         "--load-seconds", "2", "--mix", "closure=0.6,topk=0.3,lookup=0.1"],
        SERVE_SUPPORT, clock,
    )
    # an iceberg store holds only the frequent closures, so some miss
    check(out["closure_hit_rate"] > 0, "serve: no closure hit the store")
    check(set(out["query_stats"]["serve_paths"]) == {"topk/kernel"},
          f"serve: top-k path {out['query_stats']['serve_paths']}")
    load = out["serve_load"]
    check(load["completed"] == load["admitted"] > 0,
          f"serve: load completed {load['completed']} of {load['admitted']}")
    out = run_phase(
        "rules",
        ["rules", *dataset(), "--min-support", RULES_SUPPORT,
         "--min-conf", MIN_CONF, "--local-prune", "--parts", "8"],
        RULES_SUPPORT, clock,
    )
    check(set(out["query_stats"]["serve_paths"]) == {"rules/kernel"},
          f"rules: path {out['query_stats']['serve_paths']}")
    check(out["rule_hit_rate"] > 0, "rules: no query fired a rule")
    check_serve_kernels(clock)


def run_four_chips(clock) -> None:
    """``fca mine --mesh`` at 4×1 and 2×2 against the simulated plans."""
    import jax
    import jax.numpy as jnp

    from repro.core import ClosureEngine
    from repro.data import fca_datasets
    from repro.launch import fca

    devices = set(jax.devices())
    base = ["mine", *dataset(), "--min-support", MESH_SUPPORT,
            "--algorithm", "mrganter+", "--local-prune"]
    geometries = (
        ("4x1", ["--mesh"], ["--parts", "4"]),
        ("2x2", ["--mesh", "--cand-shards", "2"],
         ["--parts", "2", "--cand-shards", "2"]),
    )
    for label, mesh_flags, sim_flags in geometries:
        t0, c0 = time.perf_counter(), clock.total
        mesh = fca_run(base + mesh_flags)
        mesh_s, mesh_c = time.perf_counter() - t0, clock.total - c0
        t0 = time.perf_counter()
        sim = fca_run(base + sim_flags)
        sim_s = time.perf_counter() - t0
        for key in ("concepts_digest", "iterations"):
            check(mesh[key] == sim[key],
                  f"{label}: mesh {key} {mesh[key]} != simulated {sim[key]}")
        check(mesh["fused_steps"] > 0, f"{label}: no fused Pallas step ran")
        # placement: the plan's rows and a step's outputs span the mesh
        args = fca.parse_args(base + mesh_flags)
        ctx, _ = fca_datasets.load(args.dataset, scale=args.scale)
        plan = fca.build_plan(args)
        eng = ClosureEngine(ctx, plan=plan, backend="kernel")
        rows_devs = eng.rows.sharding.device_set
        gc, gs = eng.closure_dev(
            jnp.zeros((eng.min_bucket, ctx.W), jnp.uint32), 1
        )
        out_devs = [x.sharding.device_set for x in (gc, gs)]
        check(rows_devs == devices and all(d == devices for d in out_devs),
              f"{label}: arrays on {len(rows_devs)} / "
              f"{[len(d) for d in out_devs]} of {len(devices)} devices")
        print(json.dumps({
            "phase": f"mesh-{label}",
            "plan": mesh["plan"],
            "concepts": mesh["concepts"],
            "iterations": mesh["iterations"],
            "concepts_digest": mesh["concepts_digest"],
            "simulated_digest": sim["concepts_digest"],
            "fused_steps": mesh["fused_steps"],
            "rows_devices": len(rows_devs),
            "output_devices": [len(d) for d in out_devs],
            "mesh_wall_s": mesh_s,
            "mesh_compile_s": mesh_c,
            "mesh_driver_wall_s": mesh["wall_time_s"],
            "simulated_wall_s": sim_s,
        }), flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: the one-chip phases; 4: the four-chip mesh "
                        "path and its simulated comparison, nothing else")
    args = p.parse_args(argv)
    device = device_info()
    if device["count"] < args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips} but {device['count']} found"
        )
    from repro.launch import fca

    fca.enable_compile_cache()
    clock = CompileClock()
    (run_four_chips if args.chips == 4 else run_one_chip)(clock)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
