"""Pass 1 — jaxpr-level SPMD auditor.

Every fused step the engines cache carries an ``audit_spec`` (attached by
``repro.dist.shardplan._attach_audit``): the canonical *shard-level*
function one device runs inside the SPMD region, before shard_map/vmap
lowering.  This pass traces that function with ``jax.make_jaxpr`` under
an extended axis environment — the same named axes the plan executes
under — and verifies three contracts against the plan's analytic model:

1. **axis binding & schedule order** — every collective equation
   (psum / all_gather / all_to_all / …) binds only declared plan axes;
   object-axis collectives complete before any candidate-axis gather
   (the 2-D decomposition's "reduce inside the block, gather survivors
   after" ordering); rsag traces exactly all_to_all → all_gather and
   allgather exactly one all_gather per reduce.

2. **wire-byte census** — the bytes the traced collectives move (summed
   with the whole-collective ring convention ``modeled_comm_bytes``
   uses, times the number of independent rings the other axes induce)
   equal ``plan.modeled_reduce_bytes`` / ``plan.modeled_round_bytes_cand``
   exactly.  The analytic model the schedule autotuner and the stats
   census trust is thereby pinned to the code the compiler actually sees.

3. **region hygiene** — no pure_callback / io_callback / debug_callback
   (and hence no debug prints or host round-trips) anywhere inside an
   SPMD region.

Closure words — uint32 operands whose trailing dim is the context's W —
are the *modeled* traffic class; supports psums, gens gathers, and
scalar counts are *sideband* (reported, never counted by the model).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jax_core

from repro.analysis.findings import Finding

COLLECTIVE_PRIMS = {
    "psum", "pmin", "pmax", "all_gather", "all_to_all",
    "reduce_scatter", "ppermute", "pbroadcast",
}
CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback", "callback"}

# step-variant argument specs (shard-level, after rows_local):
# name -> tuple of ("cand"|"rep", shape_key, dtype) where shape_key is
# resolved against the geometry: "bW" candidate bucket x words (blocked
# /cand_parts at shard level for 2-D variants), "b" bucket, "W" one set,
# "s" scalar.
_SPEC_1D = {
    "plain": (("bW", "u32"),),
    "unique": (("bW", "u32"), ("s", "i32")),
    "iceberg": (("bW", "u32"), ("s", "i32"), ("s", "i32")),
    "iceberg_unique": (("bW", "u32"), ("s", "i32"), ("s", "i32")),
    "cbo": (("bW", "u32"), ("bW", "u32"), ("b", "i32"), ("s", "i32")),
    "cbo_iceberg": (
        ("bW", "u32"), ("bW", "u32"), ("b", "i32"), ("s", "i32"), ("s", "i32")
    ),
    "ganter": (("bW", "u32"), ("W", "u32"), ("s", "bool")),
    "ganter_iceberg": (("bW", "u32"), ("W", "u32"), ("s", "bool"), ("s", "i32")),
}
_DTYPES = {"u32": jnp.uint32, "i32": jnp.int32, "bool": jnp.bool_}


@dataclasses.dataclass(frozen=True)
class CollectiveEqn:
    """One collective equation lifted out of a traced SPMD region."""

    index: int  # position in schedule order (flattened eqn walk)
    prim: str
    axes: tuple[str, ...]  # named axes the collective binds
    shape: tuple[int, ...]
    dtype: str
    ring_k: int  # devices per ring (product of bound axis sizes)
    ring_count: int  # independent rings (product of unbound env axes)
    bytes_total: int  # whole-collective wire bytes across all rings
    modeled: bool  # counted by the analytic model (closure words)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        if isinstance(v, jax_core.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jax_core.Jaxpr):
            yield v
        elif isinstance(v, (list, tuple)):
            for w in v:
                if isinstance(w, jax_core.ClosedJaxpr):
                    yield w.jaxpr
                elif isinstance(w, jax_core.Jaxpr):
                    yield w


def _walk(jaxpr):
    """Yield every equation in schedule order, recursing into sub-jaxprs
    (pjit bodies, scan/cond branches, pallas_call kernels) in place."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _eqn_axes(eqn) -> tuple[str, ...]:
    raw = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(raw, str):
        raw = (raw,)
    return tuple(a for a in raw if isinstance(a, str))


def _operand_bytes(eqn) -> int:
    total = 0
    for var in eqn.invars:
        if isinstance(var, jax_core.Literal):
            continue
        aval = var.aval
        total += int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
    return total


def _ring_bytes(prim: str, k: int, nbytes: int) -> int:
    """Whole-collective wire bytes for ONE ring of ``k`` devices moving a
    per-device operand of ``nbytes`` (the ``modeled_comm_bytes``
    convention: every device's traffic summed)."""
    if k <= 1:
        return 0
    if prim in ("all_gather", "pmin", "pmax"):
        return k * (k - 1) * nbytes
    if prim == "all_to_all":
        # operand carries the leading ring axis: each device keeps 1/k
        return (k - 1) * nbytes
    if prim in ("psum", "reduce_scatter"):
        return (k - 1) * nbytes if prim == "reduce_scatter" else 2 * (k - 1) * nbytes
    return k * nbytes  # ppermute/pbroadcast: one full-operand hop per device


def trace_region(shard_fn, args, axis_env: dict, W: int):
    """Trace one shard-level SPMD function under ``axis_env`` and lift
    (collectives, callbacks) out of the jaxpr.

    ``axis_env`` maps named axis -> size for every axis the region runs
    under; a collective's ring spans the axes it binds, and the axes it
    does NOT bind multiply into independent rings (ring_count).
    """
    closed = jax.make_jaxpr(shard_fn, axis_env=list(axis_env.items()))(*args)
    collectives, callbacks = [], []
    for idx, eqn in enumerate(_walk(closed.jaxpr)):
        name = eqn.primitive.name
        if name in CALLBACK_PRIMS:
            callbacks.append((idx, name))
            continue
        if name not in COLLECTIVE_PRIMS:
            continue
        axes = _eqn_axes(eqn)
        k = math.prod(axis_env.get(a, 1) for a in axes)
        ring_count = math.prod(
            size for ax, size in axis_env.items() if ax not in axes
        )
        nbytes = _operand_bytes(eqn)
        aval = next(
            (v.aval for v in eqn.invars if not isinstance(v, jax_core.Literal)),
            None,
        )
        shape = tuple(aval.shape) if aval is not None else ()
        dtype = str(aval.dtype) if aval is not None else "?"
        modeled = (
            aval is not None
            and aval.dtype == jnp.uint32
            and len(shape) >= 1
            and shape[-1] == W
        )
        collectives.append(
            CollectiveEqn(
                index=idx,
                prim=name,
                axes=axes,
                shape=shape,
                dtype=dtype,
                ring_k=k,
                ring_count=ring_count,
                bytes_total=ring_count * _ring_bytes(name, k, nbytes),
                modeled=modeled,
            )
        )
    return collectives, callbacks


def _norm_axes(axes) -> tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def check_region(
    label: str,
    collectives,
    callbacks,
    *,
    obj_axes: tuple[str, ...],
    cand_axes: tuple[str, ...],
    impl: str,
    n_parts: int,
    cand_parts: int,
    expected_bytes: int,
    expect_obj_pattern: bool = True,
) -> list[Finding]:
    """The three contracts, applied to one traced region."""
    out = []

    def err(rule, msg):
        out.append(Finding("spmd", rule, label, msg))

    for idx, name in callbacks:
        err("callback-in-region", f"{name} equation at position {idx}")

    declared = set(obj_axes) | set(cand_axes)
    for c in collectives:
        undeclared = [a for a in c.axes if a not in declared]
        if undeclared:
            err(
                "undeclared-axis",
                f"{c.prim} binds axis(es) {undeclared} outside the plan's "
                f"declared axes {sorted(declared)}",
            )

    obj_eqns = [c for c in collectives if set(c.axes) & set(obj_axes)]
    cand_eqns = [c for c in collectives if set(c.axes) & set(cand_axes)]
    for c in collectives:
        if set(c.axes) & set(obj_axes) and set(c.axes) & set(cand_axes):
            err(
                "mixed-axis-collective",
                f"{c.prim} binds object and candidate axes together "
                f"({c.axes}) — the 2-D schedule reduces them separately",
            )

    # schedule order: all object-axis collectives precede the first
    # candidate-axis survivor gather
    if obj_eqns and cand_eqns:
        last_obj = max(c.index for c in obj_eqns)
        first_cand = min(c.index for c in cand_eqns)
        if last_obj > first_cand:
            err(
                "cand-gather-before-reduce",
                f"candidate-axis {cand_eqns[0].prim} at {first_cand} "
                f"precedes object-axis collective at {last_obj}",
            )

    # the modeled reduce schedule, in order
    obj_modeled = [c.prim for c in obj_eqns if c.modeled]
    if expect_obj_pattern:
        want = (
            []
            if n_parts <= 1
            else (["all_to_all", "all_gather"] if impl == "rsag" else ["all_gather"])
        )
        if obj_modeled != want:
            err(
                "reduce-schedule-mismatch",
                f"object-axis modeled collectives {obj_modeled} != {want} "
                f"for impl={impl!r} at k={n_parts}",
            )
    cand_modeled = [c for c in cand_eqns if c.modeled]
    if cand_axes and cand_parts > 1:
        if [c.prim for c in cand_modeled] != ["all_gather"]:
            err(
                "cand-gather-mismatch",
                "expected exactly one modeled candidate-axis all_gather "
                f"(the survivor buffer), traced "
                f"{[c.prim for c in cand_modeled]}",
            )

    traced = sum(c.bytes_total for c in collectives if c.modeled)
    if traced != expected_bytes:
        err(
            "byte-census-mismatch",
            f"traced modeled collective bytes {traced} != analytic model "
            f"{expected_bytes} (modeled eqns: "
            + "; ".join(
                f"{c.prim}{c.shape}x{c.ring_count}rings={c.bytes_total}B"
                for c in collectives
                if c.modeled
            )
            + ")",
        )
    return out


# ---------------------------------------------------------------------------
# frontier step sweep
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _variant_args(name: str, *, B: int, cand_parts: int, W: int, cap_g: int):
    """Shard-level trace avals for one frontier step variant (the operands
    after ``rows_local``)."""
    base = name[:-2] if name.endswith("2d") else name
    spec = _SPEC_1D[base]
    b = B // cand_parts if name.endswith("2d") else B
    if base.startswith("ganter"):
        b = cap_g
    shapes = {"bW": (b, W), "b": (b,), "W": (W,), "s": ()}
    return tuple(_sds(shapes[key], _DTYPES[dt]) for key, dt in spec)


def audit_step(label: str, step, args, *, W: int, n_attrs: int) -> list[Finding]:
    """Audit one cached engine step via its attached ``audit_spec``."""
    spec = getattr(step, "audit_spec", None)
    if spec is None:
        return [
            Finding(
                "spmd", "missing-audit-spec", label,
                "step carries no audit_spec — it bypassed "
                "ShardPlan.spmd/spmd_cand",
            )
        ]
    plan = spec["plan"]
    obj_axes = _norm_axes(plan.reduce_axes)
    cand_axes = _norm_axes(plan.cand_axes)
    axis_env = {a: None for a in obj_axes}
    for a in obj_axes:
        axis_env[a] = plan.n_parts  # single object axis on simulated plans
    if spec["kind"] == "spmd_cand":
        for a in cand_axes:
            axis_env[a] = plan.cand_parts
    else:
        cand_axes = ()
    batch = args[1].shape[0]  # args[0] is the rows/extent shard
    if spec["kind"] == "spmd_cand":
        expected = plan.modeled_round_bytes_cand(batch, W, n_attrs)
    else:
        expected = plan.modeled_reduce_bytes(batch, W, n_attrs)
    try:
        collectives, callbacks = trace_region(
            spec["shard_fn"], args, axis_env, W
        )
    except Exception as e:  # trace failure is itself a finding
        return [
            Finding(
                "spmd", "trace-failure", label,
                f"make_jaxpr failed: {type(e).__name__}: {e}",
            )
        ]
    return check_region(
        label,
        collectives,
        callbacks,
        obj_axes=obj_axes,
        cand_axes=cand_axes if spec["kind"] == "spmd_cand" else (),
        impl=plan.reduce_impl,
        n_parts=plan.n_parts,
        cand_parts=plan.cand_parts if spec["kind"] == "spmd_cand" else 1,
        expected_bytes=expected,
    )


GEOMETRIES = ((1, 1), (4, 1), (2, 4))
IMPLS = ("rsag", "allgather")


def _frontier_ctx(n_attrs: int = 40, n_objects: int = 24):
    from repro.core.context import FormalContext

    rng = np.random.default_rng(7)
    W = -(-n_attrs // 32)
    rows = rng.integers(0, 2**32, size=(n_objects, W), dtype=np.uint32)
    mask = np.full(W, 0xFFFFFFFF, np.uint32)
    tail = n_attrs % 32
    if tail:
        mask[-1] = (1 << tail) - 1
    return FormalContext(
        rows=rows & mask, n_objects=n_objects, n_attrs=n_attrs, attr_names=None
    )


def audit_frontier_steps(
    report,
    *,
    geometries=GEOMETRIES,
    impls=IMPLS,
    batch: int = 32,  # /cand_parts must stay a multiple of the kernels' 8-row block
) -> list[Finding]:
    """Trace every cached frontier step variant — jnp and fused-kernel
    twins — under each (n_parts x cand_parts) geometry and reduce impl."""
    from repro.core.engine import ClosureEngine
    from repro.core.frontier import DeviceFrontier
    from repro.dist.shardplan import ShardPlan
    from repro.kernels import frontier as fkern
    from repro.kernels.ops import bucket_size

    ctx = _frontier_ctx()
    findings = []
    backends = ["jnp"]
    if fkern.supports_fused("kernel", ctx.W):
        backends.append("kernel")
    for n_parts, cand_parts in geometries:
        for impl in impls:
            for backend in backends:
                plan = ShardPlan.simulated(
                    n_parts, cand_parts=cand_parts, reduce_impl=impl,
                    block_n=max(8, ctx.n_objects // max(1, n_parts)),
                )
                engine = ClosureEngine(ctx, plan=plan, backend=backend)
                frontier = DeviceFrontier(engine)
                cap_g = bucket_size(ctx.n_attrs, minimum=engine.min_bucket)
                rows_shard = _sds(engine.rows.shape[1:], jnp.uint32)
                for name in sorted(frontier._cache["builders"]):
                    label = (
                        f"{n_parts}x{cand_parts}/{impl}/{backend}/{name}"
                    )
                    step = frontier._step_fn(name)
                    args = (rows_shard,) + _variant_args(
                        name,
                        B=batch,
                        cand_parts=cand_parts if name.endswith("2d") else 1,
                        W=ctx.W,
                        cap_g=cap_g,
                    )
                    findings.extend(
                        audit_step(label, step, args, W=ctx.W, n_attrs=ctx.n_attrs)
                    )
                    report.note_checked("spmd", "frontier_steps")
    return findings


# ---------------------------------------------------------------------------
# query-engine batch steps + rules/basis device passes
# ---------------------------------------------------------------------------


def _tiny_store(n_parts: int, impl: str):
    """A real ConceptStore over a brute-force-mined 8-attribute context
    (shapes are all the auditor needs; tracing never executes)."""
    from repro.core.context import FormalContext
    from repro.dist.shardplan import ShardPlan
    from repro.query.store import ConceptStore

    rng = np.random.default_rng(11)
    n_attrs, n_objects = 8, 20
    dense = rng.integers(0, 2, size=(n_objects, n_attrs), dtype=np.uint8)
    rows = np.zeros((n_objects, 1), np.uint32)
    for a in range(n_attrs):
        rows[:, 0] |= dense[:, a].astype(np.uint32) << a
    ctx = FormalContext(
        rows=rows, n_objects=n_objects, n_attrs=n_attrs, attr_names=None
    )
    # brute-force intents: closure of every attribute subset
    intents = set()
    for m in range(1 << n_attrs):
        have = (rows[:, 0] & np.uint32(m)) == np.uint32(m)
        intent = np.uint32((1 << n_attrs) - 1)
        for r in rows[have, 0]:
            intent &= r
        intents.add(int(intent) if have.any() else (1 << n_attrs) - 1)
    intents = np.array(sorted(intents), np.uint32)[:, None]
    plan = ShardPlan.simulated(n_parts, reduce_impl=impl, block_n=8)
    return ConceptStore.build(ctx, intents, plan=plan)


def audit_query_steps(report, *, n_parts_list=(1, 4), impls=IMPLS) -> list[Finding]:
    from repro.query.engine import QueryEngine

    findings = []
    for n_parts in n_parts_list:
        for impl in impls:
            store = _tiny_store(n_parts, impl)
            qe = QueryEngine(store)
            st = store.state
            snap = st.snapshot
            S, W = qe.cfg.slots, qe.W
            rows_shard = _sds(st.rows.shape[1:], jnp.uint32)
            closure_args = (
                rows_shard,
                _sds((S, W), jnp.uint32),
                _sds((), jnp.int32),
                _sds(tuple(snap.intents.shape), jnp.uint32),
                _sds(tuple(snap.skeys.shape), snap.skeys.dtype),
                _sds((), jnp.int32),
            )
            for kind, step in (
                ("closure", qe._closure_step(impl, snap.probe)),
                ("topk", qe._topk_step(impl, 5)),
            ):
                label = f"{n_parts}x1/{impl}/query/{kind}"
                args = closure_args
                if kind == "topk":
                    args = closure_args[:4] + (
                        _sds(tuple(snap.supports.shape), snap.supports.dtype),
                        _sds((), jnp.int32),
                    )
                findings.extend(
                    audit_step(label, step, args, W=W, n_attrs=qe.n_attrs)
                )
                report.note_checked("spmd", "query_steps")

            # extents: the membership gather IS the modeled payload —
            # uint32 [Nl, S] words, one ring, charged k·(k-1)·Nl·S·4
            step = qe._extents_step()
            spec = getattr(step, "audit_spec", None)
            label = f"{n_parts}x1/{impl}/query/extents"
            if spec is None:
                findings.append(
                    Finding("spmd", "missing-audit-spec", label,
                            "extents step bypassed ShardPlan.spmd")
                )
            else:
                plan = spec["plan"]
                obj_axes = _norm_axes(plan.reduce_axes)
                n_local = st.N_padded // n_parts
                ext_shard = _sds(tuple(snap.ext_cols.shape[1:]), jnp.uint32)
                colls, cbs = trace_region(
                    spec["shard_fn"],
                    (ext_shard, _sds((S,), jnp.int32)),
                    {a: n_parts for a in obj_axes},
                    W=S,  # membership words: trailing dim is the id batch
                )
                findings.extend(
                    check_region(
                        label, colls, cbs,
                        obj_axes=obj_axes, cand_axes=(),
                        impl="allgather", n_parts=n_parts, cand_parts=1,
                        expected_bytes=(
                            n_parts * (n_parts - 1) * n_local * S * 4
                        ),
                        expect_obj_pattern=False,
                    )
                )
                report.note_checked("spmd", "query_steps")

            # rules step: replicated-table compute — a collective or a
            # callback appearing here would break snapshot consistency
            R = 8
            rules_args = (
                _sds((R, W), jnp.uint32), _sds((R, W), jnp.uint32),
                _sds((R,), jnp.float32), _sds((R,), jnp.float32),
                _sds((R,), jnp.int32), _sds((), jnp.int32),
                _sds((S, W), jnp.uint32), _sds((), jnp.float32),
            )
            colls, cbs = trace_region(
                qe._rules_step(5), rules_args, {}, W=W
            )
            label = f"{n_parts}x1/{impl}/query/rules"
            for c in colls:
                findings.append(
                    Finding("spmd", "collective-in-replicated-pass", label,
                            f"{c.prim} in the replicated rules pass")
                )
            for idx, name in cbs:
                findings.append(
                    Finding("spmd", "callback-in-region", label,
                            f"{name} equation at position {idx}")
                )
            report.note_checked("spmd", "query_steps")
    return findings


def audit_basis_passes(report) -> list[Finding]:
    """The rules/basis extraction device passes are replicated-table
    compute: assert no collectives and no callbacks sneak in."""
    from repro.rules import basis as basis_mod

    findings = []
    C, W = 8, 1
    X = _sds((4, W), jnp.uint32)
    fam = _sds((C, W), jnp.uint32)
    sup = _sds((C,), jnp.int32)
    sc = _sds((), jnp.int32)
    targets = [
        ("family_closure_jnp",
         basis_mod.family_closure_jnp, (X, fam, sc, _sds((W,), jnp.uint32))),
        ("family_support_jnp",
         basis_mod.family_support_jnp, (X, fam, sup, sc)),
        ("lclosure_jnp",
         basis_mod.lclosure_jnp, (X, fam, fam, sc)),
    ]
    for name, fn, args in targets:
        try:
            colls, cbs = trace_region(fn, args, {}, W=W)
        except Exception:
            continue  # signature drift: covered by the unit suites
        label = f"basis/{name}"
        for c in colls:
            findings.append(
                Finding("spmd", "collective-in-replicated-pass", label,
                        f"{c.prim} in replicated basis pass")
            )
        for idx, cb in cbs:
            findings.append(
                Finding("spmd", "callback-in-region", label,
                        f"{cb} equation at position {idx}")
            )
        report.note_checked("spmd", "basis_passes")
    return findings


def run(report, *, quick: bool = False) -> list[Finding]:
    """Full Pass-1 sweep; ``quick`` restricts to one geometry per shape
    class (used by the linter's own smoke tests, not the strict gate)."""
    geoms = ((1, 1), (2, 4)) if quick else GEOMETRIES
    findings = []
    findings += audit_frontier_steps(report, geometries=geoms)
    findings += audit_query_steps(
        report, n_parts_list=(2,) if quick else (1, 4)
    )
    findings += audit_basis_passes(report)
    return findings
