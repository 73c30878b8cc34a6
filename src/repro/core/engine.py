"""Distributed closure engine — the MapReduce substrate for the MR* miners.

The engine owns the *static data* (the object-partitioned context, resident
on device across iterations — Twister's defining feature) and executes the
paper's map/reduce round:

    map    : per-shard batched closure (Pallas kernel, fused-jnp or MXU
             matmul backend)
    reduce : bitwise-AND all-reduce of local closures across the object
             partition + psum of supports   (paper Theorem 2)

There is exactly one partitioned execution path: every round goes through
the engine's :class:`repro.dist.ShardPlan`, whose ``spmd`` primitive runs
the shard body under ``shard_map`` on a real mesh or under a named-axis
``vmap`` for simulated partitions on one device — same body, same
collectives, bit-identical arithmetic (see repro/dist/shardplan.py).

``spmd_step`` additionally lets callers fuse a *post* stage (canonicity,
feasibility, on-device dedupe) into the same SPMD region as the closure
map + AND-allreduce — the frontier pipeline builds its per-round fused
steps this way, so under a real mesh the whole iteration executes on the
partitions.

Supports are corrected globally: all-ones padding rows match every
candidate, so ``supports -= n_pad_total`` after the psum.
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from repro.core import bitset
from repro.core.context import FormalContext
from repro.dist import collectives
from repro.dist.shardplan import AUTO_IMPLS, ShardPlan
from repro.kernels import frontier as fkern
from repro.kernels import ops
from repro.obs import StatsBase
from repro.obs import trace as obs


BACKENDS = ("kernel", "jnp", "matmul")


@dataclasses.dataclass
class EngineStats(StatsBase):
    """Per-run mining ledger.  Inherits the schedule census
    (``reduce_rounds``/``auto_hop_bytes``/``hop_calibrated``) and the
    latency-percentile view (``latency_percentiles`` + the histogram
    registry behind it) from :class:`repro.obs.StatsBase`, shared with the
    serving tier's QueryStats so both record the autotuner identically."""

    closure_calls: int = 0
    closures_computed: int = 0
    modeled_comm_bytes: int = 0
    rounds: int = 0
    # host↔device traffic census (the frontier pipeline's whole point):
    h2d_transfers: int = 0
    h2d_bytes: int = 0
    d2h_transfers: int = 0
    d2h_bytes: int = 0
    # async speculative-round ledger (wall seconds the host spent enqueueing
    # device work vs blocked waiting on device results, the α/β split of the
    # modeled reduce cost, and the speculation outcome census).  The timing
    # fields are populated by the sync paths too, so sync-vs-async A/Bs
    # compare like with like.
    dispatch_s: float = 0.0
    host_blocked_s: float = 0.0
    modeled_dispatch_bytes: int = 0
    modeled_collective_bytes: int = 0
    spec_rounds: int = 0
    spec_fallbacks: int = 0
    spec_discarded: int = 0
    # frontier-step dispatches that ran the fused Pallas kernels
    # (repro.kernels.frontier) rather than a jnp step
    fused_steps: int = 0


class ClosureEngine:
    def __init__(
        self,
        ctx: FormalContext,
        *,
        plan: ShardPlan | None = None,
        mesh: Mesh | None = None,
        axis_names: tuple[str, ...] = ("data",),
        n_parts: int | None = None,
        backend: str | None = None,
        use_kernel: bool = True,
        reduce_impl: str | None = None,
        block_n: int | None = None,
        max_batch: int | None = None,
    ):
        # ``backend`` supersedes the old ``use_kernel`` flag:
        #   kernel — Pallas closure kernel (compiled on TPU, interpreted on
        #            CPU — repro.kernels.mosaic decides from the platform)
        #   jnp    — fused-jnp reference (fastest on CPU/XLA)
        #   matmul — MXU complement-counting closure (§Perf C2)
        if backend is None:
            backend = "kernel" if use_kernel else "jnp"
        if backend not in BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; choose {BACKENDS}")
        # ``plan`` supersedes the legacy (mesh, axis_names) / n_parts pair;
        # both legacy spellings build the same ShardPlan.  Kwarg precedence
        # is uniform: geometry (mesh/n_parts) conflicts with an explicit
        # plan and raises; the scalar knobs (reduce_impl/block_n/max_batch)
        # override the plan's values when passed.
        if plan is None:
            if mesh is not None:
                plan = ShardPlan.over_mesh(
                    mesh,
                    axis_names=tuple(axis_names),
                    reduce_impl=reduce_impl or "rsag",
                )
            else:
                plan = ShardPlan.simulated(
                    n_parts or 1, reduce_impl=reduce_impl or "rsag"
                )
        elif mesh is not None or n_parts is not None or tuple(axis_names) != ("data",):
            raise ValueError(
                "pass either plan= or the legacy mesh=/axis_names=/n_parts= "
                "geometry, not both"
            )
        overrides = {
            k: v
            for k, v in (
                ("reduce_impl", reduce_impl),
                ("block_n", block_n),
                ("max_batch", max_batch),
            )
            if v is not None
        }
        if overrides:
            plan = dataclasses.replace(plan, **overrides)
        self.plan = plan
        self.ctx = ctx
        self.mesh = plan.mesh
        self.axis_names = plan.axis_names
        self.backend = backend
        self.use_kernel = backend == "kernel"
        self.reduce_impl = plan.reduce_impl
        self.block_n = plan.block_n
        self.max_batch = plan.max_batch
        self.stats = EngineStats(
            auto_hop_bytes=plan.auto_hop_bytes,
            hop_calibrated=plan.hop_calibrated,
        )
        self.n_parts = plan.n_parts

        # Pad rows so every shard is block-aligned: N % (k * block_n) == 0.
        rows, n_pad = ctx.padded_rows(plan.row_alignment)
        self.n_pad_rows = n_pad
        self.N_padded = rows.shape[0]
        self._mask_np = ctx.attr_mask()
        self.rows = plan.place_rows(rows)

        # Guards the lazily-built ``_frontier_cache`` (set by
        # DeviceFrontier): the cache is reachable from both the main
        # thread and the admission dispatcher thread, and a concurrent
        # first-miss would otherwise build the same jitted step twice.
        self._frontier_lock = threading.Lock()

        self._step = self.spmd_step(with_supports=True)

    # -- the one partitioned execution path --------------------------------

    def _local_closure(self):
        """Per-shard map phase for the configured backend."""
        ctx = self.ctx
        backend, block_n = self.backend, self.block_n

        if backend == "matmul":

            def local_closure(rows_local, cands):
                return ops.closure_matmul(
                    rows_local,
                    cands,
                    ctx.n_attrs,
                    n_valid_rows=rows_local.shape[0],  # global pad corrected later
                )

        else:

            def local_closure(rows_local, cands):
                return ops.batched_closure(
                    rows_local,
                    cands,
                    ctx.n_attrs,
                    n_valid_rows=rows_local.shape[0],  # global pad corrected later
                    block_n=block_n,
                    use_kernel=backend == "kernel",
                )

        return local_closure

    def spmd_step(self, post=None, *, with_supports: bool = False, n_extra: int = 0):
        """Build one jitted plan-SPMD round: map → AND-allreduce [→ post].

        The returned callable is ``step(rows, cands, *extras)``.  Each
        shard computes local closures, the reduce runs the plan's
        collective schedule, and — when given — ``post`` consumes the
        *global* closures (masked to real attributes) plus the ``n_extra``
        replicated extras.  The plan places ``post``: fused into the same
        SPMD region on a mesh, applied once past the vmap on a simulated
        plan (its input is shard-invariant, so both are bit-identical).
        Without ``post`` the step returns the masked global closures, plus
        pad-corrected supports when ``with_supports``.
        """
        plan, ctx = self.plan, self.ctx
        local_closure = self._local_closure()
        axes = plan.reduce_axes
        mask_np, n_pad = self._mask_np, self.n_pad_rows

        def make(impl):
            def body(rows_local, cands):
                lc, ls = local_closure(rows_local, cands)
                gc = collectives.and_allreduce(
                    lc, axes, impl=impl, n_attrs=ctx.n_attrs
                )
                gc = gc & jnp.asarray(mask_np)
                if with_supports:
                    return gc, lax.psum(ls, axes) - n_pad
                return gc

            return jax.jit(
                plan.spmd(body, n_rep=1, post=post, n_post_rep=n_extra)
            )

        if plan.reduce_impl != "auto":
            return make(plan.reduce_impl)

        # Schedule autotuning: one jitted step per candidate schedule; the
        # dispatcher resolves the round's schedule from the padded batch
        # size (the AND semigroup makes every schedule bit-identical, so
        # the choice only moves wire cost).  ``charge_round`` sees the same
        # (cap, plan) pair and ledgers the matching bytes + choice.
        steps = {impl: make(impl) for impl in AUTO_IMPLS}

        def dispatch(rows, cands, *extras):
            impl = plan.resolve_impl(cands.shape[0], ctx.W, ctx.n_attrs)
            return steps[impl](rows, cands, *extras)

        return dispatch

    def spmd_step_cand(
        self,
        post,
        merge,
        *,
        with_supports: bool = False,
        n_cand: int = 1,
        n_post_rep: int = 0,
        n_merge_rep: int = 0,
    ):
        """2-D twin of :meth:`spmd_step` for candidate-sharded chunks.

        The returned callable is ``step(rows, *cand_ops, *extras)``: the
        ``n_cand`` candidate operands (seeds first, then lineage like
        parents/gens) are blocked over the plan's candidate axis, each
        block runs map → AND-allreduce over the *object* axes at the block
        batch size, ``post(cand_idx, gc[, gs], *passthrough, *extras)``
        filters block-locally, and only then are survivors all-gathered
        along ``cand`` and handed to ``merge``.  Pruned candidates never
        replicate across the candidate axis.  Lineage operands beyond the
        seeds ride through ``body`` untouched so the block-local filter
        sees its own block's rows.
        """
        plan, ctx = self.plan, self.ctx
        local_closure = self._local_closure()
        axes = plan.reduce_axes
        mask_np, n_pad = self._mask_np, self.n_pad_rows

        def make(impl):
            def body(rows_local, *cand_ops):
                lc, ls = local_closure(rows_local, cand_ops[0])
                gc = collectives.and_allreduce(
                    lc, axes, impl=impl, n_attrs=ctx.n_attrs
                )
                gc = gc & jnp.asarray(mask_np)
                if with_supports:
                    return (gc, lax.psum(ls, axes) - n_pad, *cand_ops[1:])
                return (gc, *cand_ops[1:])

            return jax.jit(
                plan.spmd_cand(
                    body,
                    n_cand=n_cand,
                    n_rep=0,
                    post=post,
                    n_post_rep=n_post_rep,
                    merge=merge,
                    n_merge_rep=n_merge_rep,
                )
            )

        if plan.reduce_impl != "auto":
            return make(plan.reduce_impl)

        steps = {impl: make(impl) for impl in AUTO_IMPLS}

        def dispatch(rows, cands, *extras):
            block = cands.shape[0] // plan.cand_parts
            impl = plan.resolve_impl(block, ctx.W, ctx.n_attrs)
            return steps[impl](rows, cands, *extras)

        return dispatch

    # -- fused-kernel step builders (backend="kernel") ----------------------
    #
    # Twin builders for the frontier pipeline's step variants that replace
    # the jnp closure→mask→filter op chain with the fused Pallas kernels in
    # repro.kernels.frontier.  Two placements, chosen by plan geometry:
    #
    #   n_parts == 1 — the local closure IS the global closure, so ONE
    #     ``fused_closure_call`` computes closure → support → driver filter
    #     without the block ever leaving VMEM; no collective runs (the
    #     size-1 AND-allreduce is the identity).
    #   n_parts > 1 — the filter needs the *global* closure, which only
    #     exists after the AND-allreduce, so the round is map kernel (the
    #     attr mask folded in-kernel: AND distributes over the mask, so
    #     masked locals allreduce to the masked global) → collectives →
    #     fused filter kernel (pad correction + iceberg cut + canonicity in
    #     one pass).
    #
    # Survivor *compaction* stays jnp in both placements: the stable
    # partition permutation is XLA's job and consumes only the kernel's keep mask —
    # identical masks in, identical order out, which is what makes the
    # fused steps bit-identical to the jnp builders (tests/
    # test_fused_frontier.py).  Call signatures match the jnp builders
    # exactly, so DeviceFrontier routes by name alone.

    def _fused_ctx(self, LOW):
        from repro.core.frontier import _compact, _sort_unique

        return (
            jnp.asarray(self._mask_np[None, :]),
            jnp.asarray(LOW),
            self.n_pad_rows,
            dict(block_n=self.plan.block_n),
            _compact,
            _sort_unique,
        )

    def spmd_step_fused(self, variant: str, LOW):
        """Fused-kernel 1-D step for ``variant`` ∈ ``fkern.VARIANTS``."""
        iceberg, cbo, unique = fkern.VARIANTS[variant]
        plan, ctx = self.plan, self.ctx
        mask, LOW_c, n_pad, kw, _compact, _sort_unique = self._fused_ctx(LOW)
        axes = plan.reduce_axes

        def compact_out(keep, gc):
            n, gc = _sort_unique(gc, keep) if unique else _compact(keep, gc)
            return gc, n

        if plan.n_parts == 1:
            if variant == "plain":

                def body(rows_local, cands):
                    gc, _, _ = fkern.fused_closure_call(
                        rows_local, cands, mask,
                        fkern.pack_scalars(0, 0, n_pad, 0), **kw,
                    )
                    return gc

                return jax.jit(plan.spmd(body, n_rep=1))

            if cbo:

                def body(rows_local, cands, parents, gens, n_valid, *ms):
                    sc = fkern.pack_scalars(
                        n_valid, ms[0] if iceberg else 0, n_pad, 0
                    )
                    gc, _, keep = fkern.fused_closure_call(
                        rows_local, cands, mask, sc,
                        parent=parents, lowrow=LOW_c[gens],
                        iceberg=iceberg, cbo=True, **kw,
                    )
                    return gc, keep, gens

                def post(gc, keep, gens):
                    n, gc, gens = _compact(keep, gc, gens)
                    return gc, gens, n

                return jax.jit(
                    plan.spmd(body, n_rep=5 if iceberg else 4, post=post)
                )

            def body(rows_local, cands, n_valid, *ms):
                sc = fkern.pack_scalars(
                    n_valid, ms[0] if iceberg else 0, n_pad, 0
                )
                gc, _, keep = fkern.fused_closure_call(
                    rows_local, cands, mask, sc, iceberg=iceberg, **kw,
                )
                return gc, keep

            return jax.jit(
                plan.spmd(
                    body,
                    n_rep=3 if iceberg else 2,
                    post=lambda gc, keep: compact_out(keep, gc),
                )
            )

        # multi-shard: map kernel → collectives → fused filter kernel
        with_sup = iceberg

        def make(impl):
            def body(rows_local, cands):
                lc, ls = fkern.map_closure_call(rows_local, cands, mask, **kw)
                gc = collectives.and_allreduce(
                    lc, axes, impl=impl, n_attrs=ctx.n_attrs
                )
                if with_sup:
                    return gc, lax.psum(ls, axes) - n_pad
                return gc

            if variant == "plain":
                return jax.jit(plan.spmd(body, n_rep=1))

            if cbo:
                if iceberg:

                    def post(gc, gs, parents, gens, n_valid, min_sup):
                        _, keep = fkern.filter_call(
                            gc, gs,
                            fkern.pack_scalars(n_valid, min_sup, 0, 0),
                            parent=parents, lowrow=LOW_c[gens],
                            iceberg=True, cbo=True,
                        )
                        n, gc, gens = _compact(keep, gc, gens)
                        return gc, gens, n

                    n_extra = 4
                else:

                    def post(gc, parents, gens, n_valid):
                        _, keep = fkern.filter_call(
                            gc, jnp.zeros(gc.shape[0], jnp.int32),
                            fkern.pack_scalars(n_valid, 0, 0, 0),
                            parent=parents, lowrow=LOW_c[gens],
                            cbo=True,
                        )
                        n, gc, gens = _compact(keep, gc, gens)
                        return gc, gens, n

                    n_extra = 3
            elif iceberg:

                def post(gc, gs, n_valid, min_sup):
                    _, keep = fkern.filter_call(
                        gc, gs, fkern.pack_scalars(n_valid, min_sup, 0, 0),
                        iceberg=True,
                    )
                    return compact_out(keep, gc)

                n_extra = 2
            else:  # unique — validity-only mask needs no filter kernel

                def post(gc, n_valid):
                    keep = jnp.arange(gc.shape[0]) < n_valid
                    return compact_out(keep, gc)

                n_extra = 1

            return jax.jit(
                plan.spmd(body, n_rep=1, post=post, n_post_rep=n_extra)
            )

        if plan.reduce_impl != "auto":
            return make(plan.reduce_impl)
        steps = {impl: make(impl) for impl in AUTO_IMPLS}

        def dispatch(rows, cands, *extras):
            impl = plan.resolve_impl(cands.shape[0], ctx.W, ctx.n_attrs)
            return steps[impl](rows, cands, *extras)

        return dispatch

    def spmd_step_cand_fused(self, variant: str, LOW, merge, *, n_merge_rep=0):
        """Fused-kernel 2-D twin: ``variant`` per candidate block, filters
        block-local (``row_off = cand_index · Bc`` rides the kernels'
        scalar operand), survivors gathered along ``cand`` into ``merge``.
        """
        iceberg, cbo, unique = fkern.VARIANTS[variant]
        plan, ctx = self.plan, self.ctx
        mask, LOW_c, n_pad, kw, _compact, _sort_unique = self._fused_ctx(LOW)
        axes = plan.reduce_axes

        def compact_out(keep, gc):
            n, gc = _sort_unique(gc, keep) if unique else _compact(keep, gc)
            return gc, n

        if plan.n_parts == 1:
            if variant == "plain":

                def body(rows_local, cands):
                    gc, _, _ = fkern.fused_closure_call(
                        rows_local, cands, mask,
                        fkern.pack_scalars(0, 0, n_pad, 0), **kw,
                    )
                    return gc

                return jax.jit(
                    plan.spmd_cand(body, n_cand=1, merge=merge)
                )

            if cbo:

                def body(rows_local, cands, parents, gens, n_valid, *ms):
                    sc = fkern.pack_scalars(
                        n_valid, ms[0] if iceberg else 0, n_pad,
                        plan.cand_index() * cands.shape[0],
                    )
                    gc, _, keep = fkern.fused_closure_call(
                        rows_local, cands, mask, sc,
                        parent=parents, lowrow=LOW_c[gens],
                        iceberg=iceberg, cbo=True, **kw,
                    )
                    return gc, keep, gens

                def post(idx, gc, keep, gens):
                    n, gc, gens = _compact(keep, gc, gens)
                    return gc, gens, n

                return jax.jit(
                    plan.spmd_cand(
                        body, n_cand=3, n_rep=2 if iceberg else 1,
                        post=post, merge=merge, n_merge_rep=n_merge_rep,
                    )
                )

            def body(rows_local, cands, n_valid, *ms):
                sc = fkern.pack_scalars(
                    n_valid, ms[0] if iceberg else 0, n_pad,
                    plan.cand_index() * cands.shape[0],
                )
                gc, _, keep = fkern.fused_closure_call(
                    rows_local, cands, mask, sc, iceberg=iceberg, **kw,
                )
                return gc, keep

            return jax.jit(
                plan.spmd_cand(
                    body, n_cand=1, n_rep=2 if iceberg else 1,
                    post=lambda idx, gc, keep: compact_out(keep, gc),
                    merge=merge, n_merge_rep=n_merge_rep,
                )
            )

        with_sup = iceberg

        def make(impl):
            def body(rows_local, *cand_ops):
                lc, ls = fkern.map_closure_call(
                    rows_local, cand_ops[0], mask, **kw
                )
                gc = collectives.and_allreduce(
                    lc, axes, impl=impl, n_attrs=ctx.n_attrs
                )
                if with_sup:
                    return (gc, lax.psum(ls, axes) - n_pad, *cand_ops[1:])
                return (gc, *cand_ops[1:])

            if variant == "plain":
                return jax.jit(plan.spmd_cand(body, n_cand=1, merge=merge))

            if cbo:
                if iceberg:

                    def post(idx, gc, gs, parents, gens, n_valid, min_sup):
                        sc = fkern.pack_scalars(
                            n_valid, min_sup, 0, idx * gc.shape[0]
                        )
                        _, keep = fkern.filter_call(
                            gc, gs, sc, parent=parents, lowrow=LOW_c[gens],
                            iceberg=True, cbo=True,
                        )
                        n, gc, gens = _compact(keep, gc, gens)
                        return gc, gens, n

                    n_extra = 2
                else:

                    def post(idx, gc, parents, gens, n_valid):
                        sc = fkern.pack_scalars(n_valid, 0, 0, idx * gc.shape[0])
                        _, keep = fkern.filter_call(
                            gc, jnp.zeros(gc.shape[0], jnp.int32), sc,
                            parent=parents, lowrow=LOW_c[gens],
                            cbo=True,
                        )
                        n, gc, gens = _compact(keep, gc, gens)
                        return gc, gens, n

                    n_extra = 1
                return jax.jit(
                    plan.spmd_cand(
                        body, n_cand=3, post=post, n_post_rep=n_extra,
                        merge=merge, n_merge_rep=n_merge_rep,
                    )
                )

            if iceberg:

                def post(idx, gc, gs, n_valid, min_sup):
                    sc = fkern.pack_scalars(
                        n_valid, min_sup, 0, idx * gc.shape[0]
                    )
                    _, keep = fkern.filter_call(
                        gc, gs, sc, iceberg=True
                    )
                    return compact_out(keep, gc)

                n_extra = 2
            else:  # unique — validity-only mask needs no filter kernel

                def post(idx, gc, n_valid):
                    keep = (jnp.arange(gc.shape[0]) + idx * gc.shape[0]) < n_valid
                    return compact_out(keep, gc)

                n_extra = 1

            return jax.jit(
                plan.spmd_cand(
                    body, n_cand=1, post=post, n_post_rep=n_extra,
                    merge=merge, n_merge_rep=n_merge_rep,
                )
            )

        if plan.reduce_impl != "auto":
            return make(plan.reduce_impl)
        steps = {impl: make(impl) for impl in AUTO_IMPLS}

        def dispatch(rows, cands, *extras):
            block = cands.shape[0] // plan.cand_parts
            impl = plan.resolve_impl(block, ctx.W, ctx.n_attrs)
            return steps[impl](rows, cands, *extras)

        return dispatch

    # -- stats accounting ---------------------------------------------------

    def charge_round(self, cap: int, n_valid: int, *, count_round: bool = True):
        """Ledger one SPMD closure dispatch of a ``cap``-padded batch."""
        self.stats.closure_calls += 1
        if count_round:
            self.stats.rounds += 1
        self.stats.closures_computed += n_valid
        hops, vol = self.plan.modeled_latency_split(
            cap, self.ctx.W, self.ctx.n_attrs
        )
        self.stats.modeled_comm_bytes += vol
        self.stats.modeled_dispatch_bytes += hops
        self.stats.modeled_collective_bytes += vol
        impl = self.plan.resolve_impl(cap, self.ctx.W, self.ctx.n_attrs)
        self.stats.record_reduce(impl)

    def charge_round_cand(
        self, block_cap: int, n_valid: int, *, count_round: bool = True
    ):
        """Ledger one 2-D dispatch: ``cand_parts`` blocks of ``block_cap``
        candidates each (object reduce per block + the cand-axis survivor
        gather — see ShardPlan.modeled_round_bytes_cand)."""
        self.stats.closure_calls += 1
        if count_round:
            self.stats.rounds += 1
        self.stats.closures_computed += n_valid
        hops, vol = self.plan.modeled_latency_split_cand(
            block_cap, self.ctx.W, self.ctx.n_attrs
        )
        self.stats.modeled_comm_bytes += vol
        self.stats.modeled_dispatch_bytes += hops
        self.stats.modeled_collective_bytes += vol
        impl = self.plan.resolve_impl(block_cap, self.ctx.W, self.ctx.n_attrs)
        self.stats.record_reduce(impl)

    # -- public API ----------------------------------------------------------

    @property
    def min_bucket(self) -> int:
        return max(8, self.n_parts)

    def closure(self, cands: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Global closures + supports for a host candidate batch [B, W]."""
        B = cands.shape[0]
        if B == 0:
            return (
                np.zeros((0, self.ctx.W), np.uint32),
                np.zeros((0,), np.int32),
            )
        out_c = np.empty((B, self.ctx.W), np.uint32)
        out_s = np.empty((B,), np.int32)
        self.stats.rounds += 1
        with obs.current().span("engine/closure", batch=B):
            for lo in range(0, B, self.max_batch):
                chunk = cands[lo : lo + self.max_batch]
                b = chunk.shape[0]
                cap = ops.bucket_size(b, minimum=self.min_bucket)
                if cap != b:  # pad with all-ones candidates; outputs dropped
                    pad = np.full((cap - b, self.ctx.W), 0xFFFFFFFF, np.uint32)
                    chunk = np.concatenate([chunk, pad], axis=0)
                gc, gs = self._step(self.rows, jnp.asarray(chunk))
                out_c[lo : lo + b] = np.asarray(gc)[:b]
                out_s[lo : lo + b] = np.asarray(gs)[:b]
                self.charge_round(cap, b, count_round=False)
                self.stats.h2d_transfers += 1
                self.stats.h2d_bytes += cap * self.ctx.W * 4
                self.stats.d2h_transfers += 2
                self.stats.d2h_bytes += cap * (self.ctx.W + 1) * 4
        return out_c, out_s

    def closure_dev(
        self, cands, n_valid: int, *, count_round: bool = True
    ):
        """Device-to-device closure for an already bucket-padded batch.

        ``cands`` is a device array [cap, W]; rows past ``n_valid`` are
        padding whose outputs the caller ignores.  Nothing crosses the
        host boundary — this is the frontier pipeline's map+reduce step.
        """
        cap = cands.shape[0]
        gc, gs = self._step(self.rows, cands)
        self.charge_round(cap, n_valid, count_round=count_round)
        return gc, gs

    def first_closure(self) -> tuple[np.ndarray, int]:
        """``∅''`` and its support ``|O|`` via a full map/reduce round."""
        empty = np.zeros((1, self.ctx.W), np.uint32)
        c, s = self.closure(empty)
        return c[0], int(s[0])
