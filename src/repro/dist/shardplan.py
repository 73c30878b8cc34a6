"""ShardPlan — the one partition-aware SPMD execution layer (paper §3).

Every MR* round is the same program: per-shard local closure over the
object-partitioned context, then a bitwise-AND all-reduce (Theorem 2) plus
whatever per-round filter rides along (dedupe, canonicity, feasibility).
Historically the engine kept two divergent code paths for this — a
``shard_map`` path over a real jax Mesh and a hand-rolled reshape-and-vmap
path for simulated partitions on one device.  ``ShardPlan`` collapses both
behind one abstraction that owns

  * **partition geometry** — object-axis shard count for the context
    (``n_parts``), block alignment (``block_n``) and the frontier-batch
    chunk cap for candidates (``max_batch``);
  * **device placement** — ``place_rows`` shards the context over the
    plan's axes, ``replicate`` pins frontier/table state to every shard;
  * **the collective schedule** — which AND-allreduce implementation
    (``allgather`` / ``rsag`` / ``pmin``, see :mod:`repro.dist.collectives`)
    the reduce phase runs, and its analytic wire-byte model.  With
    ``reduce_impl="auto"`` the plan autotunes: ``resolve_impl`` picks
    allgather-vs-rsag per round by minimizing the α-β cost model
    (wire volume + ring-step latency) for that round's padded batch.

The plan is 2-D capable: besides the object axes it can block the
*candidate/frontier* axis over ``cand_parts`` devices (a ``"cand"`` mesh
axis) or simulated lanes — the Spark FCA reproduction's row-block ×
column-block decomposition.  ``spmd_cand`` is the 2-D execution
primitive: candidate operands are blocked along ``cand``, the
AND-allreduce runs over the object axes only (inside each block, at the
block batch size), driver filters run block-locally, and only the
filtered survivors are all-gathered along ``cand``.

``spmd(body, n_rep)`` is the 1-D execution primitive: ``body`` receives
the local context shard plus replicated operands and may call collectives
over ``plan.reduce_axes``.  On a mesh plan it lowers through
``shard_map``; on a simulated plan the *same body* runs under ``jax.vmap``
with a named axis over the reshaped ``[k, N/k, W]`` rows — jax's batched
collective rules make ``all_gather`` / ``all_to_all`` / ``pmin`` /
``psum`` execute the identical arithmetic, so the two modes are
bit-identical by construction (asserted in tests/test_shardplan.py and the
8-device harness).  The AND semigroup is associative, commutative and
idempotent over uint32 words, so every schedule agrees bit-for-bit too.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist import collectives
from repro.dist.partition import object_axes

# vmap axis name carrying the simulated object partition. Collectives in a
# shard body reference ``plan.reduce_axes`` and never this name directly.
SIM_AXIS = "objpart"

# vmap axis name carrying the simulated *candidate* partition (the frontier
# axis of the 2-D decomposition).  On a mesh the candidate axis is the mesh
# axis named "cand"; bodies reference ``plan.cand_axes``.
SIM_CAND_AXIS = "candpart"

# Mesh axis name carrying the candidate partition on real meshes.
CAND_AXIS = "cand"

# Schedules the autotuner arbitrates between. ``pmin`` is excluded: its
# unpacked-lane volume is strictly dominated for every batch size.
AUTO_IMPLS = ("allgather", "rsag")


def _attach_audit(runner, spec: dict):
    """Attach the static-analysis contract to an SPMD runner.

    ``repro.analysis.spmd_audit`` traces ``spec["shard_fn"]`` — the
    canonical per-shard function, *before* shard_map/vmap lowering — under
    an extended axis environment to verify the collective schedule and the
    wire-byte census against the plan's analytic model.  The attribute
    survives ``jax.jit`` (the jit wrapper forwards attribute access), so
    the auditor can introspect the exact jitted steps the engine caches.
    """
    try:
        runner.audit_spec = spec
    except (AttributeError, TypeError):  # exotic callables: skip, don't break
        pass
    return runner


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Partition geometry + placement + collective schedule for one run."""

    mesh: Mesh | None
    axis_names: tuple[str, ...]
    n_parts: int
    reduce_impl: str = "rsag"
    block_n: int = 256
    max_batch: int = 8192
    # 2-D decomposition: the candidate/frontier axis is blocked over
    # ``cand_parts`` devices (mesh axes ``cand_axis_names``) or simulated
    # lanes.  Objects stay sharded over ``axis_names`` as before; the
    # AND-allreduce runs inside each candidate block (over the object axes
    # only) and survivors are all-gathered along ``cand`` after the fused
    # post-reduce filters — see :meth:`spmd_cand`.
    cand_parts: int = 1
    cand_axis_names: tuple[str, ...] = ()
    # latency term of the "auto" schedule model: bandwidth-equivalent byte
    # cost of one ring step per device (collectives.modeled_cost_bytes).
    # The 4096 B default is replaced by a measured value when the plan is
    # built with ``calibrate_hops=True`` (see :func:`probe_hop_bytes`).
    auto_hop_bytes: int = 4096
    hop_calibrated: bool = False

    def __post_init__(self):
        if (
            self.reduce_impl != "auto"
            and self.reduce_impl not in collectives.IMPLS
        ):
            raise ValueError(
                f"unknown reduce schedule {self.reduce_impl!r}; "
                f"choose {collectives.IMPLS + ('auto',)}"
            )
        if self.n_parts < 1:
            raise ValueError(f"n_parts must be >= 1, got {self.n_parts}")
        if self.cand_parts < 1:
            raise ValueError(
                f"cand_parts must be >= 1, got {self.cand_parts}"
            )
        if self.mesh is not None and self.cand_parts > 1:
            k = 1
            for a in self.cand_axis_names:
                k *= self.mesh.shape[a]
            if k != self.cand_parts:
                raise ValueError(
                    f"cand_parts ({self.cand_parts}) does not match the "
                    f"mesh's candidate axes {self.cand_axis_names} ({k})"
                )

    # -- constructors ------------------------------------------------------

    @classmethod
    def simulated(
        cls,
        n_parts: int = 1,
        *,
        cand_parts: int = 1,
        reduce_impl: str = "rsag",
        block_n: int = 256,
        max_batch: int = 8192,
        calibrate_hops: bool = False,
    ) -> "ShardPlan":
        """``n_parts`` object shards on one device (reshape + named vmap);
        ``cand_parts`` > 1 adds simulated candidate-axis lanes."""
        plan = cls(
            mesh=None,
            axis_names=(SIM_AXIS,),
            n_parts=n_parts,
            reduce_impl=reduce_impl,
            block_n=block_n,
            max_batch=max_batch,
            cand_parts=cand_parts,
            cand_axis_names=(SIM_CAND_AXIS,) if cand_parts > 1 else (),
        )
        return plan.calibrate_hops() if calibrate_hops else plan

    @classmethod
    def over_mesh(
        cls,
        mesh: Mesh,
        *,
        axis_names: tuple[str, ...] | None = None,
        cand_axis_names: tuple[str, ...] | None = None,
        reduce_impl: str = "rsag",
        block_n: int = 256,
        max_batch: int = 8192,
        calibrate_hops: bool = False,
    ) -> "ShardPlan":
        """Real SPMD over ``mesh``; object rows sharded over ``axis_names``
        (default: whichever of the pod×data axes the mesh carries).  A mesh
        axis named ``"cand"`` (or explicit ``cand_axis_names``) blocks the
        candidate/frontier axis across devices — the 2-D decomposition."""
        if cand_axis_names is None:
            cand_axis_names = (CAND_AXIS,) if CAND_AXIS in mesh.shape else ()
        if axis_names is None:
            axis_names = object_axes(mesh)
        axis_names = tuple(a for a in axis_names if a not in cand_axis_names)
        if not axis_names:
            raise ValueError(
                f"mesh {dict(mesh.shape)} has none of the object axes"
            )
        k = 1
        for a in axis_names:
            k *= mesh.shape[a]
        c = 1
        for a in cand_axis_names:
            c *= mesh.shape[a]
        plan = cls(
            mesh=mesh,
            axis_names=tuple(axis_names),
            n_parts=k,
            reduce_impl=reduce_impl,
            block_n=block_n,
            max_batch=max_batch,
            cand_parts=c,
            cand_axis_names=tuple(cand_axis_names) if c > 1 else (),
        )
        return plan.calibrate_hops() if calibrate_hops else plan

    @classmethod
    def auto(
        cls, n_parts: int = 8, *, reduce_impl: str = "rsag", **kw
    ) -> "ShardPlan":
        """Mesh plan over all local devices when there are >1, else a
        simulated ``n_parts``-way plan on the single device."""
        devices = jax.devices()
        if len(devices) > 1:
            mesh = Mesh(np.asarray(devices), ("data",))
            return cls.over_mesh(mesh, reduce_impl=reduce_impl, **kw)
        return cls.simulated(n_parts, reduce_impl=reduce_impl, **kw)

    def calibrate_hops(self) -> "ShardPlan":
        """This plan with ``auto_hop_bytes`` measured, not defaulted.

        Runs :func:`probe_hop_bytes` (one-shot per interconnect, cached at
        module level) and records the result — the "auto" schedule's
        latency term then reflects the actual allgather step cost of the
        devices under the plan instead of the 4096 B guess.
        ``hop_calibrated`` stays False when the probe hit its noise floor
        (no measurable per-byte slope) and fell back to the default —
        the stats never claim a measurement that didn't happen.
        """
        hop, measured = probe_hop_bytes(self)
        return dataclasses.replace(
            self, auto_hop_bytes=hop, hop_calibrated=measured
        )

    # -- geometry ----------------------------------------------------------

    @property
    def is_simulated(self) -> bool:
        return self.mesh is None

    @property
    def reduce_axes(self):
        """Axis name(s) the shard body's collectives reduce over."""
        if self.mesh is None:
            return SIM_AXIS
        return self.axis_names if len(self.axis_names) > 1 else self.axis_names[0]

    @property
    def cand_axes(self):
        """Axis name(s) carrying the candidate partition (2-D plans only)."""
        if self.cand_parts <= 1:
            return None
        if self.mesh is None:
            return SIM_CAND_AXIS
        return (
            self.cand_axis_names
            if len(self.cand_axis_names) > 1
            else self.cand_axis_names[0]
        )

    @property
    def row_alignment(self) -> int:
        """Context rows must pad to a multiple of this (shards block-align)."""
        return self.n_parts * self.block_n

    def shard_index(self):
        """This shard's position along the object partition, traced.

        Only meaningful inside an ``spmd`` body.  Multi-axis meshes fold
        major-to-minor in ``axis_names`` order — the same order
        ``place_rows``'s ``PartitionSpec`` splits the row axis, so
        ``shard_index() * rows_local.shape[0]`` is the global offset of the
        shard's first row.
        """
        if self.mesh is None:
            return lax.axis_index(SIM_AXIS)
        idx = lax.axis_index(self.axis_names[0])
        for a in self.axis_names[1:]:
            idx = idx * self.mesh.shape[a] + lax.axis_index(a)
        return idx

    def cand_index(self):
        """This shard's position along the candidate partition, traced.

        Only meaningful inside an ``spmd_cand`` body; 0 on 1-D plans.
        Folds multi-axis candidate meshes major-to-minor exactly as
        ``shard_index`` folds the object axes."""
        if self.cand_parts <= 1:
            return jnp.int32(0)
        if self.mesh is None:
            return lax.axis_index(SIM_CAND_AXIS)
        idx = lax.axis_index(self.cand_axis_names[0])
        for a in self.cand_axis_names[1:]:
            idx = idx * self.mesh.shape[a] + lax.axis_index(a)
        return idx

    # -- placement ---------------------------------------------------------

    def place_rows(self, rows: np.ndarray) -> jax.Array:
        """Shard padded context rows ``[N, W]`` over the object axes.

        Mesh plan: ``NamedSharding`` over ``axis_names``.  Simulated plan:
        reshape to ``[k, N/k, W]`` so the named-vmap axis is the partition.
        """
        if rows.shape[0] % self.n_parts:
            raise ValueError(
                f"rows ({rows.shape[0]}) not divisible by n_parts ({self.n_parts})"
            )
        if self.mesh is not None:
            sharding = NamedSharding(self.mesh, P(self.axis_names, None))
            return jax.device_put(jnp.asarray(rows), sharding)
        return jnp.asarray(rows).reshape(
            self.n_parts, rows.shape[0] // self.n_parts, *rows.shape[1:]
        )

    def replicate(self, arr) -> jax.Array:
        """Pin dynamic per-round state (frontier, tables) to every shard, so
        expansion/pruning compute runs partition-locally instead of on one
        device followed by a broadcast at the SPMD region boundary."""
        if self.mesh is not None:
            return jax.device_put(jnp.asarray(arr), NamedSharding(self.mesh, P()))
        return jnp.asarray(arr)

    # -- execution ---------------------------------------------------------

    def spmd(
        self,
        body,
        *,
        n_rep: int,
        post=None,
        n_post_rep: int = 0,
        out_shard: tuple[bool, ...] | None = None,
    ):
        """Wrap ``body(rows_local, *replicated)`` for per-shard execution.

        The first argument is the object-sharded context; the following
        ``n_rep`` arguments are replicated.  ``body`` may call collectives
        over ``self.reduce_axes``; outputs must be shard-invariant (i.e.
        globally reduced or computed from replicated operands) and come
        back replicated — unless ``out_shard`` marks them otherwise.

        ``out_shard`` gives one region *mixed* output placement: a tuple of
        booleans, one per ``body`` output, where True means the output stays
        object-sharded (its leading axis is this shard's row slice — the
        same layout ``place_rows`` produces) and False means replicated /
        shard-invariant.  This is how the concept store builds the extent
        table on device: one region emits the sharded packed extent columns
        *and* the psum-reduced supports without a host round-trip.
        Incompatible with ``post`` (which by definition consumes
        shard-invariant inputs).

        ``post(*body_outputs, *post_replicated)`` is an optional fused
        stage consuming the shard-invariant reduced outputs (canonicity,
        feasibility, dedupe).  Because its input is identical on every
        shard, the plan owns its placement: on a mesh it runs inside the
        same SPMD region (each partition filters locally — the whole round
        is one ``shard_map``); on a simulated plan it runs once after the
        vmapped map+reduce, instead of k redundant lane copies on the one
        device.  Bit-identical either way.  The returned callable takes
        ``(rows, *replicated, *post_replicated)``; callers normally wrap
        it in ``jax.jit``.

        ``body`` may itself be a Pallas kernel call — the fused frontier
        steps (``repro.kernels.frontier``) run their ``pallas_call``
        inside this region: on a single-part plan the whole step (closure
        → support → filter) is one kernel; on multi-part plans the map
        kernel runs per shard here and the filter kernel rides in
        ``post`` after the cross-shard AND-allreduce.
        """
        if out_shard is not None and post is not None:
            raise ValueError("out_shard= and post= are mutually exclusive")

        # Canonical shard-level function — what one device runs inside the
        # SPMD region.  The mesh branch lowers exactly this through
        # shard_map; the simulated branch is its vmap twin.  The auditor
        # traces it (via ``audit_spec``) under an extended axis env, so
        # both branches expose identical collective structure.
        def fused(rows_local, *rep):
            out = body(rows_local, *rep[:n_rep])
            if post is None:
                return out
            out = out if isinstance(out, tuple) else (out,)
            return post(*out, *rep[n_rep:])

        spec = {
            "kind": "spmd",
            "plan": self,
            "shard_fn": fused,
            "n_rep": n_rep,
            "n_post_rep": n_post_rep,
            "has_post": post is not None,
        }
        if self.mesh is not None:
            in_specs = (P(self.axis_names, None),) + (P(),) * (n_rep + n_post_rep)
            if out_shard is None:
                out_specs = P()
            else:
                out_specs = tuple(
                    P(self.axis_names) if s else P() for s in out_shard
                )
            return _attach_audit(
                jax.shard_map(
                    fused,
                    mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=out_specs,
                    check_vma=False,  # pallas_call outputs carry no vma info
                ),
                spec,
            )

        vbody = jax.vmap(
            body,
            in_axes=(0,) + (None,) * n_rep,
            out_axes=0,
            axis_name=SIM_AXIS,
        )

        def run(rows, *rep):
            outs = vbody(rows, *rep[:n_rep])
            if out_shard is not None:
                # Sharded outputs keep the [k, rows/k, ...] lane-major
                # layout (the simulated twin of place_rows); replicated
                # ones collapse to lane 0 as usual.
                return tuple(
                    o if s else jax.tree_util.tree_map(lambda x: x[0], o)
                    for o, s in zip(outs, out_shard)
                )
            # Outputs are identical on every simulated shard (same invariant
            # the mesh path's ``out_specs=P()`` asserts); keep shard 0.
            outs = jax.tree_util.tree_map(lambda o: o[0], outs)
            if post is None:
                return outs
            outs = outs if isinstance(outs, tuple) else (outs,)
            return post(*outs, *rep[n_rep:])

        return _attach_audit(run, spec)

    def spmd_cand(
        self,
        body,
        *,
        n_cand: int = 1,
        n_rep: int = 0,
        post=None,
        n_post_rep: int = 0,
        merge=None,
        n_merge_rep: int = 0,
    ):
        """2-D (candidate × object) twin of :meth:`spmd`.

        The returned callable takes ``(rows, *cand_ops, *replicated,
        *post_replicated, *merge_replicated)``.  The first ``n_cand``
        operands after ``rows`` are *candidate-sharded*: their leading axis
        (a multiple of ``cand_parts``) is blocked over the candidate axis,
        so each device materializes only its ``1/cand_parts`` block of the
        frontier chunk.  ``body(rows_local, *cand_blocks, *replicated)``
        computes the per-(object-shard × candidate-block) map and may call
        collectives over ``reduce_axes`` — the AND-allreduce runs *inside*
        each candidate block, over the object axes only, at the block's
        batch size.

        ``post(cand_idx, *body_outputs, *post_replicated)`` is the fused
        block-local filter (canonicity / dedupe / iceberg cut): its inputs
        are object-shard-invariant but *differ per candidate block*, so it
        runs once per block (every object shard of a block computes it
        redundantly on a mesh — the same placement rule as ``spmd``'s
        post).  ``cand_idx`` is the block's position, letting the filter
        reconstruct global row validity from a replicated scalar count.

        Only after ``post`` are the blocks' survivors all-gathered along
        the candidate axis — pruned candidates never replicate across
        ``cand`` — giving every output a leading ``[cand_parts, ...]``
        block axis.  ``merge(*gathered, *merge_replicated)`` (optional)
        consumes the gathered stacks; its inputs are fully shard-invariant
        so the plan places it exactly like ``spmd``'s post: in-region on a
        mesh, once past the vmaps on a simulated plan.

        Degenerates gracefully at ``cand_parts == 1``: one block, the
        gather is a length-1 stack, and the arithmetic is bit-identical to
        the 1-D path (asserted in tests/test_cand_sharding.py).
        """
        cp = self.cand_parts
        split = n_cand + n_rep
        split_post = split + n_post_rep

        def _tup(x):
            return x if isinstance(x, tuple) else (x,)

        cand_axes = self.cand_axes

        # Canonical shard-level function (see ``spmd``): the mesh branch
        # lowers exactly this; the simulated branch's nested vmaps compute
        # the same arithmetic with the cand gather as a free array axis.
        # ``cand_axes`` resolves to the simulated axis name on simulated
        # plans, so the auditor traces the identical collective schedule
        # either way.
        def fused(rows_local, *ops):
            out = _tup(body(rows_local, *ops[:split]))
            if post is not None:
                out = _tup(
                    post(self.cand_index(), *out, *ops[split:split_post])
                )
            if cp > 1:
                gathered = tuple(
                    lax.all_gather(o, cand_axes) for o in out
                )
            else:
                gathered = tuple(o[None] for o in out)
            if merge is None:
                return gathered
            return merge(*gathered, *ops[split_post:])

        spec = {
            "kind": "spmd_cand",
            "plan": self,
            "shard_fn": fused,
            "n_cand": n_cand,
            "n_rep": n_rep,
            "n_post_rep": n_post_rep,
            "n_merge_rep": n_merge_rep,
            "has_post": post is not None,
            "has_merge": merge is not None,
        }

        if self.mesh is not None:

            def run(rows, *ops):
                cand_specs = tuple(
                    P(self.cand_axis_names or None, *([None] * (op.ndim - 1)))
                    if cp > 1
                    else P()
                    for op in ops[:n_cand]
                )
                in_specs = (
                    (P(self.axis_names, None),)
                    + cand_specs
                    + (P(),) * (len(ops) - n_cand)
                )
                return jax.shard_map(
                    fused,
                    mesh=self.mesh,
                    in_specs=in_specs,
                    out_specs=P(),
                    check_vma=False,
                )(rows, *ops)

            return _attach_audit(run, spec)

        # Simulated plan: nested named-axis vmaps — inner over the object
        # partition (collectives in ``body`` reduce over it), outer over
        # the candidate blocks.  The cand "all-gather" is free: after the
        # outer vmap the block axis IS a real array axis.
        inner = jax.vmap(
            body,
            in_axes=(0,) + (None,) * split,
            out_axes=0,
            axis_name=SIM_AXIS,
        )
        outer = jax.vmap(
            inner,
            in_axes=(None,) + (0,) * n_cand + (None,) * n_rep,
            out_axes=0,
            axis_name=SIM_CAND_AXIS,
        )

        def run(rows, *ops):
            blocks = tuple(
                op.reshape(cp, op.shape[0] // cp, *op.shape[1:])
                for op in ops[:n_cand]
            )
            outs = _tup(outer(rows, *blocks, *ops[n_cand:split]))
            # [cand, obj, ...] — object-shard-invariant, keep obj lane 0
            outs = tuple(o[:, 0] for o in outs)
            if post is not None:
                post_rep = ops[split:split_post]
                outs = _tup(
                    jax.vmap(lambda idx, *o: _tup(post(idx, *o, *post_rep)))(
                        jnp.arange(cp, dtype=jnp.int32), *outs
                    )
                )
            if merge is None:
                return outs
            return merge(*outs, *ops[split_post:])

        return _attach_audit(run, spec)

    # -- accounting --------------------------------------------------------

    def resolve_impl(
        self, batch: int, W: int, n_attrs: int | None = None
    ) -> str:
        """The schedule one reduce round of ``batch`` candidates runs.

        A fixed ``reduce_impl`` is returned as-is; ``"auto"`` picks the
        α-β-cheapest of :data:`AUTO_IMPLS` for this round's measured batch
        (``collectives.modeled_cost_bytes``: allgather's single ring pass
        wins latency-bound small batches, rsag's 2(k-1)/k volume wins
        bandwidth-bound large ones).  Deterministic in the padded batch
        size, so the per-bucket jit caches see a stable choice.
        """
        if self.reduce_impl != "auto":
            return self.reduce_impl
        return min(
            AUTO_IMPLS,
            key=lambda impl: collectives.modeled_cost_bytes(
                impl, self.n_parts, batch, W, n_attrs,
                hop_bytes=self.auto_hop_bytes,
            ),
        )

    def modeled_reduce_bytes(
        self, batch: int, W: int, n_attrs: int | None = None
    ) -> int:
        """Analytic wire bytes one reduce round of ``batch`` candidates
        costs under this plan's schedule (see collectives.modeled_comm_bytes)."""
        return collectives.modeled_comm_bytes(
            self.resolve_impl(batch, W, n_attrs), self.n_parts, batch, W, n_attrs
        )

    def modeled_round_bytes_cand(
        self, block_batch: int, W: int, n_attrs: int | None = None
    ) -> int:
        """Analytic wire bytes for one 2-D round of ``cand_parts`` blocks
        of ``block_batch`` candidates each.

        Two terms: the AND-allreduce runs in ``cand_parts`` independent
        object-axis rings, each at the *block* batch size (this is the 2-D
        win — the reduce a device participates in is sized by its block,
        not the full chunk), plus the survivor all-gather along the
        candidate axis (``n_parts`` rings of ``cand_parts`` devices, one
        allgather pass over the block-sized survivor buffer each).
        """
        obj = self.cand_parts * collectives.modeled_comm_bytes(
            self.resolve_impl(block_batch, W, n_attrs),
            self.n_parts,
            block_batch,
            W,
            n_attrs,
        )
        gather = (
            self.n_parts
            * self.cand_parts
            * (self.cand_parts - 1)
            * block_batch
            * W
            * 4
        )
        return obj + gather

    def modeled_latency_split(
        self, batch: int, W: int, n_attrs: int | None = None
    ) -> tuple[int, int]:
        """``(dispatch_bytes, collective_bytes)`` — the α-β split of one
        reduce round's modeled cost for a 1-D plan.

        The *dispatch* term is the per-hop latency charged in bandwidth-
        equivalent bytes (``n_parts × ring_steps × auto_hop_bytes`` — what
        speculative async rounds overlap with the next dispatch), the
        *collective* term the actual wire volume (what the schedule moves
        regardless of overlap).  Their sum is exactly
        ``collectives.modeled_cost_bytes`` for the resolved schedule; the
        collective term alone is what ``modeled_reduce_bytes`` reports.
        """
        impl = self.resolve_impl(batch, W, n_attrs)
        vol = collectives.modeled_comm_bytes(
            impl, self.n_parts, batch, W, n_attrs
        )
        hops = (
            self.n_parts
            * collectives.ring_steps(impl, self.n_parts)
            * self.auto_hop_bytes
        )
        return hops, vol

    def modeled_latency_split_cand(
        self, block_batch: int, W: int, n_attrs: int | None = None
    ) -> tuple[int, int]:
        """``(dispatch_bytes, collective_bytes)`` for one 2-D round.

        Volume terms mirror :meth:`modeled_round_bytes_cand` (per-block
        object reduces + the cand-axis survivor gather); the hop term adds
        the two ring schedules' latency steps — ``cand_parts`` independent
        object rings at the resolved impl plus ``n_parts`` cand-axis
        allgather rings — priced at ``auto_hop_bytes`` each.
        """
        impl = self.resolve_impl(block_batch, W, n_attrs)
        obj_vol = self.cand_parts * collectives.modeled_comm_bytes(
            impl, self.n_parts, block_batch, W, n_attrs
        )
        gather_vol = (
            self.n_parts
            * self.cand_parts
            * (self.cand_parts - 1)
            * block_batch
            * W
            * 4
        )
        obj_hops = (
            self.cand_parts
            * self.n_parts
            * collectives.ring_steps(impl, self.n_parts)
            * self.auto_hop_bytes
        )
        gather_hops = (
            self.n_parts
            * self.cand_parts
            * collectives.ring_steps("allgather", self.cand_parts)
            * self.auto_hop_bytes
        )
        return obj_hops + gather_hops, obj_vol + gather_vol

    def describe(self) -> dict:
        """JSON-friendly summary for launcher output and benchmark records."""
        return {
            "mode": "simulated" if self.mesh is None else "mesh",
            "n_parts": self.n_parts,
            "axes": list(self.axis_names),
            "cand_parts": self.cand_parts,
            "cand_axes": list(self.cand_axis_names),
            "mesh_shape": None if self.mesh is None else dict(self.mesh.shape),
            "reduce_impl": self.reduce_impl,
            "block_n": self.block_n,
            "max_batch": self.max_batch,
            "auto_hop_bytes": self.auto_hop_bytes,
            "hop_calibrated": self.hop_calibrated,
        }

    def trace_tags(self) -> dict:
        """The geometry tags every round span carries (repro.obs): the
        subset of :meth:`describe` that identifies the plan in a timeline
        without bloating per-event args."""
        return {
            "plan": "simulated" if self.mesh is None else "mesh",
            "n_parts": self.n_parts,
            "cand_parts": self.cand_parts,
            "reduce_impl": self.reduce_impl,
        }


# ---------------------------------------------------------------------------
# interconnect probe (auto_hop_bytes calibration)
# ---------------------------------------------------------------------------

# One-shot per *plan geometry*: plans over the same devices with the same
# axis structure (object shard count + mesh axis shape + candidate blocks)
# share a measurement (the probe is geometry-, not schedule-, shaped).
# Keying on the full geometry — not just the interconnect — matters: an
# 8-shard ring pays different per-step latency than a 2-shard one, a
# pod×data mesh hops differently than a flat data mesh over the same
# devices, and a 2-D plan's object rings span a subset of the mesh; a
# calibrated value must never leak between them.  Values are
# (hop_bytes, measured) — measured=False marks a noise-floor fallback to
# the default.
_HOP_PROBE_CACHE: dict[tuple, tuple[int, bool]] = {}


def _probe_cache_key(plan: ShardPlan) -> tuple:
    """Cache key covering the plan geometry the probe actually measures."""
    if plan.mesh is None:
        mesh_axes = None
        devices = None
    else:
        mesh_axes = tuple((a, plan.mesh.shape[a]) for a in plan.mesh.shape)
        devices = tuple(str(d) for d in plan.mesh.devices.flat)
    return (
        plan.n_parts,
        plan.axis_names,
        plan.cand_parts,
        plan.cand_axis_names,
        mesh_axes,
        devices,
    )

_PROBE_W = 4  # packed words per probe row — scale-free, cancels in the ratio


def probe_hop_bytes(plan: ShardPlan) -> tuple[int, bool]:
    """Measure the plan's per-ring-step latency as equivalent wire bytes.

    Times the plan's own allgather AND-reduce (the exact collective the
    "auto" schedule arbitrates) at a tiny and a large batch:
    ``t(B) ≈ α + β·B`` separates the per-round fixed cost α (ring-step
    latency, dispatch) from the per-row cost β.  The model charges
    ``k·steps·hop_bytes`` latency bytes against ``k·(k-1)·B·W·4`` volume
    bytes for allgather, so the bandwidth-equivalent hop cost is
    ``hop_bytes = (α/β) · W · 4`` — independent of the probe's row width.
    Best-of-3 timings; returns ``(hop_bytes, measured)`` and caches it per
    plan geometry (:func:`_probe_cache_key` — device set × axis structure
    × shard counts on both axes).  ``measured=False`` means the probe saw no
    per-byte slope (noise floor) and fell back to the 4096 B default.
    """
    key = _probe_cache_key(plan)
    cached = _HOP_PROBE_CACHE.get(key)
    if cached is not None:
        return cached

    axes = plan.reduce_axes

    def body(rows_local, cands):
        lc = rows_local[:1] & cands  # touch the sharded operand
        return collectives.and_allreduce(
            lc, axes, impl="allgather", n_attrs=_PROBE_W * 32
        )

    fn = jax.jit(plan.spmd(body, n_rep=1))
    rows = plan.place_rows(np.ones((plan.n_parts, _PROBE_W), np.uint32))

    def timed(batch: int) -> float:
        cands = jnp.ones((batch, _PROBE_W), jnp.uint32)
        fn(rows, cands).block_until_ready()  # warm (compile excluded)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            fn(rows, cands).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best

    b_small, b_large = 8, 4096
    t_small, t_large = timed(b_small), timed(b_large)
    slope = t_large - t_small
    if slope <= 0:
        # Noise floor: the large batch measured no slower than the tiny
        # one, so the per-byte term is unobservable here — keep the
        # documented default rather than caching a nonsense ratio, and
        # report the measurement as failed.
        result = (4096, False)
    else:
        beta = slope / (b_large - b_small)
        alpha = max(t_small - beta * b_small, 0.0)
        # bound at 16 MiB: beyond that the "latency term" would just
        # mean the probe was swamped by noise
        hop = min(1 << 24, max(1, int(round(alpha / beta * _PROBE_W * 4))))
        result = (hop, True)
    _HOP_PROBE_CACHE[key] = result
    return result
