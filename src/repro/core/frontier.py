"""Device-resident frontier pipeline for the MR* drivers (§Perf F1, §Dist).

The seed drivers kept the *frontier* on the host: per-intent Python loops
built ⊕/CbO seeds, `np.unique` deduped candidates, and the two-level hash
filtered closures row by row — O(frontier · m) small host ops per
iteration.  This module runs the whole frontier side through the engine's
:class:`repro.dist.ShardPlan`:

    frontier [F, W]  ──►  vectorized seed expansion (LOW/BIT broadcast)
                     ──►  validity compaction (+ local pruning: lexsort +
                          adjacent-unique over packed words, *before* the
                          reduce — MRGanter+'s per-partition combiner)
                     ──►  plan-SPMD round, one region per chunk:
                          local closure map → AND-allreduce (+ support
                          psum) → fused canonicity / feasibility /
                          closure-dedupe / iceberg min-support cut
                     ──►  compacted survivors

Frontier state and the LOW/BIT tables are plan-replicated, so under a real
mesh the expansion and pruning stages compute partition-locally on every
device (no central expand + broadcast), and the only wire traffic per round
is the AND-allreduce itself — sized by the *pruned* candidate count, since
the chunk buckets are chosen after the dedupe.  Pruned candidates never
cross the wire.

On a 2-D plan (``ShardPlan.cand_parts > 1`` — the Spark reproduction's
row-block × column-block decomposition) the chunk itself is blocked over
the candidate axis: each device closes only its ``1/cand_parts`` block of
the chunk, the AND-allreduce runs over the object axes at the *block*
batch size, the driver filter runs block-locally, and the blocks' compacted
survivors are all-gathered along ``cand`` afterwards — so one round absorbs
``cand_parts × max_batch`` candidates at the same per-device footprint, and
pruned candidates never replicate across the candidate axis either.  XLA shapes are static, so the one scalar sync per round
(the surviving-seed count) is what lets the reduce shrink to the pruned
bucket; everything else stays on device.

Every stage is a jitted device function over bucket-padded shapes
(powers of two — recompiles are bounded by O(log max_frontier)); the host
loop shrinks to convergence control plus one bulk download of surviving
intents per iteration (and, for MRGanter+, one bulk upload of the novel
frontier after the global-registry check).  This is the Twister framing of
§3 taken to its limit: static data (context rows, LOW/BIT tables) never
moves, and the dynamic delta crossing the boundary is exactly the new
concepts.

Benchmarked in EXPERIMENTS.md §Perf/§Dist; equivalence to the host-loop
drivers is asserted in tests/test_frontier_pipeline.py and, on a real
8-device mesh, tests/test_distributed_8dev.py.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import lectic
from repro.kernels import frontier as fkern
from repro.kernels.ops import bucket_size
from repro.obs import trace as obs


# ---------------------------------------------------------------------------
# device primitives
# ---------------------------------------------------------------------------


def _partition(valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(count, perm)``: the stable permutation moving rows with ``valid``
    to the front — ``argsort(~valid)``, built from prefix sums and one
    scatter instead of a sort (XLA's TPU sort takes seconds to compile
    per shape, and the drivers compile one per bucket)."""
    n = valid.sum(dtype=jnp.int32)
    dest = jnp.where(
        valid,
        jnp.cumsum(valid, dtype=jnp.int32) - 1,
        n + jnp.cumsum(~valid, dtype=jnp.int32) - 1,
    )
    idx = jnp.arange(valid.shape[0], dtype=jnp.int32)
    return n, jnp.zeros_like(idx).at[dest].set(idx, unique_indices=True)


def _compact(valid: jax.Array, *arrays) -> tuple:
    """Stable-move rows with ``valid`` to the front of every array.

    Returns ``(count, *reordered_arrays)`` — shapes unchanged (rows past
    ``count`` are garbage the caller slices away after a scalar sync).
    """
    n, perm = _partition(valid)
    return (n, *(a[perm] for a in arrays))


def _lexsort_rows(seeds: jax.Array, valid: jax.Array) -> jax.Array:
    """``jnp.lexsort`` order of packed rows, invalid rows last: one stable
    single-key sort per word, least significant first, then the validity
    partition.  The same permutation as one sort keyed on every word, but
    XLA compiles it for a TPU in a fraction of the time (a 128k-row
    six-operand sort takes minutes)."""
    perm = jnp.arange(seeds.shape[0], dtype=jnp.int32)
    for w in reversed(range(seeds.shape[1])):
        _, perm = lax.sort((seeds[perm, w], perm), num_keys=1, is_stable=True)
    return perm[_partition(valid[perm])[1]]


def _sort_unique(seeds: jax.Array, valid: jax.Array, *arrays) -> tuple:
    """Lexsort packed rows, mark adjacent duplicates, compact survivors.

    Invalid rows sort to the end (primary key), so duplicate detection only
    ever compares real rows.  Returns ``(count, seeds, *arrays)`` with the
    unique valid rows moved to the front.
    """
    perm = _lexsort_rows(seeds, valid)
    seeds = seeds[perm]
    valid = valid[perm]
    same_prev = jnp.all(seeds == jnp.roll(seeds, 1, axis=0), axis=-1)
    same_prev = same_prev.at[0].set(False)
    keep = valid & ~(same_prev & jnp.roll(valid, 1))
    return _compact(keep, seeds, *(a[perm] for a in arrays))


def slice_pad(arr, lo: int, cap: int, fill=0):
    """Static-shape device slice ``arr[lo:lo+cap]``, zero-padded past the
    end — keeps chunk shapes bucketed without a host round-trip.

    This is a *windowing* primitive: rows past ``lo + cap`` are simply not
    in this window, and the caller is responsible for covering them with
    further windows (the drivers' chunk loops) — callers that use it to
    retain an entire array must size ``cap`` to hold every live row (see
    :meth:`DeviceFrontier._adopt`, which guards exactly that).
    """
    chunk = arr[lo : lo + cap]
    short = cap - chunk.shape[0]
    if short > 0:
        pad = jnp.full((short, *arr.shape[1:]), fill, arr.dtype)
        chunk = jnp.concatenate([chunk, pad], axis=0)
    return chunk


# ---------------------------------------------------------------------------
# jitted stages (shapes bucketed by the driver)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_attrs", "dedupe"))
def expand_oplus(frontier, n_valid, LOW, BIT, *, n_attrs: int, dedupe: bool):
    """⊕-expansion of a frontier [F, W] → compacted seeds [F·m, W] + count.

    ``dedupe=True`` is MRGanter+'s local pruning: duplicate seeds die here,
    on the partition, before any reduce is sized (``dedupe_candidates``).
    """
    F, W = frontier.shape
    row_ok = jnp.arange(F) < n_valid
    seeds, valid = lectic.oplus_seeds_jnp(frontier, LOW, BIT, n_attrs)
    valid = valid & row_ok[:, None]
    seeds = seeds.reshape(F * n_attrs, W)
    valid = valid.reshape(F * n_attrs)
    if dedupe:
        n, seeds = _sort_unique(seeds, valid)
    else:
        n, seeds = _compact(valid, seeds)
    return seeds, n


@functools.partial(jax.jit, static_argnames=("n_attrs",))
def expand_cbo(frontier, gens, n_valid, BIT, *, n_attrs: int):
    """CbO expansion: seeds ``Y ∪ {a}`` for ``a > gen(Y), a ∉ Y``.

    Returns compacted ``(seeds [F·m, W], parent_rows, gen_attr, count)`` —
    parent/generator lineage rides along for the fused canonicity stage.
    """
    F, W = frontier.shape
    row_ok = jnp.arange(F) < n_valid
    seeds, valid = lectic.cbo_seeds_jnp(frontier, gens, BIT, n_attrs)
    valid = valid & row_ok[:, None]
    seeds = seeds.reshape(F * n_attrs, W)
    valid = valid.reshape(F * n_attrs)
    parent = jnp.repeat(jnp.arange(F, dtype=jnp.int32), n_attrs)
    gen = jnp.tile(jnp.arange(n_attrs, dtype=jnp.int32), F)
    n, seeds, parent, gen = _compact(valid, seeds, parent, gen)
    return seeds, frontier[parent], gen, n


def unique_closures(closures, n_valid):
    """Intra-batch dedupe of closure outputs: sorted-unique + compaction.

    The cross-iteration novelty check stays with the host registry; this
    stage just collapses the (heavily duplicated) reduce output so only
    distinct intents cross the device→host boundary.  Fused into the
    plan's SPMD round after the AND-allreduce (the plan places it:
    in-region on a mesh, once past the vmap on a simulated plan).
    """
    valid = jnp.arange(closures.shape[0]) < n_valid
    n, closures = _sort_unique(closures, valid)
    return closures, n


# -- candidate-axis (2-D) block merges ---------------------------------------
# Post-reduce filters run block-locally on each candidate shard; these
# merges consume the cand-axis all-gather of the filtered blocks
# ([cand_parts, Bc, ...] stacks + per-block survivor counts) and produce
# the chunk's global survivors.  Shard-invariant by construction (their
# inputs are the gathered stacks), so the plan places them like any fused
# post stage.


def _block_valid(counts, Bc):
    """Flattened validity mask for gathered [cand, Bc, ...] block stacks."""
    return (jnp.arange(Bc)[None, :] < counts[:, None]).reshape(-1)


def merge_blocks_plain(gc_blocks):
    """No filter ran: concatenating blocks restores the chunk's row order
    (block i held rows [i·Bc, (i+1)·Bc) of the chunk)."""
    return gc_blocks.reshape(-1, gc_blocks.shape[-1])


def merge_blocks_compact(gc_blocks, counts):
    """Compact each block's survivors (already front-packed) into one run."""
    valid = _block_valid(counts, gc_blocks.shape[1])
    n, gc = _compact(valid, gc_blocks.reshape(-1, gc_blocks.shape[-1]))
    return gc, n


def merge_blocks_unique(gc_blocks, counts):
    """Block-local dedupe removed intra-block duplicates; this pass removes
    the cross-block ones (sorted-unique over the concatenated survivors)."""
    valid = _block_valid(counts, gc_blocks.shape[1])
    n, gc = _sort_unique(gc_blocks.reshape(-1, gc_blocks.shape[-1]), valid)
    return gc, n


def merge_blocks_cbo(gc_blocks, gen_blocks, counts):
    """CbO survivors with their generator lineage (canonicity already ran
    block-locally; canonical survivors are globally unique by the CbO
    generation-tree argument, so compaction is the whole merge)."""
    valid = _block_valid(counts, gc_blocks.shape[1])
    n, gc, gens = _compact(
        valid,
        gc_blocks.reshape(-1, gc_blocks.shape[-1]),
        gen_blocks.reshape(-1),
    )
    return gc, gens, n


def filter_canonical(closures, parents, gens, n_valid, LOW):
    """CbO canonicity ``(Z ^ Y) & LOW[a] == 0`` + survivor compaction.

    Survivors are *exactly* the new concepts (CbO generates each concept
    once under this test), so they double as the next device frontier.
    Fused into the plan's SPMD round, on the globally-reduced closures.
    """
    ok = lectic.feasible_jnp(closures, parents, gens, LOW)
    ok = ok & (jnp.arange(closures.shape[0]) < n_valid)
    n, closures, gens = _compact(ok, closures, gens)
    return closures, gens, n


def ganter_select(closures, Y, valid, LOW, mask, *, n_attrs: int):
    """NextClosure's Alg.-5 scan as one device op: feasibility for every
    generator attribute, then the *largest* feasible one wins (the shared
    argmax + dynamic-slice gather in ``lectic.select_lectic``)."""
    gens = jnp.arange(n_attrs, dtype=jnp.int32)
    ok = lectic.feasible_jnp(closures[:n_attrs], Y[None, :], gens, LOW)
    ok = ok & valid
    Y_next, _ = lectic.select_lectic(closures[:n_attrs], ok)
    return Y_next, jnp.all(Y_next == mask)


# ---------------------------------------------------------------------------
# speculative round state (async scheduler)
# ---------------------------------------------------------------------------


@jax.jit
def _pack_round(a, b, payload):
    """Pack a round's scalar outcomes + payload into ONE uint32 D2H buffer.

    Layout ``[a, b, payload.ravel()]`` — the drivers' per-round readback
    (surviving-seed count, survivor count, and the survivor rows that used
    to cross as separate ``np.asarray`` calls) collapses to a single
    transfer whose copy is started asynchronously at dispatch time.
    """
    head = jnp.stack([a.astype(jnp.uint32), b.astype(jnp.uint32)])
    return jnp.concatenate([head, payload.reshape(-1).astype(jnp.uint32)])


def _start_d2h(arr) -> None:
    """Begin the device→host copy without blocking (overlaps the next
    dispatch); purely an optimization — the later ``np.asarray`` is what
    the reconcile actually waits on."""
    try:
        arr.copy_to_host_async()
    except Exception:  # pragma: no cover — optional fast path only
        pass


@dataclasses.dataclass
class SpecRound:
    """One in-flight speculative round: the second frontier slot.

    Holds the expansion buffers round r was dispatched from (so an under-
    covered speculation can re-chunk them synchronously), the survivor
    buffers the *next* round was speculatively chained on, and the packed
    readback already copying to the host.  ``cap`` is the speculative
    chunk's padded coverage — reconciliation compares it against the true
    seed count to decide whether speculation covered the round.  ``slot``
    is how many survivor rows the adopted slot kept (the next round's
    expansion input); a true survivor count past it means the in-flight
    speculation chained on a truncated frontier and must be discarded.
    """

    kind: str  # "oplus" | "cbo" | "ganter"
    packed: jax.Array
    cap: int
    blk: int
    two_d: bool
    seeds: jax.Array | None = None
    parents: jax.Array | None = None
    gen: jax.Array | None = None
    surv_z: jax.Array | None = None
    surv_g: jax.Array | None = None
    slot: int = 0
    # observability: the round's sequence number (the async trace span id)
    # and its dispatch timestamp (per-round latency = reconcile − dispatch)
    seq: int = 0
    t_dispatch: float = 0.0


@dataclasses.dataclass
class OplusRound:
    """Reconciled MRGanter+ round: true seed count + the round's closures."""

    n_seeds: int
    closures: np.ndarray
    under_covered: bool


@dataclasses.dataclass
class CboRound:
    """Reconciled MRCbo round: true seed count + canonical survivors."""

    n_seeds: int
    new_intents: np.ndarray
    n_new: int
    under_covered: bool


# ---------------------------------------------------------------------------
# driver-facing pipeline
# ---------------------------------------------------------------------------


class DeviceFrontier:
    """Holds the plan-replicated frontier state for one mining run and
    exposes the per-iteration fused steps the MR* drivers are written in.

    The engine's ShardPlan provides placement and the SPMD round builder
    (`spmd_step`); this class owns expansion/pruning orchestration, the
    fused post-reduce filters, and the bucket/chunk bookkeeping.
    """

    def __init__(self, engine, *, dedupe_closures: bool = False):
        self.engine = engine
        self.plan = engine.plan
        self.n_attrs = engine.ctx.n_attrs
        self.W = engine.ctx.W
        # Collapse duplicate *closure outputs* on device before download.
        # Saves D2H bandwidth on real accelerators; on the CPU 'device' the
        # XLA variadic sort costs more than the memcpy it saves, so the
        # default leaves cross-closure dedupe to the (vectorized) host
        # registry.  Equivalence holds either way (tests cover both).
        self.dedupe_closures = dedupe_closures
        self._frontier = None  # [Fb, W] plan-replicated
        self._gens = None  # [Fb] plan-replicated (CbO lineage)
        self._n = 0
        # Second frontier slot (async rounds): when a speculative round is
        # adopted before its counts are reconciled, ``_n`` is None and the
        # survivor count lives on device in ``_n_dev`` — round r+1 chains
        # on the device scalar without any host readback.
        self._n_dev = None
        # Last reconciled TRUE seed / survivor counts — size the next
        # speculative chunk and its adopted slot (see _spec_caps /
        # _slot_rows).  Hints only: too small merely triggers the
        # under-coverage fallback, never an incorrect result.
        self._seed_hint = None
        self._k_hint = None
        # Round sequence counter + plan-geometry tags for the span tracer
        # (repro.obs) — the seq numbers the ``mine/round[r]`` spans and ids
        # the async round tracks, so sync/async timelines line up.
        self._seq = 0
        self._tags = engine.plan.trace_tags()

        # Everything frontier-static is memoized on the ENGINE, not this
        # object: a driver builds a fresh DeviceFrontier per run, and
        # per-run jax.jit wrappers would re-trace and re-compile the whole
        # pipeline every run (defeating the warm-run protocol).  The
        # tables are engine-ctx-determined and the fused steps are
        # identical for every DeviceFrontier of a given engine.  Steps are
        # built lazily (``_step_fn``): a run that never mines icebergs
        # never traces the iceberg variants.
        #
        # The build runs under the engine's ``_frontier_lock``: frontiers
        # are constructed from both the main thread and the admission
        # dispatcher thread, and two racing first-misses would otherwise
        # each build a cache (losing the memoization and tracing every
        # step twice).
        with engine._frontier_lock:
            cache = getattr(engine, "_frontier_cache", None)
            if cache is None:
                t = lectic.LecticTables(self.n_attrs)
                n_attrs = self.n_attrs

                # Host-side tables are closed over by the fused post stages
                # (baked into the SPMD region as compile-time constants).
                def post_cbo(gc, parents, gens, n_valid):
                    return filter_canonical(
                        gc, parents, gens, n_valid, jnp.asarray(t.LOW)
                    )

                def post_ganter(gc, Y, valid):
                    return ganter_select(
                        gc, Y, valid, jnp.asarray(t.LOW),
                        jnp.asarray(t.attr_mask), n_attrs=n_attrs,
                    )

                # Iceberg posts: min_support rides as a *traced* extra operand,
                # so one compile serves every threshold.  The support filter
                # runs right after the psum, inside the same SPMD region —
                # infrequent candidates are compacted away before they are
                # downloaded, re-expanded, or ever sized into a later reduce.
                def post_iceberg(gc, gs, n_valid, min_sup):
                    keep = (jnp.arange(gc.shape[0]) < n_valid) & (gs >= min_sup)
                    n, gc = _compact(keep, gc)
                    return gc, n

                def post_iceberg_unique(gc, gs, n_valid, min_sup):
                    keep = (jnp.arange(gc.shape[0]) < n_valid) & (gs >= min_sup)
                    n, gc = _sort_unique(gc, keep)
                    return gc, n

                def post_cbo_iceberg(gc, gs, parents, gens, n_valid, min_sup):
                    ok = lectic.feasible_jnp(gc, parents, gens, jnp.asarray(t.LOW))
                    ok = ok & (jnp.arange(gc.shape[0]) < n_valid)
                    ok = ok & (gs >= min_sup)
                    n, gc, gens = _compact(ok, gc, gens)
                    return gc, gens, n

                # Candidate-axis (2-D) posts: the same filters made
                # *block-local* — each candidate shard filters its own block of
                # the chunk right after the object-axis reduce, using its block
                # index to reconstruct row validity from the replicated valid
                # count.  Survivors are all-gathered along ``cand`` only after
                # these run (the merge_blocks_* stages above finish the job).
                def _bvalid(idx, Bc, n_valid):
                    return (jnp.arange(Bc) + idx * Bc) < n_valid

                def post2d_unique(idx, gc, n_valid):
                    n, gc = _sort_unique(gc, _bvalid(idx, gc.shape[0], n_valid))
                    return gc, n

                def post2d_iceberg(idx, gc, gs, n_valid, min_sup):
                    keep = _bvalid(idx, gc.shape[0], n_valid) & (gs >= min_sup)
                    n, gc = _compact(keep, gc)
                    return gc, n

                def post2d_iceberg_unique(idx, gc, gs, n_valid, min_sup):
                    keep = _bvalid(idx, gc.shape[0], n_valid) & (gs >= min_sup)
                    n, gc = _sort_unique(gc, keep)
                    return gc, n

                def post2d_cbo(idx, gc, parents, gens, n_valid):
                    ok = lectic.feasible_jnp(gc, parents, gens, jnp.asarray(t.LOW))
                    ok = ok & _bvalid(idx, gc.shape[0], n_valid)
                    n, gc, gens = _compact(ok, gc, gens)
                    return gc, gens, n

                def post2d_cbo_iceberg(
                    idx, gc, gs, parents, gens, n_valid, min_sup
                ):
                    ok = lectic.feasible_jnp(gc, parents, gens, jnp.asarray(t.LOW))
                    ok = ok & _bvalid(idx, gc.shape[0], n_valid)
                    ok = ok & (gs >= min_sup)
                    n, gc, gens = _compact(ok, gc, gens)
                    return gc, gens, n

                def post_ganter_iceberg(gc, gs, Y, valid, min_sup):
                    # Alg.-5 scan restricted to *frequent* successors: the next
                    # frequent closure in lectic order is Y ⊕ a for the largest
                    # feasible a with support ≥ min_sup (any smaller frequent
                    # closure between would be a subset of it — see
                    # tests/test_rules.py for the property statement).
                    gens = jnp.arange(n_attrs, dtype=jnp.int32)
                    ok = lectic.feasible_jnp(
                        gc[:n_attrs], Y[None, :], gens, jnp.asarray(t.LOW)
                    )
                    ok = ok & valid & (gs[:n_attrs] >= min_sup)
                    Y_next, found = lectic.select_lectic(gc[:n_attrs], ok)
                    return Y_next, ~found

                cache = {
                    # plan-replicated so expansion runs on every partition
                    # instead of one device + a broadcast at the region edge
                    "LOW": self.plan.replicate(t.LOW),
                    "BIT": self.plan.replicate(t.BIT),
                    # fused per-round SPMD steps: each is ONE plan round doing
                    # closure map → AND-allreduce [+ support psum] → the
                    # driver's filter.  Values are zero-arg builders; built
                    # steps land in "steps".
                    "steps": {},
                    # step names routed to the fused Pallas kernels below;
                    # each dispatch of one counts in stats.fused_steps
                    "fused": frozenset(),
                    "builders": {
                        "plain": lambda: engine.spmd_step(),
                        "unique": lambda: engine.spmd_step(
                            unique_closures, n_extra=1
                        ),
                        "cbo": lambda: engine.spmd_step(post_cbo, n_extra=3),
                        "ganter": lambda: engine.spmd_step(post_ganter, n_extra=2),
                        "iceberg": lambda: engine.spmd_step(
                            post_iceberg, with_supports=True, n_extra=2
                        ),
                        "iceberg_unique": lambda: engine.spmd_step(
                            post_iceberg_unique, with_supports=True, n_extra=2
                        ),
                        "cbo_iceberg": lambda: engine.spmd_step(
                            post_cbo_iceberg, with_supports=True, n_extra=4
                        ),
                        "ganter_iceberg": lambda: engine.spmd_step(
                            post_ganter_iceberg, with_supports=True, n_extra=3
                        ),
                        # 2-D (candidate × object) variants: one plan round per
                        # chunk of cand_parts blocks — map + object-axis reduce
                        # per block, block-local filter, cand-axis survivor
                        # gather, merge.  Built only when a driver runs on a
                        # cand-sharded plan.
                        "plain2d": lambda: engine.spmd_step_cand(
                            None, merge_blocks_plain
                        ),
                        "unique2d": lambda: engine.spmd_step_cand(
                            post2d_unique, merge_blocks_unique, n_post_rep=1
                        ),
                        "iceberg2d": lambda: engine.spmd_step_cand(
                            post2d_iceberg, merge_blocks_compact,
                            with_supports=True, n_post_rep=2,
                        ),
                        "iceberg_unique2d": lambda: engine.spmd_step_cand(
                            post2d_iceberg_unique, merge_blocks_unique,
                            with_supports=True, n_post_rep=2,
                        ),
                        "cbo2d": lambda: engine.spmd_step_cand(
                            post2d_cbo, merge_blocks_cbo,
                            n_cand=3, n_post_rep=1,
                        ),
                        "cbo_iceberg2d": lambda: engine.spmd_step_cand(
                            post2d_cbo_iceberg, merge_blocks_cbo,
                            with_supports=True, n_cand=3, n_post_rep=2,
                        ),
                    },
                }
                # backend="kernel": route every step variant above (except the
                # single-intent ganter walks, whose map already runs the Pallas
                # closure kernel and whose argmax-select has no batch filter to
                # fuse) to the fused Pallas kernels — closure → support → driver
                # filter in one VMEM-resident pass (repro.kernels.frontier).
                # Same names, same call signatures, bit-identical outputs; the
                # jnp builders above remain the oracles the kernels are
                # property-tested against (tests/test_fused_frontier.py).
                if fkern.supports_fused(engine.backend, engine.ctx.W):
                    LOWt = t.LOW
                    fused = {
                        v: (lambda v=v: engine.spmd_step_fused(v, LOWt))
                        for v in fkern.VARIANTS
                    }
                    merges = {
                        "plain": merge_blocks_plain,
                        "unique": merge_blocks_unique,
                        "iceberg": merge_blocks_compact,
                        "iceberg_unique": merge_blocks_unique,
                        "cbo": merge_blocks_cbo,
                        "cbo_iceberg": merge_blocks_cbo,
                    }
                    for v, mg in merges.items():
                        fused[v + "2d"] = (
                            lambda v=v, mg=mg: engine.spmd_step_cand_fused(
                                v, LOWt, mg
                            )
                        )
                    cache["builders"].update(fused)
                    cache["fused"] = frozenset(fused)
                engine._frontier_cache = cache
        self._cache = cache
        self.LOW = cache["LOW"]
        self.BIT = cache["BIT"]

    def _step_fn(self, name: str):
        """Fused SPMD step ``name``, built on first use and memoized on the
        engine (shared by every DeviceFrontier of that engine).

        Double-checked under the engine's ``_frontier_lock``: the steps
        dict is shared by every frontier of the engine, including ones
        driven from the admission dispatcher thread, and a concurrent
        first-miss must not build (and jit) the same step twice."""
        if name in self._cache["fused"]:
            self.engine.stats.fused_steps += 1
        steps = self._cache["steps"]
        fn = steps.get(name)
        if fn is None:
            with self.engine._frontier_lock:
                fn = steps.get(name)
                if fn is None:
                    fn = steps[name] = self._cache["builders"][name]()
        return fn

    # -- frontier state ----------------------------------------------------

    def __len__(self) -> int:
        if self._n is None:
            raise RuntimeError(
                "frontier count is speculative — reconcile the in-flight "
                "round before asking for len()"
            )
        return self._n

    def set_frontier(self, intents: np.ndarray, gens: np.ndarray | None = None):
        """Upload a new frontier (one bulk H2D — the Twister dynamic delta)."""
        n = intents.shape[0]
        cap = bucket_size(max(1, n))
        buf = np.zeros((cap, self.W), np.uint32)
        buf[:n] = intents
        self._frontier = self.plan.replicate(buf)
        st = self.engine.stats
        st.h2d_transfers += 1
        st.h2d_bytes += buf.nbytes
        if gens is not None:
            gbuf = np.zeros((cap,), np.int32)
            gbuf[:n] = gens
            self._gens = self.plan.replicate(gbuf)
            st.h2d_transfers += 1
            st.h2d_bytes += gbuf.nbytes
        self._n = n
        self._n_dev = None
        # NOT a _k_hint update: frontier row count is a poor estimate of
        # the next round's survivor count (root uploads are 1 row, round-1
        # survivors up to n_attrs) — and with _n known the next spec's cap
        # is already exact, so an untruncated slot costs nothing extra.

    def _adopt(self, frontier_dev, gens_dev, n: int):
        """Keep device survivors as the next frontier (no host round-trip).

        ``slice_pad`` here only ever *grows* the buffer to the next bucket:
        the guard makes dropping live rows a loud error instead of a silent
        truncation.  Frontier size itself is unbounded — per-round device
        footprint is bounded by the chunk loops (``max_batch`` per chunk,
        × ``cand_parts`` blocks on a 2-D plan), never by this buffer.
        """
        if n > frontier_dev.shape[0]:
            raise RuntimeError(
                f"_adopt: {n} surviving frontier rows but only "
                f"{frontier_dev.shape[0]} device rows were materialized — "
                "adopting would silently drop concepts.  Raise max_batch or "
                "shard the frontier axis (ShardPlan cand_parts / "
                "--cand-shards)."
            )
        cap = bucket_size(max(1, n))
        self._frontier = slice_pad(frontier_dev, 0, cap)
        self._gens = None if gens_dev is None else slice_pad(gens_dev, 0, cap)
        self._n = n
        self._n_dev = None
        self._k_hint = max(1, n)

    def _download(self, arr_dev, n: int) -> np.ndarray:
        st = self.engine.stats
        t0 = time.perf_counter()
        out = np.asarray(arr_dev[:n])
        st.host_blocked_s += time.perf_counter() - t0
        st.d2h_transfers += 1
        st.d2h_bytes += out.nbytes
        return out

    def _block_scalar(self, x_dev) -> int:
        """Host-blocking scalar readback, ledgered as such: a 4-byte D2H
        transfer plus the wall time the host spent waiting on it (the
        per-round coordination cost async rounds exist to remove)."""
        st = self.engine.stats
        t0 = time.perf_counter()
        v = int(x_dev)
        st.host_blocked_s += time.perf_counter() - t0
        st.d2h_transfers += 1
        st.d2h_bytes += 4
        return v

    # -- chunk geometry ----------------------------------------------------

    @property
    def cand_parts(self) -> int:
        return self.plan.cand_parts

    @property
    def round_budget(self) -> int:
        """Candidates one closure round absorbs.  The driver picks chunking
        vs candidate-sharding from plan geometry: a 1-D plan chunks the
        stream at ``max_batch``; a cand-sharded plan runs ``cand_parts``
        blocks of up to ``max_batch`` each in ONE round, so the per-round
        budget multiplies while each device's block stays bounded."""
        return self.engine.max_batch * self.cand_parts

    def _block_cap(self, b: int) -> int:
        """Bucketed per-block capacity for a chunk of ``b`` candidates
        spread over the plan's candidate blocks."""
        return bucket_size(
            -(-b // self.cand_parts), minimum=self.engine.min_bucket
        )

    def _next_seq(self) -> int:
        """Monotone round sequence number — span index + async track id."""
        s = self._seq
        self._seq = s + 1
        return s

    # -- fused per-iteration steps ----------------------------------------

    def step_oplus(
        self, *, dedupe: bool, min_support: int | None = None
    ) -> np.ndarray:
        """One MRGanter+ iteration: expand → local prune → close → collect.

        Returns the round's closure intents (host array; de-duplicated on
        device when ``dedupe_closures``); the caller runs the global-
        registry novelty check and hands the novel rows back via
        :meth:`set_frontier`.  ``dedupe=True`` prunes duplicate seeds on
        the partition *before* the reduce is sized, so they never enter
        the AND-allreduce.  With ``min_support``, infrequent closures are
        compacted away right after the support psum, inside the same SPMD
        region — they never cross the device→host boundary and (because
        the caller re-expands only what it receives) never size a later
        round's reduce.
        """
        tr = obs.current()
        seq = self._next_seq()
        t_round = time.perf_counter()
        with tr.span(
            f"mine/round[{seq}]", algo="oplus", mode="sync", **self._tags
        ) as sp:
            with tr.span(f"mine/round[{seq}]/expand"):
                t0 = time.perf_counter()
                seeds, n_dev = expand_oplus(
                    self._frontier, jnp.int32(self._n), self.LOW, self.BIT,
                    n_attrs=self.n_attrs, dedupe=dedupe,
                )
                self.engine.stats.dispatch_s += time.perf_counter() - t0
                # scalar sync — sizes the reduce to the prune
                n_seeds = self._block_scalar(n_dev)
            if n_seeds == 0:
                return np.zeros((0, self.W), np.uint32)
            self._seed_hint = n_seeds
            out = np.concatenate(
                self._oplus_chunks(
                    seeds, n_seeds, 0, min_support=min_support, first=True,
                    seq=seq,
                ),
                axis=0,
            )
            sp.set(n_seeds=n_seeds, survivors=int(out.shape[0]))
        self.engine.stats.observe_latency(
            "round", time.perf_counter() - t_round
        )
        return out

    def _charge(self, two_d: bool, blk: int, cap: int, b: int, count: bool):
        if two_d:
            self.engine.charge_round_cand(blk, b, count_round=count)
        else:
            self.engine.charge_round(cap, b, count_round=count)

    def _chunk_caps(self, b: int) -> tuple[int, int]:
        """(padded chunk capacity, per-block capacity) for ``b`` seeds."""
        if self.cand_parts > 1:
            blk = self._block_cap(b)
            return blk * self.cand_parts, blk
        cap = bucket_size(b, minimum=self.engine.min_bucket)
        return cap, cap

    def _oplus_chunks(
        self,
        seeds,
        n_seeds: int,
        lo0: int,
        *,
        min_support: int | None,
        first: bool,
        force_unique: bool = False,
        seq: int = -1,
    ) -> list[np.ndarray]:
        """Close seeds ``[lo0, n_seeds)`` in round_budget chunks, one fused
        SPMD dispatch each, downloading every chunk's survivors.  Shared by
        the sync step and the async under-coverage fallback (every filter
        is row-wise, so chunk boundaries never change the surviving rows —
        only how many dispatches produce them)."""
        eng = self.engine
        tr = obs.current()
        pfx = f"mine/round[{seq}]"
        two_d = self.cand_parts > 1
        unique = self.dedupe_closures or force_unique
        parts = []
        for lo in range(lo0, n_seeds, self.round_budget):
            b = min(self.round_budget, n_seeds - lo)
            cap, blk = self._chunk_caps(b)
            chunk = slice_pad(seeds, lo, cap)
            t0 = time.perf_counter()
            if min_support is not None:
                name = "iceberg_unique" if unique else "iceberg"
                if two_d:
                    name += "2d"
                with tr.span(pfx + "/dispatch", chunk=b, cap=cap):
                    cl, k_dev = self._step_fn(name)(
                        eng.rows, chunk, jnp.int32(b), jnp.int32(min_support)
                    )
                    eng.stats.dispatch_s += time.perf_counter() - t0
                self._charge(two_d, blk, cap, b, first)
                with tr.span(pfx + "/allreduce"):
                    k = self._block_scalar(k_dev)
                with tr.span(pfx + "/filter", survivors=k):
                    parts.append(self._download(cl, k))
            elif unique:
                with tr.span(pfx + "/dispatch", chunk=b, cap=cap):
                    cl_u, k_dev = self._step_fn(
                        "unique2d" if two_d else "unique"
                    )(eng.rows, chunk, jnp.int32(b))
                    eng.stats.dispatch_s += time.perf_counter() - t0
                self._charge(two_d, blk, cap, b, first)
                with tr.span(pfx + "/allreduce"):
                    k = self._block_scalar(k_dev)
                with tr.span(pfx + "/filter", survivors=k):
                    parts.append(self._download(cl_u, k))
            else:
                with tr.span(pfx + "/dispatch", chunk=b, cap=cap):
                    closures = self._step_fn("plain2d" if two_d else "plain")(
                        eng.rows, chunk
                    )
                    eng.stats.dispatch_s += time.perf_counter() - t0
                self._charge(two_d, blk, cap, b, first)
                with tr.span(pfx + "/filter", survivors=b):
                    parts.append(self._download(closures, b))
            first = False
        return parts

    def step_cbo(
        self, *, min_support: int | None = None
    ) -> tuple[np.ndarray, int, int]:
        """One MRCbo iteration: expand → close+canonicity (fused) → adopt.

        The canonicity filter runs inside the same SPMD region as the
        closure map and reduce; canonical survivors stay on device as the
        next frontier and the same rows are downloaded once for the result
        set.  With ``min_support`` the support filter fuses into the same
        region (CbO intents only grow along the tree, so every frequent
        concept's canonical ancestors are frequent — pruning is lossless).
        Returns ``(new_intents, n_seeds, n_new)`` — ``n_seeds`` is 0
        when the frontier was already exhausted (no closure round ran).
        """
        tr = obs.current()
        seq = self._next_seq()
        t_round = time.perf_counter()
        with tr.span(
            f"mine/round[{seq}]", algo="cbo", mode="sync", **self._tags
        ) as sp:
            with tr.span(f"mine/round[{seq}]/expand"):
                t0 = time.perf_counter()
                seeds, parents, gen, n_dev = expand_cbo(
                    self._frontier, self._gens, jnp.int32(self._n), self.BIT,
                    n_attrs=self.n_attrs,
                )
                self.engine.stats.dispatch_s += time.perf_counter() - t0
                n_seeds = self._block_scalar(n_dev)
            if n_seeds == 0:
                self._n = 0
                return np.zeros((0, self.W), np.uint32), 0, 0
            self._seed_hint = n_seeds
            surv_z, surv_g, counts = self._cbo_chunks(
                seeds, parents, gen, n_seeds, 0,
                min_support=min_support, first=True, seq=seq,
            )
            n_new = sum(counts)
            sp.set(n_seeds=n_seeds, survivors=n_new)
            if n_new == 0:
                self._n = 0
                self.engine.stats.observe_latency(
                    "round", time.perf_counter() - t_round
                )
                return np.zeros((0, self.W), np.uint32), n_seeds, 0
            z_all = surv_z[0] if len(surv_z) == 1 else jnp.concatenate(surv_z)
            g_all = surv_g[0] if len(surv_g) == 1 else jnp.concatenate(surv_g)
            self._adopt(z_all, g_all, n_new)
            with tr.span(f"mine/round[{seq}]/filter", survivors=n_new):
                out = self._download(self._frontier, n_new)
        self.engine.stats.observe_latency(
            "round", time.perf_counter() - t_round
        )
        return out, n_seeds, n_new

    def _cbo_chunks(
        self,
        seeds,
        parents,
        gen,
        n_seeds: int,
        lo0: int,
        *,
        min_support: int | None,
        first: bool,
        seq: int = -1,
    ) -> tuple[list, list, list]:
        """Close+canonicity for CbO seeds ``[lo0, n_seeds)`` in
        round_budget chunks.  Returns device survivor buffers
        ``(z_list, g_list, k_list)`` — callers adopt/concatenate.  Shared
        by the sync step and the async under-coverage fallback (canonicity
        is row-wise, so chunk boundaries never change the survivors)."""
        eng = self.engine
        tr = obs.current()
        pfx = f"mine/round[{seq}]"
        two_d = self.cand_parts > 1
        surv_z, surv_g, counts = [], [], []
        for lo in range(lo0, n_seeds, self.round_budget):
            b = min(self.round_budget, n_seeds - lo)
            cap, blk = self._chunk_caps(b)
            args = (
                eng.rows,
                slice_pad(seeds, lo, cap),
                slice_pad(parents, lo, cap),
                slice_pad(gen, lo, cap),
                jnp.int32(b),
            )
            t0 = time.perf_counter()
            with tr.span(pfx + "/dispatch", chunk=b, cap=cap):
                if min_support is not None:
                    name = "cbo_iceberg2d" if two_d else "cbo_iceberg"
                    z, g, k_dev = self._step_fn(name)(
                        *args, jnp.int32(min_support)
                    )
                else:
                    z, g, k_dev = self._step_fn(
                        "cbo2d" if two_d else "cbo"
                    )(*args)
                eng.stats.dispatch_s += time.perf_counter() - t0
            self._charge(two_d, blk, cap, b, first)
            first = False
            with tr.span(pfx + "/allreduce"):
                k = self._block_scalar(k_dev)
            if k:
                surv_z.append(z[:k])
                surv_g.append(g[:k])
                counts.append(k)
        return surv_z, surv_g, counts

    def step_ganter(
        self, *, min_support: int | None = None
    ) -> tuple[np.ndarray, bool]:
        """One MRGanter iteration: ⊕-seeds for the single current intent,
        then one fused SPMD region: closure map → AND-allreduce → Alg.-5
        feasibility scan → argmax-select.  Returns ``(next intent (host),
        reached ⊤)``.

        With ``min_support`` the scan restricts to frequent successors
        (support psum ≥ threshold, fused in-region) and the flag flips to
        "no frequent successor exists" — when True, the returned intent is
        garbage the caller must NOT emit (the full-lattice contract emits
        ⊤ and reports done in the same step; the iceberg walk only learns
        it is done from an empty scan).

        Always runs the 1-D step, even on a cand-sharded plan: the MRGanter
        frontier is a single intent whose ≤ n_attrs seeds fit any block
        budget, and the Alg.-5 argmax-select needs every seed's closure in
        one place anyway (a cand split would immediately re-gather).  The
        1-D region is candidate-axis-invariant, so on a 2-D mesh it simply
        replicates over the cand axis."""
        eng = self.engine
        tr = obs.current()
        seq = self._next_seq()
        t_round = time.perf_counter()
        with tr.span(
            f"mine/round[{seq}]", algo="ganter", mode="sync", **self._tags
        ):
            with tr.span(f"mine/round[{seq}]/dispatch"):
                Y_next, done, nv_dev, cap = self._dispatch_ganter(min_support)
            with tr.span(f"mine/round[{seq}]/allreduce"):
                eng.charge_round(cap, self._block_scalar(nv_dev))
            with tr.span(f"mine/round[{seq}]/filter"):
                Y = self._download(Y_next[None, :], 1)[0]
                flag = bool(self._block_scalar(done))
        eng.stats.observe_latency("round", time.perf_counter() - t_round)
        return Y, flag

    def _dispatch_ganter(self, min_support):
        """Enqueue one Alg.-5 step (no host sync): seed expansion, the
        fused closure→select region, and the on-device frontier swap.
        Returns ``(Y_next, done, n_valid_seeds, cap)`` — all device."""
        eng = self.engine
        t0 = time.perf_counter()
        Y = self._frontier[0]
        seeds, valid = lectic.oplus_seeds_jnp(
            Y[None, :], self.LOW, self.BIT, self.n_attrs
        )
        seeds = seeds.reshape(self.n_attrs, self.W)
        cap = bucket_size(self.n_attrs, minimum=eng.min_bucket)
        if min_support is not None:
            Y_next, done = self._step_fn("ganter_iceberg")(
                eng.rows, slice_pad(seeds, 0, cap), Y, valid[0],
                jnp.int32(min_support),
            )
        else:
            Y_next, done = self._step_fn("ganter")(
                eng.rows, slice_pad(seeds, 0, cap), Y, valid[0]
            )
        cap_f = self._frontier.shape[0]
        self._frontier = jnp.broadcast_to(Y_next, (cap_f, self.W))
        self._n = 1
        eng.stats.dispatch_s += time.perf_counter() - t0
        return Y_next, done, valid[0].sum(dtype=jnp.int32), cap

    # -- speculative rounds (async scheduler) ------------------------------
    #
    # The async drivers dispatch round r+1's expansion against round r's
    # *unreconciled* survivor buffer: every step function already takes the
    # valid count as a traced operand, so the whole chain — expand → close
    # → filter → adopt — runs on device scalars and the host never blocks
    # between rounds.  The one D2H per round is a packed buffer (counts ++
    # survivors, ``_pack_round``) whose copy starts at dispatch time;
    # ``reconcile_*`` waits on it only when the driver needs round r's
    # result, by which time round r+1 is already in flight.
    #
    # Speculation is capped at ``round_budget``: the spec chunk covers
    # min(expansion bound, round_budget) seeds (bucket-padded, so coverage
    # can exceed the budget for free).  Reconciliation compares the true
    # seed count against that coverage — over-expanded rows were already
    # masked out by the traced valid count (reconcile-on-adopt: nothing
    # re-runs), and only genuine *under*-coverage falls back to synchronous
    # re-dispatch of the uncovered tail through the shared chunk runners.
    # Stats are charged at reconcile time, when true counts are known, so
    # the ledger matches the sync path and discarded speculative rounds
    # are never charged.

    def _n_arg(self):
        """The frontier's valid count as a step operand — the host int when
        reconciled, the device scalar when speculative (never a readback)."""
        return self._n_dev if self._n is None else jnp.int32(self._n)

    def _adopt_spec(self, frontier_dev, gens_dev, k_dev):
        """Adopt a speculative survivor buffer whose count is still device-
        resident.  The buffer is pre-sliced to ``_slot_rows`` — smaller
        than the chunk cap — so ``_adopt``'s refuse-to-drop guard cannot
        run here; reconciliation performs the equivalent check against the
        true count (``k > spec.slot``) once the packed buffer lands."""
        self._frontier = frontier_dev
        self._gens = gens_dev
        self._n = None
        self._n_dev = k_dev

    def _spec_caps(self, bound: int) -> tuple[int, int]:
        """Speculative chunk coverage: min(expansion bound, round_budget),
        bucket-padded.  Returns ``(cap, blk)`` like :meth:`_chunk_caps`.

        The structural bound (slot rows × n_attrs) wildly over-states the
        post-dedupe seed count, and a speculative round pays compute for
        its whole padded cap — while an under-covered round only re-runs
        the *uncovered tail* through the sync chunk runner (the covered
        part's closures are kept).  Over-sizing is therefore the
        expensive miss, so when a reconciled round has told us the true
        count the chunk is sized at 2× that hint (growth allowance); a
        growth spurt past it under-covers and falls back.  Sizing is a
        pure latency heuristic, never a correctness input."""
        if self._seed_hint is not None:
            bound = min(
                bound, max(self.engine.min_bucket, 2 * self._seed_hint)
            )
        return self._chunk_caps(max(1, min(bound, self.round_budget)))

    def _spec_bound(self) -> int:
        """Structural expansion bound for the next speculative chunk: the
        reconciled row count when the host knows it (first spec of a run,
        or right after an under-coverage re-adoption), the padded slot
        capacity when the count is still in flight."""
        rows = self._n if self._n is not None else self._frontier.shape[0]
        return max(1, rows) * self.n_attrs

    def _slot_rows(self, cap: int) -> int:
        """Rows the adopted speculative slot keeps.  The slot is the NEXT
        round's expansion input, and expansion cost (the dedupe sort in
        particular) scales with slot rows × n_attrs — keeping the whole
        cap-row chunk buffer makes every speculative expansion pay for the
        chunk's padding.  The in-flight survivor count is unknown at
        dispatch, so the slot is sized from the last reconciled survivor
        count with a 2× growth allowance.  A growth spurt past the slot
        truncates live in-flight rows — reconciliation detects that
        (``k > spec.slot``) from the *full* packed buffer and recovers
        through the driver's ordinary under-coverage reset, so sizing
        stays a latency heuristic, never a correctness input."""
        if self._k_hint is None:
            return cap
        rows = bucket_size(
            max(self.engine.min_bucket, 2 * self._k_hint),
            minimum=self.engine.min_bucket,
        )
        return min(cap, rows)

    def discard_spec(self, spec: SpecRound | None) -> None:
        """Drop a speculative round whose premise turned out wrong (the
        true frontier emptied, or under-coverage invalidated its input).
        Nothing to undo, and the round's *modeled* cost is never ledgered
        (spec rounds charge collectives at reconciliation only) — but the
        packed readback's copy has been in flight since dispatch, so those
        bytes crossed the boundary whether or not anyone reads them and
        the transfer census charges them here (sync-vs-async census parity
        is asserted in tests/test_obs.py)."""
        if spec is not None:
            st = self.engine.stats
            st.spec_discarded += 1
            st.d2h_transfers += 1
            st.d2h_bytes += int(spec.packed.size) * 4
            tr = obs.current()
            tr.instant(f"spec/discard[{spec.seq}]")
            tr.end_async(f"mine/round[{spec.seq}]", spec.seq, outcome="discard")

    def _download_packed(self, packed) -> np.ndarray:
        """The reconcile's ONE host-blocking wait: the packed round buffer
        (copy already in flight since dispatch)."""
        st = self.engine.stats
        t0 = time.perf_counter()
        out = np.asarray(packed)
        st.host_blocked_s += time.perf_counter() - t0
        st.d2h_transfers += 1
        st.d2h_bytes += out.nbytes
        return out

    def spec_oplus(
        self, *, dedupe: bool, min_support: int | None = None
    ) -> SpecRound:
        """Dispatch one speculative MRGanter+ round (no host sync).

        Always routes through the *unique* step variants regardless of
        ``dedupe_closures``: the adopted spec slot doubles as the next
        round's expansion input, and deduping it on device bounds the
        stale-row re-expansion (the host registry still owns novelty).
        """
        eng = self.engine
        tr = obs.current()
        seq = self._next_seq()
        t0 = time.perf_counter()
        tr.begin_async(
            f"mine/round[{seq}]", seq, algo="oplus", mode="async", **self._tags
        )
        with tr.span(f"spec/dispatch[{seq}]"):
            seeds, n_dev = expand_oplus(
                self._frontier, self._n_arg(), self.LOW, self.BIT,
                n_attrs=self.n_attrs, dedupe=dedupe,
            )
            cap, blk = self._spec_caps(self._spec_bound())
            chunk = slice_pad(seeds, 0, cap)
            nv = jnp.minimum(n_dev, jnp.int32(cap))
            two_d = self.cand_parts > 1
            if min_support is not None:
                name = "iceberg_unique2d" if two_d else "iceberg_unique"
                cl, k_dev = self._step_fn(name)(
                    eng.rows, chunk, nv, jnp.int32(min_support)
                )
            else:
                cl, k_dev = self._step_fn("unique2d" if two_d else "unique")(
                    eng.rows, chunk, nv
                )
            slot = self._slot_rows(cap)
            self._adopt_spec(
                cl if slot == cap else slice_pad(cl, 0, slot), None, k_dev
            )
            packed = _pack_round(n_dev, k_dev, cl)  # full buffer: recovery
            _start_d2h(packed)
            eng.stats.dispatch_s += time.perf_counter() - t0
            eng.stats.spec_rounds += 1
        return SpecRound(
            "oplus", packed, cap, blk, two_d, seeds=seeds, slot=slot,
            seq=seq, t_dispatch=t0,
        )

    def reconcile_oplus(
        self, spec: SpecRound, *, min_support: int | None = None
    ) -> OplusRound:
        """Adopt round r's true counts: read the packed buffer, charge the
        round at its real size, and — only if the speculative chunk under-
        covered the true seed count — close the uncovered tail through the
        sync chunk runner."""
        tr = obs.current()
        with tr.span(f"spec/reconcile[{spec.seq}]") as sp:
            rec = self._reconcile_oplus(spec, min_support=min_support)
            outcome = "fallback" if rec.under_covered else "adopt"
            sp.set(outcome=outcome, n_seeds=rec.n_seeds)
        tr.end_async(f"mine/round[{spec.seq}]", spec.seq, outcome=outcome)
        self.engine.stats.observe_latency(
            "round", time.perf_counter() - spec.t_dispatch
        )
        return rec

    def _reconcile_oplus(
        self, spec: SpecRound, *, min_support: int | None = None
    ) -> OplusRound:
        eng = self.engine
        host = self._download_packed(spec.packed)
        n_seeds = int(host[0])
        k = int(host[1])
        if n_seeds == 0:
            # parity with sync: no closure round ran, nothing is charged
            return OplusRound(0, np.zeros((0, self.W), np.uint32), False)
        self._seed_hint = n_seeds
        self._charge(spec.two_d, spec.blk, spec.cap, min(n_seeds, spec.cap), True)
        closures = host[2:].reshape(spec.cap, self.W)
        if n_seeds <= spec.cap:
            self._k_hint = max(1, k)
            new = np.ascontiguousarray(closures[:k])
            if k > spec.slot:
                # the adopted slot truncated the in-flight survivors, so
                # the round already speculating on it chained on a partial
                # frontier.  The packed buffer holds the full survivor set
                # — recovery is the driver's ordinary under-coverage reset
                # (discard + set_frontier + re-spec), no recompute here.
                eng.stats.spec_fallbacks += 1
                return OplusRound(n_seeds, new, True)
            return OplusRound(n_seeds, new, False)
        eng.stats.spec_fallbacks += 1
        parts = [np.ascontiguousarray(closures[:k])]
        parts += self._oplus_chunks(
            spec.seeds, n_seeds, spec.cap,
            min_support=min_support, first=False, force_unique=True,
            seq=spec.seq,
        )
        out = np.concatenate(parts, axis=0)
        self._k_hint = max(1, out.shape[0])
        return OplusRound(n_seeds, out, True)

    def spec_cbo(self, *, min_support: int | None = None) -> SpecRound:
        """Dispatch one speculative MRCbo round (no host sync).  Canonical
        survivors are adopted as the next frontier with their count still
        on device — exactly the sync contract, minus the readbacks."""
        eng = self.engine
        tr = obs.current()
        seq = self._next_seq()
        t0 = time.perf_counter()
        tr.begin_async(
            f"mine/round[{seq}]", seq, algo="cbo", mode="async", **self._tags
        )
        with tr.span(f"spec/dispatch[{seq}]"):
            seeds, parents, gen, n_dev = expand_cbo(
                self._frontier, self._gens, self._n_arg(), self.BIT,
                n_attrs=self.n_attrs,
            )
            cap, blk = self._spec_caps(self._spec_bound())
            nv = jnp.minimum(n_dev, jnp.int32(cap))
            two_d = self.cand_parts > 1
            args = (
                eng.rows,
                slice_pad(seeds, 0, cap),
                slice_pad(parents, 0, cap),
                slice_pad(gen, 0, cap),
                nv,
            )
            if min_support is not None:
                z, g, k_dev = self._step_fn(
                    "cbo_iceberg2d" if two_d else "cbo_iceberg"
                )(*args, jnp.int32(min_support))
            else:
                z, g, k_dev = self._step_fn("cbo2d" if two_d else "cbo")(*args)
            slot = self._slot_rows(cap)
            if slot == cap:
                self._adopt_spec(z, g, k_dev)
            else:
                self._adopt_spec(
                    slice_pad(z, 0, slot), slice_pad(g, 0, slot), k_dev
                )
            packed = _pack_round(n_dev, k_dev, z)  # full buffer: recovery
            _start_d2h(packed)
            eng.stats.dispatch_s += time.perf_counter() - t0
            eng.stats.spec_rounds += 1
        return SpecRound(
            "cbo", packed, cap, blk, two_d, seeds=seeds, parents=parents,
            gen=gen, surv_z=z, surv_g=g, slot=slot, seq=seq, t_dispatch=t0,
        )

    def reconcile_cbo(
        self, spec: SpecRound, *, min_support: int | None = None
    ) -> CboRound:
        """Adopt round r's true counts.  When covered, the speculatively
        adopted slot already IS the true frontier (over-expanded rows were
        masked by the traced valid count) and the survivors come straight
        from the packed buffer.  Under-coverage closes the uncovered tail
        synchronously and re-adopts the full survivor set — restoring
        exactness before the driver re-speculates."""
        tr = obs.current()
        with tr.span(f"spec/reconcile[{spec.seq}]") as sp:
            rec = self._reconcile_cbo(spec, min_support=min_support)
            outcome = "fallback" if rec.under_covered else "adopt"
            sp.set(outcome=outcome, n_seeds=rec.n_seeds)
        tr.end_async(f"mine/round[{spec.seq}]", spec.seq, outcome=outcome)
        self.engine.stats.observe_latency(
            "round", time.perf_counter() - spec.t_dispatch
        )
        return rec

    def _reconcile_cbo(
        self, spec: SpecRound, *, min_support: int | None = None
    ) -> CboRound:
        eng = self.engine
        host = self._download_packed(spec.packed)
        n_seeds = int(host[0])
        k = int(host[1])
        if n_seeds == 0:
            # parity with sync: exhausted frontier, no round ran/charged
            self._n, self._n_dev = 0, None
            return CboRound(0, np.zeros((0, self.W), np.uint32), 0, False)
        self._seed_hint = n_seeds
        self._charge(spec.two_d, spec.blk, spec.cap, min(n_seeds, spec.cap), True)
        if n_seeds <= spec.cap:
            new = np.ascontiguousarray(host[2:].reshape(spec.cap, self.W)[:k])
            if k == 0:
                self._n, self._n_dev = 0, None
            elif k > spec.slot:
                # slot truncated the in-flight survivors — re-adopt the
                # full survivor buffer (kept in the SpecRound exactly for
                # this) so the frontier is exact before the driver
                # discards the mispremised speculation and re-dispatches.
                eng.stats.spec_fallbacks += 1
                self._adopt(spec.surv_z, spec.surv_g, k)
                return CboRound(n_seeds, new, k, True)
            else:
                self._k_hint = k
            return CboRound(n_seeds, new, k, False)
        eng.stats.spec_fallbacks += 1
        z_list, g_list, counts = self._cbo_chunks(
            spec.seeds, spec.parents, spec.gen, n_seeds, spec.cap,
            min_support=min_support, first=False, seq=spec.seq,
        )
        n_new = k + sum(counts)
        if n_new == 0:
            self._n, self._n_dev = 0, None
            return CboRound(n_seeds, np.zeros((0, self.W), np.uint32), 0, True)
        z_all = jnp.concatenate([spec.surv_z[:k], *z_list])
        g_all = jnp.concatenate([spec.surv_g[:k], *g_list])
        self._adopt(z_all, g_all, n_new)
        return CboRound(
            n_seeds, self._download(self._frontier, n_new), n_new, True
        )

    def spec_ganter(self, *, min_support: int | None = None) -> SpecRound:
        """Dispatch one speculative Alg.-5 step: the fused select's result
        is broadcast into the frontier slot on device, so the next step
        chains on it without the intent ever visiting the host."""
        eng = self.engine
        tr = obs.current()
        seq = self._next_seq()
        t_dispatch = time.perf_counter()
        tr.begin_async(
            f"mine/round[{seq}]", seq, algo="ganter", mode="async",
            **self._tags,
        )
        with tr.span(f"spec/dispatch[{seq}]"):
            Y_next, done, nv_dev, cap = self._dispatch_ganter(min_support)
            t0 = time.perf_counter()
            packed = _pack_round(done, nv_dev, Y_next[None, :])
            _start_d2h(packed)
            eng.stats.dispatch_s += time.perf_counter() - t0
            eng.stats.spec_rounds += 1
        return SpecRound(
            "ganter", packed, cap, cap, False, seq=seq, t_dispatch=t_dispatch
        )

    def reconcile_ganter(self, spec: SpecRound) -> tuple[np.ndarray, bool]:
        """Wait on the packed ``[done/exhausted, n_valid, Y_next]`` buffer
        and charge the round at its true seed count.  Returns
        ``(Y_next, flag)`` with the same contract as :meth:`step_ganter`."""
        tr = obs.current()
        with tr.span(f"spec/reconcile[{spec.seq}]") as sp:
            host = self._download_packed(spec.packed)
            self.engine.charge_round(spec.cap, int(host[1]))
            sp.set(outcome="adopt")
        tr.end_async(f"mine/round[{spec.seq}]", spec.seq, outcome="adopt")
        self.engine.stats.observe_latency(
            "round", time.perf_counter() - spec.t_dispatch
        )
        return host[2:].astype(np.uint32, copy=False), bool(host[0])
