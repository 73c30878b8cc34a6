"""QueryEngine — fixed-slot micro-batched SPMD serving over a ConceptStore.

The serving twin of :class:`repro.serve.engine.ServeEngine`'s
continuous-batching core, for lattice queries instead of tokens: requests
pad into fixed ``slots``-wide micro-batches (SPMD-friendly static shapes)
and each micro-batch executes as ONE plan round —

  * ``closure``  — closure-of-attrset: per-shard local closure over the
    object-sharded context → AND-allreduce (+ psum of supports) → fused
    two-level-hash concept lookup, all inside one ``ShardPlan.spmd``
    region.  B queries cost one collective round, not B.
  * ``top_k``    — the same closure round with a fused
    contains-mask × supports ``lax.top_k`` stage instead of the lookup.
  * ``extents``  — per-shard extent-table column gather + one all-gather.
  * ``lookup`` / ``supers`` / ``subs`` / ``children`` / ``parents`` —
    pure replicated-table reads: zero collective rounds.

The jitted steps close over the *plan*, never over a snapshot: snapshot
tables arrive as arguments, so streaming commits (new lattice versions)
reuse the compiled steps as long as the padded shapes match — the same
discipline as the mining engine's ``_frontier_cache``.

Schedule autotuning rides along: with ``plan.reduce_impl == "auto"`` each
micro-batch resolves allgather-vs-rsag from its padded slot count
(``plan.resolve_impl``) and the choice is recorded in ``stats``.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import bitset
from repro.dist import collectives
from repro.kernels import ops
from repro.kernels import serve as skern
from repro.obs import StatsBase
from repro.obs import trace as obs
from repro.query.store import (
    ConceptStore,
    lookup_ids_jnp,
    pack_bool_jnp,
)

BACKENDS = ("kernel", "jnp", "matmul")


@dataclasses.dataclass
class QueryStats(StatsBase):
    """Serving-side stats: the schedule census (``reduce_rounds`` /
    ``auto_hop_bytes`` / ``hop_calibrated``) and ``latency_percentiles``
    are inherited from :class:`repro.obs.StatsBase` — one definition
    shared with the mining engine's ``EngineStats``."""

    queries: int = 0
    micro_batches: int = 0
    collective_rounds: int = 0
    modeled_comm_bytes: int = 0
    by_type: dict = dataclasses.field(default_factory=dict)
    # micro-batches per "kind/path": which step served each top-k and
    # rules batch — the fused Pallas kernel or the jnp step
    serve_paths: dict = dataclasses.field(default_factory=dict)

    def charge(self, kind: str, n: int, batches: int):
        self.queries += n
        self.micro_batches += batches
        self.by_type[kind] = self.by_type.get(kind, 0) + n

    def count_path(self, kind: str, kernel: bool, batches: int):
        key = f"{kind}/{'kernel' if kernel else 'jnp'}"
        self.serve_paths[key] = self.serve_paths.get(key, 0) + batches


@dataclasses.dataclass
class QueryConfig:
    slots: int = 64  # fixed micro-batch width; every dispatch pads to this
    backend: str = "jnp"  # closure map backend, as in ClosureEngine
    block_n: int = 256


class QueryEngine:
    def __init__(
        self,
        store: ConceptStore,
        cfg: QueryConfig | None = None,
        *,
        clock=time.perf_counter,
    ):
        self.store = store
        self.cfg = cfg or QueryConfig()
        # Injectable clock for the per-micro-batch service timings: the
        # admission queue and load generator run under virtual clocks in
        # tests, and the engine's latency histograms must tick on the
        # same timebase (repro.analysis lints wall-clock reads here).
        self.clock = clock
        if self.cfg.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.cfg.backend!r}; choose {BACKENDS}"
            )
        self.plan = store.plan
        self.n_attrs = store.ctx.n_attrs
        self.W = store.ctx.W
        self.stats = QueryStats(
            auto_hop_bytes=self.plan.auto_hop_bytes,
            hop_calibrated=self.plan.hop_calibrated,
        )
        self._mask = bitset.attr_mask(self.n_attrs, self.W)
        # jit caches — keyed by everything static to the compiled step.
        # Guarded by ``_steps_lock``: the admission dispatcher thread and
        # the main thread can both miss a cold key, and an unguarded
        # check-then-set would trace and compile the same step twice.
        self._steps_lock = threading.Lock()
        self._closure_steps: dict = {}  # (impl, probe) -> step
        self._topk_steps: dict = {}  # (impl, k) -> step
        self._rules_steps: dict = {}  # k -> step (metric is an operand)
        self._extent_step = None

    # -- step builders (close over plan/config only) ------------------------

    def _local_closure(self):
        cfg, n_attrs = self.cfg, self.n_attrs
        if cfg.backend == "matmul":
            return lambda rows_local, cands: ops.closure_matmul(
                rows_local, cands, n_attrs, n_valid_rows=rows_local.shape[0]
            )
        return lambda rows_local, cands: ops.batched_closure(
            rows_local,
            cands,
            n_attrs,
            n_valid_rows=rows_local.shape[0],
            block_n=cfg.block_n,
            use_kernel=cfg.backend == "kernel",
        )

    def _closure_body(self, impl: str):
        plan, n_attrs = self.plan, self.n_attrs
        local_closure = self._local_closure()
        mask = self._mask
        axes = plan.reduce_axes

        def body(rows_local, cands, n_pad):
            lc, ls = local_closure(rows_local, cands)
            gc = collectives.and_allreduce(lc, axes, impl=impl, n_attrs=n_attrs)
            return gc & jnp.asarray(mask), lax.psum(ls, axes) - n_pad

        return body

    def _closure_step(self, impl: str, probe: int):
        step = self._closure_steps.get((impl, probe))  # lock: ok — racy fast path, re-checked under lock
        if step is not None:
            return step
        with self._steps_lock:
            step = self._closure_steps.get((impl, probe))
            if step is not None:
                return step
            n_attrs = self.n_attrs

            def post(gc, gs, intents, skeys, n_concepts):
                ids = lookup_ids_jnp(
                    gc, intents, skeys, n_concepts,
                    n_attrs=n_attrs, probe=probe,
                )
                return gc, gs, ids

            step = jax.jit(
                self.plan.spmd(
                    self._closure_body(impl), n_rep=2, post=post, n_post_rep=3
                )
            )
            self._closure_steps[(impl, probe)] = step
        return step

    def _topk_step(self, impl: str, k: int):
        step = self._topk_steps.get((impl, k))  # lock: ok — racy fast path, re-checked under lock
        if step is not None:
            return step
        with self._steps_lock:
            step = self._topk_steps.get((impl, k))
            if step is not None:
                return step

            def post(gc, gs, intents, supports, n_concepts):
                # backend="kernel": the whole post — subset test, validity
                # mask, k selection passes — runs as ONE fused Pallas pass
                # with the query block and intent table VMEM-resident
                # (repro.kernels.serve).  Bit-identical to the jnp stage
                # below, which remains its tested oracle; tables past the
                # VMEM bound fall back (the shapes are static at trace time).
                if self._serve_kernel("topk", intents.shape[0]):
                    idx, vals = skern.contains_topk_call(
                        gc, intents, supports, n_concepts, k=k
                    )
                    return gc, gs, idx, vals
                # concepts whose intent ⊇ the query attrset == subconcepts
                # of closure(attrset); masked top-k by support.  Extracted
                # with k unrolled argmax passes — same order as lax.top_k
                # (desc value, asc index on ties) but ~100× faster than
                # XLA CPU's top_k on a [slots, cap] score matrix.
                contains = jnp.all(
                    (gc[:, None, :] & ~intents[None, :, :]) == 0, axis=-1
                )
                valid = jnp.arange(intents.shape[0]) < n_concepts
                scores = jnp.where(
                    contains & valid[None, :], supports[None, :], -1
                ).astype(jnp.int32)
                rows_arange = jnp.arange(scores.shape[0])
                ids, vals = [], []
                for _ in range(k):
                    idx = jnp.argmax(scores, axis=1)
                    val = jnp.take_along_axis(
                        scores, idx[:, None], axis=1
                    )[:, 0]
                    ids.append(idx.astype(jnp.int32))
                    vals.append(val)
                    scores = scores.at[rows_arange, idx].set(-2)
                vals = jnp.stack(vals, axis=1)
                idx = jnp.stack(ids, axis=1)
                idx = jnp.where(vals >= 0, idx, -1)
                vals = jnp.maximum(vals, -1)  # exhausted slots read as -1
                return gc, gs, idx, vals

            step = jax.jit(
                self.plan.spmd(
                    self._closure_body(impl), n_rep=2, post=post, n_post_rep=3
                )
            )
            self._topk_steps[(impl, k)] = step
        return step

    def _extents_step(self):
        step = self._extent_step  # lock: ok — racy fast path, re-checked under lock
        if step is not None:
            return step
        with self._steps_lock:
            if self._extent_step is not None:
                return self._extent_step
            axes = self.plan.reduce_axes

            def body(ext_local, ids):
                # [Nl, B] membership bits of each queried concept's column
                w = jnp.take(ext_local, ids // 32, axis=1)
                b = (w >> (ids % 32).astype(jnp.uint32)) & jnp.uint32(1)
                return lax.all_gather(b, axes, axis=0, tiled=True)  # [Np, B]

            def post(bits):
                pad = (-bits.shape[0]) % 32
                if pad:
                    bits = jnp.concatenate(
                        [bits, jnp.zeros((pad, bits.shape[1]), bits.dtype)]
                    )
                return pack_bool_jnp(bits.T.astype(bool))  # [B, Wo]

            step = self._extent_step = jax.jit(
                self.plan.spmd(body, n_rep=1, post=post)
            )
        return step

    def _serve_kernel(self, kind: str, n_rows: int) -> bool:
        """Whether the fused ``kind`` serving kernel ("topk"/"rules")
        serves a table of ``n_rows``; the jnp step serves otherwise."""
        return skern.supports_serve(
            self.cfg.backend, kind, n_rows, self.W, self.cfg.slots
        )

    # -- micro-batch plumbing ----------------------------------------------

    def _chunks(self, arr: np.ndarray):
        """Yield ``(lo, n_valid, chunk)`` with every chunk padded to the
        fixed slot width — one compiled shape per step, ServeEngine-style.
        Callers early-return on empty batches before reaching here."""
        S = self.cfg.slots
        for lo in range(0, arr.shape[0], S):
            chunk = arr[lo : lo + S]
            b = chunk.shape[0]
            if b < S:
                pad = np.zeros((S - b, *arr.shape[1:]), arr.dtype)
                chunk = np.concatenate([chunk, pad], axis=0)
            yield lo, b, chunk

    def _obs_batch(self, kind: str, dt: float, version: int | None = None):
        """One micro-batch's telemetry: the ``micro_batch`` percentile
        key (the bench/CI contract) plus per-kind ``service_s`` service
        histograms, a dispatch counter, and the snapshot-version gauge —
        all in the stats registry the admission queue and the OpenMetrics
        exporter share."""
        st = self.stats
        st.observe_latency("micro_batch", dt)
        reg = st.registry
        reg.observe("service_s", dt, kind=kind)
        reg.counter("micro_batches_total", kind=kind)
        if version is not None:
            reg.gauge("snapshot_version", version)

    def _charge_round(self, cap: int) -> str:
        impl = self.plan.resolve_impl(cap, self.W, self.n_attrs)
        st = self.stats
        st.collective_rounds += 1
        st.record_reduce(impl)
        st.modeled_comm_bytes += collectives.modeled_comm_bytes(
            impl, self.plan.n_parts, cap, self.W, self.n_attrs
        )
        return impl

    # -- queries ------------------------------------------------------------

    def closure_batch(
        self, attrsets: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Closure-of-attrset for [B, W] packed queries → (closed intents
        [B, W], supports [B], concept ids [B]).  One SPMD round per
        micro-batch; ids resolve against the snapshot read at entry."""
        st = self.store.state  # one consistent (rows, snapshot) view
        snap, rows, n_pad = st.snapshot, st.rows, st.n_pad
        attrsets = np.ascontiguousarray(attrsets, np.uint32) & self._mask
        B = attrsets.shape[0]
        out_c = np.empty((B, self.W), np.uint32)
        out_s = np.empty((B,), np.int32)
        out_i = np.empty((B,), np.int32)
        if B == 0:
            self.stats.charge("closure", 0, 0)
            return out_c, out_s, out_i
        batches = 0
        for lo, b, chunk in self._chunks(attrsets):
            t0 = self.clock()
            with obs.current().span(
                "query/micro_batch", kind="closure", slots=chunk.shape[0]
            ):
                impl = self._charge_round(chunk.shape[0])
                gc, gs, ids = self._closure_step(impl, snap.probe)(
                    rows, jnp.asarray(chunk), jnp.int32(n_pad),
                    snap.intents, snap.skeys, jnp.int32(snap.n_concepts),
                )
                out_c[lo : lo + b] = np.asarray(gc)[:b]
                out_s[lo : lo + b] = np.asarray(gs)[:b]
                out_i[lo : lo + b] = np.asarray(ids)[:b]
            self._obs_batch("closure", self.clock() - t0, snap.version)
            batches += 1
        self.stats.charge("closure", B, batches)
        return out_c, out_s, out_i

    def topk_batch(
        self, attrsets: np.ndarray, k: int = 5
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-k concepts by support containing each query attrset →
        (ids [B, k], supports [B, k]); -1 id pads when fewer match."""
        st = self.store.state
        snap, rows, n_pad = st.snapshot, st.rows, st.n_pad
        attrsets = np.ascontiguousarray(attrsets, np.uint32) & self._mask
        B = attrsets.shape[0]
        out_i = np.empty((B, k), np.int32)
        out_v = np.empty((B, k), np.int32)
        if B == 0:
            self.stats.charge("topk", 0, 0)
            return out_i, out_v
        batches = 0
        for lo, b, chunk in self._chunks(attrsets):
            t0 = self.clock()
            with obs.current().span(
                "query/micro_batch", kind="topk", slots=chunk.shape[0]
            ):
                impl = self._charge_round(chunk.shape[0])
                _, _, idx, vals = self._topk_step(impl, k)(
                    rows, jnp.asarray(chunk), jnp.int32(n_pad),
                    snap.intents, snap.supports, jnp.int32(snap.n_concepts),
                )
                out_i[lo : lo + b] = np.asarray(idx)[:b]
                out_v[lo : lo + b] = np.asarray(vals)[:b]
            self._obs_batch("topk", self.clock() - t0, snap.version)
            batches += 1
        self.stats.charge("topk", B, batches)
        self.stats.count_path(
            "topk", self._serve_kernel("topk", snap.intents.shape[0]), batches
        )
        return out_i, out_v

    def lookup_batch(self, intents: np.ndarray) -> np.ndarray:
        """Concept ids for already-closed intents [B, W]; -1 for misses.
        Replicated-table read — no collective round."""
        snap = self.store.snapshot
        intents = np.ascontiguousarray(intents, np.uint32)
        B = intents.shape[0]
        out = np.empty((B,), np.int32)
        if B == 0:
            self.stats.charge("lookup", 0, 0)
            return out
        batches = 0
        for lo, b, chunk in self._chunks(intents):
            t0 = self.clock()
            with obs.current().span(
                "query/micro_batch", kind="lookup", slots=chunk.shape[0]
            ):
                ids = lookup_ids_jnp(
                    jnp.asarray(chunk), snap.intents, snap.skeys,
                    jnp.int32(snap.n_concepts),
                    n_attrs=self.n_attrs, probe=snap.probe,
                )
                out[lo : lo + b] = np.asarray(ids)[:b]
            self._obs_batch("lookup", self.clock() - t0, snap.version)
            batches += 1
        self.stats.charge("lookup", B, batches)
        return out

    def _order_query(self, ids, table: jax.Array, kind: str):
        snap = self.store.snapshot
        ids = np.asarray(ids, np.int32)
        safe = np.clip(ids, 0, snap.cap - 1)
        rows = np.asarray(jnp.take(table, jnp.asarray(safe), axis=0))
        self.stats.charge(kind, ids.shape[0], 1)
        out = []
        for r, i in zip(rows, ids):
            if i < 0 or i >= snap.n_concepts:
                out.append(np.zeros((0,), np.int32))
            else:
                out.append(
                    np.nonzero(bitset.unpack_bits(r, snap.cap))[0].astype(
                        np.int32
                    )
                )
        return out

    def supers(self, ids) -> list[np.ndarray]:
        """All strict superconcepts (smaller intents) per queried id."""
        return self._order_query(ids, self.store.snapshot.sup_rows, "supers")

    def subs(self, ids) -> list[np.ndarray]:
        """All strict subconcepts (larger intents) per queried id."""
        return self._order_query(ids, self.store.snapshot.sub_rows, "subs")

    def children(self, ids) -> list[np.ndarray]:
        """Covering-relation reads: the ids each concept covers
        (``ConceptLattice.children`` convention)."""
        return self._order_query(
            ids, self.store.snapshot.children_rows, "children"
        )

    def parents(self, ids) -> list[np.ndarray]:
        return self._order_query(
            ids, self.store.snapshot.parents_rows, "parents"
        )

    def extents_batch(self, ids) -> np.ndarray:
        """Packed object extents [B, Wo] for concept ids (one all-gather
        round over the object-sharded extent table per micro-batch)."""
        st = self.store.state
        snap = st.snapshot
        ids = np.asarray(ids, np.int32)
        B = ids.shape[0]
        Wo = -(-st.N_padded // 32)
        out = np.empty((B, Wo), np.uint32)
        if B == 0:
            self.stats.charge("extents", 0, 0)
            return out
        step = self._extents_step()
        batches = 0
        for lo, b, chunk in self._chunks(np.clip(ids, 0, snap.cap - 1)):
            t0 = self.clock()
            with obs.current().span(
                "query/micro_batch", kind="extents", slots=chunk.shape[0]
            ):
                packed = step(snap.ext_cols, jnp.asarray(chunk))
                out[lo : lo + b] = np.asarray(packed)[:b]
            self._obs_batch("extents", self.clock() - t0, snap.version)
            batches += 1
            self.stats.collective_rounds += 1
            # the round's all-gather moves each shard's [Nl, B] membership
            # words to every peer — charge it like the closure rounds do
            # (transfer-census parity; tested in tests/test_obs.py)
            if self.plan.n_parts > 1:
                self.stats.record_reduce("allgather")
                n_local = st.N_padded // self.plan.n_parts
                # k·(k-1) rings × each shard's [Nl, B] words — the same
                # whole-collective convention modeled_comm_bytes uses for
                # the closure rounds (and the one repro.analysis audits);
                # the old (k-1)·Nl·B charge under-counted by ×k
                self.stats.modeled_comm_bytes += (
                    self.plan.n_parts
                    * (self.plan.n_parts - 1)
                    * n_local
                    * chunk.shape[0]
                    * 4
                )
        # misses / out-of-snapshot ids get the empty extent, mirroring
        # _order_query's empty result (never another concept's objects)
        out[(ids < 0) | (ids >= snap.n_concepts)] = 0
        self.stats.charge("extents", B, batches)
        return out

    # -- rule queries (repro.rules.RuleIndex) --------------------------------

    RANK_BY = ("confidence", "lift")

    def _rules_step(self, k: int):
        # keyed by k alone: the rank metric arrives as a runtime operand,
        # so confidence- and lift-ranked queries share one compiled step
        step = self._rules_steps.get(k)  # lock: ok — racy fast path, re-checked under lock
        if step is not None:
            return step
        with self._steps_lock:
            step = self._rules_steps.get(k)
            if step is not None:
                return step

            def run(prem, added, conf, metric, rid, n_rules, queries, min_conf):
                # backend="kernel": premise-subset test → conf mask →
                # consequent union → metric top-k as one fused VMEM pass
                # (repro.kernels.serve.rules_topk_call), bit-identical to
                # the jnp stage below (its property-tested oracle).
                if self._serve_kernel("rules", prem.shape[0]):
                    return skern.rules_topk_call(
                        prem, added, conf, metric, rid, n_rules,
                        queries, min_conf, k=k,
                    )
                R = prem.shape[0]
                # applicable[b, r]: premise_r ⊆ query attrset b
                app = jnp.all(
                    (prem[None, :, :] & ~queries[:, None, :]) == 0, axis=-1
                )
                ok = (
                    app
                    & (conf >= min_conf)[None, :]
                    & (jnp.arange(R) < n_rules)[None, :]
                )
                # premise→consequent lookup: union of all firing conclusions
                union = lax.reduce(
                    jnp.where(ok[:, :, None], added[None], jnp.uint32(0)),
                    jnp.uint32(0),
                    lambda a, b: a | b,
                    (1,),
                )
                # top-k by the rank metric — k unrolled max passes (same
                # order as lax.top_k, ~100× faster on XLA CPU).  Ties on
                # the metric break by *rule id* (lowest wins), never by
                # table-slot position: the returned ranking is then
                # invariant to query-batch padding, index cap, and any
                # future rule-table layout (shard/permutation), and two
                # runs of the same query always agree.
                score = jnp.where(ok, metric[None, :], jnp.float32(-1.0))
                rows_arange = jnp.arange(score.shape[0])
                ids, vals = [], []
                for _ in range(k):
                    best = jnp.max(score, axis=1)
                    is_best = score == best[:, None]
                    sel = jnp.min(
                        jnp.where(is_best, rid[None, :], jnp.int32(0x7FFFFFFF)),
                        axis=1,
                    )
                    pos = jnp.argmax(
                        is_best & (rid[None, :] == sel[:, None]), axis=1
                    )
                    ids.append(sel)
                    vals.append(best)
                    score = score.at[rows_arange, pos].set(-2.0)
                vals = jnp.stack(vals, axis=1)
                idx = jnp.stack(ids, axis=1)
                idx = jnp.where(vals >= 0, idx, -1)
                vals = jnp.maximum(vals, -1.0)
                return idx, vals, union

            step = jax.jit(run)
            self._rules_steps[k] = step
        return step

    def rules_batch(
        self,
        index,
        attrsets: np.ndarray,
        *,
        k: int = 5,
        min_conf: float = 0.0,
        rank_by: str = "confidence",
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Batched rule lookup against a :class:`repro.rules.RuleIndex`.

        For each query attrset: the top-``k`` applicable rules (premise ⊆
        attrset, confidence ≥ ``min_conf``) ranked by ``rank_by`` ∈
        {confidence, lift}, and the premise→consequent closure — the union
        of every firing rule's added attributes.  Returns ``(rule ids
        [B, k] (-1 pads), scores [B, k], consequents [B, W])``.
        Replicated-table read, fixed-slot micro-batches, zero collective
        rounds — the rule twin of :meth:`lookup_batch`.
        """
        if rank_by not in self.RANK_BY:
            raise ValueError(
                f"unknown rank_by {rank_by!r}; choose {self.RANK_BY}"
            )
        attrsets = np.ascontiguousarray(attrsets, np.uint32) & self._mask
        B = attrsets.shape[0]
        out_i = np.empty((B, k), np.int32)
        out_s = np.empty((B, k), np.float32)
        out_c = np.empty((B, self.W), np.uint32)
        if B == 0:
            self.stats.charge("rules", 0, 0)
            return out_i, out_s, out_c
        metric = index.confidence if rank_by == "confidence" else index.lift
        step = self._rules_step(k)
        batches = 0
        for lo, b, chunk in self._chunks(attrsets):
            t0 = self.clock()
            with obs.current().span(
                "query/micro_batch", kind="rules", slots=chunk.shape[0]
            ):
                idx, vals, union = step(
                    index.premise, index.added, index.confidence, metric,
                    index.rule_id, jnp.int32(index.n_rules),
                    jnp.asarray(chunk), jnp.float32(min_conf),
                )
                out_i[lo : lo + b] = np.asarray(idx)[:b]
                out_s[lo : lo + b] = np.asarray(vals)[:b]
                out_c[lo : lo + b] = np.asarray(union)[:b]
            self._obs_batch("rules", self.clock() - t0)
            batches += 1
        self.stats.charge("rules", B, batches)
        self.stats.count_path(
            "rules", self._serve_kernel("rules", index.cap), batches
        )
        return out_i, out_s, out_c

    def describe(self) -> dict:
        return {
            "slots": self.cfg.slots,
            "backend": self.cfg.backend,
            "plan": self.plan.describe(),
            "stats": dataclasses.asdict(self.stats),
        }
