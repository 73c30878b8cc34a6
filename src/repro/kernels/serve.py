"""Fused serving kernels — the QueryEngine's subset test → mask → top-k
as one VMEM-resident Pallas pass (ISSUE 6 tentpole, serving side).

``QueryEngine.topk_batch`` and ``rules_batch`` both run the same shape of
computation over a replicated table: a bitwise subset test per (query,
table-row) pair, a validity/threshold mask, then k unrolled selection
passes.  As jnp ops the ``[slots, rows]`` score matrix and the
``[slots, rows, W]`` subset intermediate round-trip through HBM between
stages; these kernels keep the query block and the whole table VMEM-
resident from the subset test to the packed top-k result.

``contains_topk_call``
    ``topk_batch``'s post stage: concepts whose intent ⊇ the (closed)
    query == subconcepts of closure(attrset), masked top-k by support.

``rules_topk_call``
    ``rules_batch``: premise ⊆ query test, confidence/validity mask, the
    firing rules' consequent union, and metric top-k with the rule-id
    tie-break.

Both mirror the jnp steps in :mod:`repro.query.engine` bit-for-bit (the
unrolled max passes use the identical mask-and-repeat recurrence, with
the lowest index winning ties as argmax does; the in-kernel
``where(iota == pos)`` scatter equals ``.at[rows, pos].set`` because
``pos`` is unique per row).  Tables past the VMEM bound fall back to the
jnp step — see :func:`supports_serve`.  Equivalence, interpreted on CPU,
is asserted in tests/test_fused_frontier.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.closure import MAX_W, tree_reduce
from repro.kernels.mosaic import pallas_call

# Queries per grid step (the slot axis is blocked; tables ride whole).
DEFAULT_S_BLK = 8

# Scoped VMEM one grid step may use: v5e's default scoped-VMEM limit.
# Mosaic refuses a kernel above it at compile time (RESOURCE_EXHAUSTED).
VMEM_LIMIT_BYTES = 16 << 20

# Whole-table operands per kernel: ([rows, W] tables, (1, rows) vectors).
_TABLE_OPERANDS = {"topk": (1, 1), "rules": (2, 3)}


def serve_vmem_bytes(
    kind: str, n_rows: int, W: int, block_s: int = DEFAULT_S_BLK
) -> int:
    """VMEM bytes one grid step of the ``kind`` kernel ("topk" or "rules")
    needs for an ``n_rows × W``-word table.

    Every ``[.., W]`` uint32 array pads W to whole 128-lane vregs and every
    ``(1, rows)`` vector to 8 sublanes.  The ``[block_s, rows, W]`` subset
    test dominates: Mosaic keeps two copies of it live.  The whole tables
    and row vectors ride beside it.  The v5e compile rehearsal
    (tests/test_tpu_compile.py) checks the bound on both sides.
    """
    tables, vectors = _TABLE_OPERANDS[kind]
    row_bytes = -(-W // 128) * 128 * 4
    subset = 2 * block_s * n_rows * row_bytes
    table = tables * n_rows * row_bytes
    vecs = vectors * 8 * (-(-n_rows // 128) * 128) * 4
    return subset + table + vecs


def supports_serve(
    backend: str, kind: str, n_rows: int, W: int, slots: int
) -> bool:
    """Whether the fused ``kind`` serving kernel can serve this table and
    batch shape; past the VMEM bound the jnp step serves instead (tiling
    the row axis would lift the bound)."""
    return (
        backend == "kernel"
        and W <= MAX_W
        and serve_vmem_bytes(kind, n_rows, W) <= VMEM_LIMIT_BYTES
        and slots % DEFAULT_S_BLK == 0
    )


def _first_index(hit, col, n):
    """Lowest column where ``hit`` holds, per row ([S, n] → [S]) — the
    argmax of a bool/int row, written as a min over ``where(hit, iota, n)``
    because Mosaic lowers argmax for float32 only."""
    return jnp.min(jnp.where(hit, col, jnp.int32(n)), axis=1)


def _topk_int(scores, k):
    """k unrolled max passes over int scores [S, C] → (idx, vals).

    Same order as lax.top_k (desc value, asc index on ties); the repeat
    recurrence masks the taken cell with -2 < every live score ≥ -1.
    """
    C = scores.shape[1]
    col = lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    ids, vals = [], []
    for _ in range(k):
        val = jnp.max(scores, axis=1)
        idx = _first_index(scores == val[:, None], col, C)
        ids.append(idx)
        vals.append(val)
        scores = jnp.where(col == idx[:, None], jnp.int32(-2), scores)
    vals = jnp.stack(vals, axis=1)
    idx = jnp.stack(ids, axis=1)
    idx = jnp.where(vals >= 0, idx, -1)
    return idx, jnp.maximum(vals, -1)


def _contains_topk_kernel(k, s_ref, gc_ref, int_ref, sup_ref,
                          out_i_ref, out_v_ref):
    gc = gc_ref[...]  # [bs, W]
    intents = int_ref[...]  # [C, W]
    C = intents.shape[0]
    contains = jnp.all((gc[:, None, :] & ~intents[None, :, :]) == 0, axis=-1)
    valid = lax.broadcasted_iota(jnp.int32, (1, C), 1) < s_ref[0]
    scores = jnp.where(contains & valid, sup_ref[...], jnp.int32(-1))
    idx, vals = _topk_int(scores, k)
    out_i_ref[...] = idx
    out_v_ref[...] = vals


@functools.partial(
    jax.jit, static_argnames=("k", "block_s")
)
def contains_topk_call(
    gc: jax.Array,
    intents: jax.Array,
    supports: jax.Array,
    n_concepts: jax.Array,
    *,
    k: int,
    block_s: int = DEFAULT_S_BLK,
) -> tuple[jax.Array, jax.Array]:
    """Fused top-k-by-support over concepts containing each closed query.

    gc [S, W] closed queries, intents [C, W] + supports [C] the snapshot
    tables, n_concepts the live row count (traced).  Returns
    (ids [S, k], supports [S, k]) with -1 pads, bit-identical to the jnp
    post in ``QueryEngine._topk_step``.
    """
    S, W = gc.shape
    C = intents.shape[0]
    if S % block_s:
        raise ValueError(f"slots S={S} not a multiple of block_s={block_s}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S // block_s,),
        in_specs=[
            pl.BlockSpec((block_s, W), lambda b, s: (b, 0)),
            pl.BlockSpec((C, W), lambda b, s: (0, 0)),
            pl.BlockSpec((1, C), lambda b, s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_s, k), lambda b, s: (b, 0)),
            pl.BlockSpec((block_s, k), lambda b, s: (b, 0)),
        ],
    )
    out_i, out_v = pallas_call(
        functools.partial(_contains_topk_kernel, k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((S, k), jnp.int32),
            jax.ShapeDtypeStruct((S, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
    )(
        jnp.asarray(n_concepts, jnp.int32)[None],
        gc,
        intents,
        supports.astype(jnp.int32)[None, :],
    )
    return out_i, out_v


def _tree_or(x: jax.Array, axis: int) -> jax.Array:
    """Bitwise-OR reduce along ``axis``."""
    return tree_reduce(x, axis, jnp.bitwise_or)


def _rules_topk_kernel(k, s_ref, q_ref, prem_ref, add_ref, conf_ref,
                       met_ref, rid_ref, minc_ref,
                       out_i_ref, out_v_ref, out_u_ref):
    queries = q_ref[...]  # [bs, W]
    prem = prem_ref[...]  # [R, W]
    added = add_ref[...]  # [R, W]
    R = prem.shape[0]
    rid = rid_ref[...]  # [1, R]
    app = jnp.all((prem[None, :, :] & ~queries[:, None, :]) == 0, axis=-1)
    live = lax.broadcasted_iota(jnp.int32, (1, R), 1) < s_ref[0]
    ok = app & (conf_ref[...] >= minc_ref[...]) & live  # [bs, R]
    # premise→consequent closure: OR-union of every firing conclusion
    fired = jnp.where(ok[:, :, None], added[None], jnp.uint32(0))
    out_u_ref[...] = _tree_or(fired, axis=1)
    # metric top-k with rule-id tie-break (lowest id wins), mirroring
    # QueryEngine._rules_step: the where(iota == pos) scatter equals
    # .at[rows, pos].set(-2.0) because pos is unique per row.
    score = jnp.where(ok, met_ref[...], jnp.float32(-1.0))
    col = lax.broadcasted_iota(jnp.int32, score.shape, 1)
    ids, vals = [], []
    for _ in range(k):
        best = jnp.max(score, axis=1)
        is_best = score == best[:, None]
        sel = jnp.min(
            jnp.where(is_best, rid, jnp.int32(0x7FFFFFFF)), axis=1
        )
        pos = _first_index(is_best & (rid == sel[:, None]), col, R)
        ids.append(sel)
        vals.append(best)
        score = jnp.where(col == pos[:, None], jnp.float32(-2.0), score)
    vals = jnp.stack(vals, axis=1)
    idx = jnp.stack(ids, axis=1)
    out_i_ref[...] = jnp.where(vals >= 0, idx, -1)
    out_v_ref[...] = jnp.maximum(vals, -1.0)


@functools.partial(
    jax.jit, static_argnames=("k", "block_s")
)
def rules_topk_call(
    prem: jax.Array,
    added: jax.Array,
    conf: jax.Array,
    metric: jax.Array,
    rid: jax.Array,
    n_rules: jax.Array,
    queries: jax.Array,
    min_conf: jax.Array,
    *,
    k: int,
    block_s: int = DEFAULT_S_BLK,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused rule lookup: premise ⊆ query → conf/validity mask → consequent
    union → metric top-k with rule-id tie-break, one pass per query block.

    Operand order matches ``QueryEngine._rules_step``'s jnp ``run`` so the
    engine can route by backend without reshuffling: rule tables
    prem/added [R, W], conf/metric [R] f32, rid [R] i32, traced n_rules,
    queries [S, W], traced min_conf.  Returns (rule ids [S, k] (-1 pads),
    scores [S, k], consequent unions [S, W]).
    """
    S, W = queries.shape
    R = prem.shape[0]
    if S % block_s:
        raise ValueError(f"slots S={S} not a multiple of block_s={block_s}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S // block_s,),
        in_specs=[
            pl.BlockSpec((block_s, W), lambda b, s: (b, 0)),
            pl.BlockSpec((R, W), lambda b, s: (0, 0)),
            pl.BlockSpec((R, W), lambda b, s: (0, 0)),
            pl.BlockSpec((1, R), lambda b, s: (0, 0)),
            pl.BlockSpec((1, R), lambda b, s: (0, 0)),
            pl.BlockSpec((1, R), lambda b, s: (0, 0)),
            pl.BlockSpec((1, 1), lambda b, s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_s, k), lambda b, s: (b, 0)),
            pl.BlockSpec((block_s, k), lambda b, s: (b, 0)),
            pl.BlockSpec((block_s, W), lambda b, s: (b, 0)),
        ],
    )
    out_i, out_v, out_u = pallas_call(
        functools.partial(_rules_topk_kernel, k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((S, k), jnp.int32),
            jax.ShapeDtypeStruct((S, k), jnp.float32),
            jax.ShapeDtypeStruct((S, W), jnp.uint32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
    )(
        jnp.asarray(n_rules, jnp.int32)[None],
        queries,
        prem,
        added,
        conf.astype(jnp.float32)[None, :],
        metric.astype(jnp.float32)[None, :],
        rid.astype(jnp.int32)[None, :],
        jnp.asarray(min_conf, jnp.float32)[None, None],
    )
    return out_i, out_v, out_u
