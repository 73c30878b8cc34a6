"""Fused frontier-step Pallas kernels — closure, support, and the driver
filter in one VMEM-resident pass (ISSUE 6 tentpole).

Since PR 1–5 the mining hot loop is ``closure map → popcount/AND reduce →
driver filter``, executed as *separate* XLA ops that round-trip the
bit-packed ``[B, W]`` closure block through HBM between stages.  This
module fuses the whole per-chunk step into Pallas kernels so the candidate
block and context rows stay in VMEM/registers from the subset test to the
survivor mask:

``fused_closure_call``  (the full fusion)
    One ``pallas_call`` computing, per candidate block,

        closure  = AND of matching context rows   (masked to real attrs)
        support  = #matching rows − #all-ones pad rows
        keep     = row-validity ∧ [support ≥ min_sup] ∧ [CbO canonicity]

    with the iceberg threshold, valid-row count, pad count and the 2-D
    block offset riding as a **scalar-prefetch** operand (SMEM) — one
    compile serves every threshold and every candidate block.  Exact when
    local closure == global closure, i.e. on single-object-shard plans
    (``n_parts == 1``, with or without candidate-axis sharding).

``map_closure_call``
    The map half for multi-shard plans: closure + support popcount with
    the attribute mask applied **in-kernel** (AND distributes over the
    mask, so masked locals AND-allreduce to the masked global closure and
    the separate post-reduce mask op disappears).

``filter_call``
    The post-reduce half for multi-shard plans: one ``pallas_call``
    evaluating pad correction + iceberg cut + CbO canonicity on the
    globally reduced ``[B, W]`` block — the three driver-filter ops fused
    into a single VMEM pass.

The driver-side compaction (``_compact`` / ``_sort_unique`` in
:mod:`repro.core.frontier`) stays jnp: a data-dependent permutation is
XLA's job, and it consumes only the kernel's survivor mask + closures —
never a full intermediate.  CbO's canonicity operand ``LOW[gen]`` is
gathered outside the kernel (a [B, W] table row gather) and enters as a
regular blocked input.

Padding discipline matches ``kernels/closure.py``: context rows padded to
``block_n`` multiples with all-ones AND-identity rows (supports corrected
in-kernel via the scalar operand), candidate caps are power-of-two buckets
``≥ block_b``.  ``fused_closure_call`` alone lays the context out with the
objects on the 128 lanes (word planes, padded further with all-ones
objects to its object tile); the map kernel keeps the [cand, obj, W]
layout of ``kernels/closure.py``.  Everything is validated bit-identical
to the jnp step oracles with the kernels interpreted on CPU
(tests/test_fused_frontier.py) and compiled for v5e
(tests/test_tpu_compile.py); widths beyond ``MAX_W`` take the jnp path,
same as ``ops.batched_closure``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.closure import (
    DEFAULT_B_BLK,
    DEFAULT_N_BLK,
    FULL_WORD,
    MAX_W,
    _tree_and,
)
from repro.kernels.mosaic import pallas_call

# scalar-prefetch operand layout (int32 [4], SMEM):
#   [0] n_valid   — valid candidate rows in the (whole-chunk) batch
#   [1] min_sup   — iceberg threshold (ignored unless iceberg=True)
#   [2] n_pad     — all-ones context padding rows to subtract from supports
#   [3] row_off   — this block's first row's chunk-global index
#                   (cand_index * block_rows on 2-D plans, 0 on 1-D)
N_SCALARS = 4


def pack_scalars(n_valid, min_sup=0, n_pad=0, row_off=0) -> jax.Array:
    """Assemble the kernels' scalar-prefetch operand (traced values ok)."""
    return jnp.stack(
        [
            jnp.asarray(n_valid, jnp.int32),
            jnp.asarray(min_sup, jnp.int32),
            jnp.asarray(n_pad, jnp.int32),
            jnp.asarray(row_off, jnp.int32),
        ]
    )


def _row_valid(s_ref, b_step, bb):
    """Chunk-global row validity for this candidate block ([bb, 1] bool)."""
    idx = lax.broadcasted_iota(jnp.int32, (bb, 1), 0) + b_step * bb
    return (idx + s_ref[3]) < s_ref[0]


def _keep_mask(s_ref, b_step, gc, sup_c, parent, lowrow, *, iceberg, cbo):
    """The fused driver filter: validity ∧ iceberg cut ∧ CbO canonicity.

    ``gc`` is the masked closure block [bb, W], ``sup_c`` the corrected
    supports [bb, 1].  Mirrors the jnp posts bit-for-bit:
    ``post_iceberg``'s ``(arange < n_valid) & (gs >= min_sup)`` and
    ``lectic.feasible_jnp``'s ``((Z ^ Y) & LOW[a]) == 0``.
    """
    keep = _row_valid(s_ref, b_step, gc.shape[0])
    if iceberg:
        keep = keep & (sup_c >= s_ref[1])
    if cbo:
        canonical = jnp.all((gc ^ parent) & lowrow == 0, axis=-1, keepdims=True)
        keep = keep & canonical
    return keep.astype(jnp.int32)


# fused_closure_call: objects on the lanes.
#
# The context enters the kernel as word planes ``rows_t [Wg, 8, N']``:
# one object per lane, attribute word ``8g + k`` on sublane k of group g.
# A grid step takes ``bb`` candidates × ``bn`` objects; inside it, groups
# of ``sg`` candidates (sublanes) sweep the object tile 128 lanes at a
# time, so every vector op covers ``sg × 128`` candidate–object pairs
# instead of the W of 128 lanes the [cand, obj, W] layout filled.
# Per-lane partial closures (AND) and supports (+) accumulate in VMEM
# scratch across the object steps and are folded across the 128 lanes
# once per candidate tile.

LANES = 128
SUBLANES = 8
# Word loops up to this width are unrolled and their accumulators stay in
# registers; wider rows loop over word groups with the accumulators in
# VMEM (Mosaic indexes sublanes only statically, so words go 8 at a time).
UNROLL_W = 16
# VMEM budgets: the context block (double-buffered) and each of the
# per-lane scratch arrays [Wp, bb, 128] (accumulator, broadcast
# candidates; Wp is W rounded up to 8).
ROWS_BLOCK_BYTES = 4 << 20
LANE_SCRATCH_BYTES = 2 << 20
# Candidate–object pairs per grid step, enough that the fixed cost of a
# step is small beside its work.  This and GROUP_VREGS come from a tile
# sweep on a TPU v5e at the census-income and mushroom shapes (PERF.md).
STEP_PAIRS = 1 << 22
# Accumulator vregs a candidate group may hold ([W, sg, 128] words): the
# group's accumulators, candidate words and temporaries share 64 vregs.
GROUP_VREGS = 20


def _lane_tiles(B: int, N: int, W: int) -> tuple[int, int, int]:
    """(bb, sg, bn) for a [B, W] candidate batch against N objects.

    bn: objects per grid step, a multiple of 128 — the whole (padded)
    context when its word planes fit ``ROWS_BLOCK_BYTES``, so it is read
    into VMEM once per call; otherwise the fewest equal tiles that fit.
    bb: candidates per grid step, a power of two dividing B (B is a
    multiple of 8), grown until a step holds ``STEP_PAIRS`` pairs or the
    per-lane scratch reaches its budget.  sg: candidates per register
    group, a power of two dividing bb.
    """
    wp = -(-W // SUBLANES) * SUBLANES
    n128 = -(-N // LANES) * LANES
    max_bn = max(LANES, ROWS_BLOCK_BYTES // (4 * wp) // LANES * LANES)
    n_tiles = -(-n128 // max_bn)
    bn = -(-n128 // n_tiles // LANES) * LANES
    bb = SUBLANES
    while (
        B % (2 * bb) == 0
        and bb * bn < STEP_PAIRS
        and wp * 2 * bb * LANES * 4 <= LANE_SCRATCH_BYTES
    ):
        bb *= 2
    sg = SUBLANES
    while 2 * sg <= bb and W * 2 * sg <= SUBLANES * GROUP_VREGS:
        sg *= 2
    return bb, sg, bn


def _lane_and(x: jax.Array) -> jax.Array:
    """AND across the 128 lanes (last axis) of ``x``: every lane of the
    result holds the fold (Mosaic reduces no unsigned type)."""
    shift = LANES // 2
    while shift:
        x = x & pltpu.roll(x, shift, x.ndim - 1)
        shift //= 2
    return x


def _loop(n: int, unroll: bool, body, init):
    """``body(i, carry)`` for ``i < n``: unrolled with a static ``i``, or
    a loop."""
    if unroll:
        for i in range(n):
            init = body(i, init)
        return init
    return lax.fori_loop(0, n, body, init)


def _fused_kernel(
    iceberg, cbo, W, sg,
    s_ref, cand_ref, rows_ref, mask_ref, *refs,
):
    """closure → support popcount → driver filter, objects on the lanes.

    Grid is (B/bb, N'/bn) with N' innermost ("arbitrary"): ``acc_ref``
    [Wp, bb, 128] and ``cnt_ref`` [bb, 128] accumulate per lane across
    the object steps, and the last step folds the lanes and runs the
    filter.  ``cb_ref`` holds each candidate word broadcast across the
    lanes (zero past W on the looped path, so pad words never miss).
    """
    if cbo:
        parent_ref, lowrow_ref, *refs = refs
    else:
        parent_ref = lowrow_ref = None
    out_c_ref, out_s_ref, out_k_ref, cb_ref, acc_ref, cnt_ref = refs
    b_step = pl.program_id(0)
    n_step = pl.program_id(1)
    n_groups, _, bn = rows_ref.shape
    bb = cand_ref.shape[0]
    unroll = W <= UNROLL_W

    @pl.when(n_step == 0)
    def _init():
        cands = cand_ref[...]
        if not unroll:
            cb_ref[...] = jnp.zeros(cb_ref.shape, jnp.uint32)
        for w in range(W):
            cb_ref[w] = jnp.broadcast_to(cands[:, w : w + 1], (bb, LANES))
        acc_ref[...] = jnp.full(acc_ref.shape, jnp.uint32(FULL_WORD))
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.int32)

    def group(c, carry):
        gs = pl.ds(pl.multiple_of(c * sg, sg), sg)

        def misses(d, w0, nr):
            """OR into ``d`` each candidate word ``w0 + k`` AND the inverted
            object word ``nr[k]``: nonzero ⟺ candidate ⊄ object."""
            for k in range(nr.shape[0]):
                d = d | (cb_ref[w0 + k, gs, :] & nr[k : k + 1])
            return d

        def hit_skip(d):
            """hit 1 where candidate ⊆ object; skip all-ones where not
            (OR-ed into the object's words, the AND identity)."""
            hit = (d == 0).astype(jnp.int32)
            return hit, lax.bitcast_convert_type(hit - 1, jnp.uint32)

        zero = jnp.zeros((sg, LANES), jnp.uint32)
        if unroll:

            def chunk(j, carry):
                acc, cnt = carry
                lanes = pl.ds(pl.multiple_of(j * LANES, LANES), LANES)
                r = rows_ref[:, :, lanes].reshape(-1, LANES)[:W]  # [W, 128]
                hit, skip = hit_skip(misses(zero, 0, ~r))
                return acc & (r[:, None, :] | skip), cnt + hit

            acc, cnt = lax.fori_loop(
                0, bn // LANES, chunk, (acc_ref[:W, gs, :], cnt_ref[gs, :])
            )
            acc_ref[:W, gs, :] = acc
        else:

            def chunk(j, cnt):
                lanes = pl.ds(pl.multiple_of(j * LANES, LANES), LANES)
                d = lax.fori_loop(
                    0, n_groups,
                    lambda g, d: misses(
                        d, g * SUBLANES, ~rows_ref[g, :, lanes]
                    ),
                    zero,
                )
                hit, skip = hit_skip(d)

                def fold(g, carry):
                    r = rows_ref[g, :, lanes][:, None, :]  # [8, 1, 128]
                    ws = pl.ds(
                        pl.multiple_of(g * SUBLANES, SUBLANES), SUBLANES
                    )
                    acc_ref[ws, gs, :] &= r | skip
                    return carry

                lax.fori_loop(0, n_groups, fold, 0)
                return cnt + hit

            cnt = lax.fori_loop(0, bn // LANES, chunk, cnt_ref[gs, :])
        cnt_ref[gs, :] = cnt
        return carry

    lax.fori_loop(0, bb // sg, group, 0)

    @pl.when(n_step == pl.num_programs(1) - 1)
    def _finalize():
        acc_ref[:W] = _lane_and(acc_ref[:W])
        lane = lax.broadcasted_iota(jnp.int32, (bb, W), 1)
        gc = _loop(
            W, unroll,
            lambda w, c: jnp.where(lane == w, acc_ref[w, :, :1], c),
            jnp.zeros((bb, W), jnp.uint32),
        )
        gc = gc & mask_ref[...]
        sup_c = jnp.sum(cnt_ref[...], axis=1, keepdims=True) - s_ref[2]
        out_c_ref[...] = gc
        out_s_ref[...] = sup_c
        out_k_ref[...] = _keep_mask(
            s_ref, b_step, gc, sup_c,
            None if parent_ref is None else parent_ref[...],
            None if lowrow_ref is None else lowrow_ref[...],
            iceberg=iceberg, cbo=cbo,
        )


def _fused_lanes(rows_t, cands, mask, scalars, lineage, *, iceberg, cbo,
                 bb, sg, bn):
    """The objects-on-lanes ``pallas_call``: rows_t [Wg, 8, N'] word
    planes (N' % bn == 0, pad objects all-ones and counted in
    ``scalars[2]``)."""
    n_groups, _, N = rows_t.shape
    B, W = cands.shape
    wp = n_groups * SUBLANES
    cand_spec = pl.BlockSpec((bb, W), lambda b, n, s: (b, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // bb, N // bn),
        in_specs=[
            cand_spec,
            pl.BlockSpec((n_groups, SUBLANES, bn), lambda b, n, s: (0, 0, n)),
            pl.BlockSpec((1, W), lambda b, n, s: (0, 0)),
        ] + [cand_spec] * len(lineage),
        out_specs=[
            cand_spec,
            pl.BlockSpec((bb, 1), lambda b, n, s: (b, 0)),
            pl.BlockSpec((bb, 1), lambda b, n, s: (b, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((wp, bb, LANES), jnp.uint32),
            pltpu.VMEM((wp, bb, LANES), jnp.uint32),
            pltpu.VMEM((bb, LANES), jnp.int32),
        ],
    )
    return pallas_call(
        functools.partial(_fused_kernel, iceberg, cbo, W, sg),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, W), jnp.uint32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(scalars, cands, rows_t, mask, *lineage)


def _word_planes(rows: jax.Array, n_obj: int) -> jax.Array:
    """rows [N, W] → [Wg, 8, n_obj]: objects on the last axis, padded
    with all-ones objects; words padded to a multiple of 8."""
    N, W = rows.shape
    wp = -(-W // SUBLANES) * SUBLANES
    full = jnp.uint32(FULL_WORD)
    planes = jnp.pad(
        rows.T, ((0, wp - W), (0, n_obj - N)), constant_values=full
    )
    return planes.reshape(wp // SUBLANES, SUBLANES, n_obj)


@functools.partial(
    jax.jit,
    static_argnames=("iceberg", "cbo", "block_b", "block_n"),
)
def fused_closure_call(
    rows: jax.Array,
    cands: jax.Array,
    mask: jax.Array,
    scalars: jax.Array,
    *,
    parent: jax.Array | None = None,
    lowrow: jax.Array | None = None,
    iceberg: bool = False,
    cbo: bool = False,
    block_b: int = DEFAULT_B_BLK,
    block_n: int = DEFAULT_N_BLK,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The fully fused frontier step (single-object-shard plans).

    rows [N, W] (all-ones padded, N % block_n == 0), cands [B, W]
    (B % block_b == 0), mask [1, W], scalars int32 [4] (see module top).
    CbO variants additionally take parent/lowrow [B, W].
    Returns (closures [B, W] masked, supports [B] corrected, keep [B]).

    The rows become word planes [ceil(W/8), 8, N'] here, N' padded with
    all-ones objects to the kernel's object tile and the extra pad added
    to ``scalars[2]``; the tiles come from the shapes (``_lane_tiles``).
    """
    N, W = rows.shape
    B = cands.shape[0]
    if W > MAX_W:
        raise ValueError(f"W={W} exceeds MAX_W={MAX_W}; use the jnp path")
    if N % block_n or B % block_b or B % 8:
        raise ValueError(f"unaligned shapes N={N}%{block_n}, B={B}%{block_b}")
    if cbo and (parent is None or lowrow is None):
        raise ValueError("cbo=True needs parent= and lowrow= operands")

    bb, sg, bn = _lane_tiles(B, N, W)
    n_obj = -(-N // bn) * bn
    scalars = scalars.at[2].add(n_obj - N)
    out_c, out_s, out_k = _fused_lanes(
        _word_planes(rows, n_obj), cands, mask, scalars,
        (parent, lowrow) if cbo else (),
        iceberg=iceberg, cbo=cbo, bb=bb, sg=sg, bn=bn,
    )
    return out_c, out_s[:, 0], out_k[:, 0] > 0


def _map_kernel(s_ref, cand_ref, rows_ref, mask_ref, out_c_ref, out_s_ref):
    """closure + support popcount with the attr mask folded in-kernel."""
    n_step = pl.program_id(1)
    n_steps = pl.num_programs(1)
    cands = cand_ref[...]
    rows = rows_ref[...]
    inter = rows[None, :, :] & cands[:, None, :]
    match = jnp.all(inter == cands[:, None, :], axis=-1)
    full = jnp.full((), FULL_WORD, dtype=jnp.uint32)
    sel = jnp.where(match[:, :, None], rows[None, :, :], full)
    acc = _tree_and(sel, axis=1)
    sup = jnp.sum(match.astype(jnp.int32), axis=-1, keepdims=True)

    @pl.when(n_step == 0)
    def _init():
        out_c_ref[...] = acc
        out_s_ref[...] = sup

    @pl.when(n_step != 0)
    def _accum():
        out_c_ref[...] = out_c_ref[...] & acc
        out_s_ref[...] = out_s_ref[...] + sup

    @pl.when(n_step == n_steps - 1)
    def _finalize():
        out_c_ref[...] = out_c_ref[...] & mask_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_n")
)
def map_closure_call(
    rows: jax.Array,
    cands: jax.Array,
    mask: jax.Array,
    *,
    block_b: int = DEFAULT_B_BLK,
    block_n: int = DEFAULT_N_BLK,
) -> tuple[jax.Array, jax.Array]:
    """Per-shard map half for multi-shard plans: masked local closures
    [B, W] + raw local supports [B] (pad correction happens after the
    psum, in :func:`filter_call`)."""
    N, W = rows.shape
    B = cands.shape[0]
    if W > MAX_W:
        raise ValueError(f"W={W} exceeds MAX_W={MAX_W}; use the jnp path")
    if N % block_n or B % block_b:
        raise ValueError(f"unaligned shapes N={N}%{block_n}, B={B}%{block_b}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // block_b, N // block_n),
        in_specs=[
            pl.BlockSpec((block_b, W), lambda b, n, s: (b, 0)),
            pl.BlockSpec((block_n, W), lambda b, n, s: (n, 0)),
            pl.BlockSpec((1, W), lambda b, n, s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, W), lambda b, n, s: (b, 0)),
            pl.BlockSpec((block_b, 1), lambda b, n, s: (b, 0)),
        ],
    )
    out_c, out_s = pallas_call(
        _map_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, W), jnp.uint32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(jnp.zeros((N_SCALARS,), jnp.int32), cands, rows, mask)
    return out_c, out_s[:, 0]


def _filter_kernel(iceberg, cbo, s_ref, gc_ref, gs_ref, *refs):
    if cbo:
        parent_ref, lowrow_ref, out_s_ref, out_k_ref = refs
    else:
        parent_ref = lowrow_ref = None
        out_s_ref, out_k_ref = refs
    b_step = pl.program_id(0)
    gc = gc_ref[...]
    sup_c = gs_ref[...] - s_ref[2]
    out_s_ref[...] = sup_c
    out_k_ref[...] = _keep_mask(
        s_ref, b_step, gc, sup_c,
        None if parent_ref is None else parent_ref[...],
        None if lowrow_ref is None else lowrow_ref[...],
        iceberg=iceberg, cbo=cbo,
    )


@functools.partial(
    jax.jit,
    static_argnames=("iceberg", "cbo", "block_b"),
)
def filter_call(
    gc: jax.Array,
    gs: jax.Array,
    scalars: jax.Array,
    *,
    parent: jax.Array | None = None,
    lowrow: jax.Array | None = None,
    iceberg: bool = False,
    cbo: bool = False,
    block_b: int = DEFAULT_B_BLK,
) -> tuple[jax.Array, jax.Array]:
    """Post-reduce fused driver filter for multi-shard plans.

    gc [B, W] globally reduced masked closures, gs [B] psum'd raw
    supports.  Returns (supports corrected [B], keep [B] bool).
    """
    B, W = gc.shape
    if B % block_b:
        raise ValueError(f"unaligned batch B={B}%{block_b}")
    if cbo and (parent is None or lowrow is None):
        raise ValueError("cbo=True needs parent= and lowrow= operands")
    in_specs = [
        pl.BlockSpec((block_b, W), lambda b, s: (b, 0)),
        pl.BlockSpec((block_b, 1), lambda b, s: (b, 0)),
    ]
    inputs = [gc, gs[:, None]]
    if cbo:
        in_specs += [
            pl.BlockSpec((block_b, W), lambda b, s: (b, 0)),
            pl.BlockSpec((block_b, W), lambda b, s: (b, 0)),
        ]
        inputs += [parent, lowrow]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // block_b,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda b, s: (b, 0)),
            pl.BlockSpec((block_b, 1), lambda b, s: (b, 0)),
        ],
    )
    out_s, out_k = pallas_call(
        functools.partial(_filter_kernel, iceberg, cbo),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
    )(scalars, *inputs)
    return out_s[:, 0], out_k[:, 0] > 0


# ---------------------------------------------------------------------------
# step-variant metadata shared with the engine wiring
# ---------------------------------------------------------------------------

# variant name -> (iceberg, cbo, unique) flags; the engine's fused step
# builders key off these, the drivers keep using the same names they pass
# to DeviceFrontier._step_fn.
VARIANTS = {
    "plain": (False, False, False),
    "unique": (False, False, True),
    "iceberg": (True, False, False),
    "iceberg_unique": (True, False, True),
    "cbo": (False, True, False),
    "cbo_iceberg": (True, True, False),
}


def supports_fused(backend: str, W: int) -> bool:
    """Whether the fused frontier kernels can serve this engine config."""
    return backend == "kernel" and W <= MAX_W
