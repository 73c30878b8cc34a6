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
``≥ block_b``.  Everything is validated bit-identical to the jnp step
oracles with the kernels interpreted on CPU (tests/test_fused_frontier.py)
and compiled for v5e (tests/test_tpu_compile.py); widths beyond
``MAX_W`` take the jnp path, same as ``ops.batched_closure``.
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


def _fused_kernel(
    iceberg, cbo,
    s_ref, cand_ref, rows_ref, mask_ref, *refs,
):
    """closure → support popcount → driver filter, one grid pass.

    Grid is (B/bb, N/bn) with N innermost; the closure/support output
    blocks accumulate across the N steps (TPU sequential-grid semantics)
    and the filter runs once, on the final N step, against the fully
    accumulated block — nothing ever leaves VMEM in between.
    """
    if cbo:
        parent_ref, lowrow_ref, out_c_ref, out_s_ref, out_k_ref = refs
    else:
        parent_ref = lowrow_ref = None
        out_c_ref, out_s_ref, out_k_ref = refs
    b_step = pl.program_id(0)
    n_step = pl.program_id(1)
    n_steps = pl.num_programs(1)
    cands = cand_ref[...]  # [bb, W]
    rows = rows_ref[...]  # [bn, W]

    inter = rows[None, :, :] & cands[:, None, :]
    match = jnp.all(inter == cands[:, None, :], axis=-1)  # [bb, bn]
    full = jnp.full((), FULL_WORD, dtype=jnp.uint32)
    sel = jnp.where(match[:, :, None], rows[None, :, :], full)
    acc = _tree_and(sel, axis=1)  # [bb, W]
    sup = jnp.sum(match.astype(jnp.int32), axis=-1, keepdims=True)

    @pl.when(n_step == 0)
    def _init():
        out_c_ref[...] = acc
        out_s_ref[...] = sup
        out_k_ref[...] = jnp.zeros_like(out_k_ref)

    @pl.when(n_step != 0)
    def _accum():
        out_c_ref[...] = out_c_ref[...] & acc
        out_s_ref[...] = out_s_ref[...] + sup

    @pl.when(n_step == n_steps - 1)
    def _finalize():
        gc = out_c_ref[...] & mask_ref[...]  # broadcast [1, W]
        sup_c = out_s_ref[...] - s_ref[2]
        out_c_ref[...] = gc
        out_s_ref[...] = sup_c
        out_k_ref[...] = _keep_mask(
            s_ref, b_step, gc, sup_c,
            None if parent_ref is None else parent_ref[...],
            None if lowrow_ref is None else lowrow_ref[...],
            iceberg=iceberg, cbo=cbo,
        )


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
    """
    N, W = rows.shape
    B = cands.shape[0]
    if W > MAX_W:
        raise ValueError(f"W={W} exceeds MAX_W={MAX_W}; use the jnp path")
    if N % block_n or B % block_b:
        raise ValueError(f"unaligned shapes N={N}%{block_n}, B={B}%{block_b}")
    if cbo and (parent is None or lowrow is None):
        raise ValueError("cbo=True needs parent= and lowrow= operands")

    grid = (B // block_b, N // block_n)
    in_specs = [
        pl.BlockSpec((block_b, W), lambda b, n, s: (b, 0)),
        pl.BlockSpec((block_n, W), lambda b, n, s: (n, 0)),
        pl.BlockSpec((1, W), lambda b, n, s: (0, 0)),
    ]
    inputs = [cands, rows, mask]
    if cbo:
        in_specs += [
            pl.BlockSpec((block_b, W), lambda b, n, s: (b, 0)),
            pl.BlockSpec((block_b, W), lambda b, n, s: (b, 0)),
        ]
        inputs += [parent, lowrow]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, W), lambda b, n, s: (b, 0)),
            pl.BlockSpec((block_b, 1), lambda b, n, s: (b, 0)),
            pl.BlockSpec((block_b, 1), lambda b, n, s: (b, 0)),
        ],
    )
    out_c, out_s, out_k = pallas_call(
        functools.partial(_fused_kernel, iceberg, cbo),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, W), jnp.uint32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(scalars, *inputs)
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
