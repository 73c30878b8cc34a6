"""Pallas TPU kernel for batched bitset closure — the paper's ⊕ hot-spot.

The ⊕-operation (Eqn. 5) is dominated by the closure ``Y''``: find every
object row containing the candidate attribute set, then intersect those rows.
For a candidate batch ``C [B, W]`` against context rows ``R [N, W]`` (uint32
bitset words, 32 attributes/word) the kernel computes

    match[b, n]   = all_w((R[n, w] & C[b, w]) == C[b, w])
    closure[b, w] = AND_{n : match[b, n]} R[n, w]      (identity: 0xFFFFFFFF)
    support[b]    = sum_n match[b, n]

This is an AND-accumulate "matmul" of shape (B×N×W) — VPU work, not MXU —
so the tiling goal is lane occupancy and VMEM residency, not MXU alignment:

  * Grid is (B/B_BLK, N/N_BLK) with N as the **last (fastest) axis**, so the
    output block for a given b-block is revisited across consecutive grid
    steps and can be accumulated in place (TPU sequential-grid semantics;
    ``dimension_semantics=("parallel", "arbitrary")``).
  * ``W`` stays un-gridded and VMEM-resident: one block covers up to
    ``MAX_W = 512`` words = 16 384 attributes (the paper's datasets need
    ≤ 10 words).  Wider contexts take the pure-jnp fallback in ``ops.py``.
  * VMEM per step ≈ R-block (N_BLK·W·4) + C-block (B_BLK·W·4) + the fused
    [B_BLK, N_BLK, W] intermediates ≈ 1–3 MB at the default
    (B_BLK=8, N_BLK=256, W≤128) — comfortably inside v5e VMEM.
  * The AND-reduction over N_BLK uses a log₂ tree of full-width vector ANDs
    (no scalar loop), and the W-axis ``all`` is a lane reduction.

Padding discipline (enforced by ``ops.py``):
  * object rows are padded to N_BLK multiples with **all-ones** rows — the
    AND identity; they match every candidate, so supports are corrected by
    the constant pad count outside the kernel;
  * candidate rows are padded with all-ones and their outputs dropped;
  * attribute words are zero-padded; the final closure is masked with
    ``attr_mask`` outside the kernel.

dtype note: the kernel operates on uint32 words; on TPU Mosaic these lower
as 32-bit integer lanes (bitwise ops are dtype-width agnostic).  The kernel
is compiled on TPU and interpreted on CPU (:mod:`repro.kernels.mosaic`),
where it is validated against ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import pallas_call

DEFAULT_B_BLK = 8
DEFAULT_N_BLK = 256
MAX_W = 512
FULL_WORD = 0xFFFFFFFF  # python int — becomes an in-kernel literal


def tree_reduce(x: jax.Array, axis: int, op) -> jax.Array:
    """Reduce ``axis`` with the associative, commutative ``op`` via a log2
    tree (static shapes).

    Each level combines the two contiguous halves: strided pairs
    (``x[0::2]``) lower to gathers Mosaic refuses, and an empty tail slice
    is a zero-size vector it refuses too, so the odd element (if any) is
    carried by a one-row slice.  Any tree order gives the same bits.
    """
    n = x.shape[axis]
    while n > 1:
        half = n // 2
        y = op(
            lax.slice_in_dim(x, 0, half, axis=axis),
            lax.slice_in_dim(x, half, 2 * half, axis=axis),
        )
        if n % 2:
            tail = lax.slice_in_dim(x, 2 * half, n, axis=axis)
            y = jnp.concatenate([y, tail], axis=axis)
        x, n = y, y.shape[axis]
    return lax.index_in_dim(x, 0, axis, keepdims=False)


def _tree_and(x: jax.Array, axis: int) -> jax.Array:
    """Bitwise-AND reduce along ``axis``."""
    return tree_reduce(x, axis, jnp.bitwise_and)


def _closure_kernel(cand_ref, rows_ref, out_c_ref, out_s_ref):
    n_step = pl.program_id(1)
    cands = cand_ref[...]  # [B_BLK, W] uint32
    rows = rows_ref[...]  # [N_BLK, W] uint32

    # match[b, n] ⟺ candidate b ⊆ row n  (word-parallel subset test).
    inter = rows[None, :, :] & cands[:, None, :]  # [B_BLK, N_BLK, W]
    match = jnp.all(inter == cands[:, None, :], axis=-1)  # [B_BLK, N_BLK]

    # AND of matching rows; non-matching rows contribute the AND identity.
    full = jnp.full((), FULL_WORD, dtype=jnp.uint32)
    sel = jnp.where(match[:, :, None], rows[None, :, :], full)
    acc = _tree_and(sel, axis=1)  # [B_BLK, W]
    sup = jnp.sum(match.astype(jnp.int32), axis=-1, keepdims=True)  # [B_BLK, 1]

    @pl.when(n_step == 0)
    def _init():
        out_c_ref[...] = acc
        out_s_ref[...] = sup

    @pl.when(n_step != 0)
    def _accum():
        out_c_ref[...] = out_c_ref[...] & acc
        out_s_ref[...] = out_s_ref[...] + sup


@functools.partial(jax.jit, static_argnames=("block_b", "block_n"))
def closure_pallas(
    rows: jax.Array,
    cands: jax.Array,
    *,
    block_b: int = DEFAULT_B_BLK,
    block_n: int = DEFAULT_N_BLK,
) -> tuple[jax.Array, jax.Array]:
    """Raw kernel invocation.  Shapes must already be block-aligned.

    rows  [N, W] uint32, N % block_n == 0, rows padded with all-ones.
    cands [B, W] uint32, B % block_b == 0.
    Returns (closures [B, W] — unmasked, supports [B] int32 — uncorrected).
    """
    N, W = rows.shape
    B, Wc = cands.shape
    if W != Wc:
        raise ValueError(f"word-width mismatch rows W={W} cands W={Wc}")
    if W > MAX_W:
        raise ValueError(f"W={W} exceeds kernel MAX_W={MAX_W}; use jnp fallback")
    if N % block_n or B % block_b:
        raise ValueError(f"unaligned shapes N={N}%{block_n}, B={B}%{block_b}")

    grid = (B // block_b, N // block_n)
    out_c, out_s = pallas_call(
        _closure_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, W), lambda b, n: (b, 0)),
            pl.BlockSpec((block_n, W), lambda b, n: (n, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, W), lambda b, n: (b, 0)),
            pl.BlockSpec((block_b, 1), lambda b, n: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, W), jnp.uint32),
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
    )(cands, rows)
    return out_c, out_s[:, 0]
