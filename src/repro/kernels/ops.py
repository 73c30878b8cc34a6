"""Jitted public wrapper around the Pallas closure kernel.

Handles the padding/correction discipline so callers see clean semantics:

    closures, supports = batched_closure(rows, cands, n_attrs,
                                         n_valid_rows=N_real)

  * rows may carry pre-existing all-ones padding (``n_valid_rows`` real);
  * cands of any batch size (padded internally to the block multiple);
  * closures come back masked to ``n_attrs`` bits;
  * supports count only real rows.

Falls back to the pure-jnp reference for word widths beyond the kernel's
single-block limit or when ``use_kernel=False``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitset
from repro.kernels import ref
from repro.kernels.closure import MAX_W, closure_pallas

FULL_WORD = np.uint32(0xFFFFFFFF)


def _attr_mask_jnp(n_attrs: int, W: int) -> jnp.ndarray:
    return jnp.asarray(bitset.attr_mask(n_attrs, W))


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_attrs",
        "n_valid_rows",
        "block_b",
        "block_n",
        "use_kernel",
        "fused_reduce",
    ),
)
def batched_closure(
    rows: jax.Array,
    cands: jax.Array,
    n_attrs: int,
    *,
    n_valid_rows: int,
    block_b: int = 8,
    block_n: int = 256,
    use_kernel: bool = True,
    fused_reduce: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Batched closure with clean semantics.  rows [N,W], cands [B,W]."""
    N, W = rows.shape
    B = cands.shape[0]
    mask = _attr_mask_jnp(n_attrs, W)

    if not use_kernel or W > MAX_W:
        closures, supports = ref.closure_ref(rows, cands, fused_reduce=fused_reduce)
        n_pad_rows = N - n_valid_rows
        return closures & mask, supports - n_pad_rows

    # Pad rows to the N block multiple with all-ones (AND identity rows).
    N_pad = -N % block_n
    if N_pad:
        rows = jnp.concatenate(
            [rows, jnp.full((N_pad, W), FULL_WORD, dtype=jnp.uint32)], axis=0
        )
    # Pad candidate batch to the B block multiple (all-ones; outputs dropped).
    B_pad = -B % block_b
    if B_pad:
        cands = jnp.concatenate(
            [cands, jnp.full((B_pad, W), FULL_WORD, dtype=jnp.uint32)], axis=0
        )

    closures, supports = closure_pallas(
        rows, cands, block_b=block_b, block_n=block_n
    )
    closures = closures[:B] & mask
    # All-ones padding rows (pre-existing + internal) match every candidate.
    n_pad_rows = (N - n_valid_rows) + N_pad
    supports = supports[:B] - n_pad_rows
    return closures, supports


@functools.partial(
    jax.jit, static_argnames=("n_attrs", "n_valid_rows", "compute_dtype")
)
def closure_matmul(
    rows: jax.Array,
    cands: jax.Array,
    n_attrs: int,
    *,
    n_valid_rows: int,
    compute_dtype=jnp.bfloat16,
) -> tuple[jax.Array, jax.Array]:
    """Closure as two MXU matmuls over complement bit-planes (§Perf C2).

    Let ``R̄ ∈ {0,1}^{N×m}`` be the complement of the unpacked context and
    ``C ∈ {0,1}^{B×m}`` the unpacked candidates.  Then

        miss   = C · R̄ᵀ          (miss[b,n] = #candidate attrs absent in row n)
        match  = (miss == 0)
        absent = match · R̄        (absent[b,m] = #matching rows missing attr m)
        Y''    = (absent == 0)

    Both contractions are systolic-array work — the bitwise ⊕ hot-spot
    becomes matmuls, with O(B·m + B·N) HBM traffic instead of O(B·N·W).
    Exactness: {0,1} inputs with fp32 accumulation — sums are exact up to
    2²⁴ ≫ any shard's row count.  All-ones padding rows have an empty
    complement, so they match every candidate and never add absences
    (supports corrected by the pad count, as everywhere).
    """
    N, W = rows.shape
    B = cands.shape[0]
    m_pad = W * 32
    shifts = jnp.arange(32, dtype=jnp.uint32)

    def unpack(x):
        bits = (x[:, :, None] >> shifts) & jnp.uint32(1)
        return bits.reshape(x.shape[0], m_pad)[:, :n_attrs]

    rows_c = (1 - unpack(rows)).astype(compute_dtype)  # [N, m] complement
    cand_b = unpack(cands).astype(compute_dtype)  # [B, m]

    miss = jnp.einsum("bm,nm->bn", cand_b, rows_c,
                      preferred_element_type=jnp.float32)
    match = miss == 0.0  # [B, N]
    absent = jnp.einsum("bn,nm->bm", match.astype(compute_dtype), rows_c,
                        preferred_element_type=jnp.float32)
    closure_bits = (absent == 0.0)  # [B, m]

    pad = m_pad - n_attrs
    if pad:
        closure_bits = jnp.concatenate(
            [closure_bits, jnp.zeros((B, pad), bool)], axis=1
        )
    weights = (jnp.uint32(1) << shifts).astype(jnp.uint32)
    closures = (
        closure_bits.reshape(B, W, 32).astype(jnp.uint32) * weights
    ).sum(axis=-1, dtype=jnp.uint32)
    supports = match.sum(axis=-1, dtype=jnp.int32) - (N - n_valid_rows)
    return closures, supports


def bucket_size(n: int, minimum: int = 8) -> int:
    """Next power-of-two capacity ≥ n — bounds jit recompiles across the
    iterative drivers (the frontier size changes every iteration)."""
    size = minimum
    while size < n:
        size <<= 1
    return size
