"""Bitwise-AND all-reduce — the paper's reduce phase (Theorem 2) as a
device collective, in three interchangeable implementations:

  * ``allgather`` — every shard all-gathers the full [B, W] local-closure
    block and AND-folds locally.  One hop, k·B·W words on the wire per
    device; the baseline reduce.
  * ``rsag``      — reduce-scatter + all-gather: shards exchange 1/k-sized
    batch chunks (all_to_all), AND-fold their owned chunk, then all-gather
    the folded chunks.  2·(k-1)/k·B·W words per device — the bandwidth-
    optimal ring schedule, same arithmetic, bit-identical output.
  * ``pmin``      — unpack words to attribute lanes and ``lax.pmin``:
    AND of {0,1} bits == elementwise min.  Exercises the scalar-collective
    path (useful on interconnects with native min/max reductions); costs
    32× the wire bytes of the packed impls unless ``n_attrs`` is passed to
    bound the unpacked width.

All three are monoid reductions over the AND semigroup, so the results are
bit-identical regardless of shard count or schedule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

IMPLS = ("allgather", "rsag", "pmin")


def _and_fold(x: jax.Array) -> jax.Array:
    """AND-fold over the leading axis via a log2 tree (static shapes)."""
    n = x.shape[0]
    while n > 1:
        half = n // 2
        head = x[: 2 * half]
        x = jnp.concatenate([head[0::2] & head[1::2], x[2 * half :]], axis=0)
        n = x.shape[0]
    return x[0]


def and_allreduce(
    x: jax.Array,
    axis_names,
    *,
    impl: str = "rsag",
    n_attrs: int | None = None,
) -> jax.Array:
    """Global bitwise-AND of ``x [B, W]`` across ``axis_names`` shards.

    Must be called inside ``shard_map``; returns the same value on every
    shard.  ``n_attrs`` (optional) bounds the unpacked width of the
    ``pmin`` impl to the real attribute count.
    """
    if impl not in IMPLS:
        raise ValueError(f"unknown reduce impl {impl!r}; choose {IMPLS}")
    k = lax.axis_size(axis_names)
    if k == 1:
        return x

    if impl == "allgather":
        g = lax.all_gather(x, axis_names)  # [k, B, W]
        return _and_fold(g.reshape(k, *x.shape))

    if impl == "rsag":
        B, W = x.shape
        pad = -B % k
        if pad:
            x = jnp.concatenate(
                [x, jnp.full((pad, W), 0xFFFFFFFF, dtype=x.dtype)], axis=0
            )
        chunks = x.reshape(k, (B + pad) // k, W)
        # reduce-scatter: shard i receives every shard's chunk i …
        recv = lax.all_to_all(chunks, axis_names, split_axis=0, concat_axis=0)
        recv = recv.reshape(k, (B + pad) // k, W)
        owned = _and_fold(recv)  # [B/k, W] — globally-reduced chunk
        # … all-gather the folded chunks back to the full batch.
        full = lax.all_gather(owned, axis_names).reshape(B + pad, W)
        return full[:B]

    # pmin: AND of bits == min of bits, one lane per attribute.
    W = x.shape[-1]
    m = n_attrs if n_attrs is not None else W * 32
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((x[..., None] >> shifts) & jnp.uint32(1)).reshape(*x.shape[:-1], W * 32)
    bits = lax.pmin(bits[..., :m], axis_names)
    pad = W * 32 - m
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros((*bits.shape[:-1], pad), bits.dtype)], axis=-1
        )
    weights = (jnp.uint32(1) << shifts).astype(jnp.uint32)
    return (
        bits.reshape(*x.shape[:-1], W, 32).astype(jnp.uint32) * weights
    ).sum(axis=-1, dtype=jnp.uint32)


def modeled_comm_bytes(
    impl: str, n_parts: int, batch: int, W: int, n_attrs: int | None = None
) -> int:
    """Analytic wire bytes for one reduce round over all ``n_parts`` shards.

    Used for the paper's communication-cost accounting (Table 8 discussion)
    and by the dry-run/benchmarks; the simulated engine charges this model
    since nothing actually crosses a network on one device.  ``n_attrs``
    bounds the pmin lane count exactly as it bounds the implementation
    (without it the full ``W·32`` unpacked width is charged).
    """
    if n_parts <= 1:
        return 0
    word_bytes = batch * W * 4
    if impl == "allgather":
        return n_parts * (n_parts - 1) * word_bytes
    if impl == "rsag":
        return int(2 * (n_parts - 1) * word_bytes)  # ring RS + AG, summed
    if impl == "pmin":
        # one uint32 per unpacked attribute lane — what lax.pmin actually
        # exchanges (32× the packed impls when unbounded)
        lanes = n_attrs if n_attrs is not None else W * 32
        return n_parts * (n_parts - 1) * batch * lanes * 4
    raise ValueError(f"unknown reduce impl {impl!r}; choose {IMPLS}")


def ring_steps(impl: str, n_parts: int) -> int:
    """Per-device ring-step (latency hop) count for one reduce round.

    ``allgather``/``pmin`` are one ring pass (k-1 steps); ``rsag`` pays two
    passes (reduce-scatter then all-gather, 2(k-1) steps) for its lower
    wire-byte volume — the classic latency/bandwidth trade the schedule
    autotuner arbitrates.
    """
    if impl not in IMPLS:
        raise ValueError(f"unknown reduce impl {impl!r}; choose {IMPLS}")
    if n_parts <= 1:
        return 0
    k = n_parts
    return 2 * (k - 1) if impl == "rsag" else k - 1


def modeled_cost_bytes(
    impl: str,
    n_parts: int,
    batch: int,
    W: int,
    n_attrs: int | None = None,
    *,
    hop_bytes: int = 4096,
) -> int:
    """α-β reduce-cost model in byte units: wire volume + per-hop latency.

    ``hop_bytes`` is the latency term α expressed as its bandwidth-
    equivalent byte cost per ring step per device.  Small batches are
    latency-bound (allgather's single pass wins); large batches are
    bandwidth-bound (rsag's 2(k-1)/k volume wins).  This is what
    ``ShardPlan.resolve_impl`` minimizes for ``reduce_impl="auto"``.
    """
    if n_parts <= 1:
        return 0
    return modeled_comm_bytes(impl, n_parts, batch, W, n_attrs) + (
        n_parts * ring_steps(impl, n_parts) * hop_bytes
    )
