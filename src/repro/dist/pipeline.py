"""GPipe-style pipeline parallelism over one mesh axis.

Each shard along ``axis_name`` owns one pipeline stage's weights; micro-
batches stream through the ring with one ``ppermute`` hop per tick.  The
schedule is the classic trapezoid: ``n_micro + n_stages - 1`` ticks, stage
``s`` busy on microbatch ``t - s`` at tick ``t``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, stage_weights, x, mesh, *, axis_name: str = "model"):
    """Apply ``n_stages`` chained stages to microbatched input.

    stage_fn:      ``(W_s, x_mb) -> y_mb`` for one stage on one microbatch.
    stage_weights: ``[n_stages, ...]`` — leading dim sharded over
                   ``axis_name`` (one stage per shard).
    x:             ``[n_micro, ...mb_shape]`` microbatches, replicated.

    Returns ``[n_micro, ...mb_shape]``: every microbatch pushed through all
    stages in order — numerically identical to the sequential loop.
    """
    n_stages = mesh.shape[axis_name]
    if stage_weights.shape[0] != n_stages:
        raise ValueError(
            f"{stage_weights.shape[0]} stages vs {axis_name}={n_stages} shards"
        )
    n_micro = x.shape[0]
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(W_local, x_full):
        s = lax.axis_index(axis_name)
        buf = jnp.zeros_like(x_full[0])
        out = jnp.zeros_like(x_full)
        for t in range(n_micro + n_stages - 1):
            mb = t - s  # microbatch on this stage at this tick
            active = (mb >= 0) & (mb < n_micro)
            feed = jnp.where(t < n_micro, x_full[min(t, n_micro - 1)], 0)
            inp = jnp.where(s == 0, feed, buf)
            y = stage_fn(W_local[0], inp)
            y = jnp.where(active, y, 0)
            idx = jnp.clip(mb, 0, n_micro - 1)
            take = active & (s == n_stages - 1)
            out = out.at[idx].set(jnp.where(take, y, out[idx]))
            buf = lax.ppermute(y, axis_name, fwd)
        # only the last stage holds real outputs; sum-combine across shards
        out = jnp.where(s == n_stages - 1, out, 0)
        return lax.psum(out, axis_name)

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(smapped)(stage_weights, x)
