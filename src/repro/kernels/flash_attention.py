"""Pallas TPU flash-attention (forward) — the §Roofline next lever.

EXPERIMENTS.md §Roofline identifies the flash softmax chain's elementwise
HBM traffic as the dominant term for most train/prefill cells; this kernel
is the fix on real hardware: scores/probabilities live only in VMEM, HBM
sees q/k/v/out once.

Structure (classic TPU flash forward):

  * grid = (B·H, S/q_blk, T/kv_blk) — kv is the last (sequential) axis, so
    the fp32 running (m, l, acc) scratch persists across kv steps for a
    fixed (head, q-block); initialized at ki == 0, emitted at the last step.
  * GQA without materializing repeated K/V: the k/v BlockSpec index_map
    folds the q-head → kv-head mapping (h // G), so each grid step reads
    the right shared KV block directly from HBM.
  * causal masking, sliding windows, and gemma2-style logit softcaps are
    computed from block coordinates; fully-masked blocks short-circuit via
    ``pl.when`` (scores never computed).

Supports the serving/prefill forward; the training path would need the
matching backward kernel (dq/dk/dv with recomputed probabilities) — left
as the documented next step; the pure-jnp `blockwise_attention` remains
the differentiable path.

Validated, interpreted on CPU (:mod:`repro.kernels.mosaic`), against a
plain-softmax oracle (`ref.py`) over shape/window/softcap sweeps.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.mosaic import pallas_call

NEG_INF = -2.0**30
DEFAULT_Q_BLK = 128
DEFAULT_KV_BLK = 128


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, window, logit_cap, kv_blk, q_blk, seq_len,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_blk
    kv_start = ki * kv_blk
    # Entire block strictly above the diagonal ⇒ skip (causal).
    run = (not causal) or (kv_start <= q_start + q_blk - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # [q_blk, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [kv_blk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [q_blk, kv_blk]
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 0)
        kv_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)
        valid = kv_pos < seq_len
        if causal:
            valid &= kv_pos <= q_pos
        if window is not None:
            valid &= q_pos - kv_pos < window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _emit():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "logit_cap", "q_blk", "kv_blk",
    ),
)
def flash_attention(
    q: jax.Array,  # [B, H, S, hd]
    k: jax.Array,  # [B, KV, T, hd]
    v: jax.Array,  # [B, KV, T, hd]
    *,
    causal: bool = True,
    window: int | None = None,
    logit_cap: float | None = None,
    q_blk: int = DEFAULT_Q_BLK,
    kv_blk: int = DEFAULT_KV_BLK,
) -> jax.Array:
    """Returns [B, H, S, hd].  S/T padded internally to block multiples."""
    B, H, S, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    if H % KV:
        raise ValueError(f"H={H} must be a multiple of KV={KV}")
    G = H // KV
    scale = 1.0 / math.sqrt(hd)

    q_blk = min(q_blk, max(8, S))
    kv_blk = min(kv_blk, max(8, T))
    s_pad, t_pad = -S % q_blk, -T % kv_blk
    if s_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad), (0, 0)))
    if t_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, t_pad), (0, 0)))
    Sp, Tp = S + s_pad, T + t_pad

    qf = q.reshape(B * H, Sp, hd)
    grid = (B * H, Sp // q_blk, Tp // kv_blk)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window, logit_cap=logit_cap,
        kv_blk=kv_blk, q_blk=q_blk, seq_len=T,
    )
    out = pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_blk, hd), lambda bh, qi, ki: (bh, qi, 0)),
            # GQA: fold q-head → kv-head into the index_map (h // G).
            pl.BlockSpec(
                (1, 1, kv_blk, hd),
                lambda bh, qi, ki, H=H, G=G: (bh // H, (bh % H) // G, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, kv_blk, hd),
                lambda bh, qi, ki, H=H, G=G: (bh // H, (bh % H) // G, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, q_blk, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, 1), jnp.float32),
            pltpu.VMEM((q_blk, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(qf, k, v)
    return out.reshape(B, H, Sp, hd)[:, :, :S, :]
