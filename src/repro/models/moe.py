"""Mixture-of-Experts with sort-based capacity dispatch (EP over 'model').

Tokens are routed top-k, sorted by expert id, and scattered into a
``[E, C, d]`` buffer (capacity ``C = N·k/E·capacity_factor``, overflow
dropped — standard capacity-based MoE).  Expert FFNs run as one grouped
einsum over the expert-sharded buffer; under GSPMD the token→expert
scatter/gather lowers to the all-to-all pattern of expert parallelism.

Supports the two assigned MoE flavours:
  * arctic-480b   — 128 experts top-2 with a *dense residual* FFN in
    parallel (the dense branch lives in the transformer block);
  * llama4-scout  — 16 experts top-1 plus an always-on *shared expert*.

Returns a load-balance auxiliary loss (Switch-style) for training.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.config import ModelConfig


def init_moe(pb: layers.ParamBuilder, cfg: ModelConfig):
    e = cfg.moe
    d, f = cfg.d_model, e.d_ff_expert
    p = {
        "router": pb.dense((d, e.n_experts), ("embed", "experts"), scale=0.02),
        "w_gate": pb.dense((e.n_experts, d, f), ("experts", "embed", "ffn"), fan_in=d),
        "w_up": pb.dense((e.n_experts, d, f), ("experts", "embed", "ffn"), fan_in=d),
        "w_down": pb.dense((e.n_experts, f, d), ("experts", "ffn", "embed"), fan_in=f),
    }
    if e.shared_expert:
        p["shared"] = layers.init_mlp(pb, d, f, "swiglu")
    return p


def _moe_ep_shardmap(params, xf, top_w, top_i, cfg: ModelConfig, shard, exact: bool):
    """§Perf B2: explicit expert parallelism over the 'model' axis.

    GSPMD lowers the global scatter/gather dispatch as buffer-sized
    all-reduces over 'model' (~60 GB/layer/device on arctic — EXPERIMENTS.md
    §Perf).  Here each model shard owns E/tp experts; tokens are already
    model-replicated between layers (Megatron-style activations), so
    dispatch is local masking and the combine is ONE psum of [N_loc, d] —
    the same cost as a dense-FFN TP all-reduce.
    """
    from jax import lax
    from jax.sharding import PartitionSpec as P

    e = cfg.moe
    mesh = shard.mesh
    tp = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    E, k = e.n_experts, e.top_k
    E_loc = E // tp
    N = xf.shape[0]
    d = xf.shape[1]
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    N_loc = N // dp
    C = N_loc * k if exact else max(1, int(round(N_loc * k / E * e.capacity_factor)))

    def body(xf_l, top_w_l, top_i_l, wg, wu, wd):
        r = lax.axis_index("model")
        eid = top_i_l.reshape(-1)  # [N_loc·k]
        order = jnp.argsort(eid, stable=True)
        eid_s = eid[order]
        tok_s = order // k
        w_s = top_w_l.reshape(-1)[order]
        counts = jnp.zeros((E,), jnp.int32).at[eid].add(1)
        starts = jnp.cumsum(counts) - counts
        slot = jnp.arange(N_loc * k, dtype=jnp.int32) - starts[eid_s]
        # Keep only this shard's experts; OOB indices drop in the scatter.
        eidx = eid_s - r * E_loc
        oob = (eidx < 0) | (eidx >= E_loc) | (slot >= C)
        eidx = jnp.where(oob, E_loc, eidx)  # force-drop
        buf = jnp.zeros((E_loc, C, d), xf_l.dtype)
        buf = buf.at[eidx, slot].set(xf_l[tok_s], mode="drop")
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        h = g * jnp.einsum("ecd,edf->ecf", buf, wu)
        out_buf = jnp.einsum("ecf,efd->ecd", h, wd)
        contrib = out_buf.at[eidx, slot].get(mode="fill", fill_value=0)
        contrib = contrib * w_s[:, None].astype(xf_l.dtype)
        y_r = jnp.zeros((N_loc, d), xf_l.dtype).at[tok_s].add(contrib)
        return lax.psum(y_r, "model")

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp_axes, None), P(dp_axes, None), P(dp_axes, None),
            P("model", None, None), P("model", None, None), P("model", None, None),
        ),
        out_specs=P(dp_axes, None),
        check_vma=False,
    )(xf, top_w, top_i, params["w_gate"], params["w_up"], params["w_down"])


def moe_fwd(
    params, x: jax.Array, cfg: ModelConfig, shard=None, exact: bool = False
) -> tuple[jax.Array, jax.Array]:
    """x [B, S, d] → (y [B, S, d], aux_loss scalar fp32).

    ``exact=True`` sets capacity C = N·k so no token can be dropped —
    used for decode (tiny N) where capacity-dropping would corrupt single
    requests; train/prefill keep the standard capacity factor.
    """
    e = cfg.moe
    B, S, d = x.shape
    N = B * S
    E, k = e.n_experts, e.top_k
    xf = x.reshape(N, d)

    logits = (xf @ params["router"].astype(jnp.float32)
              if params["router"].dtype != jnp.float32
              else xf.astype(jnp.float32) @ params["router"])  # [N, E] fp32
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)  # [N, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance aux: E · Σ_e frac_tokens_e · mean_prob_e.
    frac = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0) / (N * k)
    aux = E * jnp.sum(frac * probs.mean(axis=0))

    # §Perf B2: explicit-EP path when a mesh with a dividing 'model' axis
    # is active (production path); pjit scatter/gather otherwise (baseline,
    # and the single-device smoke-test path).  Decode (exact=True) keeps
    # the pjit path: with one token per slot the EP in_specs would
    # re-gather FSDP expert weights every step (~60 GB/token on arctic —
    # measured 0.37 s → 2.5 s regression before this guard).
    if (
        not exact
        and shard is not None
        and getattr(shard, "mesh", None) is not None
        and getattr(shard, "constrain_attention", True)
        and "model" in shard.mesh.shape
        and E % shard.mesh.shape["model"] == 0
    ):
        y = _moe_ep_shardmap(params, xf, top_w, top_i, cfg, shard, exact)
        if e.shared_expert:
            y = y + layers.mlp_fwd(params["shared"], xf, "swiglu")
        return y.reshape(B, S, d), aux

    # Sort token-expert assignments by expert id.
    eid = top_i.reshape(-1)  # [N*k]
    order = jnp.argsort(eid, stable=True)
    eid_s = eid[order]
    tok_s = order // k
    w_s = top_w.reshape(-1)[order]

    counts = jnp.zeros((E,), jnp.int32).at[eid].add(1)
    starts = jnp.cumsum(counts) - counts
    slot = jnp.arange(N * k, dtype=jnp.int32) - starts[eid_s]

    C = N * k if exact else max(1, int(round(N * k / E * e.capacity_factor)))
    # Scatter tokens into the expert buffer; slot >= C drops (capacity).
    buf = jnp.zeros((E, C, d), x.dtype)
    buf = buf.at[eid_s, slot].set(xf[tok_s], mode="drop")
    if shard is not None:
        buf = shard(buf, "experts", None, None)

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]))
    h = g * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    if shard is not None and getattr(shard, "constrain_attention", True):
        # §Perf B1: reshard expert-major → d-major BEFORE the combine
        # gather.  With ``out_buf`` expert-sharded, GSPMD lowers the
        # [N·k, d] gather/scatter as a full all-reduce over 'model'
        # (~60 GB/layer/device); d-sharding turns both into local ops +
        # one small all-to-all (measured in EXPERIMENTS.md §Perf).
        out_buf = shard(out_buf, None, None, "moe_d")

    gathered = out_buf.at[eid_s, slot].get(mode="fill", fill_value=0)  # [N*k, d]
    y = jnp.zeros((N, d), x.dtype).at[tok_s].add(gathered * w_s[:, None].astype(x.dtype))
    if shard is not None and getattr(shard, "constrain_attention", True):
        y = shard(y, None, "moe_d")

    if e.shared_expert:
        y = y + layers.mlp_fwd(params["shared"], xf, "swiglu")
    return y.reshape(B, S, d), aux
