"""Dry-run core: lower + compile every (arch × shape) cell on a mesh and
extract the §Roofline raw metrics.  Pure library — device-count env setup
lives in ``dryrun.py`` (which must run before any jax import).
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_plan, get_shape
from repro.dist.partition import Partitioner
from repro.launch import hlo_analysis
from repro.launch import specs as S
from repro.models import transformer
from repro.models.config import ModelConfig, shape_applicable
from repro.train import step as tstep
from repro.train.optim import get_optimizer, warmup_cosine


def _sharded_bytes(partitioner: Partitioner, axes_tree, abstract_tree) -> int:
    """Exact per-device resident bytes given the sharding specs."""
    total = 0
    mesh = partitioner.mesh

    def leaf(ax, ab):
        nonlocal total
        spec = partitioner.spec_for(ax, ab.shape)
        denom = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                denom *= mesh.shape[a]
        total += int(np.prod(ab.shape, dtype=np.int64)) * ab.dtype.itemsize // denom

    jax.tree_util.tree_map(
        leaf, axes_tree, abstract_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x
        ),
    )
    return total


def build_cell(arch: str, shape_name: str, mesh, *, fsdp=None, optimizer=None,
               baseline: bool = False):
    """Returns (jitted_fn, example_args, aux) for one cell, un-lowered.

    ``baseline=True`` disables the beyond-paper §Perf optimizations
    (attention sharding constraints) for the A/B tables in EXPERIMENTS.md.
    """
    cfg = get_config(arch)
    plan = get_plan(arch)
    shape = get_shape(shape_name)
    fsdp = plan.fsdp if fsdp is None else fsdp
    opt_name = plan.optimizer if optimizer is None else optimizer

    part = Partitioner(mesh, fsdp=fsdp, constrain_attention=not baseline)
    av, ax = transformer.abstract_params(cfg)
    p_sh = part.tree_shardings(ax, av)
    specs = S.input_specs(cfg, shape)
    aux: dict[str, Any] = {"cfg": cfg, "shape": shape, "partitioner": part}

    if shape.kind == "train":
        opt = get_optimizer(opt_name, warmup_cosine(3e-4, 100, 10_000))
        a_opt = jax.eval_shape(opt.init, av)
        state_sh = {
            "params": p_sh,
            "opt": part.tree_shardings(opt.state_axes(ax), a_opt),
            "step": part.replicated(),
        }
        a_state = {"params": av, "opt": a_opt,
                   "step": jax.ShapeDtypeStruct((), jnp.int32)}
        b_sh = tstep.batch_shardings(part, specs)
        fn = tstep.make_train_step(cfg, opt, part)
        jitted = jax.jit(fn, in_shardings=(state_sh, b_sh), donate_argnums=0)
        args = (a_state, specs)
        aux["state_bytes"] = _sharded_bytes(part, ax, av) + _sharded_bytes(
            part, opt.state_axes(ax), a_opt
        )
    elif shape.kind == "prefill":
        c_sh = tstep.cache_shardings(part, cfg, specs["caches"])
        io_sh = {"inputs": part.batch_spec(specs["inputs"].shape), "caches": c_sh}
        if "positions" in specs:
            io_sh["positions"] = part.batch_spec(specs["positions"].shape, batch_dim=1)

        def fn(params, io):
            return transformer.prefill(
                params, get_config(arch), io["inputs"], io["caches"],
                rope_positions=io.get("positions"), shard=part,
            )

        jitted = jax.jit(fn, in_shardings=(p_sh, io_sh), donate_argnums=1)
        args = ({**av} if isinstance(av, dict) else av, {k: v for k, v in specs.items()})
        args = (av, specs)
        aux["state_bytes"] = _sharded_bytes(part, ax, av)
    else:  # decode
        c_sh = tstep.cache_shardings(part, cfg, specs["caches"])
        io_sh = {
            "inputs": part.batch_spec(specs["inputs"].shape),
            "t": part.replicated(),
            "caches": c_sh,
        }
        if "positions" in specs:
            io_sh["positions"] = part.batch_spec(specs["positions"].shape, batch_dim=1)

        def fn(params, io):
            return transformer.decode_step(
                params, get_config(arch), io["inputs"], io["t"], io["caches"],
                rope_positions=io.get("positions"), shard=part,
            )

        jitted = jax.jit(fn, in_shardings=(p_sh, io_sh), donate_argnums=1)
        args = (av, specs)
        aux["state_bytes"] = _sharded_bytes(part, ax, av)
        aux["cache_bytes"] = _sharded_bytes(
            part, transformer.cache_axes(cfg),
            specs["caches"],
        )
    return jitted, args, aux


def model_flops(cfg: ModelConfig, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train (bwd+fwd), 2·N·D inference."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one token per slot
    return 2.0 * n * tokens


def run_cell(arch: str, shape_name: str, mesh, mesh_label: str, **kw) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    ok, why = shape_applicable(cfg, shape)
    rec: dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_label,
        "chips": int(np.prod(list(mesh.shape.values()))),
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    try:
        t0 = time.perf_counter()
        jitted, args, aux = build_cell(arch, shape_name, mesh, **kw)
        lowered = jitted.lower(*args)
        rec["lower_s"] = round(time.perf_counter() - t0, 1)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 1)

        ca = compiled.cost_analysis()
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        an = hlo_analysis.analyze(hlo)

        rec.update(
            status="ok",
            xla_flops_per_device=float(ca.get("flops", 0.0)),
            xla_bytes_per_device=float(ca.get("bytes accessed", 0.0)),
            flops_per_device=float(an.flops),
            hbm_bytes_per_device=float(an.hbm_bytes),
            collective_bytes_per_device=float(an.collective_bytes),
            collective_by_kind={k: float(v) for k, v in an.coll_by_kind.items()},
            collective_counts={k: int(v) for k, v in an.coll_counts.items()},
            unresolved_whiles=int(an.unresolved_whiles),
            model_flops_global=model_flops(cfg, shape),
            state_bytes_per_device=int(aux.get("state_bytes", 0)),
            cache_bytes_per_device=int(aux.get("cache_bytes", 0)),
            memory_analysis={
                "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
                "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
                "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
            },
            hlo_chars=len(hlo),
        )
    except Exception as e:  # record the failure — dry-run bugs are bugs
        rec.update(status="error", error=f"{type(e).__name__}: {e}"[:2000])
    return rec


# ---------------------------------------------------------------------------
# The paper's own technique on the production mesh (FCA closure step)
# ---------------------------------------------------------------------------


def run_fca_cell(mesh, mesh_label: str, n_objects: int = 1 << 23,
                 n_attrs: int = 4096, batch: int = 4096,
                 baseline: bool = False, reduce_impl: str = "rsag",
                 method: str = "matmul") -> dict:
    """Lower one MRGanter+ map/reduce round at production scale.

    Context: 8.4M objects × 4096 attributes (≫ census-income), objects
    sharded over pod×data×(model folded in as extra object shards is NOT
    done — attributes stay word-packed on-chip).  No MXU dots: the closure
    is VPU/bitwise work, so its roofline is memory+collective-bound.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import bitset
    from repro.dist import collectives
    from repro.kernels import ops

    rec: dict[str, Any] = {
        "arch": "fca-mrganter+", "shape": f"closure_{n_objects}x{n_attrs}_B{batch}",
        "mesh": mesh_label, "chips": int(np.prod(list(mesh.shape.values()))),
    }
    try:
        W = bitset.n_words(n_attrs)
        data_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
        k = int(np.prod([mesh.shape[a] for a in data_axes]))
        rows = jax.ShapeDtypeStruct((n_objects, W), jnp.uint32)
        cands = jax.ShapeDtypeStruct((batch, W), jnp.uint32)
        mask = jnp.asarray(bitset.attr_mask(n_attrs, W))

        if baseline:
            method = "bitwise_naive"

        def shard_body(rows_local, cands):
            if method == "matmul":  # §Perf C2: MXU complement-counting
                lc, ls = ops.closure_matmul(
                    rows_local, cands, n_attrs, n_valid_rows=n_objects // k
                )
                lc = lc & mask
            else:
                lc, ls = ops.batched_closure(
                    rows_local, cands, n_attrs,
                    n_valid_rows=n_objects // k, use_kernel=False,
                    fused_reduce=(method != "bitwise_naive"),
                )
            gc = collectives.and_allreduce(lc, data_axes, impl=reduce_impl)
            gs = jax.lax.psum(ls, data_axes)
            return gc & mask, gs

        smapped = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(P(data_axes, None), P()), out_specs=(P(), P()),
            check_vma=False,
        )
        row_sh = NamedSharding(mesh, P(data_axes, None))
        rep = NamedSharding(mesh, P())
        jitted = jax.jit(smapped, in_shardings=(row_sh, rep))
        t0 = time.perf_counter()
        lowered = jitted.lower(rows, cands)
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t0, 1)
        an = hlo_analysis.analyze(compiled.as_text())
        ca = compiled.cost_analysis()
        rec.update(
            status="ok",
            flops_per_device=float(an.flops),
            xla_flops_per_device=float(ca.get("flops", 0.0)),
            hbm_bytes_per_device=float(an.hbm_bytes),
            collective_bytes_per_device=float(an.collective_bytes),
            collective_by_kind={k_: float(v) for k_, v in an.coll_by_kind.items()},
            context_bytes_per_device=n_objects * W * 4 // k,
            model_flops_global=0.0,  # bitwise VPU work — no MXU dots
        )
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}"[:2000])
    return rec
