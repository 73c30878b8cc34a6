"""Fused frontier & serving kernels ≡ jnp oracles (interpret mode).

The engine's ``backend="kernel"`` routes every ``_frontier_cache`` step
variant through the fused Pallas kernels (repro.kernels.frontier) and the
query engine's batched serving paths through repro.kernels.serve.  The
jnp builders stay in the tree as bit-exact oracles — every test here is
an equality assertion against them, across drivers, object-shard counts
and candidate-shard counts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClosureEngine, FormalContext, mrcbo, mrganter_plus
from repro.core.closure import batched_closure_np
from repro.dist.shardplan import ShardPlan
from repro.kernels import frontier as fkern
from repro.kernels import serve as skern
from repro.query import ConceptStore, QueryEngine
from repro.query.engine import QueryConfig
from repro.rules import RuleIndex, mine_iceberg
from repro.rules.basis import extract_bases


@pytest.fixture(scope="module")
def ctx():
    return FormalContext.synthetic(60, 24, 0.35, seed=42)


def _sorted_intents(intents):
    arr = np.stack([np.asarray(y, dtype=np.uint32) for y in intents])
    return arr[np.lexsort(arr.T[::-1])]


# ---------------------------------------------------------------------------
# Direct kernel-vs-oracle unit tests
# ---------------------------------------------------------------------------


def _fused_case(N=100, m=40, B=16, seed=7, block_n=64):
    ctx = FormalContext.synthetic(N, m, 0.3, seed=seed)
    cands = FormalContext.synthetic(B, m, 0.1, seed=seed + 1).rows
    rows_p, n_pad = ctx.padded_rows(block_n)
    oc, os_ = batched_closure_np(ctx.rows, cands, ctx.attr_mask())
    mask = jnp.asarray(ctx.attr_mask()[None, :])
    return ctx, jnp.asarray(rows_p), jnp.asarray(cands), mask, n_pad, oc, os_


def test_fused_plain_matches_oracle():
    ctx, rows, cands, mask, n_pad, oc, os_ = _fused_case()
    gc, sup, keep = fkern.fused_closure_call(
        rows, cands, mask, fkern.pack_scalars(cands.shape[0], 0, n_pad, 0),
        block_n=64,
    )
    np.testing.assert_array_equal(np.asarray(gc), oc)
    np.testing.assert_array_equal(np.asarray(sup), os_)
    assert np.asarray(keep).all()


def test_fused_iceberg_matches_oracle():
    ctx, rows, cands, mask, n_pad, oc, os_ = _fused_case()
    for min_sup in (1, 5, ctx.n_objects + 1):
        gc, sup, keep = fkern.fused_closure_call(
            rows, cands, mask,
            fkern.pack_scalars(cands.shape[0], min_sup, n_pad, 0),
            iceberg=True, block_n=64,
        )
        np.testing.assert_array_equal(np.asarray(sup), os_)
        np.testing.assert_array_equal(np.asarray(keep), os_ >= min_sup)
        # closures are computed for every candidate; ``keep`` is the only
        # filter signal — compaction happens downstream of the kernel
        np.testing.assert_array_equal(np.asarray(gc), oc)


def test_fused_validity_window_and_row_off():
    """Candidates at chunk-global index ≥ n_valid are masked out; row_off
    shifts the block's window exactly like the 2-D per-block offset."""
    ctx, rows, cands, mask, n_pad, oc, os_ = _fused_case()
    B = cands.shape[0]
    n_valid = B - 3
    gc, sup, keep = fkern.fused_closure_call(
        rows, cands, mask, fkern.pack_scalars(n_valid, 0, n_pad, 0),
        block_n=64,
    )
    np.testing.assert_array_equal(
        np.asarray(keep), np.arange(B) < n_valid
    )
    np.testing.assert_array_equal(np.asarray(gc), oc)
    # row_off: this block covers chunk rows [off, off+B) of a longer batch
    off = 8
    _, _, keep2 = fkern.fused_closure_call(
        rows, cands, mask, fkern.pack_scalars(n_valid, 0, n_pad, off),
        block_n=64,
    )
    np.testing.assert_array_equal(
        np.asarray(keep2), (np.arange(B) + off) < n_valid
    )


def test_map_plus_filter_equals_fused():
    """Mode B decomposition (map kernel → filter kernel) reproduces the
    fully fused Mode A outputs when run on the whole context."""
    ctx, rows, cands, mask, n_pad, oc, os_ = _fused_case()
    B = cands.shape[0]
    min_sup = 4
    gc_f, sup_f, keep_f = fkern.fused_closure_call(
        rows, cands, mask, fkern.pack_scalars(B, min_sup, n_pad, 0),
        iceberg=True, block_n=64,
    )
    loc, raw = fkern.map_closure_call(rows, cands, mask, block_n=64)
    raw = raw - n_pad  # pad correction rides the reduce in Mode B
    sup_m, keep_m = fkern.filter_call(
        loc, raw, fkern.pack_scalars(B, min_sup, 0, 0), iceberg=True,
    )
    np.testing.assert_array_equal(np.asarray(loc), np.asarray(gc_f))
    np.testing.assert_array_equal(np.asarray(sup_m), np.asarray(sup_f))
    np.testing.assert_array_equal(np.asarray(keep_m), np.asarray(keep_f))


# (N objects, W words, B candidates, variant): N below one 128-lane object
# tile and not a multiple of it, W unrolled (≤ 16, one or two sublane
# groups) and looped (> 16), every bucket end, and a context whose word
# planes take two object tiles (accumulation across the object steps).
LANE_CASES = [
    (100, 1, 8, "plain"),
    (100, 4, 16, "iceberg"),
    (100, 5, 1024, "cbo"),
    (100, 10, 8, "cbo_iceberg"),
    (1000, 1, 16, "cbo_iceberg"),
    (1000, 4, 1024, "plain"),
    (1000, 5, 8, "iceberg"),
    (1000, 10, 16, "cbo"),
    (4097, 1, 1024, "iceberg"),
    (4097, 4, 8, "cbo"),
    (4097, 5, 16, "cbo_iceberg"),
    (4097, 10, 1024, "plain"),
    (1000, 20, 16, "cbo_iceberg"),
    (4097, 17, 8, "plain"),
    (20000, 64, 8, "iceberg"),
]


@pytest.mark.parametrize("N,W,B,variant", LANE_CASES)
def test_fused_lane_layout_matches_oracle(N, W, B, variant):
    """The objects-on-lanes kernel ≡ the numpy oracle: closures, supports
    and keep flags, under an ``n_valid``/``row_off`` window whose tail
    holds all-ones pad candidates, as the frontier pads its buckets."""
    iceberg, cbo, _ = fkern.VARIANTS[variant]
    rng = np.random.default_rng(N * 1000 + W * 10 + B)
    m = 32 * W - 7 if W > 1 else 29
    ctx = FormalContext.synthetic(N, m, 0.3, seed=N + W)
    # subsets of object rows (about 3 attributes), so supports vary
    pick = FormalContext.synthetic(B, m, min(1.0, 8 / m), seed=B + W).rows
    cands = ctx.rows[rng.integers(0, N, B)] & pick
    row_off = 8 if B > 8 else 0
    n_valid = row_off + B - max(3, B // 4)
    cands[n_valid - row_off:] = 0xFFFFFFFF  # the bucket's pad candidates
    rows_p, n_pad = ctx.padded_rows(8)
    oc, os_ = batched_closure_np(ctx.rows, cands, ctx.attr_mask())
    min_sup = int(np.median(os_[: n_valid - row_off])) if iceberg else 0
    want = (np.arange(B) + row_off) < n_valid
    if iceberg:
        want &= os_ >= min_sup
    kw = {}
    if cbo:
        lowrow = FormalContext.synthetic(B, m, 0.2, seed=B + N).rows
        kw = dict(parent=jnp.asarray(cands), lowrow=jnp.asarray(lowrow))
        want &= (((oc ^ cands) & lowrow) == 0).all(axis=1)
    gc, sup, keep = fkern.fused_closure_call(
        jnp.asarray(rows_p), jnp.asarray(cands),
        jnp.asarray(ctx.attr_mask()[None, :]),
        fkern.pack_scalars(n_valid, min_sup, n_pad, row_off),
        iceberg=iceberg, cbo=cbo, block_n=8, **kw,
    )
    np.testing.assert_array_equal(np.asarray(gc), oc)
    np.testing.assert_array_equal(np.asarray(sup), os_)
    np.testing.assert_array_equal(np.asarray(keep), want)
    assert 0 < want.sum() < B  # the filter cut some and kept some


@pytest.mark.parametrize("B,N,W", [
    (8, 104192, 5), (8192, 104192, 5), (8192, 8192, 4), (16, 100, 1),
    (1024, 4104, 10), (8, 20000, 64), (64, 2048, 512),
])
def test_lane_tiles_fit(B, N, W):
    """Tiles divide the batch, cover the objects in 128-lane steps and keep
    the context block and the per-lane scratch inside their budgets."""
    bb, sg, bn = fkern._lane_tiles(B, N, W)
    wp = -(-W // 8) * 8
    assert B % bb == 0 and bb % sg == 0 and sg % 8 == 0
    assert bn % fkern.LANES == 0
    n_tiles = -(-N // bn)
    assert (n_tiles - 1) * bn < N <= n_tiles * bn
    assert n_tiles == 1 or wp * bn * 4 <= fkern.ROWS_BLOCK_BYTES
    assert wp * bb * fkern.LANES * 4 <= fkern.LANE_SCRATCH_BYTES
    if (N, W) == (20000, 64):  # LANE_CASES' multi-tile context
        assert n_tiles > 1


def test_pallas_call_traces_kernel_once():
    """Both platform branches share one kernel trace: the interpreted one
    (which runs here) computes what the kernel says."""
    from jax.experimental import pallas as pl

    from repro.kernels.mosaic import pallas_call

    traced = []

    def kernel(x_ref, o_ref):
        traced.append(1)
        o_ref[...] = x_ref[...] + 1

    call = pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32),
        in_specs=[pl.BlockSpec((8, 128), lambda: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda: (0, 0)),
    )
    x = jnp.arange(8 * 128, dtype=jnp.int32).reshape(8, 128)
    np.testing.assert_array_equal(np.asarray(jax.jit(call)(x)), x + 1)
    assert len(traced) == 1


def test_supports_fused_gate():
    assert fkern.supports_fused("kernel", 4)
    assert fkern.supports_fused("kernel", fkern.MAX_W)
    assert not fkern.supports_fused("kernel", fkern.MAX_W + 1)
    assert not fkern.supports_fused("jnp", 4)
    assert not fkern.supports_fused("matmul", 4)


# ---------------------------------------------------------------------------
# Pipeline property tests: every driver/variant, 1-D and 2-D plans
# ---------------------------------------------------------------------------

DRIVERS = [
    ("mrganter+", lambda c, e: mrganter_plus(c, e, pipeline="device")),
    ("mrganter+dc", lambda c, e: mrganter_plus(
        c, e, pipeline="device", dedupe_candidates=True)),
    ("mrganter+dc+dz", lambda c, e: mrganter_plus(
        c, e, pipeline="device", dedupe_candidates=True,
        dedupe_closures=True)),
    ("mrganter+iceberg", lambda c, e: mrganter_plus(
        c, e, pipeline="device", dedupe_candidates=True, min_support=6)),
    ("mrcbo", lambda c, e: mrcbo(c, e, pipeline="device")),
    ("mrcbo+iceberg", lambda c, e: mrcbo(
        c, e, pipeline="device", min_support=6)),
]


@pytest.mark.parametrize("name,run", DRIVERS, ids=[d[0] for d in DRIVERS])
@pytest.mark.parametrize("n_parts,cand_parts", [
    (1, 1), (2, 1), (1, 2), (2, 2),
])
def test_kernel_backend_equals_jnp(ctx, name, run, n_parts, cand_parts):
    results = {}
    for backend in ("kernel", "jnp"):
        plan = ShardPlan.simulated(
            n_parts, cand_parts=cand_parts, block_n=64
        )
        eng = ClosureEngine(ctx, plan=plan, backend=backend)
        results[backend] = run(ctx, eng)
    rk, rj = results["kernel"], results["jnp"]
    assert rk.n_concepts == rj.n_concepts
    assert rk.n_iterations == rj.n_iterations
    np.testing.assert_array_equal(
        _sorted_intents(rk.intents), _sorted_intents(rj.intents)
    )


# ---------------------------------------------------------------------------
# Serving kernels: QueryEngine backend="kernel" ≡ backend="jnp"
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(ctx):
    plan = ShardPlan.simulated(2, block_n=64)
    eng = ClosureEngine(ctx, plan=plan, backend="jnp")
    res = mine_iceberg(ctx, eng, min_support=4)
    out = {}
    for backend in ("kernel", "jnp"):
        store = ConceptStore.build(
            ctx, res.intents, plan=ShardPlan.simulated(2, block_n=64)
        )
        out[backend] = QueryEngine(
            store, QueryConfig(slots=8, backend=backend)
        )
    return out


def _queries(ctx, n=11, seed=0):
    rng = np.random.default_rng(seed)
    return ctx.rows[rng.integers(0, ctx.n_objects, n)]


@pytest.mark.parametrize("k", [1, 3, 7])
def test_serve_topk_kernel_equals_jnp(ctx, served, k):
    qs = _queries(ctx)
    ik, vk = served["kernel"].topk_batch(qs, k=k)
    ij, vj = served["jnp"].topk_batch(qs, k=k)
    np.testing.assert_array_equal(np.asarray(ik), np.asarray(ij))
    np.testing.assert_array_equal(np.asarray(vk), np.asarray(vj))


def test_serve_closure_batch_kernel_equals_jnp(ctx, served):
    qs = _queries(ctx, n=9, seed=3)
    for a, b in zip(
        served["kernel"].closure_batch(qs), served["jnp"].closure_batch(qs)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rank_by,k", [
    ("confidence", 1), ("confidence", 4), ("lift", 4),
])
def test_serve_rules_kernel_equals_jnp(ctx, served, rank_by, k):
    store = served["jnp"].store
    basis = extract_bases(store, min_conf=0.4)
    index = RuleIndex.build(basis, plan=ShardPlan.simulated(2, block_n=64))
    qs = _queries(ctx, n=6, seed=5)
    outs = {
        b: served[b].rules_batch(
            index, qs, k=k, min_conf=0.4, rank_by=rank_by
        )
        for b in ("kernel", "jnp")
    }
    for a, b in zip(outs["kernel"], outs["jnp"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
