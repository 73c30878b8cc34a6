"""Device-resident frontier pipeline ≡ host-loop drivers (the tentpole's
equivalence contract): identical concept sets on the paper datasets and on
randomized contexts, across backends, partition counts and dedupe modes."""

import numpy as np
import pytest

from repro.core import (
    ClosureEngine,
    all_closures_batched,
    bitset,
    mrcbo,
    mrganter,
    mrganter_plus,
)
from repro.core.context import FormalContext
from repro.data import fca_datasets

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # deterministic seeded fallback (repro.testing)
    from repro.testing import given, settings, st

settings.register_profile("frontier", deadline=None, max_examples=12)
settings.load_profile("frontier")


def _sorted_intents(intents):
    """Canonical comparison form: lexicographically sorted packed intents."""
    arr = np.stack([np.asarray(y, dtype=np.uint32) for y in intents])
    view = arr.view([("", np.uint8)] * arr.dtype.itemsize * arr.shape[1])
    return arr[np.argsort(view, axis=0)[:, 0]]


def _assert_equiv(ctx, algo, *, n_parts=3, backend="jnp", **kw):
    eh = ClosureEngine(ctx, n_parts=n_parts, block_n=64, backend=backend)
    ed = ClosureEngine(ctx, n_parts=n_parts, block_n=64, backend=backend)
    rh = algo(ctx, eh, pipeline="host", **kw)
    rd = algo(ctx, ed, pipeline="device", **kw)
    np.testing.assert_array_equal(
        _sorted_intents(rh.intents), _sorted_intents(rd.intents)
    )
    assert rh.n_iterations == rd.n_iterations
    assert rh.n_concepts == rd.n_concepts
    return rh, rd


# -- paper datasets (Table 7, scaled for the CPU budget) ---------------------


@pytest.fixture(scope="module", params=["mushroom", "anon-web", "census-income"])
def paper_ctx(request):
    scale = {"mushroom": 0.004, "anon-web": 0.002, "census-income": 0.0006}
    ctx, _ = fca_datasets.load(request.param, scale=scale[request.param], seed=1)
    return ctx


def test_mrganter_plus_device_matches_host_on_paper_datasets(paper_ctx):
    rh, _ = _assert_equiv(paper_ctx, mrganter_plus)
    # and both match the centralized oracle
    ref = _sorted_intents(all_closures_batched(paper_ctx))
    np.testing.assert_array_equal(_sorted_intents(rh.intents), ref)


def test_mrcbo_device_matches_host_on_paper_datasets(paper_ctx):
    _assert_equiv(paper_ctx, mrcbo)


def test_mrganter_device_matches_host_on_paper_datasets(paper_ctx):
    # strict lectic order must be preserved element-for-element
    eh = ClosureEngine(paper_ctx, n_parts=2, block_n=64, backend="jnp")
    ed = ClosureEngine(paper_ctx, n_parts=2, block_n=64, backend="jnp")
    rh = mrganter(paper_ctx, eh, max_iterations=40, pipeline="host")
    rd = mrganter(paper_ctx, ed, max_iterations=40, pipeline="device")
    assert len(rh.intents) == len(rd.intents)
    for a, b in zip(rh.intents, rd.intents):
        np.testing.assert_array_equal(a, b)


# -- dedupe modes and backends ----------------------------------------------


@pytest.mark.parametrize("dedupe_candidates", [False, True])
@pytest.mark.parametrize("dedupe_closures", [False, True])
def test_mrganter_plus_dedupe_modes(dedupe_candidates, dedupe_closures):
    ctx = FormalContext.synthetic(90, 21, 0.25, seed=4)
    _assert_equiv(
        ctx, mrganter_plus,
        dedupe_candidates=dedupe_candidates, dedupe_closures=dedupe_closures,
    )


@pytest.mark.parametrize("backend", ["kernel", "jnp", "matmul"])
def test_device_pipeline_across_backends(backend):
    ctx = FormalContext.synthetic(70, 18, 0.3, seed=9)
    ref = _sorted_intents(all_closures_batched(ctx))
    eng = ClosureEngine(ctx, n_parts=2, block_n=64, backend=backend)
    res = mrganter_plus(ctx, eng, pipeline="device", dedupe_candidates=True)
    np.testing.assert_array_equal(_sorted_intents(res.intents), ref)


def test_engine_rejects_unknown_backend():
    ctx = FormalContext.synthetic(10, 6, 0.4, seed=0)
    with pytest.raises(ValueError, match="backend"):
        ClosureEngine(ctx, n_parts=1, backend="tpu9000")


def test_driver_rejects_unknown_pipeline():
    ctx = FormalContext.synthetic(10, 6, 0.4, seed=0)
    eng = ClosureEngine(ctx, n_parts=1, backend="jnp")
    with pytest.raises(ValueError, match="pipeline"):
        mrganter_plus(ctx, eng, pipeline="quantum")


# -- transfer accounting: the pipeline's raison d'être -----------------------


def test_device_pipeline_uploads_less_than_host():
    ctx = FormalContext.synthetic(150, 24, 0.2, seed=3)
    eh = ClosureEngine(ctx, n_parts=2, block_n=64, backend="jnp")
    ed = ClosureEngine(ctx, n_parts=2, block_n=64, backend="jnp")
    mrganter_plus(ctx, eh, pipeline="host", dedupe_candidates=True)
    mrganter_plus(ctx, ed, pipeline="device", dedupe_candidates=True)
    # host ships every seed batch up; device ships only novel intents —
    # same O(1) bulk ops per round, a fraction of the bytes
    assert ed.stats.h2d_bytes * 4 < eh.stats.h2d_bytes
    assert ed.stats.h2d_transfers <= ed.stats.rounds + 1
    assert ed.stats.d2h_bytes < eh.stats.d2h_bytes


# -- randomized property sweep ----------------------------------------------


@given(
    st.integers(8, 60), st.integers(3, 22), st.floats(0.1, 0.6),
    st.integers(0, 10_000), st.integers(1, 4), st.booleans(),
)
def test_property_device_equals_host(n, m, density, seed, n_parts, dedupe):
    ctx = FormalContext.synthetic(n, m, density, seed=seed)
    _assert_equiv(
        ctx, mrganter_plus, n_parts=n_parts, dedupe_candidates=dedupe
    )
    _assert_equiv(ctx, mrcbo, n_parts=n_parts)


@pytest.mark.parametrize(
    "n,W,hi,p_valid",
    [(1, 1, 2, 0.5), (7, 3, 3, 0.5), (1000, 4, 4, 0.7),
     (5000, 5, 2**32 - 1, 0.9), (64, 2, 2, 0.0), (64, 2, 2, 1.0)],
)
def test_sort_free_orders_equal_jnp_sorts(n, W, hi, p_valid):
    """The chained single-key sorts and the prefix-sum partition give the
    exact permutations of ``jnp.lexsort`` and the stable ``argsort`` they
    replace — ties, duplicate rows, all-valid and all-invalid batches."""
    import jax.numpy as jnp

    from repro.core.frontier import _lexsort_rows, _partition

    rng = np.random.default_rng(n * W)
    seeds = jnp.asarray(
        rng.integers(0, hi, (n, W), dtype=np.uint64).astype(np.uint32)
    )
    valid = jnp.asarray(rng.random(n) < p_valid)
    keys = tuple(seeds[:, w] for w in reversed(range(W))) + (~valid,)
    np.testing.assert_array_equal(
        _lexsort_rows(seeds, valid), jnp.lexsort(keys)
    )
    count, perm = _partition(valid)
    assert int(count) == int(valid.sum())
    np.testing.assert_array_equal(perm, jnp.argsort(~valid, stable=True))
