"""Compile rehearsal: the main path's Pallas kernels compiled for one TPU
v5e chip, described and not attached, at real widths.

Nothing runs, so these say nothing about results or times; they catch
what the interpreter on CPU cannot: a slice or reduction Mosaic refuses,
and more VMEM than a kernel may use.  The topology is described inside a
fixture (never while a module is imported), and the persistent compile
cache is off around the compiles: an entry compiled for a described chip
cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import frontier as fkern
from repro.kernels import serve as skern

# (context rows, words): full Table-7 mushroom and census-income, padded
# to the 256-row block — 125 and 133 attributes are 4 and 5 words.
MINING_SHAPES = [(8192, 4), (104192, 5)]
BATCH = 1024  # a frontier chunk of candidates


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; return the compiled text."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    # the Mosaic kernel, not the interpreter's XLA ops, is what compiled
    assert "tpu_custom_call" in text
    return text


def _spec(sharding, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("N,W", MINING_SHAPES)
@pytest.mark.parametrize("variant", ["plain", "cbo_iceberg"])
def test_fused_closure_compiles(one_chip, N, W, variant):
    iceberg, cbo, _ = fkern.VARIANTS[variant]
    s = lambda shape, dt=jnp.uint32: _spec(one_chip, shape, dt)
    extra = (s((BATCH, W)), s((BATCH, W))) if cbo else ()

    def step(rows, cands, mask, scalars, *lineage):
        kw = dict(parent=lineage[0], lowrow=lineage[1]) if cbo else {}
        return fkern.fused_closure_call(
            rows, cands, mask, scalars, iceberg=iceberg, cbo=cbo, **kw
        )

    _compile(step, s((N, W)), s((BATCH, W)), s((1, W)),
             s((fkern.N_SCALARS,), jnp.int32), *extra)


@pytest.mark.parametrize("N,W", MINING_SHAPES)
@pytest.mark.parametrize("B", [8, 8192])
def test_fused_closure_compiles_at_bucket_ends(one_chip, N, W, B):
    """The smallest frontier bucket and the batch the mining cells run:
    Mosaic lowers the objects-on-lanes layout and its VMEM fits at both."""
    s = lambda shape, dt=jnp.uint32: _spec(one_chip, shape, dt)

    def step(rows, cands, mask, scalars, parent, lowrow):
        return fkern.fused_closure_call(
            rows, cands, mask, scalars, parent=parent, lowrow=lowrow,
            iceberg=True, cbo=True,
        )

    _compile(step, s((N, W)), s((B, W)), s((1, W)),
             s((fkern.N_SCALARS,), jnp.int32), s((B, W)), s((B, W)))


@pytest.mark.parametrize("N,W", MINING_SHAPES)
def test_map_closure_compiles_under_simulated_vmap(one_chip, N, W):
    parts = 8  # the simulated plan's named-axis vmap over object shards
    local = N // parts // fkern.DEFAULT_N_BLK * fkern.DEFAULT_N_BLK

    def step(rows, cands, mask):
        return jax.vmap(
            lambda r: fkern.map_closure_call(r, cands, mask), axis_name="data"
        )(rows)

    _compile(step, _spec(one_chip, (parts, local, W)),
             _spec(one_chip, (BATCH, W)), _spec(one_chip, (1, W)))


@pytest.mark.parametrize("W", [4, 5])
def test_filter_compiles(one_chip, W):
    s = lambda shape, dt=jnp.uint32: _spec(one_chip, shape, dt)

    def step(gc, gs, scalars, parent, lowrow):
        return fkern.filter_call(gc, gs, scalars, parent=parent, lowrow=lowrow,
                                 iceberg=True, cbo=True)

    _compile(step, s((BATCH, W)), s((BATCH,), jnp.int32),
             s((fkern.N_SCALARS,), jnp.int32), s((BATCH, W)), s((BATCH, W)))


def _bound(kind, W):
    """Largest table ``supports_serve`` admits for ``kind`` at width W."""
    n = 8
    while skern.supports_serve("kernel", kind, n + 8, W, 64):
        n += 8
    return n


def _contains_topk(one_chip, C, W, k=5):
    s = lambda shape, dt=jnp.uint32: _spec(one_chip, shape, dt)
    return _compile(
        lambda g, t, sup, n: skern.contains_topk_call(g, t, sup, n, k=k),
        s((64, W)), s((C, W)), s((C,), jnp.int32), s((), jnp.int32),
    )


@pytest.mark.parametrize("W", [4, 129])
def test_contains_topk_compiles_at_vmem_bound(one_chip, W):
    C = _bound("topk", W)
    assert C >= 512 if W <= 128 else C >= 256
    _contains_topk(one_chip, C, W, k=16)


def test_rules_topk_compiles_at_vmem_bound(one_chip):
    W, R = 5, _bound("rules", 5)
    s = lambda shape, dt=jnp.uint32: _spec(one_chip, shape, dt)
    f32, i32 = jnp.float32, jnp.int32
    _compile(
        lambda p, a, c, m, r, n, q, mc: skern.rules_topk_call(
            p, a, c, m, r, n, q, mc, k=5
        ),
        s((R, W)), s((R, W)), s((R,), f32), s((R,), f32), s((R,), i32),
        s((), i32), s((64, W)), s((), f32),
    )


@pytest.mark.parametrize("kind", ["topk", "rules"])
def test_serve_gate_refuses_table_past_bound(one_chip, kind):
    C = _bound(kind, 4)
    assert skern.supports_serve("kernel", kind, C, 4, 64)
    assert not skern.supports_serve("kernel", kind, C + 1, 4, 64)
    assert not skern.supports_serve("jnp", kind, 8, 4, 64)


def test_table_past_bound_overflows_vmem(one_chip):
    """The other side of the bound: the next power-of-two table (the
    store's cap above it) does not fit the kernel's scoped VMEM."""
    C = 2048
    assert not skern.supports_serve("kernel", "topk", C, 4, 64)
    with pytest.raises(Exception, match="vmem"):
        _contains_topk(one_chip, C, 4)
