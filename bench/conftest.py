"""Fixtures of the benchmark's CPU tests: a tiny copy of the layout.

The copy holds the repository's BENCHMARK.json and ``bench/`` files plus a
small configuration and one mining and one serving traffic file of its
own, with cells for them, so a whole run can be driven on the CPU in
seconds.  The harness under test is always the repository's own code.
"""

import importlib.util
import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(ROOT / "src"), str(BENCH)]

TINY_CONFIG = {
    "name": "tiny", "source": "test", "objects": 320, "attributes": 24,
    "density": 0.2, "generator_seed": 0,
    "guarantees": "exact iceberg lattice", "assumed": {}, "reduced": [],
}
TINY_TRAFFIC = {
    "tiny_mine": {
        "kind": "mine", "algorithm": "mrganter+", "local_prune": True,
        "parts": 1, "min_support": 0.05, "backend": "jnp", "rounds": "sync",
    },
    "tiny_cbo_kernel": {
        "kind": "mine", "algorithm": "mrcbo", "parts": 1, "min_support": 0.05,
        "backend": "kernel", "rounds": "sync",
    },
    "tiny_mine_2parts": {
        "kind": "mine", "algorithm": "mrganter+", "local_prune": True,
        "parts": 2, "min_support": 0.01, "backend": "jnp", "rounds": "sync",
    },
    "tiny_mine_4x1": {
        "kind": "mine", "algorithm": "mrganter+", "local_prune": True,
        "parts": 4, "min_support": 0.01, "backend": "kernel", "rounds": "sync",
    },
    "tiny_serve": {
        "kind": "serve", "store": {"algorithm": "mrcbo", "min_support": 0.1},
        "mix": {"closure": 0.6, "topk": 0.3, "lookup": 0.1},
        "arrival": {"process": "poisson"}, "qps": 400, "slots": 16,
        "max_wait_ms": 2.0, "queue_depth": 512, "topk_k": 5, "backend": "jnp",
    },
}


def load_run_module():
    """``bench/run.py`` as a module (it is a script, not a package member)."""
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def add_cell(
    root: pathlib.Path, name: str, config: str, traffic: str, chips: int = 1
) -> None:
    """Add a cell to the copy's BENCHMARK.json, listed by every metric
    that lists the cells of its kind."""
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    kind = json.loads((root / "bench" / "traffic" / f"{traffic}.json").read_text())["kind"]
    like = "mushroom.mine" if kind == "mine" else "mushroom.serve"
    bench["workloads"].append(
        {"name": name, "config": config, "traffic": traffic, "chips": chips, "why": "test"}
    )
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    path.write_text(json.dumps(bench))


def make_tiny_layout(root: pathlib.Path) -> pathlib.Path:
    """A copy of the layout at ``root`` with the tiny configuration,
    traffic and cells ``tiny.mine`` (MRGanter+ on the jnp steps),
    ``tiny.cbo`` (MRCbo on the fused Pallas kernels, as ``mushroom.mine``
    runs), ``tiny.mine2`` (two simulated parts, at a threshold low enough
    that one part's closure differs from the whole's), ``tiny.mine4x1``
    (four parts on four chips, on the kernels, as ``census.mine.4chip``
    runs), ``tiny.serve``, and two cells a run refuses: ``tiny.parts2chips4``
    (two parts on four chips) and ``tiny.mesh2of4`` (two chips, meant for a
    host with more)."""
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    for name, mix in TINY_TRAFFIC.items():
        (root / "bench" / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny", "source": "test", "file": "bench/configs/tiny.json",
        "reduced": [], "why": "test",
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    add_cell(root, "tiny.mine", "tiny", "tiny_mine")
    add_cell(root, "tiny.cbo", "tiny", "tiny_cbo_kernel")
    add_cell(root, "tiny.mine2", "tiny", "tiny_mine_2parts")
    add_cell(root, "tiny.mine4x1", "tiny", "tiny_mine_4x1", chips=4)
    add_cell(root, "tiny.serve", "tiny", "tiny_serve")
    add_cell(root, "tiny.parts2chips4", "tiny", "tiny_mine_2parts", chips=4)
    add_cell(root, "tiny.mesh2of4", "tiny", "tiny_mine_2parts", chips=2)
    return root


@pytest.fixture
def tiny_layout(tmp_path):
    """:func:`make_tiny_layout` in a temporary directory."""
    return make_tiny_layout(tmp_path / "layout")


@pytest.fixture(scope="module")
def tiny_layout_module(tmp_path_factory):
    """:func:`make_tiny_layout` shared by one test module."""
    return make_tiny_layout(tmp_path_factory.mktemp("tiny") / "layout")


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """JAX's persistent compile cache in a temporary directory for one test
    module (each mine builds a fresh engine, which would otherwise compile
    its steps anew), restored afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path_factory.mktemp("jaxcache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
    cc.reset_cache()


@pytest.fixture
def bench_run(monkeypatch, compile_cache):
    """``bench/run.py`` with its compile-cache set-up left to the fixture."""
    module = load_run_module()
    monkeypatch.setattr(module, "enable_compile_cache", lambda: None)
    return module


@pytest.fixture
def drive(bench_run):
    """``drive(root, workload, seconds=, trace=, seed=)``: one run of a
    cell of the layout at ``root`` on the CPU → its result object."""
    from harness.layout import Layout

    def run(root, workload, *, seconds=0.0, trace=0, seed=2**31 + 7):
        args = bench_run.parse_args([
            "--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace),
        ])
        return bench_run.measure(Layout(root), args, require_tpu=False)

    return run
