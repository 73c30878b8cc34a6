"""Whole runs of a mining cell on four chips, rehearsed on four virtual CPU
devices: the cell mines over a real mesh, reads correct, and reads not
correct when its timed path is broken underneath, once per fault a mesh
cell can have; a cell whose plan would not be a mesh of exactly its chips
is refused before it mines.

The runs need ``--xla_force_host_platform_device_count=4`` before JAX
starts, so one child process drives them all (:func:`_child`) and prints
one line per scenario; each test reads its line.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def _alter_closures(mp):
    """The map kernel's local closures with one attribute flipped, on every
    part: the closures are altered where they are produced."""
    from repro.kernels import frontier as fkern

    call = fkern.map_closure_call

    def broken(*args, **kw):
        lc, ls = call(*args, **kw)
        return lc.at[:, 0].set(lc[:, 0] ^ 1), ls

    mp.setattr(fkern, "map_closure_call", broken)


def _change_keep(mp, change):
    """Every round's filter kernel with its ``keep`` mask put through
    ``change``."""
    from repro.kernels import frontier as fkern

    call = fkern.filter_call

    def broken(*args, **kw):
        out, keep = call(*args, **kw)
        return out, change(keep)

    mp.setattr(fkern, "filter_call", broken)


def _every_other(keep):
    import jax.numpy as jnp

    even = jnp.arange(keep.shape[0]) % 2 == 0
    return keep * even.reshape((-1,) + (1,) * (keep.ndim - 1)).astype(keep.dtype)


def _no_exchange(mp):
    """The AND-allreduce between the chips left out: each chip's local
    closure stands for the whole."""
    from repro.dist import collectives

    mp.setattr(collectives, "and_allreduce", lambda x, *a, **k: x)


# scenario -> (cell, fault)
SCENARIOS = {
    "sound": ("tiny.mine4x1", None),
    "no_exchange": ("tiny.mine4x1", _no_exchange),
    "state_unchanged": ("tiny.mine4x1", lambda mp: _change_keep(mp, lambda k: k * 0)),
    "half_batch": ("tiny.mine4x1", lambda mp: _change_keep(mp, _every_other)),
    "answer_altered": ("tiny.mine4x1", _alter_closures),
    "parts_not_chips": ("tiny.parts2chips4", None),
    # fca.build_plan spans all four devices, the cell asks for two
    "mesh_wider_than_cell": ("tiny.mesh2of4", None),
}
FAULTS = ["no_exchange", "state_unchanged", "half_batch", "answer_altered"]
REFUSED = ["parts_not_chips", "mesh_wider_than_cell"]


def _child(root: str, cache: str) -> None:
    """Run every scenario on the layout at ``root``, one result line each."""
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from conftest import load_run_module
    from harness.layout import Layout
    from repro.launch import fca

    bench_run = load_run_module()
    bench_run.enable_compile_cache = lambda: None
    mine = fca._mine
    for name, (cell, fault) in SCENARIOS.items():
        mines = []
        mp = pytest.MonkeyPatch()
        mp.setattr(fca, "_mine", lambda *a, **k: mines.append(1) or mine(*a, **k))
        if fault is not None:
            fault(mp)
        args = bench_run.parse_args([
            "--workload", cell, "--seed", str(2**31 + 11),
            "--seconds", "0", "--trace", "0",
        ])
        try:
            out = bench_run.measure(Layout(root), args, require_tpu=False)
        except SystemExit as e:
            out = {"exit": str(e)}
        finally:
            mp.undo()
        print(json.dumps({"scenario": name, "mines": len(mines), **out}), flush=True)


@pytest.fixture(scope="module")
def mesh_runs(tiny_layout_module, tmp_path_factory):
    root = tiny_layout_module
    cache = tmp_path_factory.mktemp("jaxcache")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    code = (
        "import sys\n"
        f"sys.path[:0] = [{str(ROOT / 'src')!r}, {str(BENCH)!r}]\n"
        "import test_bench_mesh\n"
        f"test_bench_mesh._child({str(root)!r}, {str(cache)!r})\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, env=env, cwd=ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    return {line["scenario"]: line for line in lines}


def test_four_chip_cell_mines_on_the_mesh_and_reads_correct(mesh_runs):
    out = mesh_runs["sound"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["counters"]["mesh_devices"] == 4
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) >= {"mine_s", "setup_s"}


@pytest.mark.parametrize("fault", FAULTS)
def test_four_chip_fault_reads_incorrect(fault, mesh_runs):
    out = mesh_runs[fault]
    assert out["counters"]["mesh_devices"] == 4
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"] > 0


@pytest.mark.parametrize("scenario", REFUSED)
def test_cell_not_on_a_mesh_of_its_chips_is_refused_before_mining(scenario, mesh_runs):
    out = mesh_runs[scenario]
    assert "exit" in out and "correct" not in out, out
    assert out["mines"] == 0
