"""chip_smoke.py rehearsed on CPU at a tiny scale, plus the launcher
pieces it relies on (compile-cache placement, concept-set digests).

The script refuses to run without a TPU; these tests step around that
check by replacing ``device_info``, never through an option of the script.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from repro.launch import fca  # noqa: E402


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"), **extra)
    env.pop("XLA_FLAGS", None)
    return env


def _cpu_device(count=1):
    return lambda: {"platform": "cpu", "kind": "cpu", "count": count}


def test_one_chip_phases_on_cpu(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "device_info", _cpu_device())
    monkeypatch.setattr(chip_smoke, "SCALE", 0.01)
    # the tiny context's rule basis at 0.2 is past the rules kernel's
    # VMEM bound; 0.3 keeps the rules phase on the kernel
    monkeypatch.setattr(chip_smoke, "RULES_SUPPORT", "0.3")
    monkeypatch.setattr(chip_smoke, "_ORACLE", {})
    assert chip_smoke.main([]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert lines[-1] == {"ok": True, "device": _cpu_device()()}
    phases = {x["phase"]: x for x in lines[:-1]}
    assert list(phases) == [
        "mine-mrganter+", "mine-mrcbo", "mine-census", "serve", "rules",
        "serve-kernels",
    ]
    for name in ("mine-mrganter+", "mine-mrcbo", "mine-census", "serve",
                 "rules"):
        p = phases[name]
        assert p["concepts_digest"] == p["oracle_digest"], name
        assert p["fused_steps"] > 0, name
    assert phases["serve"]["serve_paths"] == {"topk/kernel": 1}
    assert set(phases["rules"]["serve_paths"]) == {"rules/kernel"}
    assert phases["serve-kernels"]["identical"] is True


def test_four_device_mesh_path_on_cpu():
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        "import jax, chip_smoke\n"
        "chip_smoke.device_info = lambda: {'platform': 'cpu', 'kind': 'cpu',"
        " 'count': len(jax.devices())}\n"
        "chip_smoke.SCALE = 0.01\n"
        "chip_smoke.main(['--chips', '4'])\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=_env(JAX_PLATFORMS="cpu"), cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()]
    assert lines[-1]["device"]["count"] == 4
    for line in lines[:-1]:
        assert line["concepts_digest"] == line["simulated_digest"]
        assert line["rows_devices"] == 4 and line["output_devices"] == [4, 4]
    assert [x["phase"] for x in lines[:-1]] == ["mesh-4x1", "mesh-2x2"]


def test_refuses_to_run_without_tpu(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=_env(JAX_PLATFORMS="cpu"), cwd=REPO,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    # alone in a directory, without the repository around it
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, env={"PATH": os.environ["PATH"], "JAX_PLATFORMS": "cpu"},
        cwd=tmp_path,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def _cache_dir_after_compile(env, tmp_path):
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch import fca\n"
        "d = fca.enable_compile_cache()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
        "print(d)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=env, cwd=tmp_path,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout.strip()


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    where = tmp_path / "cache"
    got = _cache_dir_after_compile(
        _env(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(where)),
        tmp_path,
    )
    assert got == str(where)
    assert any(where.iterdir())  # the compile was written there


def test_compile_cache_defaults_to_fixed_checkout_path(tmp_path):
    env = _env(JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    got = _cache_dir_after_compile(env, tmp_path)
    assert got == str(fca.CACHE_DIR)
    assert fca.CACHE_DIR.parent == fca.pathlib.Path(REPO)
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_concepts_digest_is_order_free():
    rng = np.random.default_rng(0)
    intents = [rng.integers(0, 2**32, 4, dtype=np.uint32) for _ in range(9)]
    d = fca.concepts_digest(intents)
    assert fca.concepts_digest(intents[::-1]) == d
    assert fca.concepts_digest(intents + intents[:2]) == d  # a set
    assert fca.concepts_digest(intents[1:]) != d
    assert fca.concepts_digest([]) == fca.concepts_digest([])
