"""The benchmark's files: BENCHMARK.json keeps to its contract, every
configuration, traffic and metric file it names loads and names only
known fields, a new traffic file is found by its name alone, and the
command refuses to run without a TPU."""

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from harness import mine
from harness.layout import Layout, LayoutError

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
RUNS_PER_CHECK, CELLS_MAX, SPARE_S, CHECK_S = 14, 24, 1200, 43200


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and not re.search(r"[\n\t]", text)


def test_top_level_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word or word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in BENCH["paths"]), word
            assert (ROOT / word).is_file()
    r = BENCH["run_seconds"]
    assert isinstance(r, int) and 1 <= r <= 51
    assert (2 + RUNS_PER_CHECK * CELLS_MAX) * (r + 60) + CELLS_MAX * 180 + SPARE_S <= CHECK_S


def test_configs_and_cells():
    configs, cells = BENCH["configs"], BENCH["workloads"]
    assert 1 <= len(configs) <= 24 and 1 <= len(cells) <= 24
    names = [c["name"] for c in configs]
    assert len(set(names)) == len(names)
    assert len({c["file"] for c in configs}) == len(configs)
    for c in configs:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in cells)
    pairs = set()
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["config"] in names and w["chips"] in (1, 4)
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(cells) == len({w["name"] for w in cells})
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)


def test_metrics():
    e2e, layers = BENCH["end_to_end"], BENCH["per_layer"]
    cells = {w["name"] for w in BENCH["workloads"]}
    names = [m["name"] for m in e2e + layers]
    assert len(set(names)) == len(names)
    assert 1 <= len(e2e) <= 16 and 1 <= len(layers) <= 128
    assert "setup_s" in {m["name"] for m in e2e}
    for m in e2e + layers:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e_cells = {m["name"]: set(m.get("workloads", cells)) for m in e2e}
    for m in layers:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in e2e_cells
        assert set(m.get("workloads", cells)) <= e2e_cells[m["moves"]], m["name"]
    for cell in cells:
        reported = {n for n, cs in e2e_cells.items() if cell in cs}
        assert "setup_s" in reported and len(reported) >= 2, cell
        assert any(cell in m.get("workloads", cells) for m in layers), cell


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_load(cell):
    layout = Layout(ROOT)
    w = layout.cell(cell)
    cfg = layout.config(w["config"])
    assert cfg["name"] == w["config"]
    assert layout.traffic(w["traffic"])["kind"] in ("mine", "serve")
    for traced in (False, True):
        for m in layout.metrics(cell, traced=traced):
            assert callable(layout.reader(m["name"]))


def test_peaks_table_keyed_by_device_kind():
    layout = Layout(ROOT)
    assert layout.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(LayoutError):
        layout.peaks("TPU v9 imaginary")


# the ``fca mine`` arguments of each one-chip mining traffic file, with the
# min support at 7, as the harness passed them before it could run a mesh
ONE_CHIP_ARGV = {
    "mrcbo_s05": ["mine", "--algorithm", "mrcbo", "--parts", "1", "--backend",
                  "kernel", "--rounds", "sync", "--min-support", "7"],
    "mrganterplus_s02": ["mine", "--algorithm", "mrganter+", "--parts", "1",
                         "--backend", "kernel", "--rounds", "sync",
                         "--min-support", "7", "--local-prune"],
}


@pytest.mark.parametrize("traffic", sorted(ONE_CHIP_ARGV))
def test_one_chip_mining_argv_is_unchanged(traffic):
    assert mine.argv(Layout(ROOT).traffic(traffic), 7, chips=1) == ONE_CHIP_ARGV[traffic]


def test_four_chip_mining_argv_runs_a_mesh():
    layout = Layout(ROOT)
    want = ONE_CHIP_ARGV["mrganterplus_s02"].copy()
    want[want.index("--parts") + 1] = "4"
    assert mine.argv(layout.traffic("mrganterplus_s02_4x1"), 7, chips=4) == want + ["--mesh"]
    with pytest.raises(SystemExit, match="parts 1"):
        mine.argv(layout.traffic("mrganterplus_s02"), 7, chips=4)


def test_new_traffic_file_is_found_by_name(tiny_layout):
    """A mix added as a file (and a cell naming it) needs no code."""
    layout = Layout(tiny_layout)
    mix = {"kind": "mine", "algorithm": "mrcbo", "parts": 1, "min_support": 0.3,
           "backend": "jnp", "rounds": "sync"}
    (tiny_layout / "bench" / "traffic" / "brand_new.json").write_text(json.dumps(mix))
    assert layout.traffic("brand_new") == mix


def test_unknown_field_is_refused(tiny_layout):
    path = tiny_layout / "bench" / "traffic" / "tiny_mine.json"
    mix = json.loads(path.read_text()) | {"min_suport": 0.1}
    path.write_text(json.dumps(mix))
    with pytest.raises(LayoutError, match="min_suport"):
        Layout(tiny_layout).traffic("tiny_mine")


@pytest.mark.parametrize("bare", [False, True], ids=["checkout", "bench_only"])
def test_command_refuses_without_a_tpu(bare, tmp_path):
    """No TPU (JAX held to the CPU), or a directory with only the
    benchmark's files: a non-zero exit and no result line."""
    root = ROOT
    if bare:
        root = tmp_path / "bare"
        shutil.copytree(ROOT / "bench", root / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(ROOT / "BENCHMARK.json", root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", "mushroom.mine",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
