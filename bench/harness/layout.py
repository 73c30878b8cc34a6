"""Where the benchmark's files live, found by the names in BENCHMARK.json.

    BENCHMARK.json             cells, configurations, metrics
    bench/configs/<name>.json  a configuration (the entry's ``file``)
    bench/traffic/<name>.json  a traffic mix: a mining job or a serving mix
    bench/metrics/<name>.py    one metric's reader: ``read(run) -> float | None``
    bench/peaks.json           device peaks by ``device_kind``

A new cell, configuration, traffic mix or metric is a new file here and a
new entry in BENCHMARK.json; no code of the harness names one.  Every file
is checked for fields this harness does not know, so a misspelt key fails
the run instead of being ignored.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

CONFIG_FIELDS = {
    "name", "source", "objects", "attributes", "density", "generator_seed",
    "guarantees", "assumed", "reduced",
}
TRAFFIC_FIELDS = {
    "mine": {
        "kind", "algorithm", "parts", "min_support", "backend", "rounds",
        "local_prune",
    },
    "serve": {
        "kind", "store", "mix", "arrival", "qps", "slots", "max_wait_ms",
        "queue_depth", "topk_k", "backend",
    },
}
PEAK_FIELDS = {"source", "hbm_bytes_per_s", "bf16_flops_per_s", "hbm_bytes"}


class LayoutError(ValueError):
    """A benchmark file is missing, or names a field the harness does not know."""


def _load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise LayoutError(f"missing benchmark file {path}")
    with open(path) as fh:
        return json.load(fh)


def _check_fields(what: str, obj: dict, known: set) -> None:
    unknown = set(obj) - known
    if unknown:
        raise LayoutError(f"{what}: unknown fields {sorted(unknown)}")


class Layout:
    """The benchmark rooted at ``root`` (the checkout: BENCHMARK.json and
    ``bench/``)."""

    def __init__(self, root):
        self.root = pathlib.Path(root)
        self.bench = _load_json(self.root / "BENCHMARK.json")
        self.dir = self.root / "bench"

    def cell(self, name: str) -> dict:
        for cell in self.bench["workloads"]:
            if cell["name"] == name:
                return cell
        raise LayoutError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for entry in self.bench["configs"]:
            if entry["name"] == name:
                cfg = _load_json(self.root / entry["file"])
                _check_fields(f"config {name}", cfg, CONFIG_FIELDS)
                return cfg
        raise LayoutError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        mix = _load_json(self.dir / "traffic" / f"{name}.json")
        kind = mix.get("kind")
        if kind not in TRAFFIC_FIELDS:
            raise LayoutError(f"traffic {name}: unknown kind {kind!r}")
        _check_fields(f"traffic {name}", mix, TRAFFIC_FIELDS[kind])
        return mix

    def metrics(self, cell: str, *, traced: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``traced`` its per-layer
        ones: those that list the cell, or list no cells at all."""
        group = self.bench["per_layer" if traced else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
        path = self.dir / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise LayoutError(f"metric {metric}: no reader {path}")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        if not callable(getattr(module, "read", None)):
            raise LayoutError(f"metric {metric}: {path} defines no read(run)")
        return module.read

    def peaks(self, device_kind: str) -> dict:
        table = _load_json(self.dir / "peaks.json")
        if device_kind not in table:
            raise LayoutError(
                f"no peaks for device kind {device_kind!r} in bench/peaks.json"
            )
        _check_fields(f"peaks {device_kind}", table[device_kind], PEAK_FIELDS)
        return table[device_kind]
