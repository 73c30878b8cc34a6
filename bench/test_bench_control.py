"""The control, the reference counting in bfloat16 in the program's
place, reads not correct, and the exact reference agrees with the
program's own host oracle.  At a size a test can hold: 1,500 objects, so
that supports pass 256, where bfloat16 stops counting exactly."""

import json

import numpy as np
import pytest

import control
from harness import context, reference
from harness.layout import Layout

CONTROL_CONFIG = {
    "name": "ctl", "source": "test", "objects": 1500, "attributes": 24,
    "density": 0.2, "generator_seed": 0,
    "guarantees": "exact iceberg lattice", "assumed": {}, "reduced": [],
}


@pytest.fixture
def control_layout(tiny_layout):
    root = tiny_layout
    (root / "bench" / "configs" / "ctl.json").write_text(json.dumps(CONTROL_CONFIG))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "ctl", "source": "test",
                             "file": "bench/configs/ctl.json", "reduced": [], "why": "test"})
    bench["workloads"] += [
        {"name": "ctl.mine", "config": "ctl", "traffic": "tiny_mine", "chips": 1, "why": "t"},
        {"name": "ctl.serve", "config": "ctl", "traffic": "tiny_serve", "chips": 1, "why": "t"},
    ]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return Layout(root)


@pytest.mark.parametrize("cell", ["ctl.mine", "ctl.serve"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5, 7])
def test_control_reads_incorrect(cell, seed, control_layout):
    checks = control.control_checks(control_layout, cell, seed, seconds=1.0)
    assert any(v > lim for v, lim in checks.values()), checks


def test_reference_lattice_matches_host_oracle():
    """The reference's iceberg lattice equals the program's host MR loop
    (``--pipeline host --backend jnp``) on a small context."""
    from repro.core.context import FormalContext
    from repro.launch import fca

    dense = context.make_context(CONTROL_CONFIG | {"objects": 200}, 3)
    ms = context.resolve_min_support(0.05, 200)
    args = fca.parse_args(["mine", "--algorithm", "mrcbo", "--parts", "1",
                           "--pipeline", "host", "--backend", "jnp",
                           "--min-support", str(ms)])
    ctx = FormalContext.from_dense(dense)
    _, res = fca._mine(args, ctx, fca.build_plan(args), "jnp", ms)
    intents, supports = reference.Reference(dense, block=64).iceberg(ms)
    assert set(reference.row_keys(np.asarray(res.intents, np.uint32))) == set(
        reference.row_keys(reference.pack(intents))
    )
    assert (supports >= ms).all()
    np.testing.assert_array_equal(reference.pack(dense), ctx.rows)
