"""Whole runs on the CPU: sound runs read correct, and a run whose timed
path is broken underneath reads not correct, once per fault the cell can
have.  The chip check is skipped (``require_tpu=False``); everything else
is a whole run as the command makes it, on the tiny layout of
``conftest.py``."""

import numpy as np
import pytest


@pytest.mark.parametrize("cell", ["tiny.mine", "tiny.cbo", "tiny.mine2", "tiny.serve"])
def test_sound_run_reads_correct(cell, tiny_layout, drive):
    out = drive(tiny_layout, cell, seconds=0.5 if cell == "tiny.serve" else 0.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("cell", ["tiny.mine", "tiny.serve"])
def test_traced_run_reads_correct(cell, tiny_layout, drive):
    """``--trace 1`` drives the same window under the profiler: the run is
    as correct, and reports the per-layer metrics that need no device
    trace (the CPU has no TPU planes)."""
    out = drive(tiny_layout, cell, seconds=0.5 if cell == "tiny.serve" else 0.0, trace=1)
    assert out["correct"], out["checks"]
    want = {"tiny.mine": {"mine.compile_ms", "mine.host_blocked_ms"},
            "tiny.serve": {"serve.service_ms", "serve.batch_fill", "serve.gen_lag_ms",
                           "serve.query_p99_ms"}}
    assert set(out["metrics"]) == want[cell]
    assert out["device"]["window_s"] > 0


def _wrap_step(monkeypatch, change):
    """MRGanter+ on the jnp steps: route every round's closures through
    ``change``."""
    from repro.core.frontier import DeviceFrontier

    step = DeviceFrontier.step_oplus

    def broken(self, **kw):
        return change(step(self, **kw))

    monkeypatch.setattr(DeviceFrontier, "step_oplus", broken)


def _wrap_kernel(monkeypatch, change):
    """MRCbo on the fused Pallas kernels: route every kernel call's
    ``(closures [B, W], supports, keep [B, 1])`` through ``change``, inside
    the compiled step."""
    from repro.kernels import frontier as fkern

    call = fkern.fused_closure_call

    def broken(*args, **kw):
        return change(*call(*args, **kw))

    monkeypatch.setattr(fkern, "fused_closure_call", broken)


def _altered(out):
    out = out.copy()
    if out.shape[0]:
        out[-1, 0] ^= np.uint32(1)  # one attribute of one intent flipped
    return out


def _every_other(keep):
    import jax.numpy as jnp

    even = jnp.arange(keep.shape[0]) % 2 == 0
    return keep * even.reshape((-1,) + (1,) * (keep.ndim - 1)).astype(keep.dtype)


MINE_FAULTS = {
    # a round that hands back no new state: the frontier stays as it was
    ("tiny.mine", "state_unchanged"): lambda mp: _wrap_step(mp, lambda out: out[:0]),
    ("tiny.cbo", "state_unchanged"): lambda mp: _wrap_kernel(
        mp, lambda c, s, k: (c, s, k * 0)
    ),
    # half of each round's closures left out
    ("tiny.mine", "half_batch"): lambda mp: _wrap_step(
        mp, lambda out: out[: out.shape[0] // 2]
    ),
    ("tiny.cbo", "half_batch"): lambda mp: _wrap_kernel(
        mp, lambda c, s, k: (c, s, _every_other(k))
    ),
    # the answer altered where it is produced
    ("tiny.mine", "answer_altered"): lambda mp: _wrap_step(mp, _altered),
    ("tiny.cbo", "answer_altered"): lambda mp: _wrap_kernel(
        mp, lambda c, s, k: (c.at[:, 0].set(c[:, 0] ^ 1), s, k)
    ),
}


@pytest.mark.parametrize(
    "cell, fault", sorted(MINE_FAULTS), ids=[f"{c}-{f}" for c, f in sorted(MINE_FAULTS)]
)
def test_mine_fault_reads_incorrect(cell, fault, tiny_layout, drive, monkeypatch):
    MINE_FAULTS[cell, fault](monkeypatch)
    out = drive(tiny_layout, cell)
    assert not out["correct"], out["checks"]
    assert out["failed"] == out["attempted"]


def test_mine_without_exchange_reads_incorrect(tiny_layout, drive, monkeypatch):
    """The AND-allreduce between object shards left out: each shard's
    closure stands for the whole."""
    from repro.dist import collectives

    monkeypatch.setattr(collectives, "and_allreduce", lambda x, *a, **k: x)
    out = drive(tiny_layout, "tiny.mine2")
    assert not out["correct"], out["checks"]


def test_serve_answer_altered_reads_incorrect(tiny_layout, drive, monkeypatch):
    from repro.query import QueryEngine

    closure_batch = QueryEngine.closure_batch

    def broken(self, attrsets):
        closed, supports, ids = closure_batch(self, attrsets)
        return closed, supports + 1, ids

    monkeypatch.setattr(QueryEngine, "closure_batch", broken)
    out = drive(tiny_layout, "tiny.serve", seconds=0.5)
    assert not out["correct"]
    assert out["checks"]["wrong_closures"]["value"] > 0


def test_serve_half_batch_reads_incorrect(tiny_layout, drive, monkeypatch):
    from repro.serve import AdmissionQueue

    run = AdmissionQueue._run

    def broken(self, kind, batch):
        results = run(self, kind, batch)
        return results[: len(results) // 2]

    monkeypatch.setattr(AdmissionQueue, "_run", broken)
    out = drive(tiny_layout, "tiny.serve", seconds=0.5)
    assert not out["correct"]
    assert out["checks"]["unanswered"]["value"] > 0
