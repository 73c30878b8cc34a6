"""repro.obs — trace export well-formedness, metrics/histogram units,
no-op-tracer transparency (traced mine bit-identical to untraced), the
async overlap signature, and the sync-vs-async transfer-census parity the
tracer made checkable."""

import json

import numpy as np
import pytest

from repro.core import ClosureEngine, all_closures_batched, bitset, mrcbo, mrganter
from repro.core.context import FormalContext
from repro.dist.shardplan import ShardPlan
from repro.obs import (
    Histogram,
    Registry,
    ScheduleCensus,
    StatsBase,
    Tracer,
    async_overlaps,
    current,
    span_rollup,
    use_tracer,
    validate_trace,
)
from repro.obs.trace import NOOP, _NULL_SPAN
from repro.query import ConceptStore, QueryEngine
from repro.query.engine import QueryConfig, QueryStats


def _keys(intents):
    return {bitset.key_bytes(y) for y in np.asarray(intents, np.uint32)}


@pytest.fixture(scope="module")
def ctx():
    return FormalContext.synthetic(60, 14, 0.3, seed=11)


# -- histogram / registry ----------------------------------------------------


def test_histogram_percentiles_within_bucket_error():
    h = Histogram()
    for v in np.linspace(0.001, 0.1, 1000):
        h.record(float(v))
    # log-bucketed: relative error bounded by the 2**(1/8) bucket factor
    for q, expect in ((50, 0.0505), (95, 0.0950), (99, 0.0990)):
        got = h.percentile(q)
        assert abs(got - expect) / expect < 0.10, (q, got)
    assert h.percentile(100) == pytest.approx(0.1)
    assert h.count == 1000


def test_histogram_empty_and_clamps():
    h = Histogram()
    assert h.percentile(50) == 0.0
    h.record(0.0)  # below the 1 µs floor → bucket 0
    assert h.percentile(99) <= 1e-6
    h2 = Histogram()
    h2.record(2.5)
    # single sample: every percentile is clamped to the observed extrema
    assert h2.percentile(50) == pytest.approx(2.5, rel=0.09)
    assert set(h2.percentiles()) == {"p50", "p95", "p99"}


def test_histogram_underflow_bucket_is_explicit():
    h = Histogram()
    h.record(5e-7)  # below the 1 µs floor
    h.record(2e-7)
    h.record(0.004)
    assert h.underflow == 2
    assert h.count == 3  # underflow counts in rank/count/sum as usual
    assert h.sum == pytest.approx(0.004 + 7e-7)
    # bucket 0's upper edge is the floor itself — the exporter renders it
    # as a real le="1e-06" bucket, not as silently-clamped observations
    edges = h.bucket_edges()
    assert edges[0] == (1e-6, 2)
    assert h.summary()["underflow"] == 2
    assert h.fraction_below(1e-6) == pytest.approx(2 / 3)


def test_registry_label_cardinality_cap_overflows_visibly():
    r = Registry(max_label_sets=4)
    for i in range(10):  # unbounded label value (e.g. a client id)
        r.counter("hits", qid=str(i))
    for i in range(6):  # histograms share the same per-name cap
        r.observe("lat_s", 0.001, qid=str(i))
    out = r.export()
    # first 4 label sets stored as-is; the rest collapse into overflow
    assert sum(1 for k in out if k.startswith("hits{qid=")) == 4
    assert out["hits{overflow=true}"] == 6
    assert out["lat_s{overflow=true}"]["count"] == 2
    # ...and the truncation is counted per metric name, never silent
    assert out["labels_overflow_total{metric=hits}"] == 6
    assert out["labels_overflow_total{metric=lat_s}"] == 2
    # unlabeled metrics are exempt (a single series can't explode)
    r2 = Registry(max_label_sets=1)
    r2.counter("a")
    r2.counter("b")
    assert set(r2.export()) == {"a", "b"}


def test_registry_labels_and_export():
    r = Registry()
    r.counter("rounds", 1, impl="rsag")
    r.counter("rounds", 2, impl="rsag")
    r.gauge("parts", 4)
    r.observe("lat", 0.01, kind="round")
    out = r.export()
    assert out["rounds{impl=rsag}"] == 3
    assert out["parts"] == 4
    assert out["lat{kind=round}"]["count"] == 1
    json.dumps(out)  # JSON-serialisable snapshot


def test_stats_base_latency_view_rides_asdict():
    import dataclasses

    st = StatsBase()
    st.record_reduce("allgather")
    st.record_reduce("allgather")
    st.observe_latency("round", 0.002)
    st.observe_latency("round", 0.004)
    d = dataclasses.asdict(st)
    assert d["reduce_rounds"] == {"allgather": 2}
    assert set(d["latency_percentiles"]["round"]) == {"p50", "p95", "p99"}
    assert "_registry" not in d  # the registry is a non-field attr
    pub = st.publish()
    assert pub["reduce_rounds{impl=allgather}"] == 2
    assert isinstance(ScheduleCensus(), ScheduleCensus)


# -- tracer export -----------------------------------------------------------


def test_trace_well_formed_and_round_trips():
    tr = Tracer()
    with tr.span("a", x=1):
        with tr.span("a/b"):
            tr.instant("mark")
        with tr.span("a/c") as sp:
            sp.set(outcome="done")
    obj = json.loads(json.dumps(tr.to_dict()))  # Perfetto JSON round-trip
    summary = validate_trace(obj)
    assert summary["spans"] == 3 and summary["max_depth"] == 2
    ts = [e["ts"] for e in obj["traceEvents"]]
    assert ts == sorted(ts)  # monotone per (single) track
    ends = {e["name"]: e.get("args") for e in obj["traceEvents"] if e["ph"] == "E"}
    assert ends["a/c"] == {"outcome": "done"}


def test_trace_async_pairing_and_save_closes_leaks(tmp_path):
    tr = Tracer()
    tr.begin_async("round", 7, algo="x")
    with tr.span("dispatch"):
        pass
    tr.end_async("round", 7, outcome="adopt")
    validate_trace(tr.to_dict())
    # a span leaked by an exception is closed by save() so the file validates
    tr2 = Tracer()
    tr2.span("leaked").__enter__()
    p = tmp_path / "t.json"
    tr2.save(str(p))
    validate_trace(json.loads(p.read_text()))


def test_validate_trace_rejects_malformed():
    base = {"pid": 0, "tid": 0, "cat": "host"}
    bad_unbalanced = {"traceEvents": [dict(base, name="a", ph="B", ts=1.0)]}
    with pytest.raises(ValueError, match="unclosed"):
        validate_trace(bad_unbalanced)
    bad_nest = {"traceEvents": [
        dict(base, name="a", ph="B", ts=1.0),
        dict(base, name="b", ph="B", ts=2.0),
        dict(base, name="a", ph="E", ts=3.0),
    ]}
    with pytest.raises(ValueError, match="nest"):
        validate_trace(bad_nest)
    bad_ts = {"traceEvents": [
        dict(base, name="a", ph="B", ts=5.0),
        dict(base, name="a", ph="E", ts=1.0),
    ]}
    with pytest.raises(ValueError, match="monotone"):
        validate_trace(bad_ts)
    bad_async = {"traceEvents": [
        dict(base, name="r", ph="e", ts=1.0, id=3, cat="round"),
    ]}
    with pytest.raises(ValueError, match="matching b"):
        validate_trace(bad_async)


def test_span_rollup_strips_indices():
    tr = Tracer()
    for i in range(3):
        with tr.span(f"mine/round[{i}]"):
            with tr.span(f"mine/round[{i}]/filter"):
                pass
    roll = span_rollup(tr.to_dict()["traceEvents"])
    assert roll["mine/round"]["count"] == 3
    assert roll["mine/round/filter"]["count"] == 3
    assert set(roll["mine/round"]) >= {"count", "total_s", "p50_s", "p95_s", "p99_s"}


def test_noop_tracer_is_allocation_free_default():
    assert current() is NOOP
    assert NOOP.span("x", a=1) is _NULL_SPAN
    with NOOP.span("x") as sp:
        sp.set(outcome="dropped")  # no-op, no state


# -- tracing transparency: traced mine ≡ untraced mine -----------------------


def _mine_fingerprint(ctx, tracer):
    plan = ShardPlan.simulated(2, block_n=64)
    eng = ClosureEngine(ctx, plan=plan, backend="jnp")
    with use_tracer(tracer):
        res = mrcbo(ctx, eng)
    s = eng.stats
    return {
        "keys": _keys(res.intents),
        "iterations": res.n_iterations,
        "closure_calls": s.closure_calls,
        "closures_computed": s.closures_computed,
        "modeled_comm_bytes": s.modeled_comm_bytes,
        "reduce_rounds": dict(s.reduce_rounds),
        "h2d": (s.h2d_transfers, s.h2d_bytes),
        "d2h": (s.d2h_transfers, s.d2h_bytes),
    }


def test_traced_mine_bit_identical_to_untraced(ctx):
    untraced = _mine_fingerprint(ctx, None)  # use_tracer(None) installs NOOP
    traced = _mine_fingerprint(ctx, Tracer())
    assert traced == untraced
    assert untraced["keys"] == _keys(all_closures_batched(ctx))


def test_mine_trace_validates_and_has_round_spans(ctx):
    plan = ShardPlan.simulated(2, block_n=64)
    eng = ClosureEngine(ctx, plan=plan, backend="jnp")
    tr = Tracer()
    with use_tracer(tr):
        mrcbo(ctx, eng)
    obj = json.loads(json.dumps(tr.to_dict()))
    validate_trace(obj)
    roll = span_rollup(obj["traceEvents"])
    for name in ("mine/mrcbo", "mine/round", "mine/round/allreduce",
                 "mine/round/filter", "engine/closure"):
        assert roll[name]["count"] >= 1, name
    # sync mine: no async windows, hence no speculative overlap
    assert not async_overlaps(obj)
    # round spans carry the shard-plan geometry tags
    b = next(e for e in obj["traceEvents"]
             if e["ph"] == "B" and e["name"].startswith("mine/round["))
    assert b["args"]["n_parts"] == 2 and b["args"]["mode"] == "sync"
    # engine invariant survives the instrumentation
    assert sum(eng.stats.reduce_rounds.values()) == eng.stats.closure_calls
    assert "round" in eng.stats.latency_percentiles


# -- async: overlap signature + transfer-census parity (satellite audit) -----


def _sync_async_pair(ctx, algo):
    out = []
    for mode in ("sync", "async"):
        plan = ShardPlan.simulated(2, block_n=64)
        eng = ClosureEngine(ctx, plan=plan, backend="jnp")
        tr = Tracer()
        with use_tracer(tr):
            res = algo(ctx, eng, rounds=mode)
        out.append((eng, res, tr))
    return out


def test_async_trace_shows_speculative_overlap(ctx):
    (_, res_s, _), (eng_a, res_a, tr_a) = _sync_async_pair(ctx, mrcbo)
    assert _keys(res_a.intents) == _keys(res_s.intents)
    obj = tr_a.to_dict()
    summary = validate_trace(obj)
    assert summary["async_spans"] >= res_a.n_iterations - 1
    ov = async_overlaps(obj)
    # the speculative signature: round r+1's dispatch begins while the
    # async window of round r is still in flight
    assert any(o["span"].startswith("spec/dispatch") for o in ov)
    roll = span_rollup(obj["traceEvents"])
    assert roll["spec/reconcile"]["count"] >= 1
    # every async round window ends with an outcome end-tag
    outcomes = [e["args"]["outcome"] for e in obj["traceEvents"]
                if e["ph"] == "e" and e.get("cat") == "round"]
    assert outcomes and set(outcomes) <= {"adopt", "fallback", "discard"}


def test_async_census_parity_charges_discarded_specs(ctx):
    """Every byte the async scheduler moves is charged like the sync path:
    the packed readback of a *discarded* speculative round still crossed
    the wire, so it appears in the d2h census (the pre-obs code dropped
    it)."""
    (eng_s, res_s, _), (eng_a, res_a, _) = _sync_async_pair(ctx, mrganter)
    assert _keys(res_a.intents) == _keys(res_s.intents)
    s = eng_a.stats
    # mrganter async: first closure readback (2 transfers) + exactly one
    # packed readback per speculative round — reconciled AND discarded
    assert s.d2h_transfers == 2 + s.spec_rounds
    assert s.spec_discarded >= 1  # the walk always over-speculates its end
    # each ganter spec packs [done, next_valid, Y_next] = (2 + W) words
    packed_bytes = s.spec_rounds * (2 + ctx.W) * 4
    assert s.d2h_bytes >= packed_bytes
    # the modeled collective traffic is mode-independent (same rounds run)
    assert s.modeled_comm_bytes == eng_s.stats.modeled_comm_bytes
    assert s.h2d_bytes == eng_s.stats.h2d_bytes


# -- query layer: stats view + extents charge --------------------------------


@pytest.fixture(scope="module")
def served(ctx):
    intents = all_closures_batched(ctx)
    plan = ShardPlan.simulated(2, block_n=16)
    store = ConceptStore.build(ctx, intents, plan=plan)
    return store, QueryEngine(store, QueryConfig(slots=8))


def test_query_stats_is_thin_view_over_census(served):
    import dataclasses

    _, qe = served
    assert isinstance(qe.stats, StatsBase)  # one census definition
    rng = np.random.default_rng(0)
    queries = qe.store.ctx.rows[rng.integers(0, qe.store.ctx.n_objects, 12)]
    qe.closure_batch(queries)
    d = dataclasses.asdict(qe.stats)
    # the public serve-JSON fields all survive, plus the percentile view
    for key in ("queries", "micro_batches", "collective_rounds",
                "modeled_comm_bytes", "by_type", "reduce_rounds",
                "auto_hop_bytes", "hop_calibrated", "latency_percentiles"):
        assert key in d, key
    assert set(d["latency_percentiles"]["micro_batch"]) == {"p50", "p95", "p99"}
    assert sum(d["reduce_rounds"].values()) == d["collective_rounds"]


def test_extents_allgather_is_charged(served):
    store, qe = served
    st = QueryStats()
    qe.stats = st
    ids = np.arange(5, dtype=np.int32)
    qe.extents_batch(ids)
    # one micro-batch (5 ≤ 8 slots): each of the k shards sends its
    # [Nl, slots] uint32 membership words to the other (k - 1) peers —
    # the whole-collective k·(k-1) convention modeled_comm_bytes uses
    k = qe.plan.n_parts
    n_local = store.state.N_padded // k
    expect = k * (k - 1) * n_local * qe.cfg.slots * 4
    assert st.modeled_comm_bytes == expect
    assert st.reduce_rounds == {"allgather": 1}
    assert st.collective_rounds == 1
    assert "micro_batch" in st.latency_percentiles


def test_extents_single_part_charges_nothing(ctx):
    intents = all_closures_batched(ctx)
    store = ConceptStore.build(ctx, intents, plan=ShardPlan.simulated(1))
    qe = QueryEngine(store, QueryConfig(slots=8))
    qe.extents_batch(np.arange(3, dtype=np.int32))
    assert qe.stats.modeled_comm_bytes == 0
    assert qe.stats.reduce_rounds == {}


def test_device_trace_failure_raises():
    """A profiler that fails surfaces: ``--device-trace`` must not exit 0
    without the trace it was asked for."""
    from repro.obs import stop_device_trace

    with pytest.raises(Exception):
        stop_device_trace()  # no trace was started
