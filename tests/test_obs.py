"""Observability plane: metrics registry, span tracer, explain records.

* registry semantics — idempotent registration, pull gauges, bounded
  histograms, snapshot/Prometheus export;
* tracer — deterministic under an injected clock, chrome-trace export
  passes (and the validator catches broken documents);
* explain — every retired query carries a record whose final
  estimate/CI equal the answer bit-for-bit; a census-converging query's
  CI-half-width trajectory is non-increasing; tier-1 rollup answers have
  a zero-round trajectory;
* server wiring — ``metrics_snapshot`` surfaces the quarantine log and
  injected-fault tallies; the NEUTRAL server is round-for-round
  bit-exact with tracing on;
* prefetcher counter lifecycle — ``close()`` preserves counters,
  ``reset_counters()`` is the only reset path (the satellite-6 bugfix).
"""

import json
import math

import numpy as np
import pytest

from repro.core.engine import EngineConfig, OLAEngine
from repro.core.queries import Linear, Query, Range
from repro.data.faults import FaultConfig, FaultInjector, RetryPolicy
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.obs.explain import ExplainRecord, RoundSample
from repro.obs.metrics import Counter, Gauge, MetricsRegistry
from repro.obs.trace import (
    NULL_TRACER,
    ProfilerTracer,
    SpanTracer,
    validate_chrome_trace,
)
from repro.sched import WorkloadScheduler
from repro.sched.scheduler import NEUTRAL
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions
from repro.serve.rollup import RollupConfig

COEF = tuple(1.0 / (k + 1) for k in range(8))


@pytest.fixture(scope="module")
def setup():
    vals = make_synthetic_zipf(2048, 8, seed=3)
    store = store_dataset(vals, 16, "ascii")
    return vals, store


def _q(name: str, epsilon: float = 0.05, hi: float = 6e7) -> Query:
    return Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, hi),
                 epsilon=epsilon, name=name)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_and_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("reqs", help="requests", labels={"kind": "a"})
    c.inc()
    c.inc(3)
    with pytest.raises(ValueError):
        c.inc(-1)
    # idempotent: same (name, labels) returns the same instrument
    assert reg.counter("reqs", labels={"kind": "a"}) is c
    assert reg.counter("reqs", labels={"kind": "b"}) is not c

    h = reg.histogram("lat", help="latency", bounds=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(100.0)            # lands in the +Inf overflow bucket
    snap = reg.snapshot()
    assert snap['reqs{kind="a"}'] == 4
    assert snap["lat"]["count"] == 3
    assert snap["lat"]["sum"] == pytest.approx(100.55)


def test_registry_pull_gauge_tracks_source():
    reg = MetricsRegistry()
    box = {"v": 1}
    g = reg.gauge("depth", help="queue depth", fn=lambda: box["v"])
    assert reg.snapshot()["depth"] == 1
    box["v"] = 7
    assert reg.snapshot()["depth"] == 7          # evaluated at read time
    with pytest.raises(ValueError):
        g.set(3)                                 # pull gauges reject pushes


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs", help="requests", labels={"kind": "a"}).inc(2)
    reg.histogram("lat", help="latency", bounds=(0.1, 1.0)).observe(0.5)
    text = reg.to_prometheus()
    assert "# HELP reqs requests" in text
    assert "# TYPE reqs counter" in text
    assert 'reqs{kind="a"} 2' in text
    # histogram buckets are cumulative and end at +Inf
    assert 'lat_bucket{le="1"} 1' in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_count 1" in text


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_tracer_deterministic_under_injected_clock():
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    tr = SpanTracer(clock=clock)
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
    doc = tr.to_chrome_trace()
    assert validate_chrome_trace(doc) == []
    xs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    # outer: enter t=2 exit t=5; inner: enter t=3 exit t=4 (t=1 is the
    # tracer's construction-time epoch read)
    assert xs["outer"]["ts"] == pytest.approx(1e6)
    assert xs["outer"]["dur"] == pytest.approx(3e6)
    assert xs["inner"]["dur"] == pytest.approx(1e6)
    assert xs["outer"]["args"] == {"k": 1}
    json.dumps(doc)                              # export is JSON-clean


def test_null_tracer_records_nothing():
    with NULL_TRACER.span("x", a=1), NULL_TRACER.round(0) as r:
        assert r is NULL_TRACER.span("y")       # one shared no-op object
    # and the real tracer's buffer caps instead of growing without bound
    tr = SpanTracer(max_events=2)
    for i in range(5):
        with tr.span(f"e{i}"):
            pass
    assert len(tr.events) == 2 and tr.dropped == 3


def test_chrome_trace_validator_catches_breakage():
    tr = SpanTracer()
    with tr.span("a"):
        pass
    doc = tr.to_chrome_trace()
    assert validate_chrome_trace(doc) == []

    bad_phase = {"traceEvents": [dict(doc["traceEvents"][0], ph="Z")]}
    assert validate_chrome_trace(bad_phase)
    bad_ts = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 1, "ts": float("nan"),
         "dur": 1.0}]}
    assert validate_chrome_trace(bad_ts)
    # partially overlapping same-tid spans cannot nest
    overlap = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0},
        {"name": "b", "ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": 10.0}]}
    assert validate_chrome_trace(overlap)
    assert validate_chrome_trace({"traceEvents": "nope"})


# ---------------------------------------------------------------------------
# explain records
# ---------------------------------------------------------------------------

def test_explain_trajectory_thins_past_cap(monkeypatch):
    monkeypatch.setattr(ExplainRecord, "max_samples", 8)
    rec = ExplainRecord(qid=0, name="q", t_submit=0.0)
    for r in range(100):
        rec.record_round(RoundSample(round=r, m=r, est=1.0,
                                     ci_halfwidth=0.1, b_eff=4, weight=1.0))
    assert len(rec.trajectory) <= 8
    rounds = [s.round for s in rec.trajectory]
    assert rounds == sorted(rounds) and rounds[0] == 0
    d = rec.to_dict()
    assert "_stride" not in d and isinstance(d["trajectory"][0], dict)


def test_explain_final_equals_answer_bit_for_bit(setup):
    _, store = setup
    cfg = EngineConfig(num_workers=2, seed=5)
    srv = OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=3))
    for i in range(3):
        srv.submit(_q(f"q{i}", epsilon=0.05), arrival_t=1e-5 * i)
    res = srv.run()
    srv.close()
    assert len(res) == 3
    for r in res:
        ex = r.explain
        assert ex is not None
        assert ex.final_estimate == r.estimate          # bit-for-bit
        assert ex.final_ci_halfwidth == r.halfwidth
        assert ex.sched_outcome == r.sched_outcome
        assert ex.tier == "scan" and ex.rounds_resident > 0
        assert ex.plan == r.plan and ex.admission_reason
        assert ex.cost_t_io_s > 0 and ex.cost_t_cpu_s > 0
        assert ex.effective_epsilon == pytest.approx(0.05)
        # trajectory endpoints are consistent with the lifecycle
        assert len(ex.trajectory) == ex.rounds_resident
        assert ex.trajectory[-1].m == r.tuples_seen
        json.dumps(ex.to_dict())


def test_census_trajectory_ci_halfwidth_non_increasing(setup):
    """A census-converging query (ε ≈ 0 forces a full scan) on the ref
    backend: its CI half-width trajectory converges to zero.  The
    half-width is itself a *sample-variance estimate*, so individual
    rounds can tick up as new strata enter the sample — the check allows
    bounded per-round noise, and pins the envelope: every round must stay
    under 1.5x the running minimum's last improvement, the trajectory must
    collapse by an order of magnitude, and the census endpoint is exactly
    tight (FPC drives the width to zero at full coverage)."""
    _, store = setup
    cfg = EngineConfig(num_workers=2, seed=5, extract_backend="ref")
    srv = OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=2))
    srv.submit(_q("census", epsilon=1e-9), arrival_t=0.0)
    res = srv.run()
    srv.close()
    (r,) = res
    hw = [s.ci_halfwidth for s in r.explain.trajectory]
    assert len(hw) >= 2
    # non-increasing up to statistical noise: no round may exceed 1.5x its
    # predecessor, and the running minimum never regresses
    assert all(b <= a * 1.5 for a, b in zip(hw, hw[1:])), hw
    assert hw[-1] <= hw[0] / 10.0, hw                  # real convergence
    assert hw[-1] == pytest.approx(0.0, abs=1e-6)      # census: exact
    ms = [s.m for s in r.explain.trajectory]
    assert ms == sorted(ms) and ms[-1] > ms[0]         # sample only grows


def test_tier1_answer_has_zero_round_trajectory(setup):
    _, store = setup
    cfg = EngineConfig(num_workers=2, seed=5)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=4,
                  rollup=RollupConfig(promote_hits=2)))
    for i in range(2):                       # promote the pattern...
        srv.submit(_q(f"h{i}", epsilon=0.08), arrival_t=1e-5 * i)
    srv.run()
    srv.submit(_q("hot", epsilon=0.08))      # ...then hit the cell
    r = srv.run()[-1]
    srv.close()
    assert r.sched_outcome == "tier1"
    ex = r.explain
    assert ex.tier == "tier1" and "rollup" in ex.tier_reason
    assert ex.trajectory == [] and ex.rounds_resident == 0
    assert ex.final_estimate == r.estimate
    assert ex.final_ci_halfwidth == r.halfwidth


# ---------------------------------------------------------------------------
# server wiring: metrics snapshot, fault surfacing, traced parity
# ---------------------------------------------------------------------------

def _answer_key(results):
    return [(r.qid, repr(r.estimate), repr(r.lo), repr(r.hi),
             repr(r.latency_model_s), r.sched_outcome, r.rounds_resident,
             r.tuples_seen) for r in results]


def test_neutral_server_bit_exact_with_tracing_on(setup):
    _, store = setup
    cfg = EngineConfig(num_workers=2, seed=5)
    queries = [_q(f"q{i}", epsilon=0.05) for i in range(4)]

    def _run(tracer):
        srv = OLAWorkloadServer(
                  store, cfg,
                  options=ServerOptions(max_slots=2, tracer=tracer,
                      scheduler=WorkloadScheduler(NEUTRAL)))
        for i, q in enumerate(queries):
            srv.submit(q, arrival_t=1e-5 * i)
        res = srv.run()
        stats = (srv.rounds, srv.tuples_scanned, srv.t_model)
        srv.close()
        return res, stats, srv

    # the default (profiler annotations), tracing off, and the chrome-trace
    # buffer give the same answers, bit for bit
    res_off, stats_off, _ = _run(NULL_TRACER)
    res_def, stats_def, srv_def = _run(None)
    res_on, stats_on, srv_on = _run(SpanTracer())
    assert isinstance(srv_def.tracer, ProfilerTracer)
    assert _answer_key(res_def) == _answer_key(res_off)
    assert _answer_key(res_on) == _answer_key(res_off)
    assert stats_def == stats_off and stats_on == stats_off
    doc = srv_on.tracer.to_chrome_trace()
    assert validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"ola.submit", "ola.admit", "ola.admit_query", "ola.round",
            "ola.schedule", "ola.claims", "ola.dispatch", "ola.device_wait",
            "ola.merge", "ola.retire", "ola.report", "ola.retire_slots",
            "ola.groups", "ola.topup", "ola.retire_query"} <= names
    assert not names & {"round", "kernel", "estimate"}
    rounds = [e for e in doc["traceEvents"] if e["name"] == "ola.round"]
    assert len(rounds) == stats_on[0]


def test_metrics_snapshot_counts_lifecycle(setup):
    _, store = setup
    cfg = EngineConfig(num_workers=2, seed=5)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2,
                  scheduler=WorkloadScheduler(NEUTRAL)))
    for i in range(3):
        srv.submit(_q(f"q{i}", epsilon=0.05), arrival_t=1e-5 * i)
    res = srv.run()
    snap = srv.metrics_snapshot()
    srv.close()
    retired = sum(v for k, v in snap.items() if k.startswith("queries_total"))
    assert retired == len(res) == 3
    assert snap["server_rounds"] == srv.rounds > 0
    assert snap["server_tuples_scanned"] == srv.tuples_scanned
    assert snap["query_latency_s"]["count"] == 3
    # wall seconds: every query waited in the queue before its slot, and
    # each answer came later than its submit
    assert snap["server_queue_wait_seconds"] > 0
    assert snap["query_latency_s"]["sum"] >= snap["server_queue_wait_seconds"]
    assert snap["quarantine_log"] == []
    assert snap['admission_decisions{action="admitted"}'] >= 1
    # the text exposition renders the same registry without raising
    assert "server_rounds" in srv.metrics.to_prometheus()


def test_metrics_snapshot_surfaces_quarantine_and_faults():
    vals = make_synthetic_zipf(512, 8, seed=3)
    store = store_dataset(vals, 8, "ascii")
    cfg = EngineConfig(num_workers=2, seed=9, residency="stream")
    inj = FaultInjector(store, FaultConfig())
    srv = OLAWorkloadServer(
              inj, cfg,
              options=ServerOptions(max_slots=2,
                  scheduler=WorkloadScheduler(NEUTRAL)))
    if srv.engine.pipeline is not None:
        srv.engine.pipeline.retry = RetryPolicy(sleep=lambda s: None,
                                                max_attempts=2)
    lost = int(np.asarray(srv.state.schedule)[0])
    inj.config = FaultConfig(seed=7, lost_chunks=(lost,))
    srv.submit(_q("q0", epsilon=0.08), arrival_t=0.0)
    res = srv.run()
    snap = srv.metrics_snapshot()
    srv.close()
    assert snap["quarantine_log"] == [lost]
    assert snap["server_chunks_quarantined"] == 1
    assert snap['faults_injected{kind="lost"}'] >= 1
    # the quarantine round is recorded on the resident query's explain
    (r,) = res
    assert r.degraded
    deg = r.explain.degradation
    assert len(deg) == 1 and deg[0]["chunk_ids"] == [lost]


# ---------------------------------------------------------------------------
# prefetcher counter lifecycle (satellite: close() must not clear counters)
# ---------------------------------------------------------------------------

def test_prefetcher_counters_survive_close_reset_is_explicit(setup):
    _, store = setup
    cfg = EngineConfig(num_workers=2, seed=5, residency="stream")
    eng = OLAEngine(store, [_q("q0", epsilon=0.05)], cfg)
    state = eng.init_state()
    for _ in range(3):
        b = eng.budget_ladder(float(state.budget))
        state, data = eng.round_data(state)
        state, rep = eng.round_fn(b)(state, data, eng.speeds)
        if bool(rep.all_stopped) or bool(rep.exhausted):
            break
    pf = eng.pipeline
    reg = MetricsRegistry()
    pf.bind_metrics(reg)
    before = pf.counters()
    assert before["chunk_reads"] > 0
    assert reg.snapshot()["prefetch_chunk_reads"] == before["chunk_reads"]
    pf.close()
    # close() ends the reader thread but preserves the counters — a server
    # shutdown must not erase the telemetry about the run that just ended
    assert pf.counters() == before
    assert reg.snapshot()["prefetch_chunk_reads"] == before["chunk_reads"]
    pf.reset_counters()                      # the one explicit reset path
    after = pf.counters()
    assert after["chunk_reads"] == 0
    assert all(after[f] == 0 for f in pf.COUNTER_FIELDS)
    assert reg.snapshot()["prefetch_chunk_reads"] == 0
