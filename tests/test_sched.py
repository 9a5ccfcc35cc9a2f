"""SLO-aware workload scheduler: admission control, fairness under slot
pressure, variance-guided chunk claiming.

Gates (ISSUE 4 acceptance):

* **parity** — with the neutral scheduler (infinite SLOs, uniform weights,
  default claim order) the scheduled server reproduces the unscheduled one
  round-for-round, bit-exactly, on the ref backend for packed and stream
  residency (single-device here; the SPMD side lives in a subprocess test);
* **pressure** — a high-priority late-arriving query meets a deadline the
  unscheduled FIFO server misses;
* **shed** — an infeasible-deadline query is shed and still returns a
  flagged synopsis-seeded estimate.
"""

import dataclasses
import json
import math
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.engine import EngineConfig
from repro.core.queries import Linear, Query, Range
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.sched import (
    NEUTRAL,
    AdmissionController,
    P2Quantile,
    QuerySLO,
    SchedulerConfig,
    ServerLoad,
    ServiceTimeModel,
    WorkloadScheduler,
    max_min_weights,
    measured_slot_capacity,
    select_victim,
    slot_chunk_variances,
    variance_claim_order,
)
from repro.sched.admission import eq4_cost_terms, scan_tuples_per_s
from repro.serve.ola_server import (
    MeasuredRates,
    OLAWorkloadServer,
    ServerOptions,
    poisson_workload,
    select_plan,
)

COEF = tuple(1.0 / (k + 1) for k in range(8))


@pytest.fixture(scope="module")
def setup():
    vals = make_synthetic_zipf(4096, 8, seed=3)
    store = store_dataset(vals, 32, "ascii")
    return vals, store


def _truth_sum(vals):
    return float((vals @ np.asarray(COEF)).sum())


# ---------------------------------------------------------------------------
# Policy units
# ---------------------------------------------------------------------------

def test_max_min_weights_properties():
    act = np.array([True, True, True, False])
    pri = np.array([1.0, 1.0, 1.0, 1.0])
    # uncontended -> exactly 1.0 everywhere (the engine-parity precondition)
    np.testing.assert_array_equal(
        max_min_weights(pri, act, math.inf), np.ones(4))
    np.testing.assert_array_equal(
        max_min_weights(pri, act, 3.0), np.ones(4))
    # equal weights under contention -> equal split
    w = max_min_weights(pri, act, 1.5)
    np.testing.assert_allclose(w[:3], 0.5)
    assert w[3] == 1.0                       # inactive slots stay neutral
    # priority-proportional split: 1:4 at capacity 1.0 -> 0.2 / 0.8
    w = max_min_weights(np.array([1.0, 4.0]), np.array([True, True]), 1.0)
    np.testing.assert_allclose(w, [0.2, 0.8])
    # saturation: a dominant slot caps at 1.0 and the surplus water-fills
    w = max_min_weights(np.array([1.0, 100.0]), np.array([True, True]), 1.5)
    assert w[1] == 1.0
    np.testing.assert_allclose(w[0], 0.5)
    # total grant never exceeds capacity; every active slot makes progress
    w = max_min_weights(np.array([1.0, 2.0, 4.0]), np.ones(3, bool), 2.0)
    assert w.sum() <= 2.0 + 1e-9 and (w > 0).all()


def test_admission_required_tuples_and_decisions():
    rt = AdmissionController.required_tuples
    assert rt(0, math.inf, 0.05, 10_000) == 10_000       # no seed: full pass
    assert rt(100, 0.02, 0.05, 10_000) == 0.0            # seed already meets ε
    # CLT extrapolation: err halves when m quadruples
    assert rt(100, 0.10, 0.05, 10_000) == pytest.approx(300.0)
    assert rt(100, 0.10, 0.001, 200) == 100.0            # capped at the table

    ac = AdmissionController()
    load_free = ServerLoad(now=0.0, free_slots=1, queue_ahead=0,
                           scan_rate=1000.0, total_tuples=1000)
    load_busy = dataclasses.replace(load_free, free_slots=0)
    no_slo = QuerySLO()
    d = ac.decide(arrival_t=0.0, slo=no_slo, epsilon=0.05, load=load_free)
    assert d.action == "admitted"
    d = ac.decide(arrival_t=0.0, slo=no_slo, epsilon=0.05, load=load_busy)
    assert d.action == "queued"              # no deadline -> never shed
    # a deadline shorter than the (full-pass) service prediction -> shed...
    tight = QuerySLO(deadline_s=0.1)
    d = ac.decide(arrival_t=0.0, slo=tight, epsilon=0.05, load=load_free)
    assert d.action == "shed" and "deadline" in d.reason
    # ...unless a synopsis seed shows only a sliver of work remains
    d = ac.decide(arrival_t=0.0, slo=tight, epsilon=0.05, load=load_free,
                  seed_m=500, seed_err=0.052)
    assert d.action == "admitted"
    # shedding disabled degrades to queue
    d = AdmissionController(shed_enabled=False).decide(
        arrival_t=0.0, slo=tight, epsilon=0.05, load=load_busy)
    assert d.action == "queued"


def test_variance_claim_order_bands():
    """Unstarted chunks keep the committed order (band 0), started-open ones
    sort by variance desc (band 1), dead ones go last (band 2); the claimed
    prefix is never touched."""
    n = 8
    schedule = np.array([5, 2, 7, 0, 1, 3, 6, 4], np.int32)
    m = np.zeros((2, n))
    ys = np.zeros((2, n))
    yq = np.zeros((2, n))
    # chunks 0 and 1 started: chunk 1 has the larger within-variance
    m[:, [0, 1]] = 10
    ys[0, 0], yq[0, 0] = 10.0, 11.0          # var ~ 1/9
    ys[0, 1], yq[0, 1] = 10.0, 110.0         # var ~ 100/9
    state = SimpleNamespace(
        stats=SimpleNamespace(m=m, ysum=ys, ysq=yq),
        scan_m=np.array([10, 10, 0, 0, 0, 0, 0, 64]),
        closed=np.array([False] * 7 + [True]),
        head=2, schedule=schedule)
    sizes = np.full(n, 64)
    out = variance_claim_order(state, sizes)
    assert out is not None
    np.testing.assert_array_equal(out[:2], schedule[:2])  # prefix untouched
    # tail: never-started chunks first in committed order (unknown variance
    # counts as infinite, and first-touch order must stay a prefix of the
    # committed order), then started-open {0, 1} by variance (1 before 0),
    # then the exhausted chunk 7 last
    np.testing.assert_array_equal(out[2:], [3, 6, 4, 1, 0, 7])
    assert sorted(out.tolist()) == list(range(n))
    # nothing measured in the tail and nothing dead -> no reorder
    state2 = SimpleNamespace(
        stats=SimpleNamespace(m=np.zeros((2, n)), ysum=ys * 0, ysq=yq * 0),
        scan_m=np.zeros(n, int), closed=np.zeros(n, bool),
        head=0, schedule=schedule)
    assert variance_claim_order(state2, sizes) is None


def test_poisson_workload_deterministic():
    qs = [Query(agg="count", name=f"q{i}") for i in range(16)]
    a = poisson_workload(qs, rate_per_model_s=100.0, seed=42)
    b = poisson_workload(qs, rate_per_model_s=100.0, seed=42)
    assert [t for _, t in a] == [t for _, t in b]
    c = poisson_workload(qs, rate_per_model_s=100.0, seed=43)
    assert [t for _, t in a] != [t for _, t in c]
    # caller-owned rng: one stream split across two sections stays
    # reproducible end to end
    rng = np.random.default_rng(7)
    d1 = poisson_workload(qs[:8], 100.0, rng=rng)
    d2 = poisson_workload(qs[8:], 100.0, rng=rng)
    rng2 = np.random.default_rng(7)
    e = poisson_workload(qs, 100.0, rng=rng2)
    gaps = np.diff([0.0] + [t for _, t in d1]).tolist() \
        + np.diff([0.0] + [t for _, t in d2]).tolist()
    np.testing.assert_allclose(gaps, np.diff([0.0] + [t for _, t in e]))


# ---------------------------------------------------------------------------
# Parity gate: neutral scheduler == unscheduled server, round for round
# ---------------------------------------------------------------------------

def _mixed_workload():
    return [
        (Query(agg="sum", expr=Linear(COEF), epsilon=0.04, name="a"), 0.0),
        (Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 8e7),
               epsilon=0.06, name="b"), 1e-5),
        (Query(agg="count", pred=Range(1, 0.0, 7e7), epsilon=0.08,
               name="c"), 2e-5),
        (Query(agg="avg", expr=Linear(COEF), epsilon=0.07, name="d"), 3e-5),
        (Query(agg="sum", expr=Linear(COEF), epsilon=0.10, name="e"), 4e-4),
    ]


@pytest.mark.parametrize("residency", ["packed", "stream"])
def test_neutral_scheduler_parity(setup, residency):
    """Scheduled server with the NEUTRAL config == unscheduled server:
    identical per-round scan trace and bit-identical results (ref backend),
    for both residencies — slots only ever see max_slots pressure here."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=9, residency=residency)

    def run(scheduler):
        srv = OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=2))
        if scheduler is not None:
            srv.scheduler = scheduler           # same ctor state otherwise
        for q, at in _mixed_workload():
            srv.submit(q, arrival_t=at)
        trace = []
        res = srv.run(on_round=lambda s: trace.append(
            (int(s.tuples_scanned), int(np.asarray(s.state.head)))))
        out = [(r.qid, r.estimate, r.lo, r.hi, r.err, r.tuples_seen,
                r.t_admit, r.t_done, r.rounds_resident, r.sched_outcome,
                r.queue_wait_model_s, r.from_synopsis) for r in res]
        rounds, tuples = srv.rounds, srv.tuples_scanned
        srv.close()
        return out, trace, rounds, tuples

    base = run(None)
    neutral = run(WorkloadScheduler(NEUTRAL))
    assert neutral[1] == base[1], "per-round scan trace diverged"
    assert neutral[0] == base[0], "results diverged (must be bit-exact)"
    assert neutral[2:] == base[2:]


_SPMD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json
import numpy as np, jax
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.core.queries import Query, Linear, Range
from repro.core.engine import EngineConfig
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions
from repro.sched import NEUTRAL, QuerySLO, SchedulerConfig, WorkloadScheduler

vals = make_synthetic_zipf(2048, 8, seed=3)
store = store_dataset(vals, 12, 'ascii', uneven=True)
coef = tuple(1.0/(k+1) for k in range(8))
cfg = EngineConfig(num_workers=8, budget_init=32, budget_min=32,
                   budget_max=32, seed=5)
mesh = jax.make_mesh((4,), ('data',))
active = SchedulerConfig(slot_capacity=1.5, claim_policy='variance',
                         shed_enabled=False, deadline_enforcement=False)

def serve(mesh=None, sched=None):
    srv = OLAWorkloadServer(store, cfg, options=ServerOptions(
        max_slots=3, synopsis_budget_tuples=0, mesh=mesh,
        scheduler=sched))
    srv.submit(Query(agg='sum', expr=Linear(coef), pred=Range(0, 0.0, 0.6e8),
                     epsilon=0.04), arrival_t=0.0)
    srv.submit(Query(agg='count', pred=Range(1, 0.0, 0.7e8), epsilon=0.06),
               arrival_t=0.0, slo=QuerySLO(priority='interactive'))
    srv.submit(Query(agg='avg', expr=Linear(coef), epsilon=0.05),
               arrival_t=1e-5, slo=QuerySLO(priority='batch'))
    res = srv.run(max_rounds=4000)
    return ([(r.qid, float(r.estimate), r.tuples_seen, r.sched_outcome)
             for r in res], srv.rounds)

plain_single = serve()
plain_spmd = serve(mesh=mesh)
neutral_spmd = serve(mesh=mesh, sched=WorkloadScheduler(NEUTRAL))
sched_single = serve(sched=WorkloadScheduler(active))
sched_spmd = serve(mesh=mesh, sched=WorkloadScheduler(active))
print(json.dumps({
  "spmd_matches_single": plain_spmd == plain_single,
  "neutral_parity_spmd": neutral_spmd == plain_spmd,
  "sched_spmd_matches_single": sched_spmd == sched_single,
  "sched_differs_from_plain": sched_single != plain_single,
}))
"""


def test_scheduler_spmd_parity():
    """On a forced 4-device CPU mesh: the neutral scheduler is bit-exact vs
    the unscheduled SPMD server, and the *active* scheduler (fairness
    contention + variance claims) produces identical results on SPMD and
    single-device — the claim reordering and per-slot weights preserve the
    deterministic hand-out."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SPMD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["spmd_matches_single"], res
    assert res["neutral_parity_spmd"], res
    assert res["sched_spmd_matches_single"], res


# ---------------------------------------------------------------------------
# Pressure: priority admission meets a deadline FIFO misses
# ---------------------------------------------------------------------------

def _pressure_run(store, slo_hot, scheduler):
    cfg = EngineConfig(num_workers=2, seed=13)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=1, synopsis_budget_tuples=0,
                  scheduler=scheduler))
    for i in range(3):
        srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.02,
                         name=f"long{i}"), arrival_t=0.0)
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.08, name="hot"),
               arrival_t=1e-6, slo=slo_hot)
    res = {r.name: r for r in srv.run()}
    srv.close()
    return res


def test_priority_pressure_meets_deadline(setup):
    """max_slots=1, three tight queries ahead: FIFO makes the late
    interactive query wait out the whole backlog; the priority scheduler
    admits it at the first slot hand-over, meeting a deadline FIFO misses."""
    vals, store = setup
    sched_cfg = SchedulerConfig(shed_enabled=False)
    # measure both policies on the same workload (no deadline yet)
    probe = QuerySLO(priority="interactive")
    lat_fifo = _pressure_run(store, probe, None)["hot"].latency_model_s
    lat_pri = _pressure_run(
        store, probe, WorkloadScheduler(sched_cfg))["hot"].latency_model_s
    assert lat_pri < lat_fifo, (lat_pri, lat_fifo)
    # a deadline between the two: scheduler meets it, FIFO provably misses
    deadline = (lat_pri + lat_fifo) / 2.0
    slo = QuerySLO(deadline_s=deadline, priority="interactive")
    res_pri = _pressure_run(store, slo, WorkloadScheduler(sched_cfg))
    res_fifo = _pressure_run(store, slo, None)
    assert res_pri["hot"].slo_met is True
    assert res_fifo["hot"].slo_met is False
    assert res_pri["hot"].sched_outcome == "queued"  # it did wait, once
    # the backlog still completes correctly under either policy (a tail
    # query can end unserved once the scan became a census — no synopsis
    # here — but every *answered* one must be accurate)
    truth = _truth_sum(vals)
    for res in (res_pri, res_fifo):
        answered = [res[f"long{i}"] for i in range(3)
                    if not res[f"long{i}"].unserved]
        assert len(answered) >= 2
        for r in answered:
            assert abs(r.estimate - truth) / truth < 3 * 0.02


def test_shed_returns_flagged_synopsis_estimate(setup):
    """An infeasible-deadline query is shed — never holds a slot — and its
    result is a flagged, synopsis-seeded best-effort estimate."""
    vals, store = setup
    truth = _truth_sum(vals)
    cfg = EngineConfig(num_workers=2, seed=17)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2, synopsis_budget_tuples=4096,
                  scheduler=WorkloadScheduler(SchedulerConfig())))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.04,
                     name="warm"), arrival_t=0.0)
    srv.run()
    scanned = srv.tuples_scanned
    # tighter ε than the synopsis delivers + a deadline far below the
    # predicted service -> shed
    t_full = store.num_tuples / srv._scan_rate
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.001,
                     name="doomed"),
               slo=QuerySLO(deadline_s=t_full * 1e-6))
    res = {r.name: r for r in srv.run()}
    doomed = res["doomed"]
    assert doomed.sched_outcome == "shed"
    assert doomed.from_synopsis and not doomed.unserved
    assert doomed.rounds_resident == 0
    assert srv.tuples_scanned == scanned        # zero extra raw access
    assert np.isfinite(doomed.estimate)
    assert abs(doomed.estimate - truth) / truth < 0.2   # best effort, sane
    assert doomed.err > 0.001                   # honestly flagged as short
    assert srv.shed_count == 1
    srv.close()


# ---------------------------------------------------------------------------
# Fairness under slot pressure
# ---------------------------------------------------------------------------

def test_fairness_weights_divide_round_budget(setup):
    """slot_capacity=1.0 with a batch and an interactive slot resident:
    weights must be 0.2/0.8 and the per-slot sample sizes must track the
    4:1 split (each slot counts a weight-proportional window prefix)."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=19)
    sc = SchedulerConfig(slot_capacity=1.0, shed_enabled=False,
                         claim_policy="schedule")
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2, synopsis_budget_tuples=0,
                  scheduler=WorkloadScheduler(sc)))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6, name="bat"),
               arrival_t=0.0, slo=QuerySLO(priority="batch"))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6, name="hot"),
               arrival_t=0.0, slo=QuerySLO(priority="interactive"))
    for _ in range(4):
        srv.step()
    # priority intake: the interactive query was admitted first -> slot 0
    w = np.asarray(srv.table.weight)
    np.testing.assert_allclose(w, [0.8, 0.2], rtol=1e-5)
    m = np.asarray(srv.state.stats.m).sum(axis=1).astype(float)
    assert m[1] > 0
    assert 3.0 < m[0] / m[1] < 5.0, m           # ≈ 4:1 modulo per-window ceil
    # scan-level extraction is unaffected by the split (same chunks read)
    assert int(np.asarray(srv.state.scan_m).sum()) >= m.max()
    srv.close()


def test_deadline_enforcement_frees_slot(setup):
    """A query whose deadline lands mid-scan is retired at the deadline with
    the best estimate so far (finite, flagged unmet ε) instead of holding
    its slot."""
    vals, store = setup
    truth = _truth_sum(vals)
    cfg = EngineConfig(num_workers=2, seed=23)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=1, synopsis_budget_tuples=0,
                  scheduler=WorkloadScheduler(
                                SchedulerConfig(shed_enabled=False))))
    t_full = store.num_tuples / srv._scan_rate
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-9,
                     name="boxed"),
               arrival_t=0.0, slo=QuerySLO(deadline_s=t_full * 0.25))
    res = srv.run()[0]
    assert res.tuples_seen < store.num_tuples   # stopped before the census
    assert np.isfinite(res.estimate)
    assert abs(res.estimate - truth) / truth < 0.25
    assert res.err > 1e-9                       # target honestly unmet
    assert res.slo_met is False                 # retired at, not within, t
    srv.close()


# ---------------------------------------------------------------------------
# Variance-guided claiming
# ---------------------------------------------------------------------------

def test_variance_claims_reorder_topup_and_stay_correct(setup):
    """A top-up pass under claim_policy="variance" reorders the re-opened
    tail — re-opened started chunks are claimed ahead of exhausted ones —
    while the schedule stays a permutation and the late tight query still
    converges to the truth.

    Phase 1 is a near-certain COUNT (within-chunk variance ≈ 0), whose local
    accuracy closes its chunks *early* (partially extracted); the tight SUM
    that follows drives the scan to wind-down and must re-open them."""
    vals, store = setup
    truth = _truth_sum(vals)
    cfg = EngineConfig(num_workers=2, seed=29)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2, synopsis_budget_tuples=512,
                  scheduler=WorkloadScheduler(
                                SchedulerConfig(shed_enabled=False))))
    committed = np.asarray(srv.engine.program.schedule_np)
    srv.submit(Query(agg="count", pred=Range(0, 0.0, 1e12), epsilon=0.02,
                     name="loose"), arrival_t=0.0, plan="single_pass")
    srv.run()
    closed = np.asarray(srv.state.closed)
    scan_m = np.asarray(srv.state.scan_m)
    early = closed & (scan_m < np.asarray(store.chunk_sizes))
    assert early.sum() > 0, "phase 1 closed no chunk early"
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.005,
                     name="tight"), plan="single_pass")
    saw_reorder = []

    def watch(s):
        sched = np.asarray(s.state.schedule)
        assert sorted(sched.tolist()) == list(range(len(sched)))
        if not np.array_equal(sched, committed):
            saw_reorder.append(True)

    res = {r.name: r for r in srv.run(on_round=watch)}
    assert srv.topup_passes >= 1
    assert saw_reorder, "variance policy never reordered the claim tail"
    tight = res["tight"]
    assert abs(tight.estimate - truth) / truth < 3 * 0.005
    srv.close()


# ---------------------------------------------------------------------------
# Review regressions: honest accounting at the edges
# ---------------------------------------------------------------------------

def test_unserved_never_counts_as_slo_hit():
    """A NaN half-width (unserved result) is never an SLO hit, even for a
    deadline-only SLO — meeting a deadline with no answer is not service."""
    assert QuerySLO(deadline_s=1.0).met(0.1, float("nan")) is False
    assert QuerySLO(deadline_s=1.0).met(0.1, 5.0) is True
    assert QuerySLO().met(0.1, float("nan")) is False


def test_deadline_enforced_zero_tuple_slot_is_unserved(setup):
    """A query admitted after the scan became a census (no synopsis seed,
    nothing left to extract) and deadline-enforced before any round served
    it must retire flagged unserved with a NaN estimate — not a fabricated
    zero counted as an SLO hit."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=31)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=1, synopsis_budget_tuples=0,
                  scheduler=WorkloadScheduler(
                                SchedulerConfig(shed_enabled=False))))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-9,
                     name="census"), arrival_t=0.0)
    # queued behind the census; its deadline expires while it waits, and by
    # the time it gets the slot there is nothing left to extract
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.05,
                     name="late"), arrival_t=0.0,
               slo=QuerySLO(deadline_s=1e-12))
    res = {r.name: r for r in srv.run()}
    assert res["census"].tuples_seen == store.num_tuples
    late = res["late"]
    assert late.unserved and np.isnan(late.estimate)
    assert late.tuples_seen == 0
    assert late.slo_met is False
    srv.close()


def test_admission_respects_target_halfwidth(setup):
    """Feasibility triage must judge against the *effective* ε a finite
    target_halfwidth implies, not the query's loose nominal ε: a query the
    seed already satisfies at ε=0.5 but whose half-width target demands far
    more data is shed when its deadline cannot cover that work."""
    vals, store = setup
    truth = _truth_sum(vals)
    cfg = EngineConfig(num_workers=2, seed=37)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2, synopsis_budget_tuples=4096,
                  scheduler=WorkloadScheduler(SchedulerConfig())))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.04,
                     name="warm"), arrival_t=0.0)
    srv.run()
    t_full = store.num_tuples / srv._scan_rate
    # nominal ε=0.5 is trivially met by the seed; the half-width target
    # (~0.1% relative) is not, and the deadline cannot cover the gap
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.5,
                     name="hw"),
               slo=QuerySLO(deadline_s=t_full * 1e-6,
                            target_halfwidth=abs(truth) * 1e-3))
    res = {r.name: r for r in srv.run()}
    assert res["hw"].sched_outcome == "shed"
    assert res["hw"].from_synopsis
    srv.close()


def test_fairness_weights_survive_slot_churn(setup):
    """Admitting a new query into a freed slot resets that row's table
    weight to 1.0; the scheduler must re-write the fair share even when the
    *computed* weight vector is unchanged — otherwise the new occupant runs
    at full budget for its whole residence (stale-cache regression)."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=41)
    sc = SchedulerConfig(slot_capacity=1.0, shed_enabled=False,
                         claim_policy="schedule")
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2, synopsis_budget_tuples=0,
                  scheduler=WorkloadScheduler(sc)))
    # two equal-priority residents -> [0.5, 0.5]
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6, name="a"),
               arrival_t=0.0)
    srv.submit(Query(agg="count", pred=Range(0, 0.0, 1e12), epsilon=0.5,
                     name="b"), arrival_t=0.0)
    srv.step()
    w = np.asarray(srv.table.weight)
    assert w[0] == pytest.approx(0.5)       # a's contended fair share
    # b (a loose count) may retire within this very step; its cleared row
    # then resets to the neutral 1.0 (a release keeps inactive slots
    # neutral so no contended weight leaks to the next occupant)
    assert w[1] == pytest.approx(1.0 if srv.slot_wq[1] is None else 0.5)
    # b retires fast (loose count); c takes its slot — same computed vector
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6, name="c"))
    for _ in range(6):
        srv.step()
        if any(w is not None and w.query.name == "c" for w in srv.slot_wq):
            break
    assert any(w is not None and w.query.name == "c" for w in srv.slot_wq)
    np.testing.assert_allclose(np.asarray(srv.table.weight), [0.5, 0.5])
    srv.close()


# ---------------------------------------------------------------------------
# Service-time model: quantile sketch + cold-start blend (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------

def test_p2_quantile_tracks_percentile():
    """The P² sketch stays close to the exact empirical quantile on heavy
    -tailed streams — the service-time shape it exists for — and is exact
    below five observations."""
    for p, seed, draw in [(0.9, 0, "lognormal"), (0.5, 1, "lognormal"),
                          (0.9, 2, "exponential"), (0.75, 3, "uniform")]:
        rng = np.random.default_rng(seed)
        xs = getattr(rng, draw)(size=4000)
        sk = P2Quantile(p)
        for x in xs:
            sk.observe(x)
        exact = float(np.percentile(xs, 100 * p))
        assert sk.value() == pytest.approx(exact, rel=0.15), (p, draw)
    # exact small-sample path
    sk = P2Quantile(0.5)
    for x in (3.0, 1.0, 2.0):
        sk.observe(x)
    assert sk.value() == pytest.approx(2.0)
    assert P2Quantile(0.9).value() is None
    with pytest.raises(ValueError):
        P2Quantile(1.0)
    # regression: at EXACTLY five observations the markers are still the
    # raw sorted sample — a p90 over [1,1,1,1,100] must interpolate (~60),
    # not collapse to the median marker (1)
    sk = P2Quantile(0.9)
    for x in (1.0, 1.0, 1.0, 1.0, 100.0):
        sk.observe(x)
    assert sk.value() == pytest.approx(np.percentile(
        [1, 1, 1, 1, 100], 90, method="linear"))
    assert sk.value() > 50.0


def test_service_model_cold_start_blend():
    """predict() slides from the caller's prior to the class sketch as
    observations accumulate; unknown classes stay on the prior."""
    m = ServiceTimeModel(quantile=0.9, min_samples=4)
    assert m.predict("batch", 10.0) == 10.0          # no evidence: prior
    m.observe("batch", 2.0)
    # 1 of 4 samples: 25% sketch (2.0), 75% prior (10.0)
    assert m.predict("batch", 10.0) == pytest.approx(0.25 * 2.0 + 0.75 * 10.0)
    for _ in range(5):
        m.observe("batch", 2.0)
    assert m.predict("batch", 10.0) == pytest.approx(2.0)   # evidence wins
    assert m.predict("interactive", 7.0) == 7.0      # other classes untouched
    m.observe("batch", float("nan"))                 # garbage is ignored
    assert m.n_obs("batch") == 6


def test_admission_queue_priced_at_model_not_candidate():
    """Regression (ISSUE 5 bugfix): with no completed-query history, queued
    work ahead must be priced at the full-pass bound — not the candidate's
    own seed-discounted service — and with a trained model, at the class
    quantile."""
    ac = AdmissionController()
    load_busy = ServerLoad(now=0.0, free_slots=0, queue_ahead=2,
                           scan_rate=1000.0, total_tuples=10_000)
    full_pass = 10.0
    # candidate's seed says it needs almost nothing; 3 jobs ahead (occupant
    # + 2 queued) are full passes.  The old model priced them at the
    # candidate's ~0s service and predicted a feasible finish.
    slo = QuerySLO(deadline_s=5.0)
    d = ac.decide(arrival_t=0.0, slo=slo, epsilon=0.05, load=load_busy,
                  seed_m=5000, seed_err=0.051)
    assert d.predicted_finish_t >= 3 * full_pass
    assert d.action == "shed"
    # a model trained on fast completions for this class restores admission
    model = ServiceTimeModel(quantile=0.9, min_samples=4)
    for _ in range(8):
        model.observe("normal", 0.5)
    d = AdmissionController(service_model=model).decide(
        arrival_t=0.0, slo=slo, epsilon=0.05, load=load_busy,
        seed_m=5000, seed_err=0.051)
    assert d.action == "queued"
    # the server-priced components take precedence over the per-job fallback
    load_priced = dataclasses.replace(load_busy, slot_drain_s=0.25,
                                      queue_ahead_service_s=1.0)
    d = ac.decide(arrival_t=0.0, slo=slo, epsilon=0.05, load=load_priced,
                  seed_m=5000, seed_err=0.051)
    assert d.action == "queued"
    assert d.predicted_finish_t < 2.0


def test_quantile_admission_sheds_on_tail_not_mean(setup):
    """A bimodal service history (many fast, some near-full-pass) whose p90
    is slow: the quantile-priced wait sheds a deadline the mean would have
    accepted — the tentpole's 'shed on a quantile, not the mean' behavior."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=43)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=1, synopsis_budget_tuples=0,
                  scheduler=WorkloadScheduler(SchedulerConfig())))
    t_full = store.num_tuples / srv._scan_rate
    model = srv.scheduler.service_model
    # observed history: 9 fast batch queries, 3 slow ones -> p90 ~ slow
    for _ in range(9):
        model.observe("normal", 0.05 * t_full)
    for _ in range(3):
        model.observe("normal", 0.9 * t_full)
    mean_service = (9 * 0.05 + 3 * 0.9) / 12 * t_full
    srv._service_times = [0.05 * t_full] * 9 + [0.9 * t_full] * 3
    # occupy the only slot so the candidate must wait
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6,
                     name="hold"), arrival_t=0.0)
    srv.step()
    # candidate: no seed (full-pass service), deadline covers service plus a
    # mean-priced wait but not a p90-priced one
    deadline = t_full + mean_service * 2.0
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.05,
                     name="edge"),
               slo=QuerySLO(deadline_s=deadline))
    res = {r.name: r for r in srv.run()}
    assert res["edge"].sched_outcome == "shed"
    srv.close()


# ---------------------------------------------------------------------------
# Measured-capacity fairness (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------

def test_measured_slot_capacity_derivation():
    rates = MeasuredRates(io_bytes_per_sec=5e8, cpu_tuples_per_sec=3e5,
                          round_base_us=3000.0, round_slot_us=300.0)
    # headroom 0.5: half the scan-side round cost worth of slot evaluation
    assert measured_slot_capacity(rates, 0.5) == pytest.approx(5.0)
    assert measured_slot_capacity(rates, 1.0) == pytest.approx(10.0)
    # floor at 1.0: a lone slot always gets the full window
    tight = dataclasses.replace(rates, round_slot_us=30000.0)
    assert measured_slot_capacity(tight, 0.5) == 1.0
    # fit unavailable (old calibration / degenerate slope) -> None
    assert measured_slot_capacity(None) is None
    assert measured_slot_capacity(
        dataclasses.replace(rates, round_slot_us=0.0)) is None
    assert measured_slot_capacity(
        dataclasses.replace(rates, round_base_us=0.0)) is None
    with pytest.raises(ValueError):
        measured_slot_capacity(rates, headroom=0.0)


def test_scheduler_calibrate_binds_measured_capacity():
    rates = MeasuredRates(io_bytes_per_sec=5e8, cpu_tuples_per_sec=3e5,
                          round_base_us=3000.0, round_slot_us=500.0)
    sched = WorkloadScheduler(SchedulerConfig(slot_capacity="measured"))
    assert sched.fairness.slot_capacity == math.inf    # pre-calibration
    sched.calibrate(rates)
    assert sched.fairness.slot_capacity == pytest.approx(3.0)
    sched.calibrate(None)                              # lost calibration
    assert sched.fairness.slot_capacity == math.inf
    # hand-set capacities are never overridden
    fixed = WorkloadScheduler(SchedulerConfig(slot_capacity=2.0))
    fixed.calibrate(rates)
    assert fixed.fairness.slot_capacity == 2.0


def test_measured_capacity_drives_round_weights(setup):
    """A server built with slot_capacity="measured" and a calibration whose
    fit affords ~1 slot-unit must contend two residents (weights < 1),
    where an inf capacity would give both full budget."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=47)
    rates = MeasuredRates(io_bytes_per_sec=5e8, cpu_tuples_per_sec=3e5,
                          round_base_us=1000.0, round_slot_us=500.0)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2, synopsis_budget_tuples=0,
                  measured_rates=rates,
                  scheduler=WorkloadScheduler(SchedulerConfig(
            slot_capacity="measured", shed_enabled=False,
            claim_policy="schedule"))))
    assert srv.scheduler.fairness.slot_capacity == pytest.approx(1.0)
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6, name="a"),
               arrival_t=0.0, slo=QuerySLO(priority="batch"))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6, name="b"),
               arrival_t=0.0, slo=QuerySLO(priority="interactive"))
    for _ in range(3):
        srv.step()
    w = np.asarray(srv.table.weight)
    np.testing.assert_allclose(w, [0.8, 0.2], rtol=1e-5)
    srv.close()


# ---------------------------------------------------------------------------
# Preemption (ISSUE 5 tentpole + acceptance gate)
# ---------------------------------------------------------------------------

def test_select_victim_policy():
    slos = [QuerySLO(priority="batch"), QuerySLO(priority="normal"),
            None, QuerySLO(priority="batch")]
    admit_t = [0.0, 1.0, 2.0, 3.0]
    hot = QuerySLO(deadline_s=1.0, priority="interactive")
    # lowest weight wins; among equal weights, the latest-admitted slot
    assert select_victim(hot, slos, admit_t, [True] * 4) == 3
    assert select_victim(hot, slos, admit_t, [True, True, True, False]) == 0
    # equal priority is never evicted
    norm = QuerySLO(deadline_s=1.0, priority="batch")
    assert select_victim(norm, slos, admit_t, [True] * 4) is None
    # no evictable slots
    assert select_victim(hot, slos, admit_t, [False] * 4) is None


def test_preemption_meets_deadline_only_with_it(setup):
    """ISSUE 5 acceptance: an interactive deadline that is feasible *only*
    with preemption — met with preempt=True, missed with the PR-4 behavior
    (preempt=False), and the evicted batch query still completes with an
    accurate answer, flagged sched_outcome="preempted"."""
    vals, store = setup
    truth = _truth_sum(vals)

    def serve(preempt: bool):
        cfg = EngineConfig(num_workers=2, seed=51)
        srv = OLAWorkloadServer(
                  store, cfg,
                  options=ServerOptions(max_slots=1,
                      synopsis_budget_tuples=0,
                      scheduler=WorkloadScheduler(SchedulerConfig(preempt=preempt))))
        t_full = store.num_tuples / srv._scan_rate
        # a near-census batch query holds the only slot...
        srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6,
                         name="bat"), arrival_t=0.0,
                   slo=QuerySLO(priority="batch"))
        # ...and an interactive query arrives whose deadline covers its own
        # (full-pass-bounded) service but not the batch occupant's drain
        srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.08,
                         name="hot"), arrival_t=t_full * 0.01,
                   slo=QuerySLO(deadline_s=t_full * 1.5,
                                priority="interactive"))
        res = {r.name: r for r in srv.run()}
        count = srv.preempt_count
        srv.close()
        return res, count

    res_pre, n_pre = serve(preempt=True)
    assert n_pre == 1
    assert res_pre["hot"].slo_met is True
    # the victim completed: re-admitted from its snapshot, never dropped
    bat = res_pre["bat"]
    assert bat.sched_outcome == "preempted"
    assert not bat.unserved and np.isfinite(bat.estimate)
    assert bat.seeded_tuples > 0          # snapshot seeded the re-admission
    # tuples scanned during its absence are lost to its sample (cursors
    # never rewind), so the census retires it with a small honest CI
    # rather than an exact answer — the estimate must still be inside it
    assert np.isfinite(bat.err) and bat.err < 0.05
    assert abs(bat.estimate - truth) / abs(truth) < 3 * max(bat.err, 1e-4)
    res_fifo, n_fifo = serve(preempt=False)
    assert n_fifo == 0
    assert res_fifo["hot"].slo_met is False


def test_preempt_never_evicts_for_hopeless_deadline(setup):
    """A deadline too tight even with a slot right now must shed, not
    evict: preemption that cannot save the candidate would only hurt the
    victim."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=53)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=1, synopsis_budget_tuples=0,
                  scheduler=WorkloadScheduler(SchedulerConfig(preempt=True))))
    t_full = store.num_tuples / srv._scan_rate
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6,
                     name="bat"), arrival_t=0.0,
               slo=QuerySLO(priority="batch"))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.08,
                     name="doomed"), arrival_t=t_full * 0.01,
               slo=QuerySLO(deadline_s=t_full * 1e-6,
                            priority="interactive"))
    res = {r.name: r for r in srv.run()}
    assert srv.preempt_count == 0
    assert res["doomed"].sched_outcome == "shed"
    assert res["bat"].sched_outcome == "admitted"
    srv.close()


# ---------------------------------------------------------------------------
# ε-distance-weighted variance claims (ISSUE 5 tentpole)
# ---------------------------------------------------------------------------

def test_eps_distance_weighting_flips_claim_key():
    """Two slots, two started chunks: the unweighted max key ranks chunk 0
    first (slot 0's huge variance), but slot 0 has already met its ε target
    (need 0) while far-from-target slot 1 cares about chunk 1 — the
    need-weighted key must flip the order."""
    n = 4
    m = np.zeros((2, n))
    ys = np.zeros((2, n))
    yq = np.zeros((2, n))
    m[:, [0, 1]] = 10
    ys[0, 0], yq[0, 0] = 10.0, 200.0         # slot 0: chunk 0 variance huge
    ys[1, 1], yq[1, 1] = 10.0, 60.0          # slot 1: chunk 1 variance modest
    state = SimpleNamespace(
        stats=SimpleNamespace(m=m, ysum=ys, ysq=yq),
        scan_m=np.array([10, 10, 0, 0]), closed=np.zeros(n, bool),
        head=2, schedule=np.array([2, 3, 0, 1], np.int32))
    vmax = slot_chunk_variances(state)
    assert vmax[0] > vmax[1]                 # unweighted: chunk 0 leads
    need = np.array([0.0, 3.0])              # slot 0 done, slot 1 at 4x ε
    vw = slot_chunk_variances(state, slot_need=need)
    assert vw[1] > vw[0] == 0.0              # weighted: chunk 1 leads
    out = variance_claim_order(state, np.full(n, 64), slot_need=need)
    np.testing.assert_array_equal(out, [2, 3, 1, 0])
    with pytest.raises(ValueError):
        slot_chunk_variances(state, slot_need=np.ones(3))


# ---------------------------------------------------------------------------
# eq4_cost_terms: one cost model for plan choice and admission (ISSUE 5)
# ---------------------------------------------------------------------------

def _stub_store(rng):
    sizes = rng.integers(8, 512, size=int(rng.integers(2, 40)))
    cost = float(rng.uniform(10.0, 5000.0))

    class Codec:
        record_bytes = int(rng.integers(16, 256))

        @staticmethod
        def extract_cost_per_tuple():
            return cost

    return SimpleNamespace(chunk_sizes=np.asarray(sizes), codec=Codec(),
                           num_tuples=int(sizes.sum()), num_chunks=len(sizes))


def test_eq4_cost_terms_shared_by_selectors():
    """Property (random-draw) test: select_plan's regime choice and the
    admission controller's scan rate are both pure functions of the SAME
    eq4_cost_terms output for any (store, config, rates) — a divergence
    would admit under one cost regime and plan under another."""
    rng = np.random.default_rng(101)
    for trial in range(60):
        store = _stub_store(rng)
        cfg = EngineConfig(num_workers=int(rng.integers(1, 16)),
                           io_bytes_per_sec=float(rng.uniform(1e6, 1e9)),
                           cpu_tuple_ops_per_sec=float(rng.uniform(1e7, 1e10)))
        rates = None
        if trial % 2:                        # measured-rates branch
            rates = MeasuredRates(
                io_bytes_per_sec=float(rng.uniform(1e6, 1e9)),
                cpu_tuples_per_sec=float(rng.uniform(1e3, 1e7)),
                workers=int(rng.integers(1, 16)),
                cost_per_tuple=float(rng.choice([0.0, rng.uniform(10, 5e3)])))
        t_io, t_cpu = eq4_cost_terms(store, cfg, rates)
        assert t_io > 0 and t_cpu > 0
        # deterministic: both callers see identical terms
        assert (t_io, t_cpu) == eq4_cost_terms(store, cfg, rates)
        # admission's scan rate is the overlapped-pipeline reading
        assert scan_tuples_per_s(store, cfg, rates) == pytest.approx(
            store.num_tuples / max(t_io, t_cpu))
        # select_plan's choice matches the regime the shared terms imply
        q = Query(agg="sum", expr=Linear((1.0,)),
                  epsilon=float(rng.choice([0.0, 0.05])))
        plan = select_plan(store, cfg, q, rates=rates)
        ratio = t_cpu / max(t_io, 1e-12)
        if q.epsilon <= 0:
            expect = "chunk_level"
        elif ratio < 0.5:
            expect = "holistic"
        elif ratio > 2.0:
            expect = "single_pass"
        else:
            expect = "resource_aware"
        assert plan == expect, (trial, ratio)


def test_eq4_cost_terms_rates_absent_fallback():
    """MeasuredRates-absent case: the modeled EngineConfig constants price
    the pass, and worker count divides only the CPU term."""
    rng = np.random.default_rng(7)
    store = _stub_store(rng)
    cfg = EngineConfig(num_workers=4, io_bytes_per_sec=1e8,
                       cpu_tuple_ops_per_sec=1e9)
    t_io, t_cpu = eq4_cost_terms(store, cfg, None)
    total_bytes = store.chunk_sizes.sum() * store.codec.record_bytes
    assert t_io == pytest.approx(total_bytes / 1e8)
    cfg2 = dataclasses.replace(cfg, num_workers=8)
    t_io2, t_cpu2 = eq4_cost_terms(store, cfg2, None)
    assert t_io2 == t_io
    assert t_cpu2 == pytest.approx(t_cpu / 2)
