"""Tier-1 rollup answer cache (repro.serve.rollup + server wiring).

Pins the ISSUE 6 acceptance behavior: a repeated hot-pattern query is
answered from the rollup tier without consuming any scan round; a fully
covered cell's answer matches a fresh census scan; a partially covered
cell's answer is still a valid confidence interval; and cells die when
the store's content changes or the pattern goes cold.
"""

import dataclasses

import numpy as np
import pytest

from repro.core.engine import EngineConfig, slot_stats_fold, slot_stats_snapshot
from repro.core.queries import Custom, Linear, Query, Range
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.sched import TIER1, SchedulerConfig, WorkloadScheduler
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions
from repro.serve.rollup import RollupConfig, RollupTier, pattern_key

COEF = tuple(1.0 / (k + 1) for k in range(8))


@pytest.fixture(scope="module")
def setup():
    vals = make_synthetic_zipf(4096, 8, seed=3)
    store = store_dataset(vals, 32, "ascii")
    return vals, store


def _hot(name: str, epsilon: float = 0.08, hi: float = 6e7) -> Query:
    """A fresh Query object per call — the cache must match on *pattern*,
    never on object identity."""
    return Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, hi),
                 epsilon=epsilon, name=name)


def _truth(vals, hi: float = 6e7) -> float:
    sel = (vals[:, 0] >= 0.0) & (vals[:, 0] < hi)
    return float((vals @ np.asarray(COEF)) @ sel)


# ---------------------------------------------------------------------------
# Pattern keys
# ---------------------------------------------------------------------------

def test_pattern_key_collapses_equivalent_queries():
    a = pattern_key(_hot("a", epsilon=0.08), 8)
    b = pattern_key(_hot("b", epsilon=0.01), 8)      # different ε and name
    c = pattern_key(dataclasses.replace(_hot("c"), confidence=0.99), 8)
    assert a is not None
    assert a == b == c                # accuracy knobs are not the pattern
    other = pattern_key(_hot("d", hi=7e7), 8)
    assert other != a                 # different predicate is
    count = pattern_key(Query(agg="count", pred=Range(0, 0.0, 6e7)), 8)
    assert count != a                 # different measure is
    weird = Query(agg="sum", expr=Custom(lambda c: c[..., 0] ** 2))
    assert pattern_key(weird, 8) is None   # non-linear: never cacheable


# ---------------------------------------------------------------------------
# Cell fold semantics
# ---------------------------------------------------------------------------

def test_fold_replaces_by_larger_sample_never_adds():
    cell_cfg = dict(key=("k",), query=_hot("q"), n_chunks=3, now=0.0,
                    content_version=0)
    from repro.serve.rollup import RollupCell

    cell = RollupCell(**cell_cfg)
    row1 = dict(m=np.array([4, 0, 2]), ysum=np.array([4.0, 0.0, 2.0]),
                ysq=np.array([8.0, 0.0, 3.0]), psum=np.array([4.0, 0.0, 1.0]))
    assert cell.fold(row1) == 2
    # re-folding the same row must be a no-op (replacement, not addition —
    # adding would double count the shared permutation-prefix windows)
    assert cell.fold(dict(row1)) == 0
    np.testing.assert_array_equal(cell.m, [4, 0, 2])
    # a row larger on chunk 1 only upgrades chunk 1
    row2 = dict(m=np.array([1, 5, 2]), ysum=np.array([9.0, 5.0, 9.0]),
                ysq=np.array([9.0, 7.0, 9.0]), psum=np.array([9.0, 5.0, 9.0]))
    assert cell.fold(row2) == 1
    np.testing.assert_array_equal(cell.m, [4, 5, 2])
    np.testing.assert_array_equal(cell.ysum, [4.0, 5.0, 2.0])
    np.testing.assert_array_equal(cell.covered(np.array([4, 5, 8])),
                                  [True, True, False])


# ---------------------------------------------------------------------------
# Miner / maintenance policy (no server needed)
# ---------------------------------------------------------------------------

def test_miner_promotes_after_threshold(setup):
    _, store = setup
    tier = RollupTier(store, RollupConfig(promote_hits=3))
    q = _hot("q")
    key = pattern_key(q, 8)
    assert tier.observe(q, key, now=0.0) is None
    assert tier.observe(q, key, now=0.1) is None
    cell = tier.observe(q, key, now=0.2)     # third completion promotes
    assert cell is not None and tier.get(key) is cell
    # already promoted: further completions refresh recency, not re-promote
    assert tier.observe(q, key, now=0.3) is None
    assert cell.last_hit_t == 0.3
    assert tier.promotions == 1


def test_lru_eviction_and_cold_demotion(setup):
    _, store = setup
    tier = RollupTier(store, RollupConfig(promote_hits=1, max_cells=1,
                                          cold_after_s=10.0))
    qa, qb = _hot("a", hi=5e7), _hot("b", hi=6e7)
    ka, kb = pattern_key(qa, 8), pattern_key(qb, 8)
    assert tier.observe(qa, ka, now=0.0) is not None
    assert tier.observe(qb, kb, now=1.0) is not None
    assert tier.get(ka) is None              # LRU-evicted by the second cell
    assert tier.get(kb) is not None
    assert tier.demotions == 1
    # demotion zeroed the miner count: stale log entries must not instantly
    # resurrect the cell... one fresh completion re-promotes (promote_hits=1)
    tier.maintain(now=12.0)                  # 11s > cold_after_s: b demoted
    assert tier.get(kb) is None
    assert tier.demotions == 2


def test_invalidation_on_content_version_change(setup):
    _, store = setup
    tier = RollupTier(store, RollupConfig(promote_hits=1))
    q = _hot("q")
    key = pattern_key(q, 8)
    cell = tier.observe(q, key, now=0.0)
    cell.fold(dict(m=np.ones(store.num_chunks, np.int64),
                   ysum=np.ones(store.num_chunks),
                   ysq=np.ones(store.num_chunks),
                   psum=np.ones(store.num_chunks)))
    store.mark_content_changed()
    tier.maintain(now=1.0)
    assert tier.get(key) is None             # stale aggregate dropped
    assert tier.invalidations == 1
    # the pattern is still hot in the miner: the next completion rebuilds
    assert tier.observe(q, key, now=2.0) is not None


# ---------------------------------------------------------------------------
# Engine fold-out hook
# ---------------------------------------------------------------------------

def test_slot_stats_fold_matches_snapshot(setup):
    _, store = setup
    cfg = EngineConfig(num_workers=2, seed=5)
    srv = OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=3))
    srv.submit(_hot("a", epsilon=0.02, hi=5e7), arrival_t=0.0)
    srv.submit(_hot("b", epsilon=0.02, hi=7e7), arrival_t=0.0)
    for _ in range(3):
        srv.step()
    ids = [s for s in range(3) if srv.slot_wq[s] is not None]
    assert ids, "no resident slots to fold"
    batched = slot_stats_fold(srv.state, ids)
    assert set(batched) == set(ids)
    for s in ids:
        single = slot_stats_snapshot(srv.state, s)
        for k in ("m", "ysum", "ysq", "psum"):
            np.testing.assert_array_equal(np.asarray(batched[s][k]),
                                          np.asarray(single[k]))
    assert slot_stats_fold(srv.state, []) == {}
    srv.close()


# ---------------------------------------------------------------------------
# Acceptance: hot repeat answered Tier-1 with zero scan cost
# ---------------------------------------------------------------------------

def test_hot_repeat_answered_from_rollup_without_scan_rounds(setup):
    """ISSUE 6 acceptance: after the promotion threshold, a repeated
    hot-pattern query is answered from the rollup tier — no slot, no scan
    round, no extracted tuple."""
    vals, store = setup
    srv = OLAWorkloadServer(
              store, EngineConfig(num_workers=2, seed=5),
              options=ServerOptions(max_slots=4,
                  rollup=RollupConfig(promote_hits=2)))
    srv.submit(_hot("r0"), arrival_t=0.0)
    srv.submit(_hot("r1"), arrival_t=0.0)
    srv.run()
    assert len(srv.rollup.cells) == 1        # two completions promoted it
    tuples_before, rounds_before = srv.tuples_scanned, srv.rounds

    srv.submit(_hot("r2"))
    res = srv.run()
    r2 = next(r for r in res if r.name == "r2")
    assert r2.sched_outcome == "tier1"
    assert r2.plan == "rollup"
    assert r2.rounds_resident == 0
    assert srv.tuples_scanned == tuples_before   # not one extracted tuple
    assert srv.rounds == rounds_before           # not one engine round
    assert srv.rollup.tier1_hits == 1
    # the answer is a real estimate with a CI containing the truth
    truth = _truth(vals)
    assert r2.lo <= truth <= r2.hi
    assert r2.err <= 0.08
    srv.close()


def test_fully_covered_cell_matches_fresh_census(setup):
    """A cell whose every chunk is fully extracted answers *exactly*: the
    FPC zeroes all variance, the cell holds a fresh full scan's statistics,
    and the estimate matches that scan's census row answered the way a
    cached row is (on the host, in float64) bit for bit."""
    vals, store = setup
    q_census = lambda name: _hot(name, epsilon=1e-9)   # forces a census
    cfg = EngineConfig(num_workers=2, seed=5)

    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=4,
                  rollup=RollupConfig(promote_hits=2)))
    srv.submit(q_census("c0"), arrival_t=0.0)
    srv.submit(q_census("c1"), arrival_t=0.0)
    srv.run()
    (cell,) = srv.rollup.cells.values()
    assert cell.covered(store.chunk_sizes).all()

    srv.submit(q_census("c2"))
    res = srv.run()
    r2 = next(r for r in res if r.name == "c2")
    assert r2.sched_outcome == "tier1"
    assert r2.err == 0.0                      # FPC: census answer is exact
    assert r2.tuples_seen == store.num_tuples

    fresh = OLAWorkloadServer(
                store, cfg,
                options=ServerOptions(max_slots=4, synopsis_budget_tuples=0))
    fresh.submit(q_census("ref"), arrival_t=0.0)
    (ref,) = fresh.run()
    row = slot_stats_snapshot(fresh.state, 0)
    for k in ("m", "ysum", "ysq", "psum"):
        np.testing.assert_array_equal(getattr(cell, k), row[k])
    _, census, *_ = fresh._seed_answer(q_census("ref"), seed=row)
    assert r2.estimate == census              # bit-identical, not just close
    np.testing.assert_allclose(ref.estimate, census, rtol=1e-6)
    np.testing.assert_allclose(r2.estimate, _truth(vals), rtol=1e-5)
    srv.close()
    fresh.close()


def test_partially_covered_cell_answer_is_ci_valid(setup):
    """A cell built from an early-stopping scan covers only part of each
    chunk; its Tier-1 answer must still be a statistically valid interval
    (contains the ground truth) rather than pretending to be exact."""
    vals, store = setup
    srv = OLAWorkloadServer(
              store, EngineConfig(num_workers=2, seed=5),
              options=ServerOptions(max_slots=4,
                  rollup=RollupConfig(promote_hits=2)))
    srv.submit(_hot("p0", epsilon=0.10), arrival_t=0.0)
    srv.submit(_hot("p1", epsilon=0.10), arrival_t=0.0)
    srv.run()
    (cell,) = srv.rollup.cells.values()
    assert not cell.covered(store.chunk_sizes).all(), (
        "scan ran to census; the partial-coverage scenario is vacuous")

    srv.submit(_hot("p2", epsilon=0.10))
    res = srv.run()
    r2 = next(r for r in res if r.name == "p2")
    assert r2.sched_outcome == "tier1"
    assert r2.err > 0.0                        # honest uncertainty
    assert r2.lo < r2.hi
    assert r2.lo <= _truth(vals) <= r2.hi
    srv.close()


def test_repeat_with_tighter_target_routes_tier2_with_cell_seed(setup):
    """A repeat whose ε the cell cannot meet is *not* answered Tier-1 — it
    takes a slot, but seeded from the cell's partial aggregate (richer than
    the synopsis), so it scans only the remainder."""
    _, store = setup
    srv = OLAWorkloadServer(
              store, EngineConfig(num_workers=2, seed=5),
              options=ServerOptions(max_slots=4,
                  rollup=RollupConfig(promote_hits=2)))
    srv.submit(_hot("s0", epsilon=0.10), arrival_t=0.0)
    srv.submit(_hot("s1", epsilon=0.10), arrival_t=0.0)
    srv.run()
    (cell,) = srv.rollup.cells.values()
    cell_m = int(cell.m.sum())
    assert cell_m < store.num_tuples

    srv.submit(_hot("s2", epsilon=1e-9))       # cache can't meet a census ask
    res = srv.run()
    r2 = next(r for r in res if r.name == "s2")
    assert r2.sched_outcome != "tier1"
    assert r2.seeded_tuples >= cell_m          # started from the cell, not 0
    srv.close()


def test_content_change_forces_rescan(setup):
    """After the raw bytes change, a hot repeat must NOT be served from the
    (now stale) cell — the version-pinned cache drops it and the query goes
    back to the scan."""
    _, store = setup
    srv = OLAWorkloadServer(
              store, EngineConfig(num_workers=2, seed=5),
              options=ServerOptions(max_slots=4,
                  rollup=RollupConfig(promote_hits=2)))
    srv.submit(_hot("v0"), arrival_t=0.0)
    srv.submit(_hot("v1"), arrival_t=0.0)
    srv.run()
    assert len(srv.rollup.cells) == 1
    store.mark_content_changed()

    srv.submit(_hot("v2"))
    res = srv.run()
    r2 = next(r for r in res if r.name == "v2")
    assert r2.sched_outcome != "tier1"
    assert srv.rollup.invalidations == 1
    srv.close()


def test_scheduled_path_serves_tier1(setup):
    """With the SLO scheduler active, admission's TIER1 decision routes the
    repeat to the cache before the admit/queue/shed triage."""
    _, store = setup
    sched = WorkloadScheduler(SchedulerConfig(slot_capacity=2.0))
    srv = OLAWorkloadServer(
              store, EngineConfig(num_workers=2, seed=5),
              options=ServerOptions(max_slots=4, scheduler=sched,
                  rollup=RollupConfig(promote_hits=2)))
    srv.submit(_hot("t0"), arrival_t=0.0)
    srv.submit(_hot("t1"), arrival_t=0.0)
    srv.run()
    rounds_before = srv.rounds

    srv.submit(_hot("t2"))
    res = srv.run()
    r2 = next(r for r in res if r.name == "t2")
    assert r2.sched_outcome == TIER1 == "tier1"
    assert srv.rounds == rounds_before
    srv.close()
