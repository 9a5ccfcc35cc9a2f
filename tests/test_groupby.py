"""Grouped OLA query plane: discovery sketch, grouped-vs-fanout oracle,
kernel parity, ServerOptions surface, and admission pricing.

The load-bearing invariant (ISSUE 10): a ``Query(group_by=...)`` over
*pre-known* group values must be bit-exact against the Section 2.2 fan-out
(:func:`repro.core.queries.group_fanout`) on the ref backend — every mask
factor in the grouped kernels is an exact 0/1 float, so a tracked cell's
sufficient stats are the same IEEE sums a dedicated fan-out slot computes.
"""

import dataclasses
import math
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

import repro.serve.ola_server as ola_server_mod
from repro.core.engine import EngineConfig, SlotOLAEngine
from repro.core.groupby import GroupSketch, promote_values, pure_buckets
from repro.core.queries import (
    GroupBy, Linear, Query, Range, empty_slot_table, encode_slot,
    expand_group_by, group_fanout, slot_table_set,
)
from repro.data.generator import make_wiki_like, store_dataset
from repro.kernels.ops import slot_extract
from repro.sched.admission import AdmissionController, ServerLoad
from repro.sched.slo import QuerySLO
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions


# ---------------------------------------------------------------------------
# discovery sketch (host-side, plain numpy)
# ---------------------------------------------------------------------------

def test_sketch_offer_evict_guaranteed_mass():
    sk = GroupSketch(2)
    sk.offer(1.0, 5.0)
    sk.offer(2.0, 3.0)
    sk.offer(1.0, 4.0)                  # tracked value accumulates
    assert sk.counts[1.0] == 9.0
    assert sk.mass == 12.0
    sk.offer(3.0, 2.0)                  # evicts the min (2.0 @ 3), inherits
    assert 2.0 not in sk.counts
    assert sk.counts[3.0] == 5.0        # floor 3 + count 2
    assert sk.guaranteed(3.0) == 2.0    # count - inherited error
    assert sk.guaranteed(1.0) == 9.0
    assert sk.top(1) == [(1.0, 9.0)]
    sk.offer(4.0, 0.0)                  # zero-count offers are ignored
    assert sk.mass == 14.0


def test_pure_buckets_moment_test():
    h = 8
    tal = np.zeros((3, h), np.float32)
    # bucket 0: 5 copies of value 3.0 -> pure
    tal[:, 0] = [5.0, 15.0, 45.0]
    # bucket 1: values 2.0 and 4.0 mixed -> nonzero variance, dropped
    tal[:, 1] = [2.0, 6.0, 20.0]
    # bucket 2: empty -> dropped
    out = dict(pure_buckets(tal))
    assert out == {3.0: 5.0}


def test_promote_values_grow_only():
    sk = GroupSketch(8)
    for v, c in [(1.0, 50.0), (2.0, 40.0), (3.0, 30.0), (4.0, 20.0)]:
        sk.offer(v, c)
    # 1.0 already tracked; two free cells -> next-heaviest untracked pair
    assert promote_values(sk, [1.0], 3) == [2.0, 3.0]
    assert promote_values(sk, [1.0, 2.0, 3.0], 3) == []   # no free cells


# ---------------------------------------------------------------------------
# engine-level oracle: grouped slot == fan-out slots, bit-exact
# ---------------------------------------------------------------------------

def _wiki_store(t=2048, chunks=8, langs=6, seed=11):
    vals, _ = make_wiki_like(t, num_languages=langs, seed=seed)
    return store_dataset(vals, chunks, "ascii", uneven=True, seed=seed)


def _drive(engine, table, rounds):
    state = engine.init_state()
    reports = []
    for _ in range(rounds):
        b = engine.budget_ladder(float(state.budget))
        state, data = engine.round_data(state)
        state, rep = engine.round_fn(b)(state, table, data, engine.speeds)
        reports.append(rep)
    return state, reports


def test_grouped_vs_fanout_bit_exact():
    """Pinned tracked cells == dedicated fan-out slots through exhaustion:
    same per-round estimates and bitwise-identical sufficient stats, and the
    ``__other__`` spill conserves the base predicate's mass."""
    store = _wiki_store()
    pinned = [0.0, 1.0, 2.0]
    base = Query(agg="sum", expr=Linear((0.0, 1.0, 0.0, 0.0)),
                 pred=Range(3, 0.0, 18.0), epsilon=1e-9)
    gq = dataclasses.replace(base, group_by=GroupBy(
        col=0, max_groups=4, top_k=3, values=pinned))
    fq = group_fanout(base, 0, pinned)

    # fixed budget ladder: both drives hand out chunks in schedule order
    cfg = EngineConfig(num_workers=4, budget_init=64, budget_min=64,
                       budget_max=64, seed=5, cache_cap=16)
    cfg_g = dataclasses.replace(cfg, max_groups=4)

    tg = empty_slot_table(1, 4, max_groups=4)
    tg = slot_table_set(tg, 0, encode_slot(gq, 4, plan="holistic",
                                           max_groups=4))
    tf = empty_slot_table(len(fq), 4)
    for i, q in enumerate(fq):
        tf = slot_table_set(tf, i, encode_slot(q, 4, plan="holistic"))

    sg, rg = _drive(SlotOLAEngine(store, 1, cfg_g), tg, 40)
    sf, rf = _drive(SlotOLAEngine(store, len(fq), cfg), tf, 40)
    assert float(np.asarray(sg.scan_m).sum()) == 2048.0   # exhausted

    for a, b in zip(rg, rf):
        ge = np.asarray(a.g_est)[0, :len(pinned)]
        fe = np.asarray(b.estimate)[:len(pinned)]
        assert np.array_equal(ge, fe, equal_nan=True), (ge, fe)

    gm = np.asarray(sg.gm)[0]
    gys = np.asarray(sg.gys)[0]
    gyq = np.asarray(sg.gyq)[0]
    gps = np.asarray(sg.gps)[0]
    for i in range(len(pinned)):
        # a live cell samples every row its slot samples, so gm == fan-out m
        assert np.array_equal(gm[i], np.asarray(sf.stats.m[i]))
        assert np.array_equal(gys[i], np.asarray(sf.stats.ysum[i]))
        assert np.array_equal(gyq[i], np.asarray(sf.stats.ysq[i]))
        assert np.array_equal(gps[i], np.asarray(sf.stats.psum[i]))

    # mass conservation: cells partition the base slot's matched rows, and
    # 0/1-indicator sums are exact integers, so psum splits exactly
    base_psum = np.asarray(sg.stats.psum[0])
    assert np.array_equal(gps.sum(axis=0), base_psum)
    # the untracked languages actually spill: __other__ saw matched rows
    assert float(gps[-1].sum()) > 0.0


def test_grouped_stream_pallas_rejected():
    store = _wiki_store(256, 2)
    cfg = EngineConfig(num_workers=2, max_groups=2, residency="stream",
                       extract_backend="pallas-interpret")
    with pytest.raises(ValueError, match="packed"):
        SlotOLAEngine(store, 1, cfg)


# ---------------------------------------------------------------------------
# kernel parity: ref oracle vs pallas interpret, grouped plane
# ---------------------------------------------------------------------------

def test_grouped_kernel_matches_ref_oracle():
    rng = np.random.default_rng(0)
    from repro.data.formats import AsciiFixedFormat

    n, m, c, w, b, s, g = 6, 37, 6, 4, 16, 3, 4
    codec = AsciiFixedFormat(c)
    vals = rng.uniform(-1e6, 1e6, (n * m, c))
    vals[:, 0] = rng.integers(0, 5, n * m)     # integer group column
    packed = jnp.asarray(codec.encode(vals).reshape(n, m, codec.record_bytes))
    jw = rng.integers(0, n, w).astype(np.int32)
    idx = rng.integers(0, m, (w, b)).astype(np.int32)
    b_eff = np.array([b, 7, 0, 3], np.int32)
    coeffs = rng.normal(size=(s, c)).astype(np.float32)
    lo = np.full((s, c), -np.inf, np.float32)
    hi = np.full((s, c), np.inf, np.float32)
    lo[:, 1] = rng.uniform(-1e6, 0, s)
    hi[:, 1] = rng.uniform(0, 1e6, s)
    is_count = np.array([0, 1, 0], np.float32)
    gate = np.array([1, 1, 1], np.float32)
    # slot 0: three tracked values + live __other__; slot 1 ungrouped;
    # slot 2: discovery mode (only __other__ live, tallies on)
    gcol = np.array([0, -1, 0], np.int32)
    gval = np.zeros((s, g), np.float32)
    gval[0, :3] = [0.0, 1.0, 2.0]
    gact = np.zeros((s, g), np.float32)
    gact[0, :3] = 1.0
    gact[0, -1] = 1.0
    gact[2, -1] = 1.0

    outs = {}
    for be in ("ref", "pallas-interpret"):
        st, _, gs, tal = slot_extract(
            packed, jw, idx, b_eff, coeffs, lo, hi, is_count, gate,
            backend=be, gcol=gcol, gval=gval, gact=gact, salt=7)
        outs[be] = (np.asarray(st), np.asarray(gs), np.asarray(tal))
    np.testing.assert_allclose(outs["ref"][0], outs["pallas-interpret"][0],
                               rtol=2e-5, atol=1e-2)
    np.testing.assert_allclose(outs["ref"][1], outs["pallas-interpret"][1],
                               rtol=2e-5, atol=1e-2)
    # tallies are integer-weighted moment sums of identical products
    np.testing.assert_array_equal(outs["ref"][2], outs["pallas-interpret"][2])
    # ungrouped slot contributes no cells or tallies
    assert np.all(outs["ref"][1][:, 1] == 0.0)
    assert np.all(outs["ref"][2][:, 1] == 0.0)


# ---------------------------------------------------------------------------
# server: NEUTRAL ungrouped bit-exactness with grouped support compiled in
# ---------------------------------------------------------------------------

def test_ungrouped_server_unchanged_by_group_capacity():
    """An ungrouped workload on a grouped-capable server (max_groups > 0) is
    round-for-round bit-identical to the max_groups=0 server."""
    store = _wiki_store(1024, 6)
    queries = [
        Query(agg="sum", expr=Linear((0.0, 1.0, 0.0, 0.0)),
              pred=Range(3, 0.0, 12.0), epsilon=0.05),
        Query(agg="count", pred=Range(0, 0.0, 3.0), epsilon=0.08),
        Query(agg="avg", expr=Linear((0.0, 0.0, 1.0, 0.0)), epsilon=0.06),
    ]

    def run(max_groups):
        cfg = EngineConfig(num_workers=2, seed=9, max_groups=max_groups)
        srv = OLAWorkloadServer(store, cfg, options=ServerOptions(
            max_slots=2, synopsis_budget_tuples=0))
        for i, q in enumerate(queries):
            srv.submit(q, arrival_t=1e-5 * i)
        trace = []
        res = srv.run(on_round=lambda s: trace.append(
            int(s.tuples_scanned)))
        out = [(r.qid, r.estimate, r.lo, r.hi, r.err, r.tuples_seen,
                r.groups) for r in res]
        return out, trace

    a = run(0)
    b = run(4)
    assert a == b


# ---------------------------------------------------------------------------
# server: online discovery, __other__ spill, top-K recall on Zipf data
# ---------------------------------------------------------------------------

def test_server_discovery_topk_recall_zipf():
    vals, _ = make_wiki_like(8192, num_languages=16, seed=0)
    store = store_dataset(vals, 12, "ascii", uneven=True, seed=0)
    cfg = EngineConfig(num_workers=4, seed=7, max_groups=8)
    srv = OLAWorkloadServer(store, cfg, options=ServerOptions(
        max_slots=2, synopsis_budget_tuples=0))
    q = Query(agg="sum", expr=Linear((0.0, 1.0, 0.0, 0.0)), epsilon=0.05,
              group_by=GroupBy(col=0, max_groups=8, top_k=5))
    srv.submit(q, arrival_t=0.0)
    res = srv.run(max_rounds=4000)
    assert len(res) == 1
    groups = res[0].groups
    assert groups is not None
    tracked = [g for g in groups if not g.is_other]
    other = [g for g in groups if g.is_other]
    assert len(other) == 1 and math.isnan(other[0].value)
    assert 1 <= len(tracked) <= 8

    # ground truth: top-5 languages by total hits
    per_lang = {}
    for lang, hits in zip(vals[:, 0], vals[:, 1]):
        per_lang[float(lang)] = per_lang.get(float(lang), 0.0) + float(hits)
    true_top = {v for v, _ in
                sorted(per_lang.items(), key=lambda kv: -kv[1])[:5]}
    got = {g.value for g in tracked}
    recall = len(true_top & got) / len(true_top)
    assert recall >= 0.9, (sorted(got), sorted(true_top))

    # spill cell absorbed the untracked languages' mass
    assert other[0].n > 0
    # tracked estimates approximate the exact per-language totals
    for gres in tracked:
        if gres.value in per_lang and per_lang[gres.value] > 0:
            assert abs(gres.estimate - per_lang[gres.value]) <= max(
                0.15 * per_lang[gres.value], 1e3), gres


def test_discovery_with_large_rounds_finds_exact_top_k():
    """One round can carry the whole warmup mass while that round's salted
    collisions hide a heavy value; promotion waits for several folds, so
    large rounds still track the exact top-K (the paper's Zipf column 15:
    the 3rd and 4th values hold 1.5% and 0.5% of the rows)."""
    from repro.data.generator import make_synthetic_zipf

    vals = make_synthetic_zipf(1 << 18, 16, seed=0)
    store = store_dataset(vals, 16, "ascii")
    cfg = EngineConfig(num_workers=8, seed=0, budget_init=4096,
                       budget_max=4096, max_groups=8)
    srv = OLAWorkloadServer(store, cfg, options=ServerOptions(
        max_slots=1, synopsis_budget_tuples=0))
    q = Query(agg="count", epsilon=0.05,
              group_by=GroupBy(col=15, max_groups=8, top_k=4))
    srv.submit(q, arrival_t=0.0, plan="single_pass")
    srv.step()
    assert srv._slot_sketch[0].mass >= 1024      # the mass gate alone opens
    assert srv._slot_groups[0] == []             # ... the rounds gate holds
    res = srv.run(max_rounds=4000)
    assert len(res) == 1
    keys, counts = np.unique(np.float32(vals[:, 15]), return_counts=True)
    top = set(keys[np.argsort(-counts)[:4]].tolist())
    tracked = {float(np.float32(g.value)) for g in res[0].groups
               if not g.is_other}
    assert top <= tracked, (sorted(top), sorted(tracked))


def test_grouped_requires_group_capacity():
    store = _wiki_store(256, 2)
    srv = OLAWorkloadServer(store, EngineConfig(num_workers=2),
                            options=ServerOptions(max_slots=1))
    q = Query(agg="count", group_by=GroupBy(col=0, max_groups=4))
    with pytest.raises(ValueError, match="max_groups"):
        srv.submit(q, arrival_t=0.0)


# ---------------------------------------------------------------------------
# API surface: expand_group_by deprecation, ServerOptions shim
# ---------------------------------------------------------------------------

def test_expand_group_by_deprecated_and_equivalent():
    base = Query(agg="sum", expr=Linear((1.0, 0.0)), pred=Range(1, 0.0, 5.0))
    with pytest.warns(DeprecationWarning, match="group_by"):
        old = expand_group_by(base, group_col=0, group_values=[1.0, 2.0])
    new = group_fanout(base, 0, [1.0, 2.0])
    assert old == new


def test_server_options_legacy_shim():
    store = _wiki_store(256, 2)
    cfg = EngineConfig(num_workers=2)
    ola_server_mod._legacy_kwargs_warned = False
    try:
        with pytest.warns(DeprecationWarning, match="ServerOptions"):
            srv = OLAWorkloadServer(store, cfg, max_slots=2)
        assert srv.max_slots == 2
        # warns once per process, not per construction
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            OLAWorkloadServer(store, cfg, max_slots=2)
    finally:
        ola_server_mod._legacy_kwargs_warned = False

    with pytest.raises(TypeError, match="max_slotz"):
        OLAWorkloadServer(store, cfg, max_slotz=2)
    with pytest.raises(TypeError):
        OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=2),
                          max_slots=2)


# ---------------------------------------------------------------------------
# admission: per-group Eq. (4) pricing
# ---------------------------------------------------------------------------

def test_admission_prices_group_cells():
    load = ServerLoad(now=0.0, free_slots=1, queue_ahead=0,
                      scan_rate=1000.0, total_tuples=100_000)
    slo = QuerySLO()

    def service(group_count, **kw):
        ctl = AdmissionController()
        return ctl.decide(arrival_t=0.0, slo=slo, epsilon=0.05, load=load,
                          group_count=group_count, **kw).predicted_service_s

    seed = dict(seed_m=1000, seed_err=0.1)
    s1 = service(0, **seed)       # CLT: 1000*(0.1/0.05)^2 - 1000 = 3000
    s5 = service(5, **seed)       # x5 cells, still under a full pass
    s50 = service(50, **seed)     # capped at one full pass (a census
    assert s1 == pytest.approx(3.0)            # answers every cell)
    assert s5 == pytest.approx(15.0)
    assert s50 == pytest.approx(100.0)
    # no seed: already the full-pass bound; cells cannot exceed it
    assert service(5) == service(0) == pytest.approx(100.0)
