"""Bi-level sample synopsis (paper §6)."""

import numpy as np
import pytest

from repro.core.controller import EstimationController
from repro.core.engine import EngineConfig
from repro.core.queries import (
    TRUE, Custom, Linear, Query, Range, SquaredDiff, compile_queries)
from repro.core.synopsis import BiLevelSynopsis, SynopsisChunk
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.sampling.permutation import chunk_seed, feistel_permute

import jax.numpy as jnp


@pytest.fixture(scope="module")
def setup():
    vals = make_synthetic_zipf(4096, 8, seed=3)
    store = store_dataset(vals, 32, "ascii")
    return vals, store


COEF = tuple(1.0 / (k + 1) for k in range(8))


def test_budget_enforced_and_variance_allocation():
    syn = BiLevelSynopsis(n_chunks=4, num_cols=2, budget_tuples=100,
                          chunk_sizes=np.full(4, 1000))
    rng = np.random.default_rng(0)
    for j in range(4):
        syn.chunks[j] = SynopsisChunk(start=0, values=rng.normal(size=(50, 2)))
    variances = np.asarray([1.0, 1.0, 10.0, 0.1])
    syn._fit_budget(variances)
    assert syn.total_tuples <= 100
    # variance-driven: high-variance chunk keeps the most tuples
    assert syn.chunks[2].count > syn.chunks[3].count
    assert syn.chunks[2].count >= syn.chunks[0].count


def test_shrink_keeps_window_tail():
    """Dropping from the front preserves the permutation-window property."""
    syn = BiLevelSynopsis(n_chunks=2, num_cols=1, budget_tuples=10,
                          chunk_sizes=np.asarray([40, 40]))
    vals = np.arange(30, dtype=np.float64)[:, None]
    syn.chunks[0] = SynopsisChunk(start=0, values=vals.copy())
    syn.chunks[1] = SynopsisChunk(start=0, values=vals.copy())
    syn._fit_budget(np.asarray([1.0, 1.0]))
    ch = syn.chunks[0]
    assert ch.count <= 5 + 1
    # surviving values are the tail of the original window; start advanced
    np.testing.assert_array_equal(ch.values[:, 0],
                                  np.arange(30 - ch.count, 30))
    assert ch.start == 30 - ch.count


def test_seed_evaluates_new_query():
    syn = BiLevelSynopsis(n_chunks=3, num_cols=2, budget_tuples=1000,
                          chunk_sizes=np.full(3, 100))
    rng = np.random.default_rng(1)
    vals = rng.uniform(0, 10, (20, 2))
    syn.chunks[1] = SynopsisChunk(start=5, values=vals)
    q = Query(agg="sum", expr=Linear((2.0, 0.0)), pred=Range(1, 0.0, 5.0))
    seed = syn.seed([q], cache_cap=32)
    sel = (vals[:, 1] >= 0) & (vals[:, 1] < 5)
    np.testing.assert_allclose(seed["ysum"][0, 1],
                               (2 * vals[:, 0] * sel).sum(), rtol=1e-5)
    assert seed["m"][1] == 20
    assert seed["offset"][1] == 25     # cursor continues past the window


def _per_chunk_seed(syn, queries):
    """The reference: each cached window evaluated on its own with the JAX
    evaluator, sums taken per window."""
    evaluate = compile_queries(queries)
    n, qn = syn.n_chunks, len(queries)
    m = np.zeros(n, np.int32)
    ysum, ysq, psum = (np.zeros((qn, n), np.float32) for _ in range(3))
    for j, ch in syn.chunks.items():
        x, p = evaluate(jnp.asarray(ch.values, jnp.float32))
        x, p = np.asarray(x), np.asarray(p)
        m[j] = ch.count
        ysum[:, j] = x.sum(-1)
        ysq[:, j] = (x * x).sum(-1)
        psum[:, j] = p.sum(-1)
    return dict(m=m, ysum=ysum, ysq=ysq, psum=psum)


def _cap_windows(cap=64, n=12, num_cols=8):
    """A synopsis whose cached chunks (all but chunk 0) hold ``cap``-tuple
    windows of values on the table's [0, 1e8) scale; each window's first
    rows sit on the bounds of ``RANGE`` and of the COUNT's range."""
    syn = BiLevelSynopsis(n_chunks=n, num_cols=num_cols, budget_tuples=4096,
                          chunk_sizes=np.full(n, 1000))
    rng = np.random.default_rng(7)
    for j in range(1, n):
        vals = rng.uniform(0, 1e8, (cap, num_cols)).astype(np.float32)
        vals[:3] = np.asarray([[2e7], [7e7], [5e7]], np.float32)
        syn.chunks[j] = SynopsisChunk(start=3 * j, values=vals)
    return syn


def _one_tuple_window(syn):
    syn.chunks[4] = SynopsisChunk(start=9, values=syn.chunks[4].values[:1])


def _dropped(syn):
    assert syn.drop_chunks([2, 5, 6]) == 3


def _shrunk(syn):
    syn.budget = 200
    syn._fit_budget(np.linspace(1.0, 50.0, syn.n_chunks))
    assert syn.total_tuples <= 200


RANGE = Range(2, 2e7, 7e7)


@pytest.mark.parametrize("query,mutate", [
    (Query(agg="sum", expr=Linear(COEF), pred=RANGE), None),
    (Query(agg="count", pred=RANGE), None),
    (Query(agg="avg", expr=Linear(COEF), pred=RANGE), None),
    (Query(agg="sum", expr=Linear(COEF), pred=TRUE), None),
    (Query(agg="sum", expr=SquaredDiff(0, 3), pred=RANGE), None),
    (Query(agg="sum", expr=Linear(COEF), pred=RANGE), _one_tuple_window),
    (Query(agg="sum", expr=Linear(COEF), pred=RANGE), _dropped),
    (Query(agg="sum", expr=Linear(COEF), pred=RANGE), _shrunk),
], ids=["sum_range", "count", "avg", "no_predicate", "nonlinear_expr",
        "one_tuple_window", "after_drop_chunks", "after_fit_budget"])
def test_packed_seed_matches_per_chunk_loop(query, mutate):
    """``seed_slot`` and ``seed`` evaluate every cached window in one packed
    pass; the statistics match the per-window evaluation they replace."""
    syn = _cap_windows()
    if mutate is not None:
        mutate(syn)
    both = [query, Query(agg="count", pred=Range(5, 0.0, 5e7))]
    ref = _per_chunk_seed(syn, both)
    slot_ref = {k: v if k == "m" else v[0] for k, v in ref.items()}
    for out, want in ((syn.seed_slot(query), slot_ref),
                      (syn.seed(both, cache_cap=64), ref)):
        np.testing.assert_array_equal(out["m"], want["m"])
        assert out["m"].dtype == np.int32
        for k in ("ysum", "ysq", "psum"):
            assert out[k].dtype == np.float32
            assert out[k].shape == want[k].shape
            np.testing.assert_allclose(out[k], want[k], rtol=1e-5,
                                       err_msg=k)


@pytest.mark.parametrize("case", ["empty", "uncached_column"])
def test_seed_slot_none_without_windows_or_columns(case):
    syn = _cap_windows()
    q = Query(agg="sum", expr=Linear(COEF), pred=Range(6, 0.0, 5e7))
    if case == "empty":
        syn.chunks.clear()
    else:
        syn.columns_cached = frozenset(range(6))
    assert syn.seed_slot(q) is None


def test_plan_schedule_uncached_first():
    syn = BiLevelSynopsis(n_chunks=5, num_cols=1, budget_tuples=10,
                          chunk_sizes=np.full(5, 10))
    syn.chunks[0] = SynopsisChunk(start=0, values=np.zeros((2, 1)))
    syn.chunks[3] = SynopsisChunk(start=0, values=np.zeros((2, 1)))
    base = np.asarray([3, 1, 4, 0, 2])
    out = syn.plan_schedule(base)
    assert set(out[:3].tolist()) == {1, 4, 2}   # uncached first (orig order)
    assert out[:3].tolist() == [1, 4, 2]
    assert out[3:].tolist() == [3, 0]


def test_supports_and_rebuild():
    syn = BiLevelSynopsis(n_chunks=2, num_cols=3, budget_tuples=10,
                          chunk_sizes=np.full(2, 10))
    syn.columns_cached = frozenset({0, 1})
    assert syn.supports([Query(agg="sum", expr=Linear((1.0,)))])
    assert not syn.supports([Query(agg="sum", expr=Linear((1.0, 1.0, 1.0)))])
    assert not syn.supports([Query(agg="sum", expr=Custom(lambda c: c[..., 0]))])
    syn.chunks[0] = SynopsisChunk(start=0, values=np.zeros((2, 3)))
    syn.rebuild()
    assert len(syn.chunks) == 0 and syn.rebuilds == 1


def test_query_sequence_uses_synopsis(setup):
    """Paper Fig. 12 shape: repeat queries get cheaper through the synopsis."""
    vals, store = setup
    cfg = EngineConfig(num_workers=4, strategy="resource_aware",
                       budget_init=64, seed=5)
    ctrl = EstimationController(store, cfg, synopsis_budget_tuples=2048)
    q = Query(agg="sum", expr=Linear(COEF), epsilon=0.05)
    r1 = ctrl.run_query([q], max_rounds=4000)
    r2 = ctrl.run_query([q], max_rounds=4000)
    assert not r1.from_synopsis and r2.from_synopsis
    assert r2.chunks_ratio <= r1.chunks_ratio + 1e-9
    assert ctrl.synopsis.total_tuples <= 2048


def test_synopsis_window_consistency(setup):
    """Synopsis windows must equal the chunk's true permutation slice —
    guarantees later cursor continuation samples without replacement."""
    vals, store = setup
    cfg = EngineConfig(num_workers=4, strategy="single_pass",
                       budget_init=32, seed=7)
    ctrl = EstimationController(store, cfg, synopsis_budget_tuples=4096)
    q = Query(agg="sum", expr=Linear(COEF), epsilon=0.02)
    ctrl.run_query([q], max_rounds=4000)
    codec = store.codec
    for j, ch in list(ctrl.synopsis.chunks.items())[:5]:
        if ch.count == 0:
            continue
        m = int(store.chunk_sizes[j])
        seed = chunk_seed(cfg.seed, j)
        pos = (ch.start + np.arange(ch.count)) % m
        idx = np.asarray(feistel_permute(seed, jnp.asarray(pos), m))
        truth = np.asarray(codec.decode_ref(jnp.asarray(store.chunk_bytes(j))))[idx]
        np.testing.assert_allclose(ch.values, truth, rtol=1e-5)


def test_shrink_under_pressure_mid_flight(setup):
    """Budget pressure arriving *mid-scan* — between ``seed_slot`` (a slot
    was just seeded from the synopsis) and the next ``update_from_engine`` —
    must leave every surviving window a contiguous slice of its chunk's
    keyed permutation, so the seeded slot's future extraction stays a
    disjoint continuation (ISSUE 4 satellite)."""
    from repro.serve.ola_server import OLAWorkloadServer, ServerOptions

    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=21, strategy="single_pass",
                       budget_init=32)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2, synopsis_budget_tuples=1024))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.02,
                     name="warm"), arrival_t=0.0)
    for _ in range(4):                      # scan mid-flight, cache growing
        srv.step()
    syn = srv.synopsis
    srv._refresh_synopsis()
    assert syn.total_tuples > 0
    follow = Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 8e7),
                   epsilon=0.08, name="late")
    seed = syn.seed_slot(follow)
    assert seed is not None and seed["m"].sum() > 0

    # budget pressure arrives now, before the next absorb: the window set
    # must shrink to the new budget with keep-the-tail semantics
    syn.budget = max(16, syn.total_tuples // 4)
    for _ in range(2):                      # scan continues mid-flight
        srv.step()
    srv._refresh_synopsis()                 # update_from_engine under pressure
    assert syn.total_tuples <= syn.budget

    checked = 0
    codec = store.codec
    for j, ch in syn.chunks.items():
        if ch.count == 0:
            continue
        m = int(store.chunk_sizes[j])
        sd = chunk_seed(cfg.seed, j)
        pos = (ch.start + np.arange(ch.count)) % m
        idx = np.asarray(feistel_permute(sd, jnp.asarray(pos), m))
        truth = np.asarray(codec.decode_ref(
            jnp.asarray(store.chunk_bytes(j))))[idx]
        np.testing.assert_allclose(ch.values, truth, rtol=1e-5)
        checked += 1
    assert checked > 0

    # the shrunk synopsis still seeds and serves the follow-up correctly
    srv.submit(follow)
    res = {r.name: r for r in srv.run()}
    sel = (vals[:, 0] >= 0) & (vals[:, 0] < 8e7)
    truth_f = float((vals @ np.asarray(COEF)) @ sel)
    assert abs(res["late"].estimate - truth_f) / abs(truth_f) < 3 * 0.08
    srv.close()
