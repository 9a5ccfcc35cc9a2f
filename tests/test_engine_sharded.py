"""Sharded residency: a table split over the chips, one claim queue per chip.

A 4-device mesh and one device running the same program with 4 queues are
bit-exact through the workload server; each device holds exactly its
quarter of the packed view; one queue is today's packed engine bit for bit;
and the stratified estimate covers the exact answer where the queues
progress unequally over strata that differ, while a pooled one does not.
The mesh runs in a subprocess because XLA_FLAGS must be set before jax
initializes.
"""

import dataclasses
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import estimators as est
from repro.core.engine import (EngineConfig, EngineProgram, OLAEngine,
                               SlotOLAEngine, queue_schedule)
from repro.core.queries import GroupBy, Having, Linear, Query, Range
from repro.data.formats import AsciiFixedFormat
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.sampling.permutation import random_chunk_order
from repro.sched.claims import variance_claim_order
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions

COEF = tuple(1.0 / (k + 1) for k in range(8))
QUERIES = [
    Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 0.6e8),
          epsilon=0.04),
    Query(agg="count", pred=Range(1, 0.0, 0.7e8), epsilon=0.06),
    Query(agg="avg", expr=Linear(COEF), epsilon=0.05),
    Query(agg="sum", expr=Linear(COEF), pred=Range(2, 0.0, 0.8e8),
          having=Having(">", 1.0), epsilon=0.02),
    Query(agg="sum", expr=Linear((0.0, 1.0) + (0.0,) * 6), epsilon=0.08,
          group_by=GroupBy(col=7, max_groups=4, top_k=2)),
    Query(agg="avg", expr=Linear(COEF), pred=Range(3, 0.0, 0.5e8),
          epsilon=0.03),
]

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import numpy as np, jax
sys.path.insert(0, os.environ["TESTS_DIR"])
from test_engine_sharded import QUERIES, serve_history, small_store
from repro.core.engine import EngineConfig

store = small_store()
cfg = EngineConfig(num_workers=8, budget_init=32, budget_min=32,
                   budget_max=32, seed=5, residency="sharded",
                   max_groups=4, extract_backend=sys.argv[1])
mesh = jax.make_mesh((4,), ("data",))
twin, twin_hist, _ = serve_history(store, cfg, queues=4)
spmd, spmd_hist, srv = serve_history(store, cfg, mesh=mesh)
full, _ = store.packed_device_view(row_multiple=32)
quarter = full.shape[0] // 4
shards = []
for s in srv.engine.packed.addressable_shards:
    lo = s.index[0].start or 0
    shards.append({"device": s.device.id, "lo": lo,
                   "rows": s.data.shape[0],
                   "same": bool(np.array_equal(np.asarray(s.data),
                                               full[lo:lo + quarter]))})
snap = srv.metrics_snapshot()
print(json.dumps({
    "twin": twin, "spmd": spmd,
    "history_same": len(twin_hist) == len(spmd_hist) and all(
        np.array_equal(a, b) for a, b in zip(twin_hist, spmd_hist)),
    "shards": shards, "quarter": quarter,
    "resident_bytes": srv.engine.resident_bytes,
    "gauge_bytes": snap["server_shard_resident_bytes"],
    "full_bytes": int(full.nbytes),
}))
"""


_FLUSH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
import numpy as np, jax
sys.path.insert(0, os.environ["TESTS_DIR"])
from test_engine_sharded import serve_history, small_store
from repro.core.engine import EngineConfig
from repro.serve.slot_writes import SlotRows

store = small_store()
mesh = jax.make_mesh((4,), ("data",))
out = {}
for residency in ("sharded", "packed"):
    cfg = EngineConfig(num_workers=8, budget_init=32, budget_min=32,
                       budget_max=32, seed=5, residency=residency,
                       max_groups=4, extract_backend="ref")
    taken = []

    def wrap(srv):
        def shardings(tree):
            return [str(x.sharding) for x in jax.tree.leaves(tree)]

        # the leaves a flush writes: statistics rows, stop flags, group cells
        first = (shardings(SlotRows.of(srv.state)), None)
        orig = srv.engine.round_fn

        def round_fn(b, mode="none"):
            fn = orig(b, mode)

            def run(state, table, data, speeds):
                taken.append((shardings(SlotRows.of(state)),
                              shardings(table)))
                return fn(state, table, data, speeds)
            return run

        srv.engine.round_fn = round_fn
        taken.append(first)

    twin, twin_hist, _ = serve_history(
        store, cfg, queues=4 if residency == "sharded" else 1)
    spmd, spmd_hist, srv = serve_history(store, cfg, mesh=mesh, wrap=wrap)
    snap = srv.metrics_snapshot()
    (state0, _), rounds = taken[0], taken[1:]
    out[residency] = {
        "same": spmd == twin and len(twin_hist) == len(spmd_hist) and all(
            np.array_equal(a, b) for a, b in zip(twin_hist, spmd_hist)),
        "answers": len(spmd), "rounds": len(rounds),
        "state_kept": all(s == state0 for s, _ in rounds),
        "table_kept": all(t == rounds[0][1] for _, t in rounds),
        "state_on_mesh": all("NamedSharding" in x for x in state0),
        "flushes": snap["server_slot_flushes_total"],
        "staged": snap["server_slot_writes_staged_total"],
    }
print(json.dumps(out))
"""


def small_store():
    return store_dataset(make_synthetic_zipf(4096, 8, seed=3), 16, "ascii",
                         uneven=True)


def serve_history(store, cfg, mesh=None, queues=1, rounds=80, wrap=None):
    """Serve QUERIES (more than the slots, so some queue and are admitted
    mid-scan with synopsis seeds) on one device with ``queues`` claim queues
    or on ``mesh``; ``wrap(server)`` runs before the first step;
    -> (answers, per-round Σx, server)."""
    engine = None
    if queues > 1:
        # the server's own extraction cache for a 256-tuple synopsis
        engine = SlotOLAEngine(store, 4, dataclasses.replace(cfg, cache_cap=64),
                               queues=queues)
    srv = OLAWorkloadServer(store, cfg, options=ServerOptions(
        max_slots=4, synopsis_budget_tuples=256, mesh=mesh, engine=engine))
    if wrap is not None:
        wrap(srv)
    for q in QUERIES:
        srv.submit(q, plan="single_pass")
    hist = []
    for _ in range(rounds):
        if not srv.step():
            break
        hist.append(np.asarray(srv.state.stats.ysum).copy())
    answers = [[r.qid, r.estimate, r.lo, r.hi, r.err, r.decision,
                r.tuples_seen] for r in srv.results]
    srv.close()
    return answers, hist, srv


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_mesh_matches_one_device_with_four_queues(backend):
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    env["TESTS_DIR"] = here
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT, backend], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res["twin"]) == len(QUERIES)
    assert res["spmd"] == res["twin"]
    assert res["history_same"]
    # each device holds exactly its quarter, in the data axis's order
    assert sorted(s["lo"] for s in res["shards"]) == [
        0, res["quarter"], 2 * res["quarter"], 3 * res["quarter"]]
    assert all(s["same"] and s["rows"] == res["quarter"]
               for s in res["shards"])
    assert res["resident_bytes"] == res["full_bytes"] // 4
    assert res["gauge_bytes"] == res["resident_bytes"]


def test_mesh_flush_keeps_shardings_and_parity():
    """On a 4-device mesh, replicated and sharded, the one update that
    applies a step's staged slot writes hands every round statistics rows,
    stop flags and group cells that keep the shardings the engine placed
    them with, and a table placed as before; the mesh still answers bit for
    bit as one device."""
    env = dict(os.environ)
    here = os.path.dirname(__file__)
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    env["TESTS_DIR"] = here
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _FLUSH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for residency, r in res.items():
        assert r["answers"] == len(QUERIES) and r["rounds"] > 0, residency
        assert r["same"] and r["state_on_mesh"], residency
        assert r["state_kept"] and r["table_kept"], residency
        assert r["staged"] >= r["flushes"] > 0, residency


def test_one_queue_is_the_packed_engine_bit_for_bit():
    store = small_store()
    cfg = EngineConfig(num_workers=8, budget_init=32, budget_min=32,
                       budget_max=32, seed=5, max_groups=4)
    packed, packed_hist, _ = serve_history(store, cfg)
    one, one_hist, _ = serve_history(
        store, dataclasses.replace(cfg, residency="sharded"))
    assert one == packed
    assert len(one_hist) == len(packed_hist)
    assert all(np.array_equal(a, b) for a, b in zip(one_hist, packed_hist))


def test_each_worker_claims_only_from_its_queue():
    """Worker w claims from queue w // (W/Q), every chunk once per pass, in
    that queue's own committed order; the heads add up to ``head``."""
    store = small_store()
    cfg = EngineConfig(num_workers=8, budget_init=16, budget_min=16,
                       budget_max=16, seed=9, strategy="holistic",
                       residency="sharded",
                       worker_speed=(1.0, 1.0, 0.5, 0.5, 1.0, 1.0, 0.25,
                                     0.25))
    eng = OLAEngine(store, [QUERIES[0]], cfg, queues=4)
    prog = eng.program
    state = eng.init_state()
    per = store.num_chunks // 4
    claims = [[] for _ in range(4)]
    for _ in range(400):
        state, rep = eng.round_fn(16)(state, eng.packed, eng.speeds)
        cur = np.asarray(state.cur)
        qhead = np.asarray(state.qhead)
        assert int(state.head) == int(qhead.sum())
        for w, c in enumerate(cur):
            if c >= 0:
                q = w // 2
                assert q * per <= c < (q + 1) * per
                j = int(np.asarray(state.schedule)[c])
                assert q * per <= j < (q + 1) * per
                if j not in claims[q]:
                    claims[q].append(j)
        if bool(rep.exhausted):
            break
    assert bool(rep.exhausted)
    assert (np.asarray(state.cur) < 0).all()
    sched = prog.schedule_np.reshape(4, per)
    for q in range(4):
        assert claims[q] == list(sched[q])
    # the committed order, split by range, keeps each range's relative order
    order = random_chunk_order(cfg.seed, store.num_chunks)
    assert list(prog.schedule_np) == [j for q in range(4)
                                      for j in order if j // per == q]


def test_topup_heads_and_claim_reorder_stay_in_their_queue():
    n, nq = 16, 4
    per = n // nq
    cfg = EngineConfig(num_workers=4, residency="sharded")
    prog = EngineProgram(codec=AsciiFixedFormat(8), queries=[QUERIES[0]],
                         config=cfg, n_chunks=n, m_max=64,
                         chunk_sizes=np.full(n, 64), queues=nq)
    sched = prog.schedule_np
    closed = np.zeros(n, bool)
    closed[sched[:3]] = True                       # queue 0: 3 closed
    closed[sched[per:2 * per]] = True              # queue 1: all closed
    head, qhead = prog.open_heads(closed, sched)
    assert list(qhead) == [3, per, 0, 0] and head == 3 + per
    one = EngineProgram(codec=AsciiFixedFormat(8), queries=[QUERIES[0]],
                        config=EngineConfig(num_workers=4), n_chunks=n,
                        m_max=64, chunk_sizes=np.full(n, 64))
    closed1 = np.zeros(n, bool)
    closed1[one.schedule_np[:5]] = True
    head1, qhead1 = one.open_heads(closed1, one.schedule_np)
    assert head1 == 5 and qhead1.shape == (0,)

    # a variance-guided reorder moves chunks only inside their segment
    rng = np.random.default_rng(0)
    m = rng.integers(0, 5, (2, n)).astype(np.float64)
    state = SimpleNamespace(
        stats=SimpleNamespace(m=m, ysum=rng.normal(size=(2, n)) * m,
                              ysq=rng.uniform(1, 9, (2, n)) * m),
        schedule=sched, head=np.int32(2), qhead=np.array([1, 0, 2, 0]),
        scan_m=m.max(axis=0).astype(np.int32), closed=np.zeros(n, bool))
    out = variance_claim_order(state, np.full(n, 64))
    assert out is not None and not np.array_equal(out, sched)
    for q, h in enumerate(state.qhead):
        seg = slice(q * per, (q + 1) * per)
        assert sorted(out[seg]) == sorted(sched[seg])
        assert list(out[q * per:q * per + h]) == list(sched[q * per:
                                                            q * per + h])


def _sorted_store(n_chunks=128, rows=32):
    """A table sorted by column 0: its four chunk ranges differ."""
    vals = make_synthetic_zipf(n_chunks * rows, 8, seed=11)
    vals = vals[np.argsort(vals[:, 0], kind="stable")]
    return vals, store_dataset(vals, n_chunks, "ascii")


def test_stratified_estimate_covers_where_pooled_does_not():
    """The queues progress unequally (worker speeds 1/4, 1/2, 1/2 and 1)
    over strata sorted by the summed column.  Over committed orders drawn
    anew, the stratified 95% CI of SUM(col 0) covers the exact float64
    answer at about its stated rate; an unweighted pooled estimate over the
    same chunks is biased toward the fast strata and does not."""
    vals, store = _sorted_store()
    exact = float(np.sum(vals[:, 0].astype(np.float32).astype(np.float64)))
    nq, per_queue = 4, 4
    speeds = tuple(s for s in (0.25, 0.5, 0.5, 1.0) for _ in range(per_queue))
    cfg = EngineConfig(num_workers=nq * per_queue, budget_init=8,
                       budget_min=8, budget_max=8, strategy="holistic",
                       residency="sharded", worker_speed=speeds)
    q = Query(agg="sum", expr=Linear((1.0,) + (0.0,) * 7), epsilon=1e-6)
    eng = OLAEngine(store, [q], cfg, queues=nq)
    step = eng.round_fn(8)
    n = store.num_chunks
    strat_cover = pooled_cover = 0
    reps = 120
    for r in range(reps):
        state = eng.init_state()._replace(schedule=jnp.asarray(
            queue_schedule(random_chunk_order(1000 + r, n), nq)))
        for _ in range(24):
            state, rep = step(state, eng.packed, eng.speeds)
        lo, hi = float(rep.lo[0]), float(rep.hi[0])
        strat_cover += lo <= exact <= hi
        tau = est.tau_hat(state.stats)[0]
        var = est.var_hat(state.stats)[0][0]
        plo, phi = est.confidence_bounds(tau, var, 0.95)
        pooled_cover += float(plo) <= exact <= float(phi)
    assert strat_cover / reps >= 0.88, strat_cover
    assert pooled_cover / reps <= 0.2, pooled_cover


def _row(case: str, nq: int, n=64, seed=7):
    """One statistics row drawn tuple by tuple: chunk sizes, sampled
    counts, Σx, Σx², Σp, and the quarantined chunks."""
    rng = np.random.default_rng(seed)
    sizes = rng.integers(40, 65, n)
    m = np.where(rng.random(n) < 0.5, rng.integers(1, 40, n), 0)
    m[1] = 1                                       # a one-tuple sample
    per = n // nq
    if case == "census":
        m[:per] = sizes[:per]                      # stratum 0 read whole
    if case == "one_chunk":
        h = min(1, nq - 1) * per                   # stratum 1 (or the one)
        m[h:h + per] = 0
        m[h] = 7                                   # ... samples one chunk
    if case == "singletons":
        m[::3] = np.minimum(m[::3], 1)             # m_j == 1 on many chunks
    match = 0.0 if case == "no_match" else 0.7     # predicate selectivity
    ysum, ysq, psum = (np.zeros(n) for _ in range(3))
    for j in range(n):
        x = rng.gamma(2.0, 30.0, m[j])
        p = (rng.random(m[j]) < match).astype(np.float64)
        ysum[j], ysq[j], psum[j] = (x * p).sum(), (x * x * p).sum(), p.sum()
    quarantined = np.zeros(n, bool)
    quarantined[[5, 40]] = True
    for a in (m, ysum, ysq, psum):
        a[quarantined] = 0
    return sizes, m, ysum, ysq, psum, quarantined


_CASES = ("partial", "census", "one_chunk", "singletons", "no_match")


@pytest.mark.parametrize(
    "case,nq", [pytest.param(c, 4, id=c) for c in _CASES]
    + [pytest.param(c, 1, id=f"{c}-one_stratum") for c in _CASES])
@pytest.mark.parametrize("agg", ["sum", "count", "avg"])
def test_host_stratified_answer_matches_the_device_estimate(agg, case, nq):
    """The serving host's float64 numpy answer of one row is the device's
    stratified estimate and CLT bounds (float32) of the same row; one
    stratum is the whole table, and Eq. (1) and (3) on the device."""
    sizes, m, ysum, ysq, psum, gone = _row(case, nq)
    n_h, m_h = est.stratum_totals(sizes, gone, nq)
    stats = est.BiLevelStats(
        M=jnp.asarray(sizes, jnp.int32), m=jnp.asarray(m, jnp.int32),
        ysum=jnp.asarray(ysum, jnp.float32)[None],
        ysq=jnp.asarray(ysq, jnp.float32)[None],
        psum=jnp.asarray(psum, jnp.float32)[None], n_total=n_h, m_total=m_h)
    sum_t, sum_v, cnt_t, cnt_v, avg_t, avg_v = est.bilevel_estimates(
        stats, nq, aggs=(agg,))
    e, v = {"sum": (sum_t, sum_v), "count": (cnt_t, cnt_v),
            "avg": (avg_t, avg_v)}[agg]
    lo, hi = est.confidence_bounds(e, v, 0.9)
    device = np.asarray([e[0], lo[0], hi[0], est.error_ratio(e, lo, hi)[0]],
                        np.float64)
    host = np.asarray(est.stratified_row_answer(
        agg, sizes, np.asarray(n_h), m, ysum, ysq, psum, 0.9))
    # AVG of a sample the predicate matched nowhere has no known variance
    assert np.isfinite(host).all() == (
        case != "one_chunk" and (case, agg) != ("no_match", "avg"))
    if nq == 1 and agg == "avg" and case == "one_chunk":
        # one sampled chunk leaves every variance infinite; the device's
        # one-stratum delta method reads inf - inf as NaN, the host (as the
        # stratified device path) as an infinite interval
        assert np.isnan(device[1:]).all() and np.isinf(host[1:]).all()
        host, device = host[:1], device[:1]
    np.testing.assert_allclose(host, device, rtol=2e-5)
