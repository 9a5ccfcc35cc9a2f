"""Streaming slab pipeline: bounded-memory chunk delivery store → kernel.

Contract under test (``EngineConfig.residency="stream"``):

* the :class:`~repro.data.pipeline.SlabPrefetcher` delivers exactly the
  chunks the round's CLAIM step will hand out (host-side ``plan_claims``
  prediction == the jitted claim), with a bounded host cache;
* round-for-round estimates match ``residency="packed"`` **bit-exactly** on
  the ref backend (same gathers, same arithmetic) for the frozen and
  slot-table planes, including mid-scan admission and top-up passes under
  the workload server;
* the slab-streaming Pallas kernel (row tiles instead of whole-chunk VMEM
  windows) matches its oracle and the ref engine to fp32 tolerance;
* an engine run completes on a store whose packed view exceeds the slab
  budget, with peak raw device bytes ≤ 2 slabs + slack (subprocess test —
  clean ``jax.live_arrays`` accounting).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from repro.core.engine import EngineConfig, OLAEngine, SlotOLAEngine
from repro.core.queries import (
    Linear,
    Query,
    Range,
    empty_slot_table,
    encode_slot,
    slot_table_set,
)
from repro.data.formats import AsciiFixedFormat
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.data.pipeline import SlabPrefetcher
from repro.kernels.ops import slot_extract_stream
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions

COEF = tuple(1.0 / (k + 1) for k in range(8))
QUERIES = [
    Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 0.6e8),
          epsilon=0.04, name="q-sum"),
    Query(agg="count", pred=Range(1, 0.0, 0.7e8), epsilon=0.06,
          name="q-count"),
    Query(agg="avg", expr=Linear(COEF), epsilon=0.05, name="q-avg"),
]


def _store(t=2048, chunks=12, seed=3, directory=None):
    return store_dataset(make_synthetic_zipf(t, 8, seed=seed), chunks,
                         "ascii", uneven=True, directory=directory)


def _cfg(**kw):
    base = dict(num_workers=4, strategy="single_pass", budget_init=32,
                seed=5, cache_cap=16)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# SlabPrefetcher unit behaviour
# ---------------------------------------------------------------------------

def test_prefetcher_assembles_claimed_chunks():
    store = _store(t=512, chunks=6)
    pf = SlabPrefetcher(store, num_workers=3, row_multiple=64, lookahead=2)
    try:
        chunk_ids = np.array([4, 0, 2])
        active = np.array([True, False, True])
        slab = np.asarray(pf.assemble(chunk_ids, active))
        assert slab.shape == (3, pf.rows_max, store.codec.record_bytes)
        assert pf.rows_max % 64 == 0
        for w, j in enumerate(chunk_ids):
            raw = store.chunk_bytes(int(j))
            if active[w]:
                np.testing.assert_array_equal(slab[w, : raw.shape[0]], raw)
                assert not slab[w, raw.shape[0]:].any()
            else:
                assert not slab[w].any()   # inactive workers get zero rows
    finally:
        pf.close()


def test_prefetcher_cache_is_bounded_and_hints_warm_it():
    import time

    store = _store(t=512, chunks=8)
    pf = SlabPrefetcher(store, num_workers=2, max_cached_chunks=3)
    try:
        pf.prefetch(range(5))
        deadline = time.time() + 5.0
        while pf.chunk_reads < 5 and time.time() < deadline:
            time.sleep(0.01)
        assert pf.chunk_reads == 5          # hints were read in background
        assert len(pf._cache) <= 3          # LRU stays bounded
        reads = pf.chunk_reads
        pf.assemble(np.array([4, 3]), np.array([True, True]))
        assert pf.chunk_reads == reads      # warm chunks: no re-read
    finally:
        pf.close()


def test_plan_claims_predicts_jitted_claim():
    """The host-side claim prediction must land on exactly the chunks the
    jitted round hands out — the streaming pipeline's correctness anchor."""
    store = _store(t=1024, chunks=10)
    eng = OLAEngine(store, QUERIES[:1], _cfg())
    sched = eng.program.schedule_np
    state = eng.init_state()
    for _ in range(6):
        j_pred, active, head_pred = eng.program.plan_claims(state)
        state, rep = eng.round_fn(32)(state, eng.packed, eng.speeds)
        assert head_pred == int(state.head)
        cur = np.asarray(state.cur)
        # workers that still hold their chunk after the round must hold the
        # predicted one (closed chunks drop the worker back to IDLE)
        holding = cur >= 0
        np.testing.assert_array_equal(sched[cur[holding]], j_pred[holding])
        assert not active[cur == -2].any()  # EXHAUSTED was predicted too


# ---------------------------------------------------------------------------
# Round-for-round parity: stream == packed (bit-exact on ref)
# ---------------------------------------------------------------------------

def _run_engine(residency, store, **cfg_kw):
    eng = OLAEngine(store, QUERIES, _cfg(residency=residency, **cfg_kw))
    state, hist = eng.run(max_rounds=300)
    ests = np.array([np.asarray(r.estimate) for r in hist])
    return eng, state, ests


def test_frozen_stream_matches_packed_bit_exact():
    store = _store()
    _, sp, ep = _run_engine("packed", store)
    eng, ss, es = _run_engine("stream", store)
    assert ep.shape == es.shape
    np.testing.assert_array_equal(ep, es)
    for name in ("m", "ysum", "ysq", "psum"):
        np.testing.assert_array_equal(np.asarray(getattr(sp.stats, name)),
                                      np.asarray(getattr(ss.stats, name)))
    np.testing.assert_array_equal(np.asarray(sp.cache), np.asarray(ss.cache))
    np.testing.assert_array_equal(np.asarray(sp.scan_m),
                                  np.asarray(ss.scan_m))
    assert eng.pipeline.slabs_built == len(es)
    eng.close()


def test_slot_stream_matches_packed_with_midscan_admission():
    store = _store()
    engines = {res: SlotOLAEngine(store, 4, _cfg(residency=res))
               for res in ("packed", "stream")}
    states = {res: e.init_state() for res, e in engines.items()}
    table = empty_slot_table(4, 8)
    table = slot_table_set(table, 0, encode_slot(QUERIES[0], 8,
                                                 plan="single_pass"))
    for r in range(12):
        if r == 3:   # mid-scan admission
            table = slot_table_set(table, 1, encode_slot(
                QUERIES[1], 8, plan="single_pass"))
        for res, e in engines.items():
            b = e.budget_ladder(float(states[res].budget))
            states[res], data = e.round_data(states[res])
            states[res], rep = e.round_fn(b)(
                states[res], table, data, e.speeds)
    for name in ("m", "ysum", "ysq", "psum"):
        np.testing.assert_array_equal(
            np.asarray(getattr(states["packed"].stats, name)),
            np.asarray(getattr(states["stream"].stats, name)))


def test_server_stream_matches_packed_including_topup():
    """End-to-end workload server parity: admission, synopsis seeding, early
    leave, and a top-up pass (the prefetcher re-serves re-opened chunks)."""
    store = _store()
    out = {}
    for res in ("packed", "stream"):
        with OLAWorkloadServer(
                 store, _cfg(residency=res),
                 options=ServerOptions(max_slots=4, synopsis_budget_tuples=256)) as srv:
            srv.submit(QUERIES[0], arrival_t=0.0)
            srv.submit(QUERIES[1], arrival_t=0.0)
            srv.submit(QUERIES[2], arrival_t=0.002)   # joins mid-scan
            results = srv.run(max_rounds=4000)
            assert not srv.truncated
            out[res] = (srv.rounds, srv.topup_passes,
                        [(r.qid, r.estimate, r.tuples_seen) for r in results])
    assert out["packed"][0] == out["stream"][0]       # same round count
    assert out["packed"][1] == out["stream"][1]       # same top-up passes
    for a, b in zip(out["packed"][2], out["stream"][2]):
        assert a[0] == b[0] and a[2] == b[2]
        assert a[1] == b[1] or np.isnan(a[1]) and np.isnan(b[1])


# ---------------------------------------------------------------------------
# Slab-streaming Pallas kernel
# ---------------------------------------------------------------------------

def test_stream_kernel_matches_ref_oracle():
    rng = np.random.default_rng(0)
    w, r, c, b, s = 4, 300, 8, 64, 5    # r % row_tile != 0 exercises padding
    codec = AsciiFixedFormat(c)
    vals = rng.uniform(-1e7, 1e7, (w * r, c))
    slab = jnp.asarray(codec.encode(vals).reshape(w, r, codec.record_bytes))
    idx = rng.integers(0, r, (w, b)).astype(np.int32)
    b_eff = np.array([b, 7, 0, 33], np.int32)
    coeffs = rng.normal(size=(s, c)).astype(np.float32)
    lo = np.full((s, c), -np.inf, np.float32)
    hi = np.full((s, c), np.inf, np.float32)
    lo[:, 0] = rng.uniform(-1e7, 0, s)
    hi[:, 0] = rng.uniform(0, 1e7, s)
    is_count = np.array([0, 1, 0, 0, 1], np.float32)
    gate = np.array([1, 1, 0, 1, 0], np.float32)
    args = (idx, b_eff, coeffs, lo, hi, is_count, gate)

    sr = np.asarray(slot_extract_stream(slab, *args, backend="ref"))
    sp = np.asarray(slot_extract_stream(slab, *args,
                                        backend="pallas-interpret"))
    np.testing.assert_allclose(sr, sp, rtol=1e-5, atol=1e-3)
    assert np.all(sp[:, 2, 1:] == 0.0)          # gated-off slot contributes 0
    assert np.all(sp[:, :, 0] == b_eff[:, None])    # m column is b_eff

    # duplicated window rows must fold with multiplicity, not 0/1
    idx_dup = np.full((w, b), 5, np.int32)
    sr = np.asarray(slot_extract_stream(slab, idx_dup, *args[1:],
                                        backend="ref"))
    sp = np.asarray(slot_extract_stream(slab, idx_dup, *args[1:],
                                        backend="pallas-interpret"))
    np.testing.assert_allclose(sr, sp, rtol=1e-5, atol=1e-3)


def test_stream_engine_pallas_matches_ref():
    """residency="stream" × the Pallas kernel (interpreted): the row-tiled
    kernel drives the full engine round to fp32 tolerance against the ref path,
    including the separately-decoded synopsis cache."""
    store = _store(t=1024, chunks=8)
    states, reps = {}, {}
    for be in ("ref", "pallas-interpret"):
        eng = OLAEngine(store, QUERIES, _cfg(
            residency="stream", extract_backend=be,
            budget_min=32, budget_max=32))
        s = eng.init_state()
        for _ in range(6):
            s, data = eng.round_data(s)
            s, r = eng.round_fn(32)(s, data, eng.speeds)
        states[be], reps[be] = s, r
        eng.close()
    np.testing.assert_allclose(np.asarray(reps["ref"].estimate),
                               np.asarray(reps["pallas-interpret"].estimate),
                               rtol=2e-5, atol=1e-6)
    for name in ("ysum", "ysq", "psum"):
        np.testing.assert_allclose(
            np.asarray(getattr(states["ref"].stats, name)),
            np.asarray(getattr(states["pallas-interpret"].stats, name)),
            rtol=2e-5, atol=1e-3)
    pal = states["pallas-interpret"]
    np.testing.assert_allclose(np.asarray(states["ref"].cache),
                               np.asarray(pal.cache), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(states["ref"].scan_m),
                                  np.asarray(pal.scan_m))


# ---------------------------------------------------------------------------
# Bounded residency: the acceptance criterion
# ---------------------------------------------------------------------------

_RESIDENCY_SCRIPT = r"""
import json
import numpy as np
from repro.core.engine import OLAEngine, EngineConfig
from repro.core.queries import Query, Linear, Range
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.data.pipeline import device_resident_bytes

# 48 chunks x ~85 rows: packed view ~24x one slab (W=2 workers)
store = store_dataset(make_synthetic_zipf(4096, 8, seed=0), 48, "ascii",
                      uneven=True)
coef = tuple(1.0 / (k + 1) for k in range(8))
q = Query(agg="sum", expr=Linear(coef), pred=Range(0, 0.0, 0.5e8),
          epsilon=0.03)
cfg = EngineConfig(num_workers=2, strategy="single_pass", budget_init=64,
                   budget_min=64, budget_max=64, seed=5, residency="stream")
eng = OLAEngine(store, [q], cfg)
packed_bytes = (store.num_chunks * store.max_chunk_tuples
                * store.codec.record_bytes)
slab_bytes = eng.pipeline.slab_bytes
assert packed_bytes > 2 * slab_bytes, (packed_bytes, slab_bytes)

state = eng.init_state()
peak = 0
rounds = 0
for _ in range(2000):
    b = eng.budget_ladder(float(state.budget))
    state, data = eng.round_data(state)
    state, rep = eng.round_fn(b)(state, data, eng.speeds)
    peak = max(peak, device_resident_bytes(np.uint8))
    rounds += 1
    if bool(rep.all_stopped) or bool(rep.exhausted):
        break
print(json.dumps({
    "rounds": rounds,
    "stopped": bool(rep.all_stopped) or bool(rep.exhausted),
    "peak_u8": peak,
    "slab_bytes": slab_bytes,
    "packed_bytes": packed_bytes,
    "host_cache_chunks": len(eng.pipeline._cache),
    "capacity": eng.pipeline.capacity,
}))
"""


def test_stream_residency_stays_bounded():
    """An engine run completes on a store whose packed view exceeds the slab
    budget, with peak raw device bytes ≤ 2 slabs (double buffer) + slack.
    Subprocess: jax.live_arrays must only see this engine's buffers."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _RESIDENCY_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["stopped"], res
    budget = 2 * res["slab_bytes"] + 65536      # double buffer + slack
    assert res["peak_u8"] <= budget, res
    assert res["peak_u8"] < res["packed_bytes"], res
    assert res["host_cache_chunks"] <= res["capacity"], res


# ---------------------------------------------------------------------------
# Adaptive prefetch lookahead (measured READ/CPU rate ratio)
# ---------------------------------------------------------------------------

class _PacedStore:
    """Store proxy whose raw reads take a fixed wall time (slow-disk sim)."""

    def __init__(self, store, read_delay_s: float):
        self._store = store
        self._delay = read_delay_s

    def __getattr__(self, name):
        return getattr(self._store, name)

    def chunk_bytes(self, j):
        import time

        if self._delay > 0:
            time.sleep(self._delay)
        return self._store.chunk_bytes(j)


def _drive_prefetcher(pf, store, rounds=6, workers=2):
    order = np.arange(store.num_chunks)
    for r in range(rounds):
        ids = order[(r * workers) % store.num_chunks:][:workers]
        if len(ids) < workers:
            ids = order[:workers]
        pf.assemble(ids, np.ones(workers, bool))


def test_adaptive_lookahead_raises_on_slow_reader():
    """A store whose READ is slow relative to the round cadence must drive
    the adaptive lookahead above its base (the reader needs more runway),
    while a fast store leaves it at the base.  ROADMAP PR-3 follow-on."""
    store = _store(t=2048, chunks=12)
    slow = SlabPrefetcher(_PacedStore(store, read_delay_s=0.05),
                          num_workers=2, lookahead=2, adaptive=True,
                          device_put=lambda a: a)
    assert slow.base_lookahead == 2 and slow.max_lookahead >= 4
    _drive_prefetcher(slow, store)
    assert slow.lookahead > 2, (slow.lookahead, slow.read_seconds)
    assert slow.lookahead <= slow.max_lookahead
    # the cache is provisioned for the ceiling, so a raised lookahead never
    # causes prefetch thrash
    assert slow.capacity >= 2 * slow.num_workers + slow.max_lookahead
    slow.close()

    fast = SlabPrefetcher(_PacedStore(store, read_delay_s=0.0),
                          num_workers=2, lookahead=2, adaptive=True,
                          device_put=lambda a: a)
    import time

    order = np.arange(store.num_chunks)
    for r in range(6):
        ids = order[(r * 2) % store.num_chunks:][:2]
        if len(ids) < 2:
            ids = order[:2]
        fast.assemble(ids, np.ones(2, bool))
        time.sleep(0.01)        # compute dominates: reads stay hidden
    assert fast.lookahead == 2, fast.lookahead
    fast.close()


def test_non_adaptive_lookahead_untouched():
    """adaptive=False (the default) must never move the lookahead — the
    parity configuration for existing streaming deployments."""
    store = _store(t=2048, chunks=12)
    pf = SlabPrefetcher(_PacedStore(store, read_delay_s=0.02), num_workers=2,
                        lookahead=3, device_put=lambda a: a)
    _drive_prefetcher(pf, store, rounds=4)
    assert pf.lookahead == 3
    pf.close()
