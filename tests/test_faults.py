"""Fault-tolerant scan plane: injection, retry/backoff, CRC integrity,
quarantine, and degraded-answer semantics.

Contracts under test (``repro.data.faults`` + the wiring through the
pipeline, engines, and workload server):

* :class:`RetryPolicy` — bounded exponential backoff with deterministic
  jitter; exhaustion/deadline converts the failure into a
  :class:`ChunkLostError` carrying the chunk id and retry count, while a
  direct :class:`ChunkLostError` propagates immediately;
* :class:`FaultInjector` — seeded and deterministic; an all-zero config is
  a bit-exact pass-through across every engine (ref/pallas × packed/stream)
  and the scheduled server (NEUTRAL config), so the wrapper can stay on in
  CI without perturbing any parity gate;
* per-chunk CRC32 — recorded at ingest, verified on disk re-reads and
  end-to-end by the prefetcher (injected bit flips are caught even though
  the disk bytes are fine); legacy manifests without checksums still open;
* the reader thread stashes failures per chunk id instead of swallowing
  them, and ``close()`` joins it;
* quarantine oracle — after a chunk is permanently lost, the masked
  N-slot estimator state (zeroed columns + surviving ``n_total/m_total``)
  is *bit-for-bit* the compact survivors-only computation, and a census
  run's estimate equals a fresh scan over the surviving chunks;
* acceptance gates — a seeded transient-fault run heals bit-exactly with
  zero quarantines (``degraded=False``); a permanently lost chunk finishes
  every query ``degraded=True`` over the surviving population without a
  stall or raise.
"""

import json
import zlib

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from repro.core import estimators as E
from repro.core.engine import EngineConfig, OLAEngine, quarantine_chunks
from repro.core.estimators import BiLevelStats
from repro.core.queries import Linear, Query, Range
from repro.data.chunkstore import ChunkStore
from repro.data.faults import (
    ChunkLostError,
    CorruptChunkError,
    FaultConfig,
    FaultInjector,
    RetryPolicy,
    TransientReadError,
    _unit_hash,
)
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.data.pipeline import SlabPrefetcher
from repro.sched import NEUTRAL, WorkloadScheduler
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions

COEF = tuple(1.0 / (k + 1) for k in range(8))


def _queries(eps):
    return [
        Query(agg="sum", expr=Linear(COEF), epsilon=eps, name="q-sum"),
        Query(agg="count", pred=Range(1, 0.0, 7e7), epsilon=eps,
              name="q-count"),
        Query(agg="avg", expr=Linear(COEF), epsilon=eps, name="q-avg"),
    ]


def _vals(t=512, seed=3):
    return make_synthetic_zipf(t, 8, seed=seed)


def _store(vals=None, chunks=6, directory=None):
    return store_dataset(vals if vals is not None else _vals(), chunks,
                         "ascii", directory=directory)


def _cfg(**kw):
    base = dict(num_workers=2, strategy="single_pass", budget_init=64,
                seed=5, residency="stream")
    base.update(kw)
    return EngineConfig(**base)


def _no_sleep_retry(**kw):
    return RetryPolicy(sleep=lambda s: None, **kw)


def _run_engine(store, queries, cfg, quarantine0=(), max_rounds=4000):
    """Drive an engine loop to stop/exhaustion; returns (state, last report,
    rounds).  ``quarantine0`` marks chunks lost before round 1 — the "fresh
    scan over the survivors" arm of the oracle test."""
    eng = OLAEngine(store, queries, cfg)
    if eng.pipeline is not None:
        eng.pipeline.retry = _no_sleep_retry()
    try:
        state = eng.init_state()
        if quarantine0:
            state = quarantine_chunks(state, list(quarantine0))
        rep = None
        rounds = 0
        for _ in range(max_rounds):
            b = eng.budget_ladder(float(state.budget))
            state, data = eng.round_data(state)
            state, rep = eng.round_fn(b)(state, data, eng.speeds)
            rounds += 1
            if bool(rep.all_stopped) or bool(rep.exhausted):
                break
        else:
            raise AssertionError("engine did not converge")
        return state, rep, rounds, list(eng.quarantine_log)
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# RetryPolicy units
# ---------------------------------------------------------------------------

def test_retry_policy_heals_transient_deterministically():
    sleeps = []
    pol = RetryPolicy(max_attempts=4, seed=11, sleep=sleeps.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] <= 2:
            raise TransientReadError("flaky", chunk_id=3)
        return "ok"

    out, retries = pol.call(flaky, 3)
    assert out == "ok" and retries == 2
    # backoff schedule is a pure function of (seed, chunk, attempt)
    assert sleeps == [pol.delay_s(3, 0), pol.delay_s(3, 1)]
    assert sleeps == [RetryPolicy(max_attempts=4, seed=11).delay_s(3, a)
                      for a in range(2)]
    assert sleeps[1] > sleeps[0] > 0  # exponential growth survives jitter


def test_retry_policy_exhaustion_raises_chunk_lost():
    pol = _no_sleep_retry(max_attempts=3)

    def always():
        raise OSError("EIO")

    with pytest.raises(ChunkLostError) as ei:
        pol.call(always, 7)
    assert ei.value.chunk_id == 7
    assert ei.value.retries == 3
    assert isinstance(ei.value.__cause__, OSError)


def test_retry_policy_lost_propagates_immediately():
    calls = {"n": 0}

    def gone():
        calls["n"] += 1
        raise ChunkLostError("gone", chunk_id=2)

    with pytest.raises(ChunkLostError):
        _no_sleep_retry(max_attempts=5).call(gone, 2)
    assert calls["n"] == 1  # not retried: the store says it is gone


def test_retry_policy_deadline_stops_backoff():
    sleeps = []
    pol = RetryPolicy(max_attempts=8, deadline_s=0.0, sleep=sleeps.append)

    def always():
        raise TransientReadError("flaky", chunk_id=1)

    with pytest.raises(ChunkLostError) as ei:
        pol.call(always, 1)
    assert sleeps == []          # first backoff would cross the deadline
    assert ei.value.retries == 1


# ---------------------------------------------------------------------------
# FaultInjector determinism + pass-through
# ---------------------------------------------------------------------------

def test_fault_injector_is_deterministic():
    store = _store()
    cfg = FaultConfig(seed=7, transient_rate=0.5, transient_fails=1)
    rolls = [FaultInjector(store, cfg).chunk_is_transient(j)
             for j in range(store.num_chunks)]
    assert rolls == [_unit_hash(7, "transient", j) < 0.5
                     for j in range(store.num_chunks)]
    assert any(rolls) and not all(rolls)  # seed 7 splits the 6-chunk store

    def read_all(inj):
        out = []
        for j in range(store.num_chunks):
            try:
                out.append(inj.chunk_bytes(j).tobytes())
            except TransientReadError:
                out.append(None)
        return out, dict(inj.injected)

    a = read_all(FaultInjector(store, cfg))
    b = read_all(FaultInjector(store, cfg))
    assert a == b
    assert a[1]["transient"] == sum(rolls)


def test_fault_injector_transient_heals_after_k_failures():
    store = _store()
    inj = FaultInjector(store, FaultConfig(seed=7, transient_rate=1.0,
                                           transient_fails=2))
    for _ in range(2):
        with pytest.raises(TransientReadError):
            inj.chunk_bytes(0)
    np.testing.assert_array_equal(inj.chunk_bytes(0), store.chunk_bytes(0))
    assert inj.injected["transient"] == 2


def test_fault_injector_zero_config_is_passthrough():
    store = _store()
    inj = FaultInjector(store, FaultConfig())
    for j in range(store.num_chunks):
        np.testing.assert_array_equal(inj.chunk_bytes(j),
                                      store.chunk_bytes(j))
    assert all(v == 0 for v in inj.injected.values())
    # attribute delegation: the wrapper is store-shaped
    assert inj.num_chunks == store.num_chunks
    np.testing.assert_array_equal(inj.chunk_sizes, store.chunk_sizes)


# ---------------------------------------------------------------------------
# CRC32 integrity at the ChunkStore boundary
# ---------------------------------------------------------------------------

def test_crc_recorded_and_verified_on_disk_reread(tmp_path):
    vals = _vals(t=256, seed=1)
    store = _store(vals, chunks=4, directory=str(tmp_path))
    for j in range(store.num_chunks):
        raw = store.chunk_bytes(j)
        assert store.meta[j].crc32 == zlib.crc32(raw.tobytes()) & 0xFFFFFFFF

    reopened = ChunkStore.open(str(tmp_path), "dataset")
    np.testing.assert_array_equal(reopened.chunk_bytes(2),
                                  store.chunk_bytes(2))

    # flip one byte in the backing file -> CorruptChunkError on re-read
    path = reopened.meta[1].path
    blob = bytearray(open(path, "rb").read())
    blob[5] ^= 0x04
    open(path, "wb").write(bytes(blob))
    with pytest.raises(CorruptChunkError) as ei:
        reopened.chunk_bytes(1)
    assert ei.value.chunk_id == 1

    # truncation -> short read, also CorruptChunkError
    open(path, "wb").write(bytes(blob[:-7]))
    with pytest.raises(CorruptChunkError):
        reopened.chunk_bytes(1)


def test_crc_legacy_manifest_opens_and_skips_verification(tmp_path):
    store = _store(_vals(t=256, seed=1), chunks=4, directory=str(tmp_path))
    manifest_path = str(tmp_path / "dataset.manifest.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    for m in manifest["chunks"]:
        del m["crc32"]           # pre-checksum manifest shape
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)

    legacy = ChunkStore.open(str(tmp_path), "dataset")
    assert all(m.crc32 is None for m in legacy.meta)
    np.testing.assert_array_equal(legacy.chunk_bytes(0),
                                  store.chunk_bytes(0))
    # corruption is NOT caught without a manifest CRC (size still is)
    path = legacy.meta[0].path
    blob = bytearray(open(path, "rb").read())
    blob[0] ^= 0x01
    open(path, "wb").write(bytes(blob))
    legacy.evict(0)
    assert legacy.chunk_bytes(0) is not None


# ---------------------------------------------------------------------------
# SlabPrefetcher: retry wiring, end-to-end CRC, reader-thread error slots
# ---------------------------------------------------------------------------

def test_prefetcher_retries_injected_corruption(tmp_path):
    store = _store(_vals(t=256, seed=1), chunks=4, directory=str(tmp_path))
    inj = FaultInjector(store, FaultConfig(seed=7, corrupt_chunks=(2,),
                                           corrupt_once=True))
    pf = SlabPrefetcher(inj, num_workers=2, lookahead=2,
                        retry=_no_sleep_retry(max_attempts=4))
    try:
        # the injected bit flip passes the store's own disk-boundary check
        # (the disk bytes are fine) but is caught by the prefetcher's
        # end-to-end CRC verification and healed by the retried re-read
        got = pf._read_chunk(2)
        np.testing.assert_array_equal(got, ChunkStore.open(
            str(tmp_path), "dataset").chunk_bytes(2))
        assert pf.read_retries == 1
        assert pf.chunk_reads == 1
        assert inj.injected["corrupt"] == 1
        assert pf.read_errors == {}
    finally:
        pf.close()


def test_prefetcher_persistent_corruption_exhausts_to_lost(tmp_path):
    store = _store(_vals(t=256, seed=1), chunks=4, directory=str(tmp_path))
    inj = FaultInjector(store, FaultConfig(seed=7, corrupt_chunks=(1,)))
    pf = SlabPrefetcher(inj, num_workers=2, lookahead=2,
                        retry=_no_sleep_retry(max_attempts=2))
    try:
        with pytest.raises(ChunkLostError) as ei:
            pf._read_chunk(1)
        assert ei.value.chunk_id == 1
        assert isinstance(ei.value.__cause__, CorruptChunkError)
        assert pf.read_retries == 2
    finally:
        pf.close()


def test_reader_thread_stashes_failures_and_close_joins():
    store = _store()
    inj = FaultInjector(store, FaultConfig(seed=7, lost_chunks=(4,)))
    pf = SlabPrefetcher(inj, num_workers=2, lookahead=2,
                        retry=_no_sleep_retry(max_attempts=2))
    try:
        pf.prefetch([4])
        deadline = 5.0
        import time
        t0 = time.monotonic()
        while pf.read_failures == 0 and time.monotonic() - t0 < deadline:
            time.sleep(0.01)
        assert pf.read_failures >= 1, "reader thread swallowed the failure"
        assert isinstance(pf.read_errors[4], ChunkLostError)
        # assemble retries synchronously and surfaces the loss to the caller
        with pytest.raises(ChunkLostError):
            pf.assemble(np.array([4, 0]), np.array([True, False]))
    finally:
        pf.close()
    assert not pf._reader.is_alive()     # close() joined the reader


# ---------------------------------------------------------------------------
# Zero-fault wrapper parity: ref/pallas × packed/stream + scheduled server
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", [
    "ref", pytest.param("pallas-interpret", id="pallas")])
@pytest.mark.parametrize("residency", ["packed", "stream"])
def test_zero_fault_wrapper_engine_parity(backend, residency):
    vals = _vals(t=384, seed=3)
    queries = _queries(0.05)
    cfg = _cfg(extract_backend=backend, residency=residency)

    def run(store):
        state, rep, rounds, qlog = _run_engine(store, queries, cfg)
        assert qlog == []
        return (np.asarray(rep.estimate).tobytes(),
                np.asarray(rep.lo).tobytes(),
                np.asarray(rep.hi).tobytes(), rounds, int(rep.m_tuples))

    base = run(_store(vals))
    wrapped = run(FaultInjector(_store(vals), FaultConfig()))
    assert wrapped == base


def test_zero_fault_wrapper_server_parity_neutral():
    vals = _vals(t=512, seed=3)
    cfg = EngineConfig(num_workers=2, seed=9, residency="stream")
    workload = [(q, 1e-5 * i) for i, q in enumerate(_queries(0.08))]

    def run(store):
        srv = OLAWorkloadServer(
                  store, cfg,
                  options=ServerOptions(max_slots=2,
                      scheduler=WorkloadScheduler(NEUTRAL)))
        for q, at in workload:
            srv.submit(q, arrival_t=at)
        trace = []
        res = srv.run(on_round=lambda s: trace.append(
            (int(s.tuples_scanned), int(np.asarray(s.state.head)))))
        out = [(r.qid, r.estimate, r.lo, r.hi, r.err, r.tuples_seen,
                r.degraded, r.chunks_quarantined, r.read_retries)
               for r in res]
        srv.close()
        return out, trace

    base = run(_store(vals, chunks=8))
    wrapped = run(FaultInjector(_store(vals, chunks=8), FaultConfig()))
    assert wrapped[1] == base[1], "per-round scan trace diverged"
    assert wrapped[0] == base[0], "results diverged (must be bit-exact)"
    assert all(not r[6] and r[7] == 0 for r in base[0])


# ---------------------------------------------------------------------------
# Acceptance gate 1: transient faults + retries heal bit-exactly
# ---------------------------------------------------------------------------

def test_transient_faults_heal_bit_exact_ref():
    vals = _vals()
    queries = _queries(0.05)
    cfg = _cfg()
    state0, rep0, rounds0, _ = _run_engine(_store(vals), queries, cfg)

    inj = FaultInjector(_store(vals),
                        FaultConfig(seed=7, transient_rate=0.5,
                                    transient_fails=2))
    state1, rep1, rounds1, qlog = _run_engine(inj, queries, cfg)
    assert inj.injected["transient"] > 0, "sweep injected nothing"
    assert qlog == []                        # retries absorbed every fault
    assert rounds1 == rounds0
    np.testing.assert_array_equal(np.asarray(rep1.estimate),
                                  np.asarray(rep0.estimate))
    np.testing.assert_array_equal(np.asarray(rep1.lo), np.asarray(rep0.lo))
    np.testing.assert_array_equal(np.asarray(rep1.hi), np.asarray(rep0.hi))


# ---------------------------------------------------------------------------
# Acceptance gate 2 + oracle: lost chunk -> quarantine-rescaled estimates
# ---------------------------------------------------------------------------

def _compact_survivors(stats, alive, sizes):
    """The survivors-only estimator state a fresh scan over the surviving
    chunks would hold (same samples, quarantined columns removed)."""
    k = int(alive.sum())
    m_tot = int(sizes[alive].sum())
    return BiLevelStats(
        M=jnp.asarray(np.asarray(stats.M)[alive]),
        m=jnp.asarray(np.asarray(stats.m)[..., alive]),
        ysum=jnp.asarray(np.asarray(stats.ysum)[..., alive]),
        ysq=jnp.asarray(np.asarray(stats.ysq)[..., alive]),
        psum=jnp.asarray(np.asarray(stats.psum)[..., alive]),
        n_total=k, m_total=m_tot)


def test_lost_chunk_quarantine_oracle_ref():
    vals = _vals()
    lost = 3
    queries = _queries(1e-9)     # unreachable eps -> census of the survivors
    cfg = _cfg()
    inj = FaultInjector(_store(vals), FaultConfig(seed=7, lost_chunks=(lost,)))
    state, rep, rounds, qlog = _run_engine(inj, queries, cfg)

    # no stall, no raise: the scan quarantined the chunk and ran to census
    assert qlog == [lost]
    assert bool(np.asarray(state.quarantined)[lost])
    assert bool(rep.exhausted)

    sizes = np.asarray(inj.chunk_sizes)
    alive = ~np.asarray(state.quarantined)
    assert int(np.asarray(state.stats.m)[lost]) == 0

    # --- oracle (bit-exact): masked N-slot stats with the surviving
    # population totals ARE the compact survivors-only computation --------
    masked = state.stats._replace(n_total=int(alive.sum()),
                                  m_total=int(sizes[alive].sum()))
    compact = _compact_survivors(state.stats, alive, sizes)
    for fn in (E.tau_hat, E.count_tau_hat):
        np.testing.assert_array_equal(np.asarray(fn(masked)),
                                      np.asarray(fn(compact)))
    for fn in (E.var_hat, E.count_var_hat):
        vm, okm = fn(masked)
        vc, okc = fn(compact)
        np.testing.assert_array_equal(np.asarray(vm), np.asarray(vc))
        np.testing.assert_array_equal(np.asarray(okm), np.asarray(okc))
    rm, vrm, _ = E.avg_estimate(masked)
    rc, vrc, _ = E.avg_estimate(compact)
    np.testing.assert_array_equal(np.asarray(rm), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(vrm), np.asarray(vrc))

    # the engine's reported estimate is that same rescaled computation, and
    # a census of the survivors is exact: zero-width intervals
    np.testing.assert_array_equal(np.asarray(rep.estimate)[0],
                                  np.asarray(E.tau_hat(masked))[0])
    np.testing.assert_allclose(np.asarray(rep.hi) - np.asarray(rep.lo),
                               0.0, atol=1e-6)

    # --- fresh scan over the survivors: same store, chunk marked lost
    # before round 1 -> same census answer ---------------------------------
    state2, rep2, _, _ = _run_engine(_store(vals), queries, cfg,
                                     quarantine0=(lost,))
    np.testing.assert_allclose(np.asarray(rep.estimate),
                               np.asarray(rep2.estimate), rtol=1e-5)

    # --- ground truth over the surviving tuples (f64) ---------------------
    offs = np.concatenate([[0], np.cumsum(sizes)])
    keep = np.ones(len(vals), bool)
    keep[offs[lost]:offs[lost + 1]] = False
    x = vals[keep].astype(np.float64) @ np.asarray(COEF, np.float64)
    np.testing.assert_allclose(float(np.asarray(rep.estimate)[0]),
                               float(x.sum()), rtol=1e-5)


def test_lost_chunk_server_degraded_answers():
    vals = _vals(t=512, seed=3)
    cfg = EngineConfig(num_workers=2, seed=9, residency="stream")
    inj = FaultInjector(_store(vals, chunks=8), FaultConfig())
    srv = OLAWorkloadServer(
              inj, cfg,
              options=ServerOptions(max_slots=2,
                  scheduler=WorkloadScheduler(NEUTRAL)))
    if srv.engine.pipeline is not None:
        srv.engine.pipeline.retry = _no_sleep_retry(max_attempts=2)
    # lose the first chunk the scan will claim: the quarantine lands in
    # round 1, before any retirement, so every answer must be degraded
    lost = int(np.asarray(srv.state.schedule)[0])
    inj.config = FaultConfig(seed=7, lost_chunks=(lost,))
    for i, q in enumerate(_queries(0.08)):
        srv.submit(q, arrival_t=1e-5 * i)
    res = srv.run()
    assert not srv.truncated, "lost chunk stalled the workload"
    assert srv.chunks_quarantined == 1
    assert len(res) == 3 and all(r.degraded for r in res)
    assert all(r.chunks_quarantined == 1 for r in res)

    # estimates describe the surviving population: census ground truth
    sizes = np.asarray(inj.chunk_sizes)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    keep = np.ones(len(vals), bool)
    keep[offs[lost]:offs[lost + 1]] = False
    x = vals[keep].astype(np.float64) @ np.asarray(COEF, np.float64)
    for r in res:
        if r.qid == "q-sum":
            lo, hi = float(r.lo), float(r.hi)
            assert lo <= x.sum() * (1 + 1e-4) and hi >= x.sum() * (1 - 1e-4)
    srv.close()
