"""Parse-once decoded-chunk cache: the budgeted cache of decoded ``(rows,
C)`` float32 blocks between the prefetcher and the kernels, the
decoded-input slot-eval fast path, and the invariants that make it safe to
leave on:

* **kernel parity** — the decoded-input kernel equals gather+parse on the
  same window (decode is row-elementwise, so parse-then-gather and
  gather-then-parse are the same bits), and a mixed raw/decoded round with
  complementary budgets sums to the all-raw round bit-for-bit;
* **in-kernel synopsis-cache emission** — with ``cache_cap > 0`` the
  streaming kernel returns exactly ``(stats (W, S, 4), cache_rows
  (W, cap, C))`` and never re-emits the full decoded slab to HBM;
* **modeled-clock neutrality** — an engine run with the cache on is
  *bit-exact* vs off on the ref backend: estimates, synopsis cache, scan
  state, and the Eq. (4) ``t_io``/``t_cpu`` clock (decoded workers keep
  as-if-raw costs; only the host-side Eq. (4) pricing sees the discount,
  via ``decoded_fraction``);
* **budget, cost-aware eviction, version invalidation** — eviction scores
  ``extract_cost × touches / recency-age``, so ASCII blocks outlive binary
  ones at equal touch history; a ``content_version`` bump clears the cache
  (the rollup tier's invalidation contract);
* **zero-copy slab assembly** — the prefetcher's ring buffers alternate and
  ``readinto`` lands file bytes directly in the slab slice, with the direct
  path disabled under store wrappers (FaultInjector) so injection still
  intercepts reads;
* **quarantine** (tests/test_faults.py holds the estimator oracle) — a
  chunk quarantined mid-scan leaves the decoded cache and the
  ``decoded_fraction`` Eq. (4) discount re-prices over the survivors;
* **server e2e** — workload answers are bit-identical cache on/off.
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax

from repro.core.engine import EngineConfig, OLAEngine
from repro.core.queries import Linear, Query, Range
from repro.data.faults import FaultConfig, FaultInjector, RetryPolicy
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.data.pipeline import DecodedChunkCache, SlabPrefetcher
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kref
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions

COEF = tuple(1.0 / (k + 1) for k in range(8))


def _queries(eps=0.04):
    return [
        Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 0.6e8),
              epsilon=eps, name="q-sum"),
        Query(agg="count", pred=Range(1, 0.0, 7e7), epsilon=eps,
              name="q-count"),
        Query(agg="avg", expr=Linear(COEF), epsilon=eps, name="q-avg"),
    ]


def _store(t=2048, chunks=12, seed=3, directory=None, codec="ascii"):
    return store_dataset(make_synthetic_zipf(t, 8, seed=seed), chunks, codec,
                         uneven=True, directory=directory)


def _cfg(**kw):
    base = dict(num_workers=4, strategy="single_pass", budget_init=32,
                seed=5, cache_cap=16, residency="stream")
    base.update(kw)
    return EngineConfig(**base)


def _no_sleep_retry(**kw):
    return RetryPolicy(sleep=lambda s: None, **kw)


def _run(store, queries, cfg, max_rounds=600):
    eng = OLAEngine(store, queries, cfg)
    if eng.pipeline is not None:
        eng.pipeline.retry = _no_sleep_retry()
    try:
        state, _ = eng.run(max_rounds=max_rounds, collect_history=False)
        pf = eng.pipeline
        return {
            "ysum": np.asarray(state.stats.ysum),
            "m": np.asarray(state.stats.m),
            "cache": np.asarray(state.cache),
            "scan_m": np.asarray(state.scan_m),
            "t_cpu": float(state.t_cpu),
            "t_io": float(state.t_io),
            "quarantined": np.asarray(state.quarantined),
            "hits": pf.decoded_hits if pf is not None else 0,
            "fraction": pf.decoded_fraction() if pf is not None else 0.0,
            "qlog": list(eng.quarantine_log),
        }
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# DecodedChunkCache units: budget, cost-aware eviction, version invalidation
# ---------------------------------------------------------------------------

def _blk(rows, cols=8, fill=1.0):
    return np.full((rows, cols), fill, np.float32)


def test_cache_budget_admission_and_accounting():
    cache = DecodedChunkCache(budget_bytes=4 * 8 * 4 * 10)  # 10 8-col rows*4
    assert not cache.put(0, _blk(100))          # oversize: rejected outright
    assert cache.put(1, _blk(4))
    assert cache.put(2, _blk(4))
    assert 1 in cache and 2 in cache and len(cache) == 2
    assert cache.tuples_cached == 8
    assert cache.bytes_cached == 2 * 4 * 8 * 4
    assert cache.get(1) is not None and cache.get(99) is None
    assert cache.drop(1) and not cache.drop(1)
    assert cache.tuples_cached == 4


def test_cache_eviction_is_cost_aware():
    """At equal touch history an ASCII block (≈100× the re-extract cost)
    must outlive a binary one; the cheapest-to-rebuild block is the victim."""
    cache = DecodedChunkCache(budget_bytes=2 * 4 * 8 * 4)   # fits two blocks
    assert cache.put(0, _blk(4), cost_per_tuple=3360.0)     # ASCII
    assert cache.put(1, _blk(4), cost_per_tuple=32.0)       # binary
    assert cache.put(2, _blk(4), cost_per_tuple=3360.0)     # forces eviction
    assert cache.evictions == 1
    assert 1 not in cache and 0 in cache and 2 in cache


def test_cache_eviction_prefers_cold_blocks():
    cache = DecodedChunkCache(budget_bytes=2 * 4 * 8 * 4, cost_per_tuple=1.0)
    assert cache.put(0, _blk(4)) and cache.put(1, _blk(4))
    for _ in range(5):
        cache.get(0)                      # chunk 0 is hot, chunk 1 cold
    assert cache.put(2, _blk(4))
    assert 1 not in cache and 0 in cache


def test_cache_content_version_invalidation():
    cache = DecodedChunkCache(budget_bytes=1 << 20)
    cache.check_version(7)
    assert cache.put(0, _blk(4))
    cache.check_version(7)                # same version: no-op
    assert 0 in cache
    cache.check_version(8)                # re-ingest: everything distrusted
    assert len(cache) == 0 and cache.bytes_cached == 0


# ---------------------------------------------------------------------------
# Kernel parity: decoded-input eval vs raw EXTRACT vs the ref oracle
# ---------------------------------------------------------------------------

def _slab_and_dec(store, workers):
    """(slab (W, R, rec) u8, dec (W, R, C) f32, rows (W,)) for the first
    ``workers`` chunks, zero-padded to the store's max chunk rows."""
    rec = store.codec.record_bytes
    rows_max = int(store.max_chunk_tuples)
    slab = np.zeros((workers, rows_max, rec), np.uint8)
    dec = np.zeros((workers, rows_max, store.codec.num_cols), np.float32)
    rows = np.zeros(workers, np.int32)
    for w in range(workers):
        raw = np.asarray(store.chunk_bytes(w)).reshape(-1, rec)
        slab[w, :raw.shape[0]] = raw
        dec[w, :raw.shape[0]] = np.asarray(store.codec.decode_ref(
            jnp.asarray(raw)), np.float32)
        rows[w] = raw.shape[0]
    return jnp.asarray(slab), jnp.asarray(dec), rows


def _slot_params(s=3, c=8, seed=0):
    rng = np.random.default_rng(seed)
    coeffs = jnp.asarray(rng.normal(size=(s, c)), jnp.float32)
    lo = np.full((s, c), -1e30, np.float32)
    hi = np.full((s, c), 1e30, np.float32)
    lo[1, 0], hi[1, 0] = 0.0, 0.6e8      # one selective range slot
    is_count = jnp.asarray([0.0, 1.0, 0.0], jnp.float32)
    gate = jnp.ones((s,), jnp.float32)
    return coeffs, jnp.asarray(lo), jnp.asarray(hi), is_count, gate


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_decoded_kernel_matches_raw_and_oracle(backend):
    store = _store(t=1024, chunks=6)
    w = 4
    slab, dec, rows = _slab_and_dec(store, w)
    rng = np.random.default_rng(1)
    b = 48
    idx = jnp.asarray(rng.integers(0, rows[:, None], size=(w, b)), jnp.int32)
    b_eff = jnp.asarray(np.minimum(rows, [48, 31, 7, 0]), jnp.int32)
    params = _slot_params()

    raw_stats = kernel_ops.slot_extract_stream(slab, idx, b_eff, *params,
                                               backend=backend)
    dec_stats = kernel_ops.slot_eval_decoded(dec, idx, b_eff, *params,
                                             backend=backend)
    oracle = kref.slot_eval_decoded_ref(dec, idx, b_eff, *params)
    if backend == "ref":
        np.testing.assert_array_equal(np.asarray(dec_stats),
                                      np.asarray(oracle))
        np.testing.assert_array_equal(np.asarray(dec_stats),
                                      np.asarray(raw_stats))
    else:
        np.testing.assert_allclose(np.asarray(dec_stats), np.asarray(oracle),
                                   rtol=1e-6, atol=1e-4)
        np.testing.assert_allclose(np.asarray(dec_stats),
                                   np.asarray(raw_stats),
                                   rtol=1e-6, atol=1e-4)


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_mixed_round_complementary_budgets_sum_exactly(backend):
    """A mixed raw/decoded round — raw workers on ``b_raw = where(dec, 0,
    b)``, decoded workers on the complement — sums to the all-raw stats:
    zero-budget workers contribute exact float zeros, so the split is not
    just close, it is the same computation routed two ways."""
    store = _store(t=1024, chunks=6)
    w = 4
    slab, dec, rows = _slab_and_dec(store, w)
    rng = np.random.default_rng(2)
    idx = jnp.asarray(rng.integers(0, rows[:, None], size=(w, 32)), jnp.int32)
    b_eff = jnp.asarray(np.minimum(rows, 32), jnp.int32)
    is_dec = jnp.asarray([True, False, True, False])
    params = _slot_params()

    full = kernel_ops.slot_extract_stream(slab, idx, b_eff, *params,
                                          backend=backend)
    b_raw = jnp.where(is_dec, 0, b_eff)
    part_raw = kernel_ops.slot_extract_stream(slab, idx, b_raw, *params,
                                              backend=backend)
    part_dec = kernel_ops.slot_eval_decoded(dec, idx, b_eff - b_raw, *params,
                                            backend=backend)
    mixed = np.asarray(part_raw) + np.asarray(part_dec)
    if backend == "ref":
        np.testing.assert_array_equal(mixed, np.asarray(full))
    else:
        np.testing.assert_allclose(mixed, np.asarray(full),
                                   rtol=1e-6, atol=1e-4)


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_stream_cache_cap_output_spec(backend):
    """With ``cache_cap > 0`` the streaming kernel's entire HBM output is
    ``(stats (W, S, 4), cache_rows (W, cap, C))`` — the synopsis-cache
    scatter moved into the kernel, so enabling the cache no longer re-emits
    the whole decoded slab.  The rows themselves must match the ref
    emission oracle."""
    store = _store(t=1024, chunks=6)
    w, cap = 4, 8
    slab, dec, rows = _slab_and_dec(store, w)
    rng = np.random.default_rng(3)
    idx = jnp.asarray(rng.integers(0, rows[:, None], size=(w, 16)), jnp.int32)
    b_eff = jnp.asarray(np.minimum(rows, [16, 9, 3, 16]), jnp.int32)
    m_before = jnp.asarray([0, 3, 7, 20], jnp.int32)
    params = _slot_params()

    res = kernel_ops.slot_extract_stream(slab, idx, b_eff, *params,
                                         cache_cap=cap, m_before=m_before,
                                         backend=backend)
    assert isinstance(res, tuple) and len(res) == 2
    stats, cache_rows = res
    assert stats.shape == (w, 3, 4)
    assert cache_rows.shape == (w, cap, store.codec.num_cols)
    oracle = kref.stream_cache_rows_ref(slab, idx, b_eff, m_before, cap,
                                        store.codec.num_cols)
    if backend == "ref":
        np.testing.assert_array_equal(np.asarray(cache_rows),
                                      np.asarray(oracle))
    else:
        np.testing.assert_allclose(np.asarray(cache_rows),
                                   np.asarray(oracle), rtol=1e-6, atol=1e-4)
    # decoded-input flavor honors the same emission contract
    res_d = kernel_ops.slot_eval_decoded(dec, idx, b_eff, *params,
                                         cache_cap=cap, m_before=m_before,
                                         backend=backend)
    assert isinstance(res_d, tuple) and len(res_d) == 2
    assert res_d[1].shape == (w, cap, store.codec.num_cols)
    np.testing.assert_allclose(np.asarray(res_d[1]), np.asarray(oracle),
                               rtol=1e-6, atol=1e-4)


# ---------------------------------------------------------------------------
# Zero-copy slab assembly: ring buffers + direct readinto gating
# ---------------------------------------------------------------------------

def test_assemble_ring_alternates_and_counts_hits(tmp_path):
    store = _store(t=512, chunks=4, directory=str(tmp_path))
    pf = SlabPrefetcher(store, num_workers=2, lookahead=2,
                        decoded_cache_bytes=1 << 22)
    try:
        assert pf._direct_readinto       # plain disk store: zero-copy path
        act = np.array([True, True])
        a = pf.assemble(np.array([0, 1]), act)
        b = pf.assemble(np.array([1, 0]), act)   # swapped assignment
        raw_a, raw_b = np.asarray(a[0]), np.asarray(b[0])
        rec = store.codec.record_bytes
        for w, j in ((0, 0), (1, 1)):
            rows = int(store.chunk_sizes[j])
            np.testing.assert_array_equal(
                raw_a[w, :rows].reshape(-1),
                np.asarray(store.chunk_bytes(j)).reshape(-1)[:rows * rec])
        # second assemble served both chunks decoded, new holds counted
        assert pf.decoded_misses == 2 and pf.decoded_hits == 2
        assert pf.extract_tuples_avoided == int(store.chunk_sizes[:2].sum())
        assert bool(np.asarray(b[2]).all()) and b[3] is True
        # all-decoded rounds skip the raw ring: the raw leaf is the cached
        # zero-row slab, not a freshly zeroed + transferred buffer
        assert raw_b.shape == (2, 0, rec)
    finally:
        pf.close()


def test_direct_readinto_disabled_under_store_wrappers():
    """FaultInjector intercepts ``chunk_bytes`` only; the zero-copy
    ``read_chunk_into`` path must stay off under a wrapper or injection
    (and CRC checks riding it) would be silently bypassed."""
    store = _store(t=512, chunks=4)
    inj = FaultInjector(store, FaultConfig())
    pf_direct = SlabPrefetcher(store, num_workers=2, lookahead=2)
    pf_wrapped = SlabPrefetcher(inj, num_workers=2, lookahead=2)
    try:
        assert not pf_wrapped._direct_readinto
        a = pf_direct.assemble(np.array([0, 1]), np.array([True, True]))
        b = pf_wrapped.assemble(np.array([0, 1]), np.array([True, True]))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        pf_direct.close()
        pf_wrapped.close()


# ---------------------------------------------------------------------------
# Engine: cache on == cache off, bit-exact (ref), including the Eq. 4 clock
# ---------------------------------------------------------------------------

KEYS = ("ysum", "m", "cache", "scan_m", "t_cpu", "t_io")


def test_engine_stream_decoded_bit_exact_ref():
    store_kw = dict(t=2048, chunks=12, seed=3)
    queries = _queries()
    off = _run(_store(**store_kw), queries, _cfg(extract_backend="ref"))
    on = _run(_store(**store_kw), queries,
              _cfg(extract_backend="ref", decoded_cache_bytes=1 << 26))
    for k in KEYS:
        np.testing.assert_array_equal(np.asarray(off[k]), np.asarray(on[k]),
                                      err_msg=k)
    assert on["hits"] > 0                  # the fast path actually ran
    assert on["fraction"] > 0.0
    assert off["hits"] == 0 and off["fraction"] == 0.0


def test_engine_stream_decoded_close_pallas():
    store_kw = dict(t=2048, chunks=12, seed=3)
    queries = _queries()
    off = _run(_store(**store_kw), queries,
               _cfg(extract_backend="pallas-interpret"))
    on = _run(_store(**store_kw), queries,
              _cfg(extract_backend="pallas-interpret",
                   decoded_cache_bytes=1 << 26))
    for k in KEYS:
        np.testing.assert_allclose(np.asarray(off[k]), np.asarray(on[k]),
                                   rtol=1e-6, atol=1e-4, err_msg=k)
    assert on["hits"] > 0


def test_engine_decoded_matches_packed_answers():
    """The decoded stream round answers the same queries as the packed
    plane: stats agree to float tolerance (different gather order)."""
    store_kw = dict(t=2048, chunks=12, seed=3)
    queries = _queries()
    packed = _run(_store(**store_kw), queries,
                  _cfg(extract_backend="ref", residency="packed"))
    dec = _run(_store(**store_kw), queries,
               _cfg(extract_backend="ref", decoded_cache_bytes=1 << 26))
    np.testing.assert_allclose(np.asarray(packed["ysum"]).sum(axis=-1),
                               np.asarray(dec["ysum"]).sum(axis=-1),
                               rtol=1e-5)


def test_tiny_budget_forces_mixed_rounds_still_bit_exact():
    """A budget fitting ~2 chunks keeps most workers raw while some run
    decoded — the mixed-mode kernel composition — and must still be
    bit-exact vs cache-off on the ref backend."""
    store_kw = dict(t=2048, chunks=12, seed=3)
    store = _store(**store_kw)
    blk_bytes = int(store.max_chunk_tuples) * 8 * 4
    queries = _queries()
    off = _run(_store(**store_kw), queries, _cfg(extract_backend="ref"))
    on = _run(_store(**store_kw), queries,
              _cfg(extract_backend="ref", decoded_cache_bytes=2 * blk_bytes))
    for k in KEYS:
        np.testing.assert_array_equal(np.asarray(off[k]), np.asarray(on[k]),
                                      err_msg=k)


# ---------------------------------------------------------------------------
# Quarantine: a lost chunk leaves the decoded cache and re-prices Eq. (4)
# ---------------------------------------------------------------------------

def test_lost_chunk_drops_from_decoded_cache_and_reprices():
    lost = 3
    store = _store(t=2048, chunks=12, seed=3)
    inj = FaultInjector(store, FaultConfig(seed=7, lost_chunks=(lost,)))
    cfg = _cfg(extract_backend="ref", decoded_cache_bytes=1 << 26)
    eng = OLAEngine(inj, _queries(), cfg)
    eng.pipeline.retry = _no_sleep_retry(max_attempts=2)
    try:
        state, _ = eng.run(max_rounds=600, collect_history=False)
        assert eng.quarantine_log == [lost]
        assert lost not in eng.pipeline.decoded
        # decoded_fraction prices only the surviving coverage
        sizes = np.asarray(inj.chunk_sizes)
        frac = eng.pipeline.decoded_fraction()
        assert 0.0 < frac <= (sizes.sum() - sizes[lost]) / sizes.sum() + 1e-9
    finally:
        eng.close()


def test_lost_chunk_decoded_on_off_same_answers():
    """Fault + cache interplay: the quarantined-population answers are
    bit-identical whether the decoded cache was on or off."""
    lost = 3
    store_kw = dict(t=2048, chunks=12, seed=3)
    fc = FaultConfig(seed=7, lost_chunks=(lost,))
    queries = _queries()
    off = _run(FaultInjector(_store(**store_kw), fc), queries,
               _cfg(extract_backend="ref"))
    on = _run(FaultInjector(_store(**store_kw), fc), queries,
              _cfg(extract_backend="ref", decoded_cache_bytes=1 << 26))
    assert off["qlog"] == on["qlog"] == [lost]
    for k in KEYS:
        np.testing.assert_array_equal(np.asarray(off[k]), np.asarray(on[k]),
                                      err_msg=k)


def test_server_quarantine_drops_decoded_and_discount():
    """The server's quarantine hook (the same one the rollup/synopsis
    invalidation rides) evicts the chunk's decoded block and recomputes the
    Eq. (4) scan rate with the shrunken ``decoded_fraction``."""
    store = _store(t=2048, chunks=12, seed=3)
    cfg = _cfg(extract_backend="ref", decoded_cache_bytes=1 << 26,
               strategy="resource_aware")
    srv = OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=2))
    try:
        for i, q in enumerate(_queries(eps=0.08)):
            srv.submit(q, arrival_t=1e-5 * i)
        srv.run()
        pf = srv.engine.pipeline
        cached = sorted(j for j in range(store.num_chunks) if j in pf.decoded)
        assert cached, "scan never populated the decoded cache"
        victim = cached[0]
        rate_before = srv._scan_rate
        frac_before = pf.decoded_fraction()
        srv.quarantine([victim])
        assert victim not in pf.decoded
        assert pf.decoded_fraction() < frac_before
        assert srv._scan_rate != rate_before   # re-priced over survivors
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Server e2e: answers bit-identical cache on/off
# ---------------------------------------------------------------------------

def test_server_answers_bit_identical_cache_on_off():
    store_kw = dict(t=2048, chunks=12, seed=3)
    workload = [(q, 1e-5 * i) for i, q in enumerate(_queries(eps=0.08))]

    def serve(decoded_bytes):
        cfg = _cfg(extract_backend="ref", strategy="resource_aware",
                   decoded_cache_bytes=decoded_bytes)
        srv = OLAWorkloadServer(
                  _store(**store_kw), cfg,
                  options=ServerOptions(max_slots=2))
        try:
            for q, at in workload:
                srv.submit(q, arrival_t=at)
            res = srv.run()
            return [(r.qid, r.estimate, r.lo, r.hi, r.err, r.tuples_seen)
                    for r in res]
        finally:
            srv.close()

    assert serve(1 << 26) == serve(0)


# ---------------------------------------------------------------------------
# SPMD: decoded rounds shard like raw rounds — cache on/off bit-exact,
# and SPMD == single-device with the cache on.  Subprocess because
# XLA_FLAGS must be set before jax initializes.
# ---------------------------------------------------------------------------

_SPMD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import numpy as np, jax
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.core.queries import Query, Linear, Range
from repro.core.engine import OLAEngine, EngineConfig
from repro.core.engine_spmd import SPMDEngine

store = store_dataset(make_synthetic_zipf(2048, 8, seed=3), 12, 'ascii',
                      uneven=True)
coef = tuple(1.0 / (k + 1) for k in range(8))
q = Query(agg='sum', expr=Linear(coef), pred=Range(0, 0.0, 0.6e8),
          epsilon=0.04, name='q-sum')

def cfg(dec):
    return EngineConfig(num_workers=4, strategy='single_pass', budget_init=32,
                        seed=5, cache_cap=16, residency='stream',
                        extract_backend='ref', decoded_cache_bytes=dec)

KEYS = ('ysum', 'm', 'cache', 'scan_m', 't_cpu', 't_io')

def run(make):
    eng = make()
    try:
        state, hist = eng.run(max_rounds=600, collect_history=True)
        ests = [float(r.estimate[0]) for r in hist]
        snap = {k: np.asarray(getattr(state.stats, k)
                              if hasattr(state.stats, k)
                              else getattr(state, k)) for k in KEYS}
        hits = eng.pipeline.decoded_hits if eng.pipeline else 0
        return ests, snap, hits
    finally:
        eng.close()

mesh = jax.make_mesh((4,), ('data',))
e_on, s_on, hits_on = run(lambda: SPMDEngine(store, [q], cfg(1 << 26), mesh))
e_off, s_off, _ = run(lambda: SPMDEngine(store, [q], cfg(0), mesh))
e_one, s_one, hits_one = run(lambda: OLAEngine(store, [q], cfg(1 << 26)))
print(json.dumps({
    "hits_on": int(hits_on),
    "hits_one": int(hits_one),
    "spmd_on_off_exact": e_on == e_off and all(
        np.array_equal(s_on[k], s_off[k]) for k in KEYS),
    "spmd_vs_single_exact": e_on == e_one and all(
        np.array_equal(s_on[k], s_one[k]) for k in KEYS),
}))
"""


@pytest.mark.slow
def test_spmd_decoded_rounds_bit_exact():
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SPMD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["hits_on"] > 0 and res["hits_one"] > 0, res
    assert res["spmd_on_off_exact"], res
    assert res["spmd_vs_single_exact"], res
