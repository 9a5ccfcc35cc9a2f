"""Parity of the fused Pallas extraction path against the reference path.

``EngineConfig.extract_backend="pallas"`` routes the round's EXTRACT stage
(gather + parse + slot eval + partial stats) through the compiled fused
``kernels/slot_extract.py`` kernel on a TPU; ``"pallas-interpret"`` runs the
same kernel under the Pallas interpreter, which is what these tests (and the
CI fast job) exercise on the CPU.  The contract: the pallas engine
matches the ref engine's ``RoundReport`` and ``BiLevelStats`` to fp32
tolerance, round for round, in both query planes — the only difference is
float summation order inside the fused reductions.
"""

import dataclasses

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from repro.core.engine import EngineConfig, OLAEngine, SlotOLAEngine
from repro.core.queries import (
    And,
    Cmp,
    Linear,
    Query,
    Range,
    SquaredDiff,
    empty_slot_table,
    encode_slot,
    slot_table_set,
)
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.kernels.ops import slot_extract
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions

COEF = tuple(1.0 / (k + 1) for k in range(8))
QUERIES = [
    Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 0.6e8),
          epsilon=0.04),
    Query(agg="count", pred=Range(1, 0.0, 0.7e8), epsilon=0.06),
    Query(agg="avg", expr=Linear(COEF), epsilon=0.05),
]


def _store(t=2048, chunks=12, seed=3):
    # uneven chunk sizes: the final permutation window of every chunk is a
    # partial (padded) tile, and m_max is not a multiple of the budget ladder
    return store_dataset(make_synthetic_zipf(t, 8, seed=seed), chunks,
                         "ascii", uneven=True)


def _cfg(**kw):
    base = dict(num_workers=4, strategy="single_pass", budget_init=32,
                seed=5, cache_cap=16)
    base.update(kw)
    return EngineConfig(**base)


def _assert_report_close(ra, rb, rtol=2e-5):
    for name in ra._fields:
        a, b = np.asarray(getattr(ra, name)), np.asarray(getattr(rb, name))
        np.testing.assert_allclose(a, b, rtol=rtol, atol=1e-6,
                                   err_msg=f"RoundReport.{name}")


def _assert_stats_close(sa, sb, rtol=2e-5):
    for name in ("m", "ysum", "ysq", "psum"):
        np.testing.assert_allclose(
            np.asarray(getattr(sa, name)), np.asarray(getattr(sb, name)),
            rtol=rtol, atol=1e-6, err_msg=f"BiLevelStats.{name}")


def test_kernel_matches_ref_oracle():
    """Kernel-level parity incl. zero budgets, inactive gates, COUNT slots."""
    rng = np.random.default_rng(0)
    from repro.data.formats import AsciiFixedFormat

    n, m, c, w, b, s = 6, 37, 8, 4, 16, 5   # m % tile != 0 by construction
    codec = AsciiFixedFormat(c)
    vals = rng.uniform(-1e7, 1e7, (n * m, c))
    packed = jnp.asarray(codec.encode(vals).reshape(n, m, codec.record_bytes))
    jw = rng.integers(0, n, w).astype(np.int32)
    idx = rng.integers(0, m, (w, b)).astype(np.int32)
    b_eff = np.array([b, 7, 0, 3], np.int32)
    coeffs = rng.normal(size=(s, c)).astype(np.float32)
    lo = np.full((s, c), -np.inf, np.float32)
    hi = np.full((s, c), np.inf, np.float32)
    lo[:, 0] = rng.uniform(-1e7, 0, s)
    hi[:, 0] = rng.uniform(0, 1e7, s)
    is_count = np.array([0, 1, 0, 0, 1], np.float32)
    gate = np.array([1, 1, 0, 1, 0], np.float32)

    sr, cr = slot_extract(packed, jw, idx, b_eff, coeffs, lo, hi, is_count,
                          gate, return_cols=True, backend="ref")
    sp, cp = slot_extract(packed, jw, idx, b_eff, coeffs, lo, hi, is_count,
                          gate, return_cols=True, backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(sr), np.asarray(sp), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(cr), np.asarray(cp), rtol=1e-6)
    # gated-off slots contribute exactly nothing
    assert np.all(np.asarray(sp)[:, 2, 1:] == 0.0)


def test_frozen_mode_parity():
    """OLAEngine pallas == ref per round: report, stats, and the synopsis
    extraction cache (fed by the kernel's decoded-slab output)."""
    store = _store()
    engines = {be: OLAEngine(store, QUERIES, _cfg(extract_backend=be))
               for be in ("ref", "pallas-interpret")}
    states = {be: e.init_state() for be, e in engines.items()}
    for _ in range(12):
        reps = {}
        for be, e in engines.items():
            b = e.budget_ladder(float(states[be].budget))
            states[be], reps[be] = e.round_fn(b)(states[be], e.packed,
                                                 e.speeds)
        _assert_report_close(reps["ref"], reps["pallas-interpret"])
    _assert_stats_close(states["ref"].stats, states["pallas-interpret"].stats)
    pal = states["pallas-interpret"]
    np.testing.assert_allclose(np.asarray(states["ref"].cache),
                               np.asarray(pal.cache), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(states["ref"].scan_m),
                                  np.asarray(pal.scan_m))


def test_cmp_predicates_agree_across_backends():
    """`Cmp` boundary ops must lower to coefficient form *exactly* (closed
    bounds shift one f32 ulp), so ref and pallas agree tuple-for-tuple even
    on values equal to the threshold; '!=' has no range form and must raise
    at build, never be silently approximated."""
    # a table where column values land exactly on the comparison thresholds
    vals = np.zeros((256, 8))
    vals[:, 0] = np.tile([1.0, 2.0, 3.0, 4.0], 64)
    store = store_dataset(vals, 4, "ascii")
    qs = [Query(agg="count", pred=Cmp(0, "<=", 2.0), name="le"),
          Query(agg="count", pred=Cmp(0, ">", 2.0), name="gt"),
          Query(agg="count", pred=And((Cmp(0, ">=", 2.0), Cmp(0, "<", 4.0))),
                name="band"),
          Query(agg="count", pred=Cmp(0, "==", 3.0), name="eq")]
    finals = {}
    for be in ("ref", "pallas-interpret"):
        eng = OLAEngine(store, qs, _cfg(extract_backend=be, cache_cap=0,
                                        strategy="holistic"))
        state, _ = eng.run(max_rounds=50)
        finals[be] = np.asarray(state.stats.psum).sum(axis=1)
    np.testing.assert_array_equal(finals["ref"], finals["pallas-interpret"])
    assert finals["ref"][0] == 128  # <= includes the threshold value
    assert finals["ref"][1] == 128  # > excludes it
    with pytest.raises(ValueError, match="not range-encodable"):
        OLAEngine(store, [Query(agg="count", pred=Cmp(0, "!=", 2.0))],
                  _cfg(extract_backend="pallas-interpret"))


def test_frozen_mode_pallas_rejects_nonlinear():
    store = _store(t=512, chunks=4)
    q = Query(agg="sum", expr=SquaredDiff(0, 1), epsilon=0.05)
    with pytest.raises(ValueError, match="not linear"):
        OLAEngine(store, [q], _cfg(extract_backend="pallas-interpret"))
    OLAEngine(store, [q], _cfg(extract_backend="ref"))  # ref path still fine
    # the kernel accumulates in f32: a non-f32 stats dtype must fail loud on
    # the explicit backend (and 'auto' silently resolves to ref instead)
    with pytest.raises(ValueError, match="float32 stats"):
        OLAEngine(store, QUERIES[:1], _cfg(extract_backend="pallas-interpret",
                                           stats_dtype="bfloat16"))
    eng = OLAEngine(store, QUERIES[:1], _cfg(extract_backend="auto",
                                             stats_dtype="bfloat16"))
    assert not eng.program.extract_pallas


def test_slot_mode_parity_with_midscan_admission():
    """SlotOLAEngine pallas == ref round for round, with a query admitted
    mid-scan (round 4) and one retired early (round 8)."""
    store = _store()
    engines = {be: SlotOLAEngine(store, 4, _cfg(extract_backend=be))
               for be in ("ref", "pallas-interpret")}
    states = {be: e.init_state() for be, e in engines.items()}
    table = empty_slot_table(4, 8)
    table = slot_table_set(table, 0, encode_slot(QUERIES[0], 8,
                                                 plan="single_pass"))
    table = slot_table_set(table, 1, encode_slot(QUERIES[1], 8,
                                                 plan="single_pass"))
    for r in range(14):
        if r == 4:  # mid-scan admission into slot 2
            table = slot_table_set(table, 2, encode_slot(
                QUERIES[2], 8, plan="single_pass"))
        if r == 8:  # early retirement of slot 1
            table = table._replace(active=table.active.at[1].set(False))
        reps = {}
        for be, e in engines.items():
            b = e.budget_ladder(float(states[be].budget))
            states[be], reps[be] = e.round_fn(b)(states[be], table, e.packed,
                                                 e.speeds)
        _assert_report_close(reps["ref"], reps["pallas-interpret"])
    _assert_stats_close(states["ref"].stats, states["pallas-interpret"].stats)


def test_workload_server_on_pallas_backend():
    """End-to-end: the workload server (admission, synopsis seeding from the
    kernel-fed cache, retirement) answers the same queries on both backends."""
    store = _store()
    results = {}
    for be in ("ref", "pallas-interpret"):
        srv = OLAWorkloadServer(
                  store, _cfg(extract_backend=be),
                  options=ServerOptions(max_slots=4,
                      synopsis_budget_tuples=256))
        for q in QUERIES:
            srv.submit(q, arrival_t=0.0)
        res = srv.run(max_rounds=4000)
        assert not srv.truncated
        results[be] = res
    assert ([r.qid for r in results["ref"]]
            == [r.qid for r in results["pallas-interpret"]])
    for ra, rb in zip(results["ref"], results["pallas-interpret"]):
        assert ra.tuples_seen == rb.tuples_seen, (ra, rb)
        np.testing.assert_allclose(ra.estimate, rb.estimate, rtol=2e-5)
        np.testing.assert_allclose(ra.err, rb.err, rtol=1e-3, atol=1e-6)


def test_auto_backend_resolves_off_tpu():
    """'auto' picks ref off-TPU — no interpret-mode overhead in production
    CPU deployments — and the engine still runs."""
    store = _store(t=512, chunks=4)
    eng = OLAEngine(store, QUERIES[:1], _cfg(extract_backend="auto"))
    on_tpu = __import__("jax").default_backend() == "tpu"
    assert eng.program.extract_pallas == on_tpu
    assert eng.program.extract_backend == ("pallas" if on_tpu else "ref")
    state, hist = eng.run(max_rounds=3)
    assert len(hist) >= 1
    # 'auto' must degrade to ref (not raise) for non-linear frozen queries
    eng2 = OLAEngine(store, [Query(agg="sum", expr=SquaredDiff(0, 1),
                                   epsilon=0.05)],
                     _cfg(extract_backend="auto"))
    assert not eng2.program.extract_pallas


def test_pallas_interpret_backend_forced():
    """'pallas-interpret' is a first-class backend (the benchmark's
    correctness lane): it selects the kernel path with the interpreter
    forced regardless of platform."""
    store = _store(t=512, chunks=4)
    eng = OLAEngine(store, QUERIES[:1], _cfg(
        extract_backend="pallas-interpret"))
    assert eng.program.extract_pallas
    assert eng.program.extract_backend == "pallas-interpret"
    state, hist = eng.run(max_rounds=3)
    assert len(hist) >= 1


def test_pallas_backend_requires_tpu():
    """'pallas' is the compiled kernel: off-TPU it raises, at the kernel
    entry point and at engine build, instead of running the interpreter."""
    if __import__("jax").default_backend() == "tpu":
        pytest.skip("the compiled kernel runs here")
    store = _store(t=512, chunks=4)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        OLAEngine(store, QUERIES[:1], _cfg(extract_backend="pallas"))
    with pytest.raises(RuntimeError, match="needs a TPU"):
        SlotOLAEngine(store, 2, _cfg(extract_backend="pallas"))
    packed, _ = store.packed_device_view()
    z = np.zeros((1, 8), np.float32)
    with pytest.raises(RuntimeError, match="pallas-interpret"):
        slot_extract(jnp.asarray(packed), np.zeros(2, np.int32),
                     np.zeros((2, 8), np.int32), np.zeros(2, np.int32),
                     z, z - np.inf, z + np.inf, np.zeros(1), np.ones(1),
                     backend="pallas")
