"""Workload server: slot engine parity, mid-scan admission, early leave,
synopsis-seeded slots."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import EstimationController
from repro.core.engine import (
    EngineConfig,
    OLAEngine,
    SlotOLAEngine,
    quarantine_chunks,
    slot_stats_write,
)
from repro.core.queries import (
    GroupBy,
    Having,
    Linear,
    Query,
    Range,
    empty_slot_table,
    encode_slot,
    slot_table_set,
)
from repro.core.synopsis import BiLevelSynopsis
from repro.data.generator import make_synthetic_zipf, store_dataset
from repro.sched import QuerySLO, SchedulerConfig, WorkloadScheduler
from repro.serve.ola_server import (
    MeasuredRates,
    OLAWorkloadServer,
    ServerOptions,
    load_measured_rates,
    select_plan,
)
from repro.serve.slot_writes import apply_slot_writes

COEF = tuple(1.0 / (k + 1) for k in range(8))


@pytest.fixture(scope="module")
def setup():
    vals = make_synthetic_zipf(4096, 8, seed=3)
    store = store_dataset(vals, 32, "ascii")
    return vals, store


def _truth_sum(vals):
    return float((vals @ np.asarray(COEF)).sum())


# ---------------------------------------------------------------------------
# Slot engine ≡ frozen engine for an equivalent static workload
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ["single_pass", "chunk_level",
                                      "holistic", "resource_aware"])
def test_slot_engine_matches_frozen_engine(setup, strategy):
    """A single query run through the dynamic slot table must reproduce the
    frozen-query engine round for round (same scan, same estimators), for
    every plan/strategy."""
    vals, store = setup
    q = Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 6e7),
              epsilon=0.04)
    cfg = EngineConfig(num_workers=2, strategy=strategy, seed=5)

    frozen = OLAEngine(store, [q], cfg)
    slot = SlotOLAEngine(store, max_slots=3, config=cfg)
    table = slot_table_set(empty_slot_table(3, 8),
                           0, encode_slot(q, 8, plan=strategy))

    fs = frozen.init_state()
    ss = slot.init_state()
    ss = ss._replace(stopped=ss.stopped.at[0].set(False))
    for _ in range(200):
        b = frozen.budget_ladder(float(fs.budget))
        assert b == slot.budget_ladder(float(ss.budget))
        fs, fr = frozen.round_fn(b)(fs, frozen.packed, frozen.speeds)
        ss, sr = slot.round_fn(b)(ss, table, slot.packed, slot.speeds)
        np.testing.assert_allclose(np.asarray(fr.estimate[0]),
                                   np.asarray(sr.estimate[0]), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(fr.err[0]),
                                   np.asarray(sr.err[0]), rtol=1e-4, atol=1e-7)
        np.testing.assert_array_equal(np.asarray(fs.scan_m),
                                      np.asarray(ss.scan_m))
        if bool(fr.all_stopped) or bool(fr.exhausted):
            assert bool(ss.stopped[0])
            break
    else:
        pytest.fail("frozen engine never stopped")


def test_per_slot_confidence_honored(setup):
    """Two slots running the same query at different confidence levels must
    report interval widths scaled by their own z — not an engine-wide one."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, strategy="single_pass", seed=5)
    eng = SlotOLAEngine(store, max_slots=2, config=cfg)
    q_lo = Query(agg="sum", expr=Linear(COEF), epsilon=1e-6, confidence=0.80)
    q_hi = Query(agg="sum", expr=Linear(COEF), epsilon=1e-6, confidence=0.99)
    table = empty_slot_table(2, 8)
    table = slot_table_set(table, 0, encode_slot(q_lo, 8))
    table = slot_table_set(table, 1, encode_slot(q_hi, 8))
    state = eng.init_state()
    state = state._replace(stopped=state.stopped & False)
    for _ in range(3):
        b = eng.budget_ladder(float(state.budget))
        state, rep = eng.round_fn(b)(state, table, eng.packed, eng.speeds)
    w_lo = float(rep.hi[0] - rep.lo[0])
    w_hi = float(rep.hi[1] - rep.lo[1])
    # identical stats, so widths differ exactly by the z ratio (1.282/2.576)
    from jax.scipy.special import ndtri
    z_ratio = float(ndtri(0.995) / ndtri(0.90))
    assert w_lo > 0
    np.testing.assert_allclose(w_hi / w_lo, z_ratio, rtol=1e-4)


# ---------------------------------------------------------------------------
# Mid-scan admission
# ---------------------------------------------------------------------------

def test_mid_scan_admission_matches_cold_start(setup):
    """A query admitted mid-scan (synopsis-seeded, over the already-started
    chunk set) must land within tolerance of the same query cold-started on
    its own scan — mid-scan joining costs coverage, not correctness."""
    vals, store = setup
    truth = _truth_sum(vals)
    occupant = Query(agg="sum", expr=Linear(COEF), epsilon=0.02, name="long")
    joiner = Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 8e7),
                   epsilon=0.06, name="late")
    sel = (vals[:, 0] >= 0) & (vals[:, 0] < 8e7)
    truth_j = float((vals @ np.asarray(COEF)) @ sel)

    cfg = EngineConfig(num_workers=2, seed=9)
    # warm: joiner arrives while the occupant's scan is in flight
    warm = OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=4))
    warm.submit(occupant, arrival_t=0.0)
    warm.submit(joiner, arrival_t=1e-4)
    warm_res = {r.name: r for r in warm.run()}
    # cold: the joiner alone on a fresh scan
    cold = OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=4))
    cold.submit(joiner, arrival_t=0.0)
    cold_res = {r.name: r for r in cold.run()}

    w, c = warm_res["late"], cold_res["late"]
    assert abs(w.estimate - truth_j) / abs(truth_j) < 3 * joiner.epsilon
    assert abs(c.estimate - truth_j) / abs(truth_j) < 3 * joiner.epsilon
    assert abs(w.estimate - c.estimate) / abs(truth_j) < 3 * joiner.epsilon
    # the warm joiner was genuinely seeded mid-scan
    assert warm_res["late"].seeded_tuples > 0
    assert abs(warm_res["long"].estimate - truth) / truth < 3 * occupant.epsilon


# ---------------------------------------------------------------------------
# Early leave isolation
# ---------------------------------------------------------------------------

def test_early_leaver_does_not_perturb_survivor(setup):
    """With plans that never close chunks early (holistic), the shared scan
    is query-independent — so a HAVING query that retires early must leave
    the survivor's statistics bit-for-bit unchanged vs running alone."""
    vals, store = setup
    truth = _truth_sum(vals)
    survivor = Query(agg="sum", expr=Linear(COEF), epsilon=0.03, name="surv")
    leaver = Query(agg="sum", expr=Linear(COEF),
                   having=Having("<", truth * 4), epsilon=0.05, name="quick")

    cfg = EngineConfig(num_workers=2, seed=11)
    alone = OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=4))
    alone.submit(survivor, plan="holistic", arrival_t=0.0)
    res_alone = {r.name: r for r in alone.run()}

    shared = OLAWorkloadServer(store, cfg, options=ServerOptions(max_slots=4))
    shared.submit(survivor, plan="holistic", arrival_t=0.0)
    shared.submit(leaver, plan="holistic", arrival_t=0.0)
    res_shared = {r.name: r for r in shared.run()}

    # the leaver decided its HAVING and left before the survivor finished
    assert res_shared["quick"].decision == 1
    assert res_shared["quick"].t_done <= res_shared["surv"].t_done
    # survivor's answer is unchanged by the co-resident query
    np.testing.assert_allclose(res_shared["surv"].estimate,
                               res_alone["surv"].estimate, rtol=1e-6)
    np.testing.assert_allclose(res_shared["surv"].err,
                               res_alone["surv"].err, rtol=1e-5, atol=1e-8)
    assert res_shared["surv"].tuples_seen == res_alone["surv"].tuples_seen


# ---------------------------------------------------------------------------
# Synopsis-seeded slots ≡ controller synopsis reuse
# ---------------------------------------------------------------------------

def test_seed_slot_agrees_with_controller_seed(setup):
    """`seed_slot` (per-slot, workload server) and `seed` (frozen engine,
    EstimationController reuse) must derive identical sufficient statistics
    from the same synopsis."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=13)
    ctrl = EstimationController(store, cfg, synopsis_budget_tuples=2048)
    ctrl.run_query([Query(agg="sum", expr=Linear(COEF), epsilon=0.04)])
    syn = ctrl.synopsis
    assert syn is not None and len(syn.chunks) > 0

    follow = Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 5e7),
                   epsilon=0.08)
    batch_seed = syn.seed([follow], cache_cap=64)
    slot_seed = syn.seed_slot(follow)
    assert slot_seed is not None
    np.testing.assert_array_equal(slot_seed["m"], batch_seed["m"])
    np.testing.assert_allclose(slot_seed["ysum"], batch_seed["ysum"][0],
                               rtol=1e-6)
    np.testing.assert_allclose(slot_seed["ysq"], batch_seed["ysq"][0],
                               rtol=1e-6)
    np.testing.assert_allclose(slot_seed["psum"], batch_seed["psum"][0],
                               rtol=1e-6)


def test_server_synopsis_answer_matches_truth(setup):
    """End to end: a repeat query answered purely from the server's synopsis
    (zero extra scan rounds) is still a statistically sound estimate."""
    vals, store = setup
    truth = _truth_sum(vals)
    cfg = EngineConfig(num_workers=2, seed=17)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=4, synopsis_budget_tuples=4096))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.03, name="warm"),
               arrival_t=0.0)
    srv.run()
    scanned_before = srv.tuples_scanned
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.10,
                     name="repeat"))
    res = {r.name: r for r in srv.run()}
    rep = res["repeat"]
    assert rep.from_synopsis
    assert rep.rounds_resident == 0
    assert srv.tuples_scanned == scanned_before  # no extra raw access
    assert abs(rep.estimate - truth) / truth < 3 * 0.10


@pytest.mark.parametrize("budget", [4096, 0])
def test_synopsis_seed_counters(setup, budget):
    """A query admitted mid-scan is seeded from the synopsis, and the
    registry counts the seed and the cached tuples it was evaluated on; a
    server without a synopsis counts none."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=19)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2,
                                    synopsis_budget_tuples=budget))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.01,
                     name="warm"), arrival_t=0.0)
    for _ in range(3):
        srv.step()
    srv.submit(Query(agg="count", pred=Range(1, 0.0, 5e7), epsilon=0.01,
                     name="late"))
    srv.step()
    snap = srv.metrics_snapshot()
    srv.close()
    seeds = snap["server_synopsis_seeds_total"]
    tuples = snap["server_synopsis_seed_tuples_total"]
    if budget:
        assert seeds >= 1
        assert 0 < tuples <= seeds * budget
    else:
        assert seeds == 0 and tuples == 0


# ---------------------------------------------------------------------------
# Plan selector + top-up
# ---------------------------------------------------------------------------

def test_select_plan_regimes(setup):
    vals, store = setup
    q = Query(agg="sum", expr=Linear(COEF), epsilon=0.05)
    # CPU-bound regime (slow extraction) -> single_pass
    cpu_cfg = EngineConfig(num_workers=1, cpu_tuple_ops_per_sec=1e6,
                           io_bytes_per_sec=1e12)
    assert select_plan(store, cpu_cfg, q) == "single_pass"
    # IO-bound regime (slow disk) -> holistic
    io_cfg = EngineConfig(num_workers=8, cpu_tuple_ops_per_sec=1e12,
                          io_bytes_per_sec=1e3)
    assert select_plan(store, io_cfg, q) == "holistic"
    # exact answers -> chunk_level
    assert select_plan(store, cpu_cfg,
                       Query(agg="sum", expr=Linear(COEF),
                             epsilon=0.0)) == "chunk_level"


def test_select_plan_measured_rates_override(setup, tmp_path):
    """Bench-measured rates override the modeled constants in Eq. (4); a
    missing/garbled measurement file falls back to the modeled defaults."""
    vals, store = setup
    q = Query(agg="sum", expr=Linear(COEF), epsilon=0.05)
    # modeled config says CPU-bound, the measurement says IO-bound
    cpu_cfg = EngineConfig(num_workers=1, cpu_tuple_ops_per_sec=1e6,
                           io_bytes_per_sec=1e12)
    assert select_plan(store, cpu_cfg, q) == "single_pass"
    io_rates = MeasuredRates(io_bytes_per_sec=1e3, cpu_tuples_per_sec=1e12)
    assert select_plan(store, cpu_cfg, q, rates=io_rates) == "holistic"

    # loader round-trip through a bench result file
    path = tmp_path / "BENCH_slot_kernel.json"
    path.write_text('{"calibration": {"backend": "ref", '
                    '"cpu_tuples_per_sec": 1e12, "io_bytes_per_sec": 1e3}}')
    rates = load_measured_rates(str(path))
    assert rates is not None and rates.io_bytes_per_sec == 1e3
    assert select_plan(store, cpu_cfg, q, rates=rates) == "holistic"
    # the measured CPU rate is aggregate over the calibration run's worker
    # count and must be rescaled to the serving config's: with these rates a
    # same-shape deployment is CPU-bound, a 16x-wider one IO-bound
    few = EngineConfig(num_workers=8)
    many = EngineConfig(num_workers=128)
    tb = float(store.chunk_sizes.sum()) * store.codec.record_bytes
    bal = MeasuredRates(io_bytes_per_sec=tb,                       # t_io = 1s
                        cpu_tuples_per_sec=store.num_tuples / 4.0,  # 4s @ 8w
                        workers=8)
    assert select_plan(store, few, q, rates=bal) == "single_pass"
    assert select_plan(store, many, q, rates=bal) == "holistic"
    # fallback paths: missing file, unusable payload, NaN rates
    assert load_measured_rates(str(tmp_path / "nope.json")) is None
    path.write_text('{"calibration": {"cpu_tuples_per_sec": 0}}')
    assert load_measured_rates(str(path)) is None
    path.write_text('{"calibration": {"cpu_tuples_per_sec": NaN, '
                    '"io_bytes_per_sec": 1e6}}')
    assert load_measured_rates(str(path)) is None
    srv = OLAWorkloadServer(
              store, EngineConfig(num_workers=2),
              options=ServerOptions(rates_path=str(tmp_path / "nope.json")))
    assert srv.rates is None  # modeled defaults still in force


def test_measured_rates_loader_round_cost_fit(tmp_path):
    """The loader carries the calibration's S-sweep round-cost fit (the
    scheduler's measured-capacity input) and treats absent/garbage fit
    fields as 'fit unavailable' (0.0) without rejecting the calibration."""
    path = tmp_path / "BENCH_slot_kernel.json"
    path.write_text('{"calibration": {"backend": "ref", '
                    '"cpu_tuples_per_sec": 1e6, "io_bytes_per_sec": 1e8, '
                    '"round_base_us": 3000.0, "round_slot_us": 250.0}}')
    rates = load_measured_rates(str(path))
    assert rates.round_base_us == 3000.0
    assert rates.round_slot_us == 250.0
    # predates the fit -> 0.0 sentinels, calibration still usable
    path.write_text('{"calibration": {"backend": "ref", '
                    '"cpu_tuples_per_sec": 1e6, "io_bytes_per_sec": 1e8}}')
    rates = load_measured_rates(str(path))
    assert rates is not None
    assert rates.round_base_us == 0.0 and rates.round_slot_us == 0.0
    # NaN/negative fit values are sanitized, not propagated
    path.write_text('{"calibration": {"backend": "ref", '
                    '"cpu_tuples_per_sec": 1e6, "io_bytes_per_sec": 1e8, '
                    '"round_base_us": NaN, "round_slot_us": -4.0}}')
    rates = load_measured_rates(str(path))
    assert rates.round_base_us == 0.0 and rates.round_slot_us == 0.0


def test_measured_rates_rescale_across_codecs(setup):
    """The calibrated tuple rate is codec-relative (ASCII parsing vs
    near-free binary decode): with the calibration's cost_per_tuple
    recorded, select_plan rescales it for the serving store's codec instead
    of treating a binary store as ASCII-slow."""
    vals, store = setup                                  # ascii store
    bstore = store_dataset(vals, 32, "binary")
    q = Query(agg="sum", expr=Linear(COEF), epsilon=0.05)
    cfg = EngineConfig(num_workers=4)
    tb = float(store.chunk_sizes.sum()) * store.codec.record_bytes
    # tuned so the ASCII store sits in the balanced band (resource_aware)
    rates = MeasuredRates(io_bytes_per_sec=tb,            # t_io = 1 s
                          cpu_tuples_per_sec=store.num_tuples,  # t_cpu = 1 s
                          workers=4,
                          cost_per_tuple=store.codec.extract_cost_per_tuple())
    assert select_plan(store, cfg, q, rates=rates) == "resource_aware"
    # binary decode is far cheaper per tuple -> the same calibration must
    # classify the binary store as IO-bound (holistic), not CPU-bound
    assert (bstore.codec.extract_cost_per_tuple()
            < store.codec.extract_cost_per_tuple() / 4)
    tbb = float(bstore.chunk_sizes.sum()) * bstore.codec.record_bytes
    rates_b = dataclasses.replace(rates, io_bytes_per_sec=tbb)
    assert select_plan(bstore, cfg, q, rates=rates_b) == "holistic"
    # without the recorded cost the loader/selector keep the raw rate
    raw = dataclasses.replace(rates_b, cost_per_tuple=0.0)
    assert select_plan(bstore, cfg, q, rates=raw) == "resource_aware"


def test_default_rates_path_ignores_cwd(tmp_path, monkeypatch):
    """The default calibration path is anchored to the repo root (or the
    OLA_RATES_PATH env knob), not the process CWD — a server started from
    another directory must still find (or cleanly miss) the bench file."""
    import os

    from repro.serve.ola_server import default_rates_path

    monkeypatch.delenv("OLA_RATES_PATH", raising=False)
    monkeypatch.chdir(tmp_path)                     # CWD must be irrelevant
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert default_rates_path() == os.path.join(repo_root,
                                                "BENCH_slot_kernel.json")

    # hit: env knob points at a usable calibration; loader default finds it
    path = tmp_path / "elsewhere" / "cal.json"
    path.parent.mkdir()
    path.write_text('{"calibration": {"backend": "ref", "workers": 4, '
                    '"cpu_tuples_per_sec": 2e9, "io_bytes_per_sec": 5e8}}')
    monkeypatch.setenv("OLA_RATES_PATH", str(path))
    rates = load_measured_rates()
    assert rates is not None
    assert rates.io_bytes_per_sec == 5e8 and rates.workers == 4

    # miss: knob points nowhere -> None -> modeled fallback stays in force
    monkeypatch.setenv("OLA_RATES_PATH", str(tmp_path / "nope.json"))
    assert load_measured_rates() is None


def test_post_exhaustion_without_synopsis_fails_loud(setup):
    """Once the scan is a census and there is no synopsis, a new query can
    never be served: submit() rejects it, and one already queued retires
    flagged `unserved` with a NaN estimate — never a plausible-looking 0."""
    vals, store = setup
    cfg = EngineConfig(num_workers=2, seed=23)
    exact = Query(agg="sum", expr=Linear(COEF), epsilon=1e-9, name="census")
    late = Query(agg="sum", expr=Linear(COEF), epsilon=0.1, name="late")

    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(synopsis_budget_tuples=0))
    srv.submit(exact)
    assert srv.run()[0].tuples_seen == store.num_tuples
    with pytest.raises(ValueError, match="synopsis"):
        srv.submit(late)

    srv2 = OLAWorkloadServer(
               store, cfg,
               options=ServerOptions(max_slots=1, synopsis_budget_tuples=0))
    srv2.submit(exact, arrival_t=0.0)
    srv2.submit(late, arrival_t=0.0)   # queued behind the census
    res = {r.name: r for r in srv2.run()}
    assert res["late"].unserved
    assert np.isnan(res["late"].estimate)
    assert not res["census"].unserved


def test_topup_pass_serves_late_tight_query(setup):
    """A tight-ε query arriving after the scan wound down forces a top-up
    pass (re-opened chunks) and still converges."""
    vals, store = setup
    truth = _truth_sum(vals)
    cfg = EngineConfig(num_workers=2, seed=19)
    srv = OLAWorkloadServer(
              store, cfg,
              options=ServerOptions(max_slots=2, synopsis_budget_tuples=512))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.10,
                     name="loose"), arrival_t=0.0)
    srv.run()
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.02,
                     name="tight"))
    res = {r.name: r for r in srv.run()}
    tight = res["tight"]
    assert abs(tight.estimate - truth) / truth < 3 * 0.02
    assert tight.err <= 0.02 + 1e-6 or srv.topup_passes > 0


# ---------------------------------------------------------------------------
# Slot writes staged on the host, applied by one update before the round
# ---------------------------------------------------------------------------

def zero_group_cells(state, s, cells=None):
    """Zero slot ``s``'s group cells (all, or the given indices) through a
    host round trip: the oracle of a staged group restart."""
    sel = slice(None) if cells is None else np.asarray(list(cells), np.int64)
    out = {}
    for k in ("gm", "gys", "gyq", "gps"):
        a = np.asarray(getattr(state, k)).copy()
        a[s, sel] = 0
        out[k] = jnp.asarray(a)
    return state._replace(**out)


class EagerWrites:
    """The oracle: every slot write an eager device op on the server's
    state and a device table, made the moment the server asks for it."""

    def __init__(self, srv):
        self.srv = srv
        self.table = empty_slot_table(srv.max_slots, srv.store.codec.num_cols,
                                      srv.max_groups)

    def _stopped(self, slots, value):
        st = self.srv.state
        self.srv.state = st._replace(
            stopped=st.stopped.at[jnp.asarray(list(slots))].set(value))

    def slot(self, s, seed):
        stats, seeded = slot_stats_write(self.srv.state.stats, s, seed,
                                         self.srv.store.num_chunks)
        self.srv.state = self.srv.state._replace(stats=stats)
        self._stopped([s], False)
        return seeded

    def stop(self, slots):
        self._stopped(slots, True)

    def restart_groups(self, s, cells=None):
        self.srv.state = zero_group_cells(self.srv.state, s, cells)

    def table_row(self, s, row):
        self.table = slot_table_set(self.table, s, row)

    def release(self, s):
        t = self.table
        self.table = t._replace(active=t.active.at[s].set(False),
                                weight=t.weight.at[s].set(jnp.float32(1.0)))
        self.stop([s])

    def groups(self, s, gval, gact):
        t = self.table
        self.table = t._replace(
            gval=t.gval.at[s].set(jnp.asarray(gval, jnp.float32)),
            gact=t.gact.at[s].set(jnp.asarray(gact, jnp.float32)))

    def weights(self, w):
        self.table = self.table._replace(weight=jnp.asarray(w, jnp.float32))

    def drop_chunks(self, quarantined):
        pass                      # the quarantine write zeroed the device

    def view(self, state):
        return state

    def flush(self, state):
        return state, self.table


def _assert_bits_equal(a, b):
    """Every leaf of two pytrees identical: dtype, shape and bytes."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def _scheduled_server(store, backend, writes=None):
    """A scheduled, grouped server with preemption on, recording the state
    and table every round takes; ``writes`` swaps its slot writes."""
    cfg = EngineConfig(num_workers=2, seed=5, max_groups=4,
                       extract_backend=backend)
    srv = OLAWorkloadServer(store, cfg, options=ServerOptions(
        max_slots=3, synopsis_budget_tuples=512, group_warmup_tuples=64,
        scheduler=WorkloadScheduler(SchedulerConfig(
            preempt=True, queue_policy="fifo", shed_enabled=False))))
    if writes is not None:
        srv._writes = writes(srv)
    taken = []
    orig = srv.engine.round_fn

    def round_fn(b, mode="none"):
        fn = orig(b, mode)

        def run(state, table, data, speeds):
            taken.append(jax.tree.map(np.array, (state, table)))
            return fn(state, table, data, speeds)
        return run

    srv.engine.round_fn = round_fn
    t = store.num_tuples / srv._scan_rate
    # two admissions in the first step, one of them grouped (it promotes)
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6, name="a"),
               arrival_t=0.0, slo=QuerySLO(priority="batch"))
    srv.submit(Query(agg="sum", expr=Linear((0.0, 1.0) + (0.0,) * 6),
                     epsilon=0.01, name="g",
                     group_by=GroupBy(col=7, max_groups=4, top_k=2)),
               arrival_t=0.0)
    # v takes the free slot from the synopsis, and p evicts it in the
    # same step: the snapshot reads v's row before any round wrote it
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.03, name="v"),
               arrival_t=0.1 * t, slo=QuerySLO(priority="batch"))
    srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.08, name="p"),
               arrival_t=0.1 * t,
               slo=QuerySLO(deadline_s=0.3 * t, priority="interactive"))
    # d is stopped at its deadline; s retires from its seed at admission
    srv.submit(Query(agg="count", pred=Range(1, 0.0, 5e7), epsilon=1e-9,
                     name="d"),
               arrival_t=0.5 * t, slo=QuerySLO(deadline_s=0.05 * t))
    srv.submit(Query(agg="count", pred=Range(0, 0.0, 9e7), epsilon=0.5,
                     name="s"), arrival_t=0.7 * t)
    return srv, taken


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_staged_slot_writes_match_eager_writes(setup, backend):
    """Staged writes and one update before each round leave the state and
    table every round takes bit-identical to eager writes, through seed
    retirement, several admissions in a step, a grouped admission and a
    promotion, releases, a deadline stop, the census, and the preemption
    snapshot of a row staged in the same step."""
    vals, store = setup
    staged, staged_taken = _scheduled_server(store, backend)
    eager, eager_taken = _scheduled_server(store, backend, EagerWrites)
    evicted, promoted = [], []
    evict, groups = staged._evict, staged._writes.groups

    def evict_staged(s):
        evicted.append(bool(staged._writes._rows.row[s]))
        evict(s)

    def groups_counted(s, gval, gact):
        promoted.append(s)
        groups(s, gval, gact)

    staged._evict, staged._writes.groups = evict_staged, groups_counted
    for _ in range(200):
        busy = staged.step()
        assert eager.step() == busy
        # the state and table the next round will take
        _assert_bits_equal(staged._writes.view(staged.state), eager.state)
        _assert_bits_equal(staged.table, eager.table)
        if not busy and not staged.queue:
            break
    assert len(staged_taken) == len(eager_taken) == staged.rounds > 0
    for a, b in zip(staged_taken, eager_taken):
        _assert_bits_equal(a, b)
    # (repr: the __other__ cell's value is NaN; float reprs are exact)
    assert repr(staged.results) == repr(eager.results)
    res = {r.name: r for r in staged.results}
    assert res["s"].from_synopsis and res["s"].rounds_resident == 0
    assert evicted == [True] and res["v"].sched_outcome == "preempted"
    assert res["d"].err > 1e-9 and res["d"].slo_met is False
    assert promoted and any(not c.is_other for c in res["g"].groups)
    staged.close()
    eager.close()


def test_quarantine_in_the_step_reaches_staged_rows(setup):
    """A chunk quarantined by a failed read inside the step that admitted a
    seeded query (after the row was staged, before the flush) leaves the
    slot's row as the eager write followed by the quarantine write did:
    bit-identical, the chunk's columns zero."""
    vals, store = setup
    servers = []
    for writes in (None, EagerWrites):
        srv = OLAWorkloadServer(store, EngineConfig(num_workers=2, seed=5),
                                options=ServerOptions(
                                    max_slots=3, synopsis_budget_tuples=512))
        if writes is not None:
            srv._writes = writes(srv)
        orig = srv.engine.round_data

        def round_data(state, srv=srv, orig=orig):
            # the read of the first scanned chunk fails as "late" joins
            if not srv.engine.quarantine_log and any(
                    w is not None and w.query.name == "late"
                    for w in srv.slot_wq):
                j = int(np.asarray(state.schedule)[0])
                state = quarantine_chunks(state, [j])
                srv.engine.quarantine_log.append(j)
            return orig(state)

        srv.engine.round_data = round_data
        srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6,
                         name="first"), arrival_t=0.0)
        for _ in range(6):
            srv.step()
        srv.submit(Query(agg="count", pred=Range(0, 0.0, 9e7),
                         epsilon=1e-6, name="late"), arrival_t=srv.t_model)
        srv.step()
        servers.append(srv)
    staged, eager = servers
    (j,) = staged.engine.quarantine_log
    late = next(s for s, w in enumerate(staged.slot_wq)
                if w is not None and w.query.name == "late")
    assert staged.slot_seeded[late] > 0
    assert int(np.asarray(staged.state.stats.m)[late, j]) == 0
    _assert_bits_equal(staged.state, eager.state)
    for srv in servers:
        srv.close()


def test_slot_write_counters(setup):
    """One flush applies a step's several admissions; a step that admits
    and releases nothing flushes nothing."""
    vals, store = setup
    srv = OLAWorkloadServer(store, EngineConfig(num_workers=2, seed=5),
                            options=ServerOptions(max_slots=3))

    def counts():
        snap = srv.metrics_snapshot()
        return (snap["server_slot_flushes_total"],
                snap["server_slot_writes_staged_total"])

    assert counts() == (0, 0)
    for name in ("a", "b", "c"):
        srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=1e-6,
                         name=name), arrival_t=0.0)
    srv.step()
    flushes, staged = counts()
    assert flushes == 1 and staged >= 3
    srv.step()
    assert counts() == (flushes, staged)
    srv.run()
    flushes, staged = counts()
    assert staged >= flushes >= 1
    srv.close()


def test_slot_write_update_compiles_once_per_shape():
    """Servers built one after the other, as a benchmark builds one a pass,
    share the one compiled update of their shape."""
    store = store_dataset(make_synthetic_zipf(1536, 8, seed=11), 24, "ascii")
    apply_slot_writes.clear_cache()
    for _ in range(2):
        srv = OLAWorkloadServer(store, EngineConfig(num_workers=2, seed=5),
                                options=ServerOptions(max_slots=7))
        for name in ("a", "b"):
            srv.submit(Query(agg="sum", expr=Linear(COEF), epsilon=0.05,
                             name=name), arrival_t=0.0)
        srv.run()
        assert srv.metrics_snapshot()["server_slot_flushes_total"] >= 1
        srv.close()
    assert apply_slot_writes._cache_size() == 1
