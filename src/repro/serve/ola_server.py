"""Workload serving for OLA queries: one shared scan, many concurrent queries.

The paper's end goal is workload-level exploration — "OLA-RAW chooses the
sampling plan that minimizes the execution time and guarantees the required
accuracy for each query in a given workload".  This module turns the
single-batch engine into a *server*: aggregate queries arrive as a stream and
are multiplexed onto a **single shared scan** of the raw table, mirroring the
slot/queue shape of ``serve/engine.py`` (continuous batching):

* **slots** — up to ``max_slots`` queries are resident at once, described by
  a dynamic :class:`~repro.core.queries.SlotTable` the jitted round step
  takes as data (no recompilation on admission/retirement);
* **mid-scan admission** — a query can join while the scan is running: its
  per-slot sufficient statistics are seeded from the
  :class:`~repro.core.synopsis.BiLevelSynopsis` (which absorbs the scan's
  extraction cache on demand), so it starts with an estimate over the
  already-started chunk set instead of cold;
* **early leave** — a query retires the moment its HAVING verdict or ε
  target is met, freeing its slot *without* stopping the scan for others
  (the scan is query-independent, so survivors' statistics are untouched);
* **top-up passes** — if the scan wound down (chunks closed at the then-live
  accuracy targets) but a newly admitted query needs more data, the server
  re-opens non-exhausted chunks and restarts the schedule head; per-chunk
  permutation cursors continue, so samples stay prefix-of-permutation;
* **per-query plan selection** — :func:`select_plan` picks
  chunk_level/holistic/single_pass/resource_aware per admitted query from
  the Eq. (4) cost terms the resource monitor already models.

Total work is sub-additive in the number of queries: a shared scan serves the
whole workload with roughly the tuple budget of its most demanding member,
instead of one scan per query (see ``benchmarks/bench_workload.py``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.controller import _answer_from_stats
from repro.core.engine import (
    IDLE,
    EngineConfig,
    SlotOLAEngine,
    slot_group_rows,
    slot_stats_fold,
    slot_stats_snapshot,
    slot_stats_write,
    zero_group_cells,
)
from repro.core.groupby import WARMUP_ROUNDS, GroupSketch, promote_values
from repro.core.queries import (
    PLAN_CODES,
    GroupResult,
    Query,
    empty_slot_table,
    encode_slot,
    group_fanout,
    slot_table_clear,
    slot_table_set,
    slot_table_set_groups,
)
from repro.core.synopsis import BiLevelSynopsis
from repro.core import estimators as est
from repro.obs.explain import ExplainRecord, RoundSample
from repro.obs.metrics import LATENCY_BUCKETS_S, MetricsRegistry
from repro.obs.trace import ProfilerTracer
from repro.sched.admission import (
    SHED,
    TIER1,
    ServerLoad,
    eq4_cost_terms,
    scan_tuples_per_s,
)
from repro.sched.preempt import select_victim
from repro.sched.scheduler import SchedulerConfig, WorkloadScheduler
from repro.sched.slo import NO_SLO, QuerySLO
from repro.serve.rollup import RollupConfig, RollupTier, pattern_key


@dataclasses.dataclass(frozen=True)
class MeasuredRates:
    """Measured IO/CPU rates for the Eq. (4) cost model.

    ``cpu_tuples_per_sec`` is the *aggregate* extraction throughput of one
    engine round step across the ``workers`` workers of the calibration run,
    ``io_bytes_per_sec`` the measured raw read bandwidth — both as reported
    by ``benchmarks/bench_slot_kernel.py``.  :func:`select_plan` rescales the
    CPU rate to the serving config's worker count (extraction parallelizes
    over workers; the read path does not).  The modeled constants in
    :class:`EngineConfig` remain the fallback when no measurement is
    available.
    """

    io_bytes_per_sec: float
    cpu_tuples_per_sec: float
    workers: int = 1
    source: str = "measured"
    # extraction cost (codec.extract_cost_per_tuple()) of the *calibration*
    # store: tuples/s is codec-relative, so serving a different codec
    # rescales by the cost ratio.  0 = unknown -> no rescaling.
    cost_per_tuple: float = 0.0
    # linear fit of the benchmark's S sweep, round_us(S) = base + slot_us·S:
    # the scan-side round cost and the marginal cost of one fully-counted
    # slot evaluation.  Feeds the scheduler's measured slot capacity
    # (repro.sched.fairness.measured_slot_capacity).  0 = calibration
    # predates the fit -> measured capacity unavailable.
    round_base_us: float = 0.0
    round_slot_us: float = 0.0


def default_rates_path() -> str:
    """Default location of the ``bench_slot_kernel`` calibration file.

    Anchored to the repo root (where ``benchmarks/bench_slot_kernel.py``
    writes it), *not* the process CWD — a server started from any other
    directory used to silently fall back to modeled rates.  The
    ``OLA_RATES_PATH`` environment variable overrides it for deployments
    that keep the calibration elsewhere.
    """
    env = os.environ.get("OLA_RATES_PATH")
    if env:
        return env
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    if os.path.isdir(os.path.join(repo_root, "benchmarks")):
        return os.path.join(repo_root, "BENCH_slot_kernel.json")
    # non-editable install: the walk-up lands in site-packages, which the
    # benchmark never writes — fall back to CWD and let deployments pin
    # the location with OLA_RATES_PATH
    return "BENCH_slot_kernel.json"


def load_measured_rates(path: Optional[str] = None,
                        ) -> Optional[MeasuredRates]:
    """Load the calibration block of a ``bench_slot_kernel`` result file.

    ``path=None`` resolves via :func:`default_rates_path` (repo root, or
    ``$OLA_RATES_PATH``).  Returns ``None`` (→ the caller falls back to the
    modeled defaults) when the file is missing or has no usable
    calibration — a server deployed without ever running the benchmark
    keeps working on the modeled rates.
    """
    import math

    if path is None:
        path = default_rates_path()
    try:
        with open(path) as f:
            data = json.load(f)
        cal = data["calibration"]
        cost = float(cal.get("cost_per_tuple", 0.0))

        def _opt(key):
            v = float(cal.get(key, 0.0))
            return v if math.isfinite(v) and v > 0 else 0.0

        rates = MeasuredRates(
            io_bytes_per_sec=float(cal["io_bytes_per_sec"]),
            cpu_tuples_per_sec=float(cal["cpu_tuples_per_sec"]),
            workers=int(cal.get("workers", data.get("workers", 1))),
            source=f"{path}:{cal.get('backend', '?')}",
            cost_per_tuple=cost if math.isfinite(cost) and cost > 0 else 0.0,
            round_base_us=_opt("round_base_us"),
            round_slot_us=_opt("round_slot_us"))
        # json.load accepts the NaN literal, and NaN compares False to
        # everything — require finite positives or fall back to modeled
        if not all(math.isfinite(v) and v > 0 for v in
                   (rates.io_bytes_per_sec, rates.cpu_tuples_per_sec,
                    rates.workers)):
            return None
        return rates
    except (OSError, KeyError, TypeError, ValueError):
        return None


def select_plan(store, config: EngineConfig, query: Query,
                rates: Optional[MeasuredRates] = None,
                decoded_fraction: float = 0.0) -> str:
    """Cost-model plan selector for one admitted query.

    Uses the two Eq. (4) cost terms the resource monitor models — a full
    pass's READ time ``T_io`` and EXTRACT time ``T_cpu`` — to pick the
    strategy whose regime the paper's Fig. 11 shows it wins:

    * ``epsilon <= 0`` (an exact answer is demanded): ``chunk_level`` — the
      reordering barrier delivers fully-extracted chunks in schedule order.
    * IO-bound (``T_cpu < T_io / 2``): ``holistic`` — extraction is free
      relative to reading, so extract everything that is read.
    * CPU-bound (``T_cpu > 2 T_io``): ``single_pass`` — stop extracting a
      chunk at local accuracy; reading ahead is cheap.
    * otherwise: ``resource_aware`` — let the runtime monitor switch.

    With ``rates`` (bench-measured, see :func:`load_measured_rates`) the two
    terms use the machine's *actual* read bandwidth and round-step extraction
    throughput instead of the modeled constants — the measured analogue of
    the paper's testbed calibration.  The terms come from
    :func:`repro.sched.admission.eq4_cost_terms` — the same pricing the
    admission controller judges SLO feasibility with.

    ``decoded_fraction`` is the parse-once decoded-chunk cache's coverage
    (see :meth:`~repro.data.pipeline.SlabPrefetcher.decoded_fraction`): it
    discounts the CPU term, so a well-cached store reads as more IO-bound —
    extraction over cached chunks really is near-free on re-scans.
    """
    t_io, t_cpu = eq4_cost_terms(store, config, rates,
                                 decoded_fraction=decoded_fraction)
    if query.epsilon <= 0.0:
        return "chunk_level"
    ratio = t_cpu / max(t_io, 1e-12)
    if ratio < 0.5:
        return "holistic"
    if ratio > 2.0:
        return "single_pass"
    return "resource_aware"


@dataclasses.dataclass(frozen=True)
class ServerOptions:
    """Construction options for :class:`OLAWorkloadServer`.

    Everything beyond the two required arguments (the chunk store and the
    :class:`EngineConfig`) lives here: the server is built as
    ``OLAWorkloadServer(store, config, options=ServerOptions(...))``.  Field
    semantics are documented on :meth:`OLAWorkloadServer.__init__` (they are
    the former keyword parameters, collapsed into one options object so the
    construction surface can grow without another positional-kwarg sprawl).
    The legacy keyword form still works and warns once per process.
    """

    max_slots: int = 8
    synopsis_budget_tuples: int = 4096
    confidence: float = 0.95
    schedule: Optional[np.ndarray] = None
    mesh: object = None
    engine: object = None
    measured_rates: Optional[MeasuredRates] = None
    rates_path: Optional[str] = None
    scheduler: object = None
    rollup: object = None
    tracer: object = None
    metrics: Optional[MetricsRegistry] = None
    # grouped discovery: minimum pure-tally mass (tuples) the slot's sketch
    # must absorb before non-pinned values are promoted into tracked cells.
    # Promotion is grow-only, so promoting off a few noisy early rounds
    # would permanently lock true heavy hitters out of the cell set; the
    # warmup lets the SpaceSaving ranking stabilize first (it also waits for
    # groupby.WARMUP_ROUNDS folds).
    group_warmup_tuples: int = 1024


_legacy_kwargs_warned = False


def _options_from_legacy(kwargs: dict) -> ServerOptions:
    """Back-compat shim: map the pre-:class:`ServerOptions` keyword surface
    onto an options object, warning once per process."""
    global _legacy_kwargs_warned
    names = {f.name for f in dataclasses.fields(ServerOptions)}
    unknown = sorted(set(kwargs) - names)
    if unknown:
        raise TypeError(
            f"OLAWorkloadServer got unexpected keyword argument(s) {unknown}; "
            f"valid ServerOptions fields: {sorted(names)}")
    if not _legacy_kwargs_warned:
        warnings.warn(
            "passing OLAWorkloadServer construction keywords directly is "
            "deprecated; use OLAWorkloadServer(store, config, "
            "options=ServerOptions(...))",
            DeprecationWarning, stacklevel=3)
        _legacy_kwargs_warned = True
    return ServerOptions(**kwargs)


@dataclasses.dataclass
class WorkloadQuery:
    """One submitted query: the aggregate plus its workload metadata."""

    qid: int
    query: Query
    arrival_t: float = 0.0          # modeled seconds on the server clock
    plan: Optional[str] = None      # None -> cost-model selector
    row: Optional[dict] = None      # slot row encoded (and validated) at submit
    slo: Optional[QuerySLO] = None  # service-level objective (scheduler)
    queued: bool = False            # waited >= one admission pass for a slot
    preempted: bool = False         # evicted mid-residence at least once
    saved_stats: Optional[dict] = None  # eviction snapshot: re-admission seed
    key: Optional[tuple] = None     # rollup pattern key (None: not cacheable
                                    # or the server runs without a rollup tier)
    explain: Optional[ExplainRecord] = None  # lifecycle explain (repro.obs)
    t_submitted: float = 0.0        # time.perf_counter() at submit
    t_enqueued: float = 0.0         # time.perf_counter() at the last queue
                                    # entry (submit, or eviction)


@dataclasses.dataclass
class WorkloadResult:
    qid: int
    name: str
    estimate: float
    lo: float
    hi: float
    err: float
    decision: int                   # HAVING verdict (-1/0/1)
    plan: str
    t_submit: float                 # arrival (modeled s)
    t_admit: float                  # slot grant (modeled s)
    t_done: float                   # retirement (modeled s)
    seeded_tuples: int              # tuples supplied by the synopsis at admit
    tuples_seen: int                # slot sample size at retirement
    rounds_resident: int
    from_synopsis: bool = False     # answered at admission, zero scan rounds
    unserved: bool = False          # scan exhausted before the slot saw any
                                    # tuple (no synopsis seed): estimate is NaN
    # scheduler outcome: "admitted" (straight into a slot), "queued" (waited
    # for one), "preempted" (evicted mid-residence for a deadline query and
    # completed after re-queueing — never dropped), "shed" (never held a
    # slot — answered best-effort from the synopsis, or unserved), or
    # "tier1" (answered from the rollup cache: no slot, no scan rounds,
    # plan="rollup").  Lets benchmarks separate scan-served answers from
    # cached and degraded ones.
    sched_outcome: str = "admitted"
    queue_wait_model_s: float = 0.0  # t_admit - t_submit (slot wait,
                                     # modeled s)
    slo_met: Optional[bool] = None  # None when the query carried no SLO
    priority: str = "normal"        # SLO priority class (per-class latency
                                    # curves in benchmarks/bench_workload.py)
    # degraded-answer semantics (fault-tolerant scan plane): the estimate
    # describes the *surviving* population — at least one chunk was
    # quarantined (lost or irrecoverably corrupt) before this query
    # completed, so its answer is exact/valid over N - chunks_quarantined
    # chunks, not the full table.  Transient faults healed by retries never
    # set this flag (the sample is bit-identical to a fault-free run);
    # ``read_retries`` counts the retried chunk reads during the query's
    # residency (recovery overhead, 0 on packed residency).
    degraded: bool = False
    chunks_quarantined: int = 0
    read_retries: int = 0
    # grouped answer (Query(group_by=...)): one GroupResult per live group
    # cell — the tracked heavy-hitter values in discovery order, then the
    # __other__ spill cell (is_other=True) holding everything untracked.
    # None for ungrouped queries, and for grouped ones answered without a
    # scan residency (shed); the scalar estimate/lo/hi above stay
    # authoritative for the query's *base-predicate* population either way.
    groups: Optional[list[GroupResult]] = None
    # per-query explain record (repro.obs.explain): admission pricing, tier
    # routing rationale, per-round (m, est, ci) trajectory, degradation
    # events.  Excluded from equality — parity gates compare answers, not
    # telemetry — and its final est/ci_halfwidth are copied from this
    # result's own floats at finalize (bit-for-bit by construction).
    explain: Optional[ExplainRecord] = dataclasses.field(
        default=None, compare=False, repr=False)

    @property
    def latency_model_s(self) -> float:
        """Submit to retirement on the modeled Eq. (4) clock (wall seconds
        are the server's ``query_latency_s`` histogram)."""
        return self.t_done - self.t_submit

    @property
    def halfwidth(self) -> float:
        return (self.hi - self.lo) / 2.0


class OLAWorkloadServer:
    """Admits a stream of aggregate queries onto one shared OLA scan.

    The server is a host-side loop around :class:`SlotOLAEngine`:
    ``submit`` enqueues, ``step`` runs one engine round (admitting and
    retiring between rounds), ``run`` drives to completion.  The modeled
    clock is Eq. (4)'s overlapped-pipeline time ``max(t_io, t_cpu)`` plus
    any idle gaps the server skips while waiting for arrivals.
    """

    def __init__(self, store, config: EngineConfig,
                 options: Optional[ServerOptions] = None, **legacy_kwargs):
        """``options`` collects every construction knob (see
        :class:`ServerOptions`); the former keyword surface still works via
        ``**legacy_kwargs`` but warns once per process.

        ``engine`` may be a pre-built :class:`SlotOLAEngine` or
        :class:`~repro.core.engine_spmd.SlotSPMDEngine` (the server only uses
        the shared round-step protocol); with ``mesh`` and no ``engine`` a
        :class:`SlotSPMDEngine` is built over it.  ``measured_rates`` (or a
        ``rates_path`` benchmark file, see :func:`load_measured_rates`) feeds
        the Eq. (4) plan selector bench-measured IO/CPU rates; the modeled
        :class:`EngineConfig` constants stay the fallback.

        ``scheduler`` — a :class:`~repro.sched.WorkloadScheduler` (or a
        :class:`~repro.sched.SchedulerConfig`, wrapped automatically) —
        turns on SLO-aware serving: priority-ordered admission, feasibility
        shedding, weighted max-min fairness over the round budget, deadline
        enforcement, and variance-guided claim ordering.  ``None`` (default)
        keeps the historic admit-or-FIFO-queue behavior; the *neutral*
        scheduler configuration (``repro.sched.NEUTRAL``) reproduces it
        bit-exactly (gated in tests/test_sched.py).

        ``rollup`` — a :class:`~repro.serve.rollup.RollupConfig` (or a
        pre-built :class:`~repro.serve.rollup.RollupTier`) turns on the
        Tier-1 answer cache: hot query patterns mined from the completed
        log are promoted to rollup cells maintained incrementally from the
        scan's per-chunk sufficient statistics, and repeats are answered
        from the cell — no slot, no scan rounds — whenever the cached
        answer meets their accuracy target.  ``None`` (default) keeps
        every query on the Tier-2 scan path.

        ``tracer`` — where the server's spans go (:mod:`repro.obs.trace`):
        submit, admission and its per-query parts, and per round the
        claims, dispatch, device wait, merge and retirement, plus the scan
        plane's READ/prefetch spans.  The default, a
        :class:`~repro.obs.trace.ProfilerTracer`, writes them into the JAX
        profiler's trace while one is recording; a
        :class:`~repro.obs.trace.SpanTracer` also keeps them for
        chrome-trace JSON; :data:`~repro.obs.trace.NULL_TRACER` turns them
        off.  All instrumentation is host-side: a traced NEUTRAL run is
        round-for-round bit-exact with an untraced one.  ``metrics`` — a
        :class:`~repro.obs.metrics.MetricsRegistry` to surface counters
        on; one is created internally when omitted (see
        :meth:`metrics_snapshot`).
        """
        if legacy_kwargs:
            if options is not None:
                raise TypeError(
                    "pass either options=ServerOptions(...) or the legacy "
                    "keyword arguments, not both")
            options = _options_from_legacy(legacy_kwargs)
        opts = options if options is not None else ServerOptions()
        max_slots = opts.max_slots
        synopsis_budget_tuples = opts.synopsis_budget_tuples
        confidence = opts.confidence
        schedule = opts.schedule
        mesh, engine = opts.mesh, opts.engine
        measured_rates, rates_path = opts.measured_rates, opts.rates_path
        scheduler, rollup = opts.scheduler, opts.rollup
        tracer, metrics = opts.tracer, opts.metrics
        if engine is not None:
            if engine.store is not store:
                raise ValueError("engine was built over a different store")
            if synopsis_budget_tuples > 0 and engine.config.cache_cap == 0:
                raise ValueError(
                    "mid-scan synopsis seeding needs the extraction cache: "
                    "build the engine with cache_cap > 0 or pass "
                    "synopsis_budget_tuples=0")
            config = engine.config
            max_slots = engine.max_slots
        elif config.cache_cap == 0 and synopsis_budget_tuples > 0:
            # mid-scan seeding needs the extraction cache
            cap = max(64, int(np.ceil(4 * synopsis_budget_tuples
                                      / max(store.num_chunks, 1))))
            config = dataclasses.replace(config, cache_cap=cap)
        self.store = store
        self.config = config
        # observability: span tracer (profiler annotations by default),
        # made before the engine so that a sharded placement's span lands
        # in it; the metrics registry follows below
        self.tracer = tracer if tracer is not None else ProfilerTracer()
        if engine is not None:
            self.engine = engine
        elif mesh is not None:
            from repro.core.engine_spmd import SlotSPMDEngine

            self.engine = SlotSPMDEngine(store, max_slots, config, mesh,
                                         schedule=schedule,
                                         confidence=confidence,
                                         tracer=self.tracer)
        else:
            self.engine = SlotOLAEngine(store, max_slots, config,
                                        schedule=schedule,
                                        confidence=confidence)
        self.rates = measured_rates
        if self.rates is None and rates_path is not None:
            self.rates = load_measured_rates(rates_path)
        # grouped query plane: the table's group capacity follows the engine
        # config (0 keeps the group arrays zero-width — the grouped code
        # compiles away and ungrouped serving is statically unchanged)
        self.max_groups = int(self.config.max_groups)
        self.table = empty_slot_table(max_slots, store.codec.num_cols,
                                      self.max_groups)
        self.state = self.engine.init_state()
        self.max_slots = max_slots
        # per-slot online group discovery (grouped occupants only): the
        # SpaceSaving sketch fed from each round's tally report, and the
        # host mirror of the slot's tracked values (discovery order)
        self._slot_sketch: list[Optional[GroupSketch]] = [None] * max_slots
        self._slot_groups: list[Optional[list[float]]] = [None] * max_slots
        self._group_warmup = int(opts.group_warmup_tuples)
        self.synopsis: Optional[BiLevelSynopsis] = None
        if synopsis_budget_tuples > 0:
            self.synopsis = BiLevelSynopsis(
                n_chunks=store.num_chunks, num_cols=store.codec.num_cols,
                budget_tuples=synopsis_budget_tuples,
                chunk_sizes=store.chunk_sizes)
        self.queue: list[WorkloadQuery] = []
        self.slot_wq: list[Optional[WorkloadQuery]] = [None] * max_slots
        self.slot_admit_t = np.zeros(max_slots)
        self.slot_admit_round = np.zeros(max_slots, np.int64)
        self.slot_plan = [""] * max_slots
        self.slot_seeded = np.zeros(max_slots, np.int64)
        self.results: list[WorkloadResult] = []
        self.rounds = 0
        self.topup_passes = 0
        self.idle_offset = 0.0
        self.truncated = False
        self._next_qid = 0
        if isinstance(scheduler, SchedulerConfig):
            scheduler = WorkloadScheduler(scheduler)
        self.scheduler: Optional[WorkloadScheduler] = scheduler
        if self.scheduler is not None:
            # slot_capacity="measured": derive the fairness capacity from
            # the loaded calibration's round-cost fit
            self.scheduler.calibrate(self.rates)
        if isinstance(rollup, RollupConfig):
            rollup = RollupTier(store, rollup)
        self.rollup: Optional[RollupTier] = rollup
        if self.rollup is not None and self.rollup.store is not store:
            raise ValueError("rollup tier was built over a different store")
        self.shed_count = 0
        self.preempt_count = 0
        self._service_times: list[float] = []   # scan service per retirement
        self._preview_cache: dict[int, tuple] = {}  # per intake pass, by qid
        self._rollup_cache: dict[int, tuple] = {}   # per intake pass, by qid
        self._cur_weights = np.ones(max_slots, np.float32)
        self._last_err: Optional[np.ndarray] = None  # (S,) last round report
        # fault tolerance: surviving-population bookkeeping.  Quarantining a
        # chunk (lost / irrecoverably corrupt) shrinks the population every
        # price and estimate must describe; the server re-derives these from
        # engine.quarantine_log after each round (see _note_quarantine).
        self._quarantine_seen = 0       # quarantine_log entries consumed
        self._quarantine_count = 0      # chunks quarantined so far
        self._eff_chunks = int(store.num_chunks)
        self._eff_tuples = int(store.num_tuples)
        self._alive = np.ones(store.num_chunks, bool)
        self._eff_bytes = (float(np.asarray(store.chunk_sizes).sum())
                           * store.codec.record_bytes)
        self._slot_retries0 = np.zeros(max_slots, np.int64)
        self._scan_rate = scan_tuples_per_s(store, self.config,
                                            rates=self.rates)
        set_tracer = getattr(self.engine, "set_tracer", None)
        if set_tracer is not None:
            set_tracer(self.tracer)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._bind_metrics()

    def _bind_metrics(self) -> None:
        """Register the server's observable state on the metrics registry —
        all pull gauges reading live attributes (zero hot-path writes), plus
        the subsystem bindings: prefetcher counters, rollup tier tallies,
        scheduler admission decisions, and fault-injector event counts when
        the store is injector-wrapped."""
        reg = self.metrics
        reg.gauge("server_rounds", help="engine rounds run",
                  fn=lambda: self.rounds)
        reg.gauge("server_topup_passes", help="schedule re-open passes",
                  fn=lambda: self.topup_passes)
        reg.gauge("server_tuples_scanned",
                  help="raw tuples extracted by the shared scan",
                  fn=lambda: self.tuples_scanned)
        reg.gauge("server_queue_depth", help="queries waiting for a slot",
                  fn=lambda: len(self.queue))
        self._queue_wait = reg.counter(
            "server_queue_wait_seconds",
            help="wall seconds admitted queries waited in the queue")
        self._synopsis_seeds = reg.counter(
            "server_synopsis_seeds_total",
            help="seed rows computed from the synopsis")
        self._synopsis_seed_tuples = reg.counter(
            "server_synopsis_seed_tuples_total",
            help="cached tuples evaluated for synopsis seeds")
        reg.gauge("server_slots_resident", help="occupied scan slots",
                  fn=lambda: sum(w is not None for w in self.slot_wq))
        reg.gauge("server_shed_count", help="queries shed (best-effort)",
                  fn=lambda: self.shed_count)
        reg.gauge("server_preempt_count", help="slot evictions",
                  fn=lambda: self.preempt_count)
        reg.gauge("server_chunks_quarantined",
                  help="chunks removed from the population",
                  fn=lambda: self._quarantine_count)
        reg.gauge("server_quarantine_events",
                  help="engine quarantine_log length",
                  fn=lambda: len(getattr(self.engine, "quarantine_log",
                                         None) or []))
        pf = getattr(self.engine, "pipeline", None)
        if pf is not None:
            pf.bind_metrics(reg)
        if self.rollup is not None:
            self.rollup.bind_metrics(reg)
        if self.scheduler is not None:
            self.scheduler.bind_metrics(reg)
        if self.config.residency == "sharded":
            reg.gauge("server_shard_resident_bytes",
                      help="raw table bytes resident on each device",
                      fn=lambda: self.engine.resident_bytes)
            reg.gauge("server_shard_claim_spread",
                      help="most minus fewest chunks claimed among the "
                      "claim queues in this pass",
                      fn=self._claim_spread)
        injected = getattr(self.store, "injected", None)
        if isinstance(injected, dict):
            for kind in sorted(injected):
                reg.gauge("faults_injected",
                          help="FaultInjector events by kind",
                          labels={"kind": kind},
                          fn=(lambda k=kind: self.store.injected.get(k, 0)))

    def _claim_spread(self) -> int:
        qhead = np.asarray(self.state.qhead)
        return int(qhead.max() - qhead.min()) if qhead.size else 0

    def metrics_snapshot(self) -> dict:
        """Public JSON-able observability snapshot: every registry
        instrument (pull gauges evaluated now — prefetcher/rollup/
        scheduler/fault counters included) plus ``quarantine_log``, the
        quarantined chunk ids in quarantine order (previously reachable
        only through engine internals)."""
        snap = self.metrics.snapshot()
        snap["quarantine_log"] = [
            int(j) for j in
            (getattr(self.engine, "quarantine_log", None) or [])]
        return snap

    def _decoded_fraction(self) -> float:
        """Parse-once cache coverage of the scan engine (0.0 when the engine
        has no decoded cache — packed residency, foreign engines)."""
        fn = getattr(self.engine, "decoded_fraction", None)
        return float(fn()) if fn is not None else 0.0

    def close(self) -> None:
        """Release engine resources (the stream-residency prefetcher's
        reader thread and host chunk cache); idempotent, packed no-op."""
        self.engine.close()

    def __enter__(self) -> "OLAWorkloadServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------- clock ----
    @property
    def t_model(self) -> float:
        """Modeled seconds since server start (Eq. 4 clock + idle skips)."""
        return max(float(self.state.t_io), float(self.state.t_cpu)) \
            + self.idle_offset

    @property
    def tuples_scanned(self) -> int:
        """Raw tuples the shared scan has extracted (workload total)."""
        return int(np.asarray(self.state.scan_m).sum())

    # -------------------------------------------------- fault tolerance ----
    def _pipeline_retries(self) -> int:
        """Cumulative retried chunk reads (stream residency; 0 packed)."""
        pf = getattr(self.engine, "pipeline", None)
        return int(pf.read_retries) if pf is not None else 0

    @property
    def chunks_quarantined(self) -> int:
        return self._quarantine_count

    def quarantine(self, chunk_ids) -> None:
        """Quarantine chunks by hand (operator escape hatch / tests): the
        same path round_data takes when a read exhausts its retries."""
        from repro.core.engine import quarantine_chunks

        before = int(np.asarray(self.state.quarantined).sum())
        self.state = quarantine_chunks(self.state, chunk_ids)
        after = int(np.asarray(self.state.quarantined).sum())
        if after == before:
            return
        log = getattr(self.engine, "quarantine_log", None)
        if log is not None:
            qn = np.asarray(self.state.quarantined)
            known = set(int(j) for j in log)
            log.extend(sorted(int(j) for j in np.flatnonzero(qn)
                              if int(j) not in known))
        self._note_quarantine(force=True)

    def _note_quarantine(self, force: bool = False) -> None:
        """Absorb newly quarantined chunks into every population-priced
        structure: the synopsis forgets their windows, rollup cells covering
        them die, and the scan rate / admission totals re-price over the
        survivors.  Idempotent and O(cells + new ids); a no-op round costs
        one list-length check."""
        log = getattr(self.engine, "quarantine_log", None) or []
        if len(log) <= self._quarantine_seen and not force:
            return
        new = [int(j) for j in log[self._quarantine_seen:]]
        self._quarantine_seen = len(log)
        with self.tracer.span("ola.quarantine", chunks=len(new)):
            self._absorb_quarantine(new)

    def _absorb_quarantine(self, new: list[int]) -> None:
        if new:
            # degradation is a per-query fact: every resident query's answer
            # now describes a smaller population — record it on their
            # explain trajectories
            for w in self.slot_wq:
                if w is not None and w.explain is not None:
                    w.explain.record_degradation(
                        round=self.rounds, t=self.t_model, chunk_ids=new)
        qn = np.asarray(self.state.quarantined)
        self._quarantine_count = int(qn.sum())
        sizes = np.asarray(self.store.chunk_sizes)
        alive = self._alive = ~qn
        self._eff_chunks = int(alive.sum())
        self._eff_tuples = int(sizes[alive].sum())
        self._eff_bytes = (float(sizes[alive].sum())
                           * self.store.codec.record_bytes)
        # quarantined chunks leave the decoded cache too (their bytes are no
        # longer trusted), and the scan-rate CPU discount re-prices over the
        # shrunken coverage
        drop = getattr(self.engine, "drop_decoded_chunks", None)
        if drop is not None and new:
            drop(new)
        self._scan_rate = scan_tuples_per_s(
            self.store, self.config, rates=self.rates,
            total_bytes=self._eff_bytes, total_tuples=self._eff_tuples,
            decoded_fraction=self._decoded_fraction())
        if self.synopsis is not None and new:
            self.synopsis.drop_chunks(new)
        if self.rollup is not None and new:
            self.rollup.invalidate_chunks(new)

    def _mask_quarantined_seed(self, seed: Optional[dict]) -> Optional[dict]:
        """Zero a seed row's quarantined columns (preemption snapshots and
        pre-quarantine cells may still carry their tuples)."""
        if seed is None or self._quarantine_count == 0:
            return seed
        alive = ~np.asarray(self.state.quarantined)
        return dict(
            m=np.where(alive, np.asarray(seed["m"]), 0),
            ysum=np.where(alive, np.asarray(seed["ysum"]), 0.0),
            ysq=np.where(alive, np.asarray(seed["ysq"]), 0.0),
            psum=np.where(alive, np.asarray(seed["psum"]), 0.0))

    # ------------------------------------------------------------ intake ----
    def submit(self, query: Query, arrival_t: Optional[float] = None,
               plan: Optional[str] = None,
               slo: Optional[QuerySLO] = None) -> int:
        """Enqueue a query; returns its qid.  ``arrival_t`` defaults to the
        current modeled time (an online submission).  ``slo`` attaches a
        service-level objective (deadline / CI half-width target / priority
        class) — it only takes effect when the server was built with a
        ``scheduler``.

        Raises at submit time (not mid-scan at admission) when the query is
        outside the slot-encodable linear+range form, the plan is unknown,
        or the scan is already fully extracted with no synopsis to answer
        from (the query could never receive a tuple).
        """
        with self.tracer.span("ola.submit", qid=self._next_qid):
            return self._submit(query, arrival_t, plan, slo)

    def _submit(self, query: Query, arrival_t: Optional[float],
                plan: Optional[str], slo: Optional[QuerySLO]) -> int:
        if plan is not None and plan not in PLAN_CODES:
            raise ValueError(
                f"unknown plan {plan!r}; expected one of {sorted(PLAN_CODES)}")
        if query.group_by is not None and self.max_groups == 0:
            raise ValueError(
                f"query {query.name!r} has group_by but the server was built "
                f"ungrouped; construct it with EngineConfig(max_groups="
                f"{query.group_by.max_groups}) or higher")
        row = encode_slot(query, self.store.codec.num_cols,
                          max_groups=self.max_groups)  # validates early
        if self.synopsis is None and not (
                (np.asarray(self.state.scan_m)
                 < np.asarray(self.store.chunk_sizes))
                & ~np.asarray(self.state.quarantined)).any():
            raise ValueError(
                "scan fully extracted and no synopsis configured: the query "
                "can never be served; construct the server with "
                "synopsis_budget_tuples > 0")
        qid = self._next_qid
        self._next_qid += 1
        at = self.t_model if arrival_t is None else float(arrival_t)
        key = (pattern_key(query, self.store.codec.num_cols)
               if self.rollup is not None else None)
        now = time.perf_counter()
        wq = WorkloadQuery(qid=qid, query=query, arrival_t=at,
                           plan=plan, row=row, slo=slo, key=key,
                           explain=ExplainRecord(qid=qid, name=query.name,
                                                 t_submit=at),
                           t_submitted=now, t_enqueued=now)
        self.queue.append(wq)
        self.queue.sort(key=lambda wq: (wq.arrival_t, wq.qid))
        return qid

    # --------------------------------------------------------- admission ----
    def _free_slots(self) -> list[int]:
        return [s for s in range(self.max_slots) if self.slot_wq[s] is None]

    def _refresh_synopsis(self) -> None:
        """Absorb the scan's extraction cache into the synopsis (on demand,
        before seeding a newcomer)."""
        if self.synopsis is None:
            return
        if int(np.asarray(self.state.scan_m).sum()) == 0:
            return
        variances = self.synopsis.within_variances(self.state)
        self.synopsis.update_from_engine(
            self.state, np.asarray(self.state.schedule), variances)

    def _admit_ready(self) -> None:
        if self.rollup is not None:
            self.rollup.maintain(self.t_model)
            self._rollup_cache = {}
        if self.scheduler is not None:
            self._admit_ready_scheduled()
            return
        now = self.t_model
        if self.rollup is not None:
            # Tier-1 short-circuit: a rollup-served query needs no slot, so
            # every ready hit is answered now — even when the slot table is
            # full and even behind other ready work (it consumes nothing
            # the others are waiting for)
            for wq in [w for w in self.queue if w.arrival_t <= now]:
                if self._try_tier1(wq):
                    self.queue.remove(wq)
        while self.queue and self.queue[0].arrival_t <= now:
            free = self._free_slots()   # recompute: seed-answered slots refree
            if not free:
                for wq in self.queue:   # ready queries kept waiting: record it
                    if wq.arrival_t <= now:
                        wq.queued = True
                break
            wq = self.queue.pop(0)
            self._admit(free[0], wq)

    @staticmethod
    def _wants_preview(wq: WorkloadQuery) -> bool:
        slo = wq.slo or NO_SLO
        return slo.has_deadline or np.isfinite(slo.target_halfwidth)

    @staticmethod
    def _outcome(wq: WorkloadQuery) -> str:
        if wq.preempted:
            return "preempted"
        return "queued" if wq.queued else "admitted"

    def _finish(self, wq: WorkloadQuery, result: WorkloadResult) -> None:
        """Single retirement funnel for every completion path (tier-1,
        shed, seed-retire, scan-retire): finalize + attach the explain
        record (its final est/CI copied from the result's own floats —
        bit-for-bit), count the outcome, and observe the wall seconds from
        submit to now."""
        if wq.explain is not None:
            result.explain = wq.explain.finalize(result)
        self.results.append(result)
        self.metrics.counter(
            "queries_total", help="completed queries by scheduler outcome",
            labels={"outcome": result.sched_outcome}).inc()
        self.metrics.histogram(
            "query_latency_s", help="submit->retirement latency (wall s)",
            bounds=LATENCY_BUCKETS_S).observe(
                time.perf_counter() - wq.t_submitted)

    def _admit_ready_scheduled(self) -> None:
        """Scheduler intake: ready queries are considered in queue-policy
        order; each is admitted, left queued, shed — or, with
        ``config.preempt``, granted a slot by evicting a strictly-lower-
        priority resident when its deadline is feasible *only* that way."""
        sched = self.scheduler
        now = self.t_model
        ready = [wq for wq in self.queue if wq.arrival_t <= now]
        ready.sort(key=sched.queue_key)
        # one synopsis refresh per intake pass; per-query previews are cached
        # for the pass (reused by feasibility, shedding, and _admit's
        # effective-ε translation) instead of re-absorbing the extraction
        # cache for every waiting deadline query on every round
        self._preview_cache = {}
        if self.synopsis is not None and any(map(self._wants_preview, ready)):
            self._refresh_synopsis()
        while True:
            ready = [wq for wq in self.queue if wq.arrival_t <= now]
            ready.sort(key=sched.queue_key)
            ahead: list[WorkloadQuery] = []  # still queued, ahead of this one
            restart = False
            for wq in ready:
                free = self._free_slots()  # recompute: seed-retired slots refree
                if free and ahead:
                    # a slot freed mid-pass *behind* queued work (a preempt-
                    # admitted query retired instantly from its seed):
                    # restart so the highest-priority queued query gets
                    # first claim — continuing here would hand the slot to
                    # a later, lower-priority candidate and price the
                    # earlier ones against a stale no-free-slot snapshot
                    restart = True
                    break
                with self.tracer.span("ola.decide", qid=wq.qid):
                    decision = self._decide_admission(wq, len(free), ahead)
                if wq.explain is not None:
                    wq.explain.admission_reason = decision.reason
                    wq.explain.predicted_service_s = \
                        decision.predicted_service_s
                    wq.explain.predicted_finish_t = \
                        decision.predicted_finish_t
                if decision.action == TIER1 and self._try_tier1(wq):
                    # rollup cache answered: no slot consumed, the slot
                    # picture is unchanged — no restart needed
                    self.queue.remove(wq)
                    continue
                if not free and self._try_preempt(wq, decision):
                    # a victim was evicted exactly because the deadline fits
                    # if the query runs now — the freed slot is the
                    # candidate's
                    self.queue.remove(wq)
                    self._admit(self._free_slots()[0], wq)
                elif decision.action == SHED:
                    self.queue.remove(wq)
                    with self.tracer.span("ola.shed", qid=wq.qid):
                        self._shed(wq)
                elif free:
                    self.queue.remove(wq)
                    self._admit(free[0], wq)
                else:
                    wq.queued = True
                    ahead.append(wq)
            if not restart:
                break
            # termination: the restarted pass sees free slots with nothing
            # ahead, so its head query is admitted or shed — the queue
            # strictly shrinks every restart

    def _try_preempt(self, wq: WorkloadQuery, decision) -> bool:
        """Evict a strictly-lower-priority resident slot for ``wq`` when its
        deadline would die in the queue but fits if the query runs *now*.
        Returns True when a slot was freed (the victim is snapshotted and
        re-queued — see :func:`repro.sched.preempt.select_victim`)."""
        sched = self.scheduler
        slo = wq.slo or NO_SLO
        if not (sched.config.preempt and slo.has_deadline):
            return False
        deadline_t = wq.arrival_t + slo.deadline_s
        if decision.predicted_finish_t <= deadline_t:
            return False                # feasible by waiting: don't evict
        now = self.t_model
        if max(now, wq.arrival_t) + decision.predicted_service_s > deadline_t:
            return False                # hopeless even with a slot right now
        stopped = np.asarray(self.state.stopped)
        # grouped residents are not evictable: the eviction snapshot saves
        # only the scalar stats row, so a re-admitted grouped query would
        # silently lose its per-group cells and its discovered value set
        evictable = [self.slot_wq[s] is not None and not stopped[s]
                     and self.slot_wq[s].query.group_by is None
                     for s in range(self.max_slots)]
        victim = select_victim(
            wq.slo, [w.slo if w is not None else None for w in self.slot_wq],
            self.slot_admit_t, evictable)
        if victim is None:
            return False
        self._evict(victim)
        return True

    def _evict(self, s: int) -> None:
        """Preempt slot ``s``: snapshot its statistics row as the occupant's
        re-admission seed, release the slot, and re-queue the occupant
        (flagged ``preempted`` — it completes later, never dropped)."""
        wq = self.slot_wq[s]
        with self.tracer.span("ola.evict", qid=wq.qid, slot=s):
            wq.saved_stats = slot_stats_snapshot(self.state, s)
        wq.preempted = True
        wq.queued = True
        wq.t_enqueued = time.perf_counter()
        self.preempt_count += 1
        self._release(s)
        self.queue.append(wq)
        self.queue.sort(key=lambda w: (w.arrival_t, w.qid))

    def _cached_preview(self, wq: WorkloadQuery) -> tuple:
        out = self._preview_cache.get(wq.qid)
        if out is None:
            out = self._seed_answer(wq.query, seed=wq.saved_stats, key=wq.key)
            self._preview_cache[wq.qid] = out
        return out

    def _rollup_answer(self, wq: WorkloadQuery) -> Optional[tuple]:
        """Tier-1 answer preview from the query's promoted rollup cell:
        ``(m, estimate, lo, hi, err, having_decision)`` — exact over the
        cell's fully-covered chunks (the FPC zeroes their variance), CI
        over the remainder — or None when no cell serves the pattern.
        Cached per intake pass (cells only change between rounds)."""
        if self.rollup is None or wq.key is None:
            return None
        cell = self.rollup.get(wq.key)
        if cell is None or int(cell.m.sum()) == 0:
            return None
        out = self._rollup_cache.get(wq.qid)
        if out is None:
            m, est_v, lo, hi, err = self._seed_answer(
                wq.query, seed=cell.seed_dict())
            q = wq.query
            decision = -1
            if q.having is not None and m > 0:
                decision = est.having_verdict(lo, hi, q.having.op,
                                              q.having.threshold)
            out = (m, est_v, lo, hi, err, decision)
            self._rollup_cache[wq.qid] = out
        return out

    def _try_tier1(self, wq: WorkloadQuery) -> bool:
        """Serve ``wq`` from the rollup cache iff the cached answer meets
        its accuracy ask (the slot-effective ε, or a decided HAVING).
        Tier-1 answers hold no slot and consume zero scan rounds."""
        if wq.query.group_by is not None:
            # a rollup cell carries only base-predicate scalar stats — it
            # cannot produce the per-group cells a grouped answer promises
            return False
        ans = self._rollup_answer(wq)
        if ans is None:
            return False
        m, est_v, lo, hi, err, decision = ans
        if m == 0:
            return False
        eps_eff = wq.query.epsilon
        if self.scheduler is not None:
            eps_eff = self.scheduler.effective_epsilon(wq.query, wq.slo,
                                                       est_v)
        if err > eps_eff and decision == -1:
            return False
        now = self.t_model
        cell = self.rollup.get(wq.key)
        cell.touch(now)
        self.rollup.tier1_hits += 1
        self.rollup.observe(wq.query, wq.key, now)  # hits keep patterns hot
        latency = now - wq.arrival_t
        slo_met = None
        if wq.slo is not None:
            slo_met = wq.slo.met(latency, (hi - lo) / 2.0)
        if wq.explain is not None:
            wq.explain.tier = "tier1"
            wq.explain.tier_reason = (
                "promoted rollup cell decided the HAVING verdict"
                if err > eps_eff else
                f"promoted rollup cell meets target (err {err:.3g} <= "
                f"eps {eps_eff:.3g}); no slot, no scan rounds")
        self._finish(wq, WorkloadResult(
            qid=wq.qid, name=wq.query.name, estimate=est_v, lo=lo, hi=hi,
            err=err, decision=decision, plan="rollup",
            t_submit=wq.arrival_t, t_admit=now, t_done=now,
            seeded_tuples=m, tuples_seen=m, rounds_resident=0,
            sched_outcome="tier1", queue_wait_model_s=latency,
            slo_met=slo_met,
            priority=(wq.slo or NO_SLO).priority,
            degraded=self._quarantine_count > 0,
            chunks_quarantined=self._quarantine_count))
        return True

    def _rollup_on_retire(self, wq: WorkloadQuery, s: Optional[int],
                          valid: bool) -> None:
        """Completion hook for the rollup miner: log the pattern (promoting
        it when the workload has shown it hot) and, when the query retired
        from a slot with real statistics, fold that final row into its
        cell.  A newly promoted cell is birth-seeded from the synopsis so
        the *next* repeat already starts warm even if no slot runs the
        pattern again before then."""
        if self.rollup is None or wq.key is None:
            return
        promoted = self.rollup.observe(wq.query, wq.key, self.t_model)
        if promoted is not None:
            seed = self._synopsis_seed(wq.query)
            if seed is not None:
                promoted.fold(seed)
        if s is not None and valid:
            self.rollup.fold(wq.key, slot_stats_snapshot(self.state, s))

    def _observed_mean_service_s(self) -> Optional[float]:
        """Mean scan service over completed queries; None before the first
        retirement.  Single source for every admission-path consumer."""
        st = self._service_times
        return (sum(st) / len(st)) if st else None

    def _service_prior_s(self) -> float:
        """Cold-start per-job service prior for wait pricing: the observed
        mean service when any query has completed, else one full pass at
        the scan rate (the CLT worst case).  Never the *candidate's* own
        seed-discounted prediction — the queue is other people's work."""
        mean = self._observed_mean_service_s()
        if mean is not None:
            return mean
        return float(self._eff_tuples) / max(self._scan_rate, 1e-12)

    def _wait_components(self, ahead: list) -> tuple:
        """Model-priced wait parts for the admission snapshot:
        ``(slot_drain_s, queue_ahead_service_s)``.  Each resident slot's
        remaining service is its class quantile minus its elapsed
        residence; the drain is the *minimum* across slots (any slot
        freeing admits the head of the queue).  Each queued job ahead is
        priced at its own class's quantile — not the candidate's."""
        model = self.scheduler.service_model
        prior = self._service_prior_s()
        now = self.t_model
        drains = []
        for s in range(self.max_slots):
            w = self.slot_wq[s]
            if w is None:
                continue
            pred = model.predict((w.slo or NO_SLO).priority, prior)
            drains.append(max(pred - max(now - self.slot_admit_t[s], 0.0),
                              0.0))
        drain = min(drains) if drains else None
        ahead_s = sum(model.predict((w.slo or NO_SLO).priority, prior)
                      for w in ahead)
        return drain, float(ahead_s)

    def _decide_admission(self, wq: WorkloadQuery, n_free: int, ahead: list):
        slo = wq.slo or NO_SLO
        grouped = wq.query.group_by is not None
        seed_m, seed_err, seed_est = 0, float("inf"), None
        rollup_err = float("inf")
        rollup = self._rollup_answer(wq)
        if rollup is not None:
            r_m, r_est, _, _, r_err, r_dec = rollup
            # Tier-1 routing input: a decided HAVING is as good as err 0;
            # the cell also doubles as the feasibility seed (Eq. (4) prices
            # only the *remaining* scan when the cache falls short of ε)
            rollup_err = 0.0 if r_dec != -1 else r_err
            seed_m, seed_est, seed_err = r_m, r_est, r_err
        if self._wants_preview(wq):     # feasibility needs the seed preview
            m, e, _, _, err = self._cached_preview(wq)
            if m > seed_m:
                seed_m, seed_est, seed_err = m, e, err
        if grouped:
            # a cached scalar answer can neither serve nor seed the
            # per-group cells (they fill only from scan rounds while live):
            # never tier-1 route, and price the scan without a seed discount
            # (seed_est survives as the ε-translation magnitude anchor)
            rollup_err = float("inf")
            seed_m, seed_err = 0, float("inf")
        drain, ahead_s = self._wait_components(ahead)
        load = ServerLoad(
            now=self.t_model, free_slots=n_free, queue_ahead=len(ahead),
            scan_rate=self._scan_rate,
            total_tuples=int(self._eff_tuples),
            mean_service_s=self._observed_mean_service_s(),
            slot_drain_s=drain, queue_ahead_service_s=ahead_s)
        # feasibility must be judged against the ε the slot will actually
        # run at — a finite target_halfwidth tightens it (same translation
        # _admit applies to the slot row)
        eps_eff = self.scheduler.effective_epsilon(wq.query, wq.slo, seed_est)
        return self.scheduler.admission.decide(
            arrival_t=wq.arrival_t, slo=slo, epsilon=eps_eff,
            load=load, seed_m=seed_m, seed_err=seed_err,
            rollup_err=rollup_err,
            group_count=(wq.query.group_by.effective_top_k if grouped else 0))

    def _seed_answer(self, query: Query, seed: Optional[dict] = None,
                     key: Optional[tuple] = None) -> tuple:
        """Best scan-free answer available right now: ``(m, estimate, lo,
        hi, err)`` — ``(0, nan, nan, nan, inf)`` when nothing can serve the
        query.  ``seed`` overrides the lookups (a preempted query's
        statistics snapshot is a richer seed than the synopsis); otherwise
        the synopsis row and — when ``key`` names a promoted rollup cell —
        the cell row compete by sample size, and the caller is assumed to
        have refreshed the synopsis (the scheduled intake pass does,
        once).  Single construction shared by admission feasibility, the
        effective-ε translation, shedding, and the rollup preview."""
        if seed is None:
            seed = self._synopsis_seed(query)
            if self.rollup is not None and key is not None:
                cell = self.rollup.get(key)
                if cell is not None and (
                        seed is None or int(cell.m.sum())
                        > int(np.asarray(seed["m"]).sum())):
                    seed = cell.seed_dict()
        seed = self._mask_quarantined_seed(seed)
        if seed is None or int(seed["m"].sum()) == 0:
            return 0, float("nan"), float("nan"), float("nan"), float("inf")
        est_v, lo, hi, err = self._row_answer(
            query, seed["m"], seed["ysum"], seed["ysq"], seed["psum"])
        return (int(seed["m"].sum()), float(np.asarray(est_v)[0]),
                float(np.asarray(lo)[0]), float(np.asarray(hi)[0]),
                float(np.asarray(err)[0]))

    def _row_answer(self, query: Query, m, ysum, ysq, psum) -> tuple:
        """``(estimate, lo, hi, err)``, each of shape (1,), of ``query``
        from one host statistics row, over the surviving population (after
        quarantine the estimator's N/M are the surviving totals, the same
        rescale the jitted round applies).  Stratified over the claim
        queues as the round's estimates are, and then computed on the host:
        eager ops on a mesh's replicated state would each run on every
        device."""
        strata = self.engine.program.queues
        if strata > 1:
            row = self._mask_quarantined_seed(
                dict(m=m, ysum=ysum, ysq=ysq, psum=psum))
            n_h = self._alive.reshape(strata, -1).sum(axis=1)
            return tuple(np.asarray([x]) for x in est.stratified_row_answer(
                query.agg, self.store.chunk_sizes, n_h, row["m"],
                row["ysum"], row["ysq"], row["psum"], query.confidence))
        dtype = self.state.stats.ysum.dtype
        stats_row = self.state.stats._replace(
            m=jnp.asarray(m, jnp.int32), ysum=jnp.asarray(ysum, dtype)[None],
            ysq=jnp.asarray(ysq, dtype)[None],
            psum=jnp.asarray(psum, dtype)[None],
            n_total=self._eff_chunks, m_total=self._eff_tuples)
        return _answer_from_stats([query], stats_row)

    def _shed(self, wq: WorkloadQuery) -> None:
        """Answer a shed query immediately from the synopsis (flagged
        best-effort) — or flag it unserved when no seed exists.  A shed
        query never holds a slot and never costs a scan round."""
        now = self.t_model
        q = wq.query
        m_seen, estimate, lo, hi, err = self._cached_preview(wq)
        if m_seen == 0:
            decision = -1
            unserved, from_syn = True, False
        else:
            decision = -1
            if q.having is not None:
                decision = est.having_verdict(lo, hi, q.having.op,
                                              q.having.threshold)
            unserved, from_syn = False, True
        latency = now - wq.arrival_t
        slo_met = None
        if wq.slo is not None:
            # a shed answer arrives instantly, so the deadline alone would
            # always "hit" — honesty requires the best-effort estimate to
            # also meet the query's accuracy ask (ε or a HAVING verdict)
            accurate = (not unserved) and (err <= q.epsilon or decision != -1)
            slo_met = accurate and wq.slo.met(latency, (hi - lo) / 2.0)
        if wq.explain is not None and not wq.explain.tier_reason:
            wq.explain.tier_reason = (
                "shed: no seed available, answer unserved" if unserved
                else "shed: best-effort synopsis answer, no scan rounds")
        self._finish(wq, WorkloadResult(
            qid=wq.qid, name=q.name, estimate=estimate, lo=lo, hi=hi,
            err=err, decision=decision, plan="shed",
            t_submit=wq.arrival_t, t_admit=now, t_done=now,
            seeded_tuples=m_seen, tuples_seen=m_seen, rounds_resident=0,
            from_synopsis=from_syn, unserved=unserved, sched_outcome="shed",
            queue_wait_model_s=now - wq.arrival_t, slo_met=slo_met,
            priority=(wq.slo or NO_SLO).priority,
            degraded=self._quarantine_count > 0,
            chunks_quarantined=self._quarantine_count))
        self.shed_count += 1
        # a shed still evidences demand for the pattern: mine it (no fold —
        # the query never held a slot, there are no statistics to merge)
        self._rollup_on_retire(wq, None, False)

    def _admit(self, s: int, wq: WorkloadQuery) -> None:
        self._queue_wait.inc(time.perf_counter() - wq.t_enqueued)
        plan = wq.plan or select_plan(self.store, self.config, wq.query,
                                      rates=self.rates,
                                      decoded_fraction=self._decoded_fraction())
        tr = self.tracer
        with tr.span("ola.admit_query", qid=wq.qid, slot=s, plan=plan):
            row = wq.row or encode_slot(wq.query, self.store.codec.num_cols,
                                        max_groups=self.max_groups)
            row["plan"] = np.int32(PLAN_CODES[plan])
            with tr.span("ola.synopsis_refresh"):
                self._refresh_synopsis()
            with tr.span("ola.seed"):
                seed = self._admission_seed(wq)
                if (self.scheduler is not None and wq.slo is not None
                        and np.isfinite(wq.slo.target_halfwidth)):
                    # absolute CI half-width target -> effective relative ε
                    # for the slot row, anchored on the synopsis magnitude
                    # estimate (the pass-cached preview — the same one
                    # admission feasibility used)
                    _, seed_est, *_ = self._cached_preview(wq)
                    eps_eff = self.scheduler.effective_epsilon(
                        wq.query, wq.slo, seed_est)
                    row["eps"] = np.float32(eps_eff)
            with tr.span("ola.slot_write"):
                self._write_slot(s, wq, row, seed, plan)
            # Section 6.3 best case, per slot: the seed alone may already
            # meet the target — answer at admission without consuming scan
            # rounds.  No top-up here: while the newcomer is live its
            # accuracy votes keep chunks from closing early, and if the scan
            # still winds down before it is satisfied, step()'s exhausted
            # branch re-opens chunks then — top-up passes happen only when
            # provably needed.
            if seed is not None:
                with tr.span("ola.seed_retire"):
                    self._try_retire_from_seed(s, wq, seed)

    def _admission_seed(self, wq: WorkloadQuery) -> Optional[dict]:
        """The statistics row a newcomer's slot starts from, or None."""
        if wq.saved_stats is not None:
            # preempted query returning to a slot: its eviction snapshot is
            # the seed — every tuple it already counted, at full per-chunk
            # resolution (strictly richer than the synopsis)
            return wq.saved_stats
        seed = self._synopsis_seed(wq.query)
        if self.rollup is not None and wq.key is not None:
            cell = self.rollup.get(wq.key)
            if cell is not None and (
                    seed is None or int(cell.m.sum())
                    > int(np.asarray(seed["m"]).sum())):
                # Tier-2 with a Tier-1 discount: the cell alone missed the
                # target, but it out-samples the synopsis — the slot starts
                # from the cached partial aggregate and scans only the
                # remainder (both are permutation-window samples inside the
                # scanned prefix, so future round deltas compose without
                # overlap)
                seed = cell.seed_dict()
        return seed

    def _synopsis_seed(self, query: Query) -> Optional[dict]:
        """The synopsis's statistics row for ``query``, or None; every row
        served is counted with the cached tuples it was evaluated on."""
        seed = self.synopsis.seed_slot(query) if self.synopsis else None
        if seed is not None:
            self._synopsis_seeds.inc()
            self._synopsis_seed_tuples.inc(int(seed["m"].sum()))
        return seed

    def _write_slot(self, s: int, wq: WorkloadQuery, row: dict,
                    seed: Optional[dict], plan: str) -> None:
        """Give slot ``s`` to ``wq``: its statistics row from ``seed``, its
        slot-table row, fresh group cells, and the host bookkeeping."""
        n = self.store.num_chunks
        stats, seeded = slot_stats_write(self.state.stats, s, seed, n)
        self.state = self.state._replace(
            stats=stats, stopped=self.state.stopped.at[s].set(False))
        if self._last_err is not None:
            # the previous occupant's round-report error is stale for the
            # new one; claim weighting treats it as "no estimate yet"
            self._last_err = self._last_err.copy()
            self._last_err[s] = np.inf
        self.table = slot_table_set(self.table, s, row)
        # slot_table_set reset the row's fairness weight to 1.0 — keep the
        # written-weights cache in sync, or _apply_scheduling could skip the
        # next write (computed vector unchanged) and leave the new occupant
        # running at full budget instead of its max-min share
        self._cur_weights = self._cur_weights.copy()
        self._cur_weights[s] = np.float32(row.get("weight", 1.0))
        self.slot_wq[s] = wq
        self.slot_admit_t[s] = self.t_model
        self.slot_admit_round[s] = self.rounds
        self.slot_plan[s] = plan
        self.slot_seeded[s] = seeded
        self._slot_retries0[s] = self._pipeline_retries()
        gb = wq.query.group_by
        if gb is not None:
            # group cells start from zero for the new occupant (a prior
            # grouped resident may have left stale per-cell rows); pinned
            # values are live from the row write, the rest get discovered
            self.state = zero_group_cells(self.state, s)
            self._slot_sketch[s] = GroupSketch(max(2 * gb.max_groups, 8))
            self._slot_groups[s] = [float(v) for v in (gb.values or ())]
        else:
            self._slot_sketch[s] = None
            self._slot_groups[s] = None
        if wq.explain is not None:
            # the Eq. (4) pricing the plan was chosen under, frozen at the
            # admission instant (population-adjusted, cache-discounted)
            df = self._decoded_fraction()
            t_io, t_cpu = eq4_cost_terms(
                self.store, self.config, self.rates,
                total_bytes=self._eff_bytes,
                total_tuples=self._eff_tuples, decoded_fraction=df)
            wq.explain.plan = plan
            wq.explain.cost_t_io_s = float(t_io)
            wq.explain.cost_t_cpu_s = float(t_cpu)
            wq.explain.decoded_fraction = float(df)
            wq.explain.effective_epsilon = float(
                row.get("eps", wq.query.epsilon))
            if not wq.explain.admission_reason:
                wq.explain.admission_reason = "fifo: free slot"

    def _try_retire_from_seed(self, s: int, wq: WorkloadQuery,
                              seed: dict) -> bool:
        q = wq.query
        if q.group_by is not None:
            # the seed meets the scalar target at best; the per-group cells
            # only fill from scan rounds, so a grouped query always scans
            return False
        # slot s's statistics row was just written from ``seed``
        est_v, lo, hi, err = self._row_answer(q, seed["m"], seed["ysum"],
                                              seed["ysq"], seed["psum"])
        e = float(np.asarray(err)[0])
        decision = -1
        if q.having is not None:
            decision = est.having_verdict(
                np.asarray(lo)[0], np.asarray(hi)[0], q.having.op,
                q.having.threshold)
        if e > q.epsilon and decision == -1:
            return False
        self._rollup_on_retire(wq, s, True)
        lo_f, hi_f = float(np.asarray(lo)[0]), float(np.asarray(hi)[0])
        slo_met = None
        if wq.slo is not None:
            slo_met = wq.slo.met(self.t_model - wq.arrival_t,
                                 (hi_f - lo_f) / 2.0)
        if wq.explain is not None and not wq.explain.tier_reason:
            wq.explain.tier_reason = ("seed met the target at admission "
                                      "(answered without scan rounds)")
        self._finish(wq, WorkloadResult(
            qid=wq.qid, name=q.name, estimate=float(np.asarray(est_v)[0]),
            lo=lo_f, hi=hi_f, err=e,
            decision=decision, plan=self.slot_plan[s],
            t_submit=wq.arrival_t, t_admit=self.slot_admit_t[s],
            t_done=self.t_model, seeded_tuples=int(self.slot_seeded[s]),
            tuples_seen=int(self.slot_seeded[s]),
            rounds_resident=0, from_synopsis=True,
            sched_outcome=self._outcome(wq),
            queue_wait_model_s=self.slot_admit_t[s] - wq.arrival_t,
            slo_met=slo_met,
            priority=(wq.slo or NO_SLO).priority,
            degraded=self._quarantine_count > 0,
            chunks_quarantined=self._quarantine_count,
            read_retries=max(self._pipeline_retries()
                             - int(self._slot_retries0[s]), 0)))
        self._release(s)
        return True

    def _release(self, s: int) -> None:
        self.table = slot_table_clear(self.table, s)
        self.state = self.state._replace(
            stopped=self.state.stopped.at[s].set(True))
        self.slot_wq[s] = None
        self._slot_sketch[s] = None
        self._slot_groups[s] = None

    # ----------------------------------------------------------- top-up ----
    def _begin_topup_pass(self) -> bool:
        """Re-open early-closed chunks and rewind the schedule head (each
        claim queue's head) to the first not-closed position (not all the
        way to 0 — fully-extracted
        prefix chunks would only burn a claim round each).  Worker claims
        are dropped to IDLE so re-claiming is race-free; a re-opened chunk
        is charged as a fresh raw READ when extraction resumes past its
        cached tuples.  Per-chunk permutation cursors continue where they
        left off, so samples stay prefixes of each chunk's random order.
        Returns False when every chunk is fully extracted (nothing to top
        up)."""
        sizes = np.asarray(self.store.chunk_sizes)
        scan_m = np.asarray(self.state.scan_m)
        # a quarantined chunk is permanently out of the population: it can
        # never be topped up, and re-opening it would stall the scan on a
        # chunk whose reads always fail
        not_exhausted = ((scan_m < sizes)
                         & ~np.asarray(self.state.quarantined))
        if not not_exhausted.any():
            return False
        reopened = np.asarray(self.state.closed) & not_exhausted
        closed = np.asarray(self.state.closed) & ~not_exhausted
        new_head, qhead = self.engine.program.open_heads(
            closed, np.asarray(self.state.schedule))
        raw_touched = np.asarray(self.state.raw_touched) & ~reopened
        self.state = self.state._replace(
            closed=jnp.asarray(closed),
            head=jnp.asarray(new_head, jnp.int32),
            qhead=jnp.asarray(qhead),
            cur=jnp.full_like(self.state.cur, IDLE),
            raw_touched=jnp.asarray(raw_touched))
        self.topup_passes += 1
        return True

    # ---------------------------------------------------------- grouping ----
    def _group_results(self, rep, s: int, wq: WorkloadQuery,
                       ) -> Optional[list[GroupResult]]:
        """Assemble slot ``s``'s grouped answer from the round report: one
        :class:`GroupResult` per tracked value (discovery order) plus the
        ``__other__`` spill cell.  HAVING is judged per cell, host-side, on
        the same CI the report carries."""
        q = wq.query
        if q.group_by is None:
            return None
        tracked = self._slot_groups[s] or []
        g_est = np.asarray(rep.g_est[s], float)
        g_lo = np.asarray(rep.g_lo[s], float)
        g_hi = np.asarray(rep.g_hi[s], float)
        g_err = np.asarray(rep.g_err[s], float)
        g_n = np.asarray(rep.g_n[s])
        cells = [(i, float(v), False) for i, v in enumerate(tracked)]
        cells.append((self.max_groups, float("nan"), True))
        out = []
        for i, value, is_other in cells:
            decision = -1
            if q.having is not None and int(g_n[i]) > 0:
                decision = est.having_verdict(
                    float(g_lo[i]), float(g_hi[i]), q.having.op,
                    q.having.threshold)
            out.append(GroupResult(
                value=value, estimate=float(g_est[i]), lo=float(g_lo[i]),
                hi=float(g_hi[i]), err=float(g_err[i]), n=int(g_n[i]),
                decision=decision, is_other=is_other))
        return out

    def _rollup_group_cells(self, wq: WorkloadQuery, s: int) -> None:
        """Per-group rollup mining at retirement: each tracked cell is the
        completed run of the equivalent :func:`group_fanout` scalar pattern,
        so it feeds the Tier-1 miner under that pattern's key and — once
        promoted — folds the cell's per-chunk stats row through the same
        cell-fold contract scalar slots use.  A later fan-out-style repeat
        of a hot group then starts warm (or answers Tier-1 outright)."""
        gb = wq.query.group_by
        if self.rollup is None or gb is None:
            return
        tracked = self._slot_groups[s] or []
        if not tracked:
            return
        rows = slot_group_rows(self.state, s)
        base = dataclasses.replace(wq.query, group_by=None)
        for i, v in enumerate(tracked):
            fq = group_fanout(base, gb.col, [v])[0]
            key = pattern_key(fq, self.store.codec.num_cols)
            if key is None:
                continue
            self.rollup.observe(fq, key, self.t_model)
            self.rollup.fold(key, dict(
                m=rows["gm"][i], ysum=rows["gys"][i],
                ysq=rows["gyq"][i], psum=rows["gps"][i]))

    def _fold_group_discovery(self, rep) -> None:
        """Post-round online discovery for live grouped slots: fold the
        round's tally report into each slot's SpaceSaving sketch, promote
        newly-heavy values into free tracked cells (grow-only), and restart
        the ``__other__`` window whenever the tracked set changes (the spill
        cell's meaning shrank, so its stats must restart — the post-restart
        sample window stays a uniform without-replacement sample)."""
        if self.max_groups == 0:
            return
        g_tal = None
        stopped = np.asarray(self.state.stopped)
        for s in range(self.max_slots):
            wq = self.slot_wq[s]
            if (wq is None or stopped[s] or wq.query.group_by is None
                    or self._slot_sketch[s] is None):
                continue
            if g_tal is None:
                g_tal = np.asarray(rep.g_tal)
            sketch = self._slot_sketch[s]
            sketch.fold(g_tal[s])
            if (sketch.mass < self._group_warmup
                    or sketch.rounds < WARMUP_ROUNDS):
                continue    # ranking not yet trustworthy (see ServerOptions)
            gb = wq.query.group_by
            tracked = self._slot_groups[s]
            new = promote_values(sketch, tracked, gb.max_groups)
            if not new:
                continue
            tracked.extend(float(v) for v in new)
            g = self.max_groups + 1
            gval = np.zeros((g,), np.float32)
            gact = np.zeros((g,), np.float32)
            gval[:len(tracked)] = np.asarray(tracked, np.float32)
            gact[:len(tracked)] = 1.0
            gact[g - 1] = 1.0   # __other__ stays live
            with self.tracer.span("ola.group_promote", qid=wq.qid, slot=s,
                                  values=len(new)):
                self.table = slot_table_set_groups(self.table, s, gval, gact)
                self.state = zero_group_cells(self.state, s, cells=[g - 1])

    # -------------------------------------------------------------- step ----
    def _retire_finished(self, rep, unserved: frozenset = frozenset()) -> None:
        stopped = np.asarray(self.state.stopped)
        m_rows = np.asarray(self.state.stats.m)
        for s in range(self.max_slots):
            wq = self.slot_wq[s]
            if wq is None or not stopped[s]:
                continue
            # a slot that never received a single tuple (no scan round, no
            # synopsis seed — e.g. deadline-enforced before its first round
            # after the scan became a census) has no answer: flag it
            # unserved rather than reporting a fabricated zero
            bad = s in unserved or int(m_rows[s].sum()) == 0
            with self.tracer.span("ola.retire_query", qid=wq.qid, slot=s,
                                  outcome=self._outcome(wq)):
                self._retire_slot(rep, s, wq, bad, int(m_rows[s].sum()))

    def _retire_slot(self, rep, s: int, wq: WorkloadQuery, bad: bool,
                     seen: int) -> None:
        lo_f, hi_f = float(rep.lo[s]), float(rep.hi[s])
        slo_met = None
        if wq.slo is not None:
            slo_met = wq.slo.met(self.t_model - wq.arrival_t,
                                 float("nan") if bad
                                 else (hi_f - lo_f) / 2.0)
        if wq.explain is not None and not wq.explain.tier_reason:
            wq.explain.tier_reason = (
                "scan exhausted before the slot saw any tuple" if bad
                else "scan-served: retired at its stop condition")
        self._finish(wq, WorkloadResult(
            qid=wq.qid, name=wq.query.name,
            estimate=float("nan") if bad else float(rep.estimate[s]),
            lo=lo_f,
            hi=hi_f, err=float(rep.err[s]),
            decision=int(rep.decided[s]), plan=self.slot_plan[s],
            t_submit=wq.arrival_t, t_admit=self.slot_admit_t[s],
            t_done=self.t_model, seeded_tuples=int(self.slot_seeded[s]),
            tuples_seen=seen,
            rounds_resident=int(self.rounds - self.slot_admit_round[s]),
            unserved=bad,
            sched_outcome=self._outcome(wq),
            queue_wait_model_s=float(self.slot_admit_t[s] - wq.arrival_t),
            slo_met=slo_met,
            priority=(wq.slo or NO_SLO).priority,
            degraded=self._quarantine_count > 0,
            chunks_quarantined=self._quarantine_count,
            read_retries=max(self._pipeline_retries()
                             - int(self._slot_retries0[s]), 0),
            groups=None if bad else self._group_results(rep, s, wq)))
        service = self.t_model - self.slot_admit_t[s]
        self._service_times.append(service)
        if self.scheduler is not None:
            # feed the per-class service-time sketch (quantile admission)
            self.scheduler.observe_service(wq.slo, service)
        self._rollup_on_retire(wq, s, not bad)
        if not bad:
            self._rollup_group_cells(wq, s)
        self._release(s)

    def _any_active(self) -> bool:
        return any(wq is not None for wq in self.slot_wq)

    def _apply_scheduling(self) -> None:
        """Pre-round scheduler hooks: write this round's fairness weights
        into the slot table and (claim_policy="variance") permute the
        schedule's unclaimed tail.  Both are host-side writes the jitted
        round takes as data — and both run *before* ``round_data``, so the
        streaming claim prediction/prefetch follow the same order."""
        sched = self.scheduler
        active = np.asarray([wq is not None for wq in self.slot_wq])
        w = sched.round_weights(
            [wq.slo if wq is not None else None for wq in self.slot_wq],
            active)
        if not np.array_equal(w, self._cur_weights):
            self.table = self.table._replace(
                weight=jnp.asarray(w, jnp.float32))
            self._cur_weights = w
        order = sched.claim_order(self.state, self.store.chunk_sizes,
                                  active=active,
                                  slot_need=self._slot_need())
        if order is not None:
            self.state = self.state._replace(
                schedule=jnp.asarray(order, jnp.int32))

    def _slot_need(self) -> Optional[np.ndarray]:
        """Per-slot ε-distance weights for the claim key: how far each
        resident slot's last-round error ratio still is from its ε target
        (``max(err/ε − 1, 0)``); slots with no estimate yet weigh 1.0.
        ``None`` before the first round (claims fall back to the unweighted
        max key — there is nothing measured to weight anyway)."""
        if self._last_err is None:
            return None
        eps = np.asarray(self.table.eps, np.float64)
        err = self._last_err
        return np.where(np.isfinite(err),
                        np.maximum(err / np.maximum(eps, 1e-12) - 1.0, 0.0),
                        1.0)

    def _enforce_deadlines(self) -> None:
        """Stop slots whose SLO deadline has passed: the query retires this
        round with the best estimate available — the OLA contract is that
        time bounds trade against accuracy, not against an answer."""
        now = self.t_model
        stopped = np.asarray(self.state.stopped)
        late = [s for s in range(self.max_slots)
                if self.slot_wq[s] is not None and not stopped[s]
                and self.slot_wq[s].slo is not None
                and self.slot_wq[s].slo.has_deadline
                and now >= self.slot_wq[s].arrival_t
                + self.slot_wq[s].slo.deadline_s]
        if late:
            self.state = self.state._replace(
                stopped=self.state.stopped.at[jnp.asarray(late)].set(True))

    def _record_trajectory(self, rep, b) -> None:
        """Append this round's ``(m, est, ci_halfwidth, b_eff, weight)``
        point to every resident query's explain record — host-side reads of
        round-report fields the retire path materializes anyway."""
        live = [(s, self.slot_wq[s]) for s in range(self.max_slots)
                if self.slot_wq[s] is not None
                and self.slot_wq[s].explain is not None]
        if not live:
            return
        est_a = np.asarray(rep.estimate, float)
        lo = np.asarray(rep.lo, float)
        hi = np.asarray(rep.hi, float)
        m_rows = np.asarray(self.state.stats.m).sum(axis=1)
        g_est = g_lo = g_hi = None
        for s, wq in live:
            w = float(self._cur_weights[s])
            groups = None
            if wq.query.group_by is not None:
                if g_est is None:
                    g_est = np.asarray(rep.g_est, float)
                    g_lo = np.asarray(rep.g_lo, float)
                    g_hi = np.asarray(rep.g_hi, float)
                tracked = self._slot_groups[s] or []
                idx = list(range(len(tracked))) + [self.max_groups]
                vals = [float(v) for v in tracked] + [float("nan")]
                groups = tuple(
                    (v, float(g_est[s, i]),
                     float((g_hi[s, i] - g_lo[s, i]) / 2.0))
                    for v, i in zip(vals, idx))
            wq.explain.record_round(RoundSample(
                round=self.rounds, m=int(m_rows[s]),
                est=float(est_a[s]),
                ci_halfwidth=float((hi[s] - lo[s]) / 2.0),
                b_eff=int(round(float(b) * w)), weight=w,
                groups=groups))

    def step(self) -> bool:
        """Admit ready arrivals, run one engine round, retire finished
        queries.  Returns False when there is nothing to do right now."""
        tr = self.tracer
        with tr.span("ola.admit"):
            self._admit_ready()
        if not self._any_active():
            return False
        with tr.round(self.rounds):
            if self.scheduler is not None:
                with tr.span("ola.schedule"):
                    self._apply_scheduling()
            # round_data: the packed device view, or (stream residency) a
            # slab assembled from the predicted claims — which also covers
            # top-up passes, since _begin_topup_pass rewrites cur/head
            # *before* the prediction runs, so re-opened chunks are
            # re-requested from the prefetcher exactly when a worker is
            # about to claim them
            with tr.span("ola.claims"):
                b = self.engine.budget_ladder(float(self.state.budget))
                self.state, data = self.engine.round_data(self.state)
                # a failed read may have quarantined chunks inside
                # round_data: fold the survivors into every
                # population-priced structure before the round estimates
                # over them
                self._note_quarantine()
                mode, data = self.engine.data_mode(data)
            with tr.span("ola.dispatch", b=b, mode=mode):
                self.state, rep = self.engine.round_fn(b, mode)(
                    self.state, self.table, data, self.engine.speeds)
            self.rounds += 1
            # the report's first host read waits for the device anyway;
            # waiting here names that time
            with tr.span("ola.device_wait"):
                jax.block_until_ready(rep)
            with tr.span("ola.merge"):
                if self.rollup is not None and self.rollup.cells:
                    # incremental maintenance: resident slots running a
                    # promoted pattern fold their round-accumulated stats
                    # into the cell — one batched device→host copy for all
                    # such slots (near-free; empty in the
                    # no-promoted-occupant common case)
                    ids = [s for s in range(self.max_slots)
                           if self.slot_wq[s] is not None
                           and self.rollup.get(self.slot_wq[s].key)
                           is not None]
                    for s, row in slot_stats_fold(self.state, ids).items():
                        self.rollup.fold(self.slot_wq[s].key, row)
            with tr.span("ola.retire"):
                self._retire_round(rep, b)
        return True

    def _retire_round(self, rep, b) -> None:
        """Read the round's report: explain trajectories, retirements, group
        discovery, and a top-up pass or the census at exhaustion."""
        tr = self.tracer
        with tr.span("ola.report"):
            self._record_trajectory(rep, b)
            if self.scheduler is not None:
                # next round's ε-distance claim weights read this report
                self._last_err = np.asarray(rep.err, float)
        with tr.span("ola.retire_slots"):
            if (self.scheduler is not None
                    and self.scheduler.config.deadline_enforcement):
                self._enforce_deadlines()
            self._retire_finished(rep)
        with tr.span("ola.groups"):
            self._fold_group_discovery(rep)
        with tr.span("ola.topup"):
            if self._any_active() and bool(rep.exhausted):
                if not self._begin_topup_pass():
                    # census complete: estimates are as good as they will
                    # get
                    self._force_retire_exhausted(rep)

    def _force_retire_exhausted(self, rep) -> None:
        """Every chunk is fully extracted; retire survivors with their final
        (near-exact for slots that saw the whole scan) estimates.  A slot
        that never received a single tuple (admitted post-exhaustion with no
        synopsis seed) cannot be answered — its result is flagged
        ``unserved`` with a NaN estimate rather than a plausible-looking 0."""
        m = np.asarray(self.state.stats.m)
        unserved = frozenset(
            s for s in range(self.max_slots)
            if self.slot_wq[s] is not None and int(m[s].sum()) == 0)
        self.state = self.state._replace(
            stopped=jnp.ones_like(self.state.stopped))
        self._retire_finished(rep, unserved=unserved)

    # --------------------------------------------------------------- run ----
    def run(self, max_rounds: int = 200_000, wall_timeout_s: float = 600.0,
            on_round=None) -> list[WorkloadResult]:
        """Drive until the queue drains and every resident query retires.

        If ``max_rounds`` or ``wall_timeout_s`` cuts the loop short,
        ``self.truncated`` is set and the returned list is missing the
        unfinished queries — callers indexing results by name/qid should
        check it rather than assume completeness.  ``on_round(server)`` is
        called after every engine round (monitoring hooks: the benchmarks
        sample peak device residency through it).
        """
        self.truncated = False
        t0 = time.perf_counter()
        while self.queue or self._any_active():
            if self.rounds >= max_rounds:
                self.truncated = True
                break
            if time.perf_counter() - t0 > wall_timeout_s:
                self.truncated = True
                break
            stepped = self.step()
            if stepped and on_round is not None:
                on_round(self)
            if not stepped:
                if not self.queue:
                    break
                # idle: jump the modeled clock to the next arrival
                nxt = self.queue[0].arrival_t
                if nxt > self.t_model:
                    self.idle_offset += nxt - self.t_model
        self.results.sort(key=lambda r: r.qid)
        return self.results


def poisson_workload(queries: Sequence[Query], rate_per_model_s: float,
                     seed: int = 0,
                     rng: Optional[np.random.Generator] = None,
                     ) -> list[tuple[Query, float]]:
    """Poisson arrival process over a fixed query list (benchmark helper):
    returns ``(query, arrival_t)`` pairs with exponential inter-arrivals at
    ``rate_per_model_s`` arrivals per modeled second.

    Deterministic run-to-run: the same ``seed`` always yields the same
    arrival times (scheduler benchmarks compare policies on identical
    traffic).  Pass an explicit ``rng`` instead to draw from a
    caller-owned :class:`numpy.random.Generator` stream (e.g. one shared
    across several workload sections); ``seed`` is then ignored.
    """
    if rng is None:
        rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for q in queries:
        t += float(rng.exponential(1.0 / rate_per_model_s))
        out.append((q, t))
    return out
