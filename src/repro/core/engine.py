"""The OLA-RAW engine: parallel bi-level sampling over raw chunks.

This is the paper's Sections 3–5 as one lockstep-SPMD state machine.  The
hardware adaptation (DESIGN.md §3) replaces EXTRACT threads with *workers*
(vmap lanes on one device, or mesh-`data`-axis shards under shard_map — same
round semantics, property-tested equal) and the ``t_eval`` timer with a
per-round tuple *budget*:

  round r:
    1. CLAIM   — idle workers take the next positions of the committed random
                 chunk schedule from a global queue head.  The head advances
                 by an exclusive prefix-sum over (all-gathered) idle flags, so
                 the *started set is always a prefix of the schedule*: a
                 chunk's inclusion in the sample can never depend on its
                 content.  This is the engine's inspection-paradox guarantee
                 (paper §3/§4.2).  Under residency="sharded" each chunk
                 range has a queue of its own: the started set is a prefix
                 of each range's committed order, and the ranges are the
                 strata of a stratified sample.
    2. EXTRACT — each active worker extracts the next ``b`` tuples of its
                 chunk in the chunk's keyed Feistel order (paper §4.1's
                 in-memory shuffle), decodes them from raw bytes, evaluates
                 all queries (x_i = expr·pred per Table 1).
    3. MERGE   — per-chunk sufficient statistics (m_j, y'_j, y''_j, p_j) are
                 scatter-added; across devices the deltas are psum'd.
    4. DECIDE  — per-chunk local accuracy ε_j = ε (Theorem 3) closes chunks
                 under the single-pass rule; the resource monitor (modeled
                 T_io vs T_cpu, Eq. 4's two cost terms) switches the
                 resource-aware policy between holistic-like (IO-bound) and
                 single-pass-like (CPU-bound) behaviour and drives the
                 exponential-decay budget rule of §5.4.
    5. ESTIMATE— Eq. (1)/(3) over all started chunks (per stratum, summed,
                 under claim queues); HAVING early-out.

Strategies (paper Fig. 5): ``chunk_level`` (C), ``holistic`` (H),
``single_pass`` (S), ``resource_aware`` (BI).  ``chunk_level`` additionally
restricts estimation to fully-extracted chunks in schedule order (the
reordering barrier of §3); a deliberately broken ``chunk_level_unordered``
mode reproduces the inspection paradox for the Table 3 experiment.

Worker state (``cur``) is the only sharded piece; chunk-slot arrays are
replicated and advanced by identical (psum-merged) updates on every device,
so the SPMD engine is deterministic and checkpointable as a plain pytree.

Two query planes share this round machinery:

* **frozen** (classic): the query list is compiled into the round program
  (``compile_queries``); stats carry a leading (Q,) dim and ``stats.m`` is
  the shared ``(N,)`` per-chunk sample size.
* **slot table** (workload serving): ``round_body`` takes a dynamic
  :class:`~repro.core.queries.SlotTable` argument describing up to S
  concurrent linear+range queries.  Queries can be admitted or retired
  between rounds by host-side row writes — no recompilation.  Because a
  query admitted mid-scan has not seen earlier tuples, ``stats.m`` becomes
  per-slot ``(S, N)`` while the *scan-level* extraction count lives in
  ``state.scan_m (N,)`` (cursor bounds, READ accounting, calibration).
  :class:`SlotOLAEngine` is the host-facing wrapper; the workload server
  (``repro.serve.ola_server``) drives admission, early leave, and top-up
  passes on top of it.
"""

from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import estimators as est
from repro.core.estimators import BiLevelStats
from repro.data.faults import FaultError
from repro.obs.trace import NULL_TRACER
from repro.core.queries import (
    AGG_COUNT,
    AGG_SUM,
    HAVING_NONE,
    PLAN_CHUNK_LEVEL,
    PLAN_RESOURCE_AWARE,
    PLAN_SINGLE_PASS,
    Query,
    SlotTable,
    compile_queries,
    linear_plan,
    slot_evaluate,
)
from repro.kernels import ops as kernel_ops
from repro.kernels.ref import TALLY_BUCKETS, tally_hash
from repro.kernels.slot_extract import GATHER_ROWS
from repro.sampling.permutation import (
    chunk_seed,
    permutation_window_dyn,
    random_chunk_order,
)

# Chunk-claim sentinels for the per-worker `cur` slot (schedule positions).
IDLE = -1       # worker finished its chunk; will claim at next round start
EXHAUSTED = -2  # schedule empty; worker permanently idle

STRATEGIES = ("chunk_level", "holistic", "single_pass", "resource_aware",
              "chunk_level_unordered")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_workers: int = 4
    strategy: str = "resource_aware"
    budget_init: int = 64        # t_eval analog: tuples per worker per round
    budget_min: int = 8          # paper's t_eval lower bound
    budget_max: int = 4096       # upper bound (δ analog; also capped by chunk size)
    seed: int = 0
    # resource model (DESIGN.md §3): chunk fetch vs extract cost.  Defaults
    # approximate the paper's testbed ratio (565 MB/s buffered reads vs
    # CPU-bound ASCII extraction).
    io_bytes_per_sec: float = 565e6
    cpu_tuple_ops_per_sec: float = 2.0e9  # VPU-op throughput for the cost model
    # worker speed factors for straggler simulation (len == num_workers)
    worker_speed: Optional[tuple] = None
    stats_dtype: str = "float32"
    cache_cap: int = 0           # per-chunk extracted-tuple cache rows (synopsis)
    # round EXTRACT implementation: "ref" keeps the decode_ref + evaluator
    # composition (supports arbitrary Custom queries); "pallas" routes the
    # gather+parse+eval+reduce through the compiled kernels/slot_extract.py
    # kernel (linear+range plans only; raises off-TPU); "pallas-interpret"
    # runs that kernel under the Pallas interpreter on any platform (the CPU
    # parity tests, the benchmark's correctness-mode lane); "auto" picks
    # pallas on TPU when the plan supports it and ref elsewhere.
    extract_backend: str = "ref"
    # raw-data residency: "packed" keeps the whole store on device as one
    # (N, M_max, rec) tensor (fine for small stores); "stream" feeds each
    # round a bounded (W, rows_max, rec) slab through
    # data/pipeline.SlabPrefetcher — device residency O(slab), host residency
    # O(cache), READ overlapped with compute.  Round-for-round estimates are
    # identical (bit-exact on the ref backend).  "sharded" is packed
    # residency split over Q claim queues (the mesh's data axis size, or the
    # single-device engines' ``queues``): queue q owns the contiguous chunk
    # range [q·N/Q, (q+1)·N/Q), its workers claim only from it in its own
    # committed random order, and on a mesh each device holds only its
    # queue's range.  Estimates are stratified over the ranges.
    residency: str = "packed"
    slab_row_tile: int = 256     # streaming kernel's row-tile (VMEM bound)
    prefetch_lookahead: int = 8  # schedule chunks the reader thread runs ahead
    # adapt the lookahead at runtime from the measured READ/CPU rate ratio
    # (a slow store raises it toward the prefetcher's ceiling so reads stay
    # hidden under compute; purely a perf knob — estimates are unaffected)
    prefetch_adaptive: bool = False
    # parse-once decoded-chunk cache byte budget (streaming residency only):
    # the prefetcher retains each chunk's decoded (rows, C) f32 block on
    # first extraction, and later rounds feed the decoded-input kernel —
    # skipping tokenize/parse.  Estimates and the modeled resource clock are
    # bit-identical with the cache on or off; only wall time changes.
    decoded_cache_bytes: int = 0
    # grouped query plane (slot-table mode only): a slot may own up to
    # max_groups tracked group cells plus one __other__ spill cell, each with
    # its own (S, G, N) sufficient-stat rows.  0 keeps the group arrays
    # zero-width — the grouped code then compiles away and ungrouped engines
    # are statically unchanged (round-for-round bit-exact vs older builds).
    max_groups: int = 0

    def __post_init__(self):
        assert self.strategy in STRATEGIES, self.strategy
        assert self.extract_backend in ("ref", "pallas", "pallas-interpret",
                                        "auto"), self.extract_backend
        assert self.residency in ("packed", "stream", "sharded"), self.residency
        assert self.decoded_cache_bytes >= 0
        assert self.decoded_cache_bytes == 0 or self.residency == "stream", (
            "decoded_cache_bytes requires residency='stream' (the cache "
            "lives in the slab prefetcher)")
        assert self.max_groups >= 0


class EngineState(NamedTuple):
    stats: BiLevelStats          # ysum/ysq/psum: (Q, N) — replicated.
                                 # stats.m is (N,) in frozen-query mode and
                                 # per-slot (S, N) in slot-table mode.
    scan_m: jnp.ndarray          # (N,) tuples the *scan* extracted per chunk
                                 # (== stats.m in frozen mode)
    offset: jnp.ndarray          # (N,) tuples extracted so far per chunk
    closed: jnp.ndarray          # (N,) bool — chunk closed for sampling
    acc_met: jnp.ndarray         # (N,) bool — local accuracy ε_j reached
    head: jnp.ndarray            # () int32 — schedule positions handed out
                                 # (queue head; >= N: nothing left)
    cur: jnp.ndarray             # (P,) int32 — schedule position per worker (sharded under SPMD)
    budget: jnp.ndarray          # () f32 — current t_eval-analog budget
    decay: jnp.ndarray           # () f32 — §5.4 exponential-decay factor
    calib_sum: jnp.ndarray       # () f32 — Σ tuples-at-accuracy (calibration)
    calib_cnt: jnp.ndarray       # () f32
    first_est: jnp.ndarray       # () bool — first chunk estimate produced
    stopped: jnp.ndarray         # (Q,) bool — per-query global stop
    round: jnp.ndarray           # () int32
    t_io: jnp.ndarray            # () f32 — cumulative modeled read seconds
    t_cpu: jnp.ndarray           # () f32 — cumulative modeled extract seconds
    cpu_bound: jnp.ndarray       # () bool — monitor verdict from last round
    cached_m: jnp.ndarray        # (N,) int32 — tuples supplied by the synopsis
    raw_touched: jnp.ndarray     # (N,) bool — chunk has caused a raw READ
    cache: jnp.ndarray           # (N, cap, C) f32 — extracted-tuple cache for
                                 # synopsis construction (cap may be 0)
    schedule: jnp.ndarray        # (N,) int32 — claim order over chunk ids.
                                 # Initialized from the program's committed
                                 # random order; the workload scheduler may
                                 # permute the *unclaimed tail* (positions
                                 # >= head) between rounds — variance-guided
                                 # claiming.  Chunks never yet started stay
                                 # in their original relative order, so the
                                 # first-touch set remains a prefix of the
                                 # committed random order (the inspection-
                                 # paradox guarantee is ordering-invariant).
    quarantined: jnp.ndarray     # (N,) bool — chunk dropped from the
                                 # population (read retries exhausted / CRC
                                 # mismatch).  A host-side write (like the
                                 # scheduler's claim reorder): the round
                                 # treats it as closed with a zero budget,
                                 # and estimation rescales to the surviving
                                 # chunk count and tuple total (CIs widen;
                                 # answers are flagged degraded upstream).
    # grouped query plane (G = max_groups+1 incl. the __other__ spill cell;
    # all four are (S, 0, N) when EngineConfig.max_groups == 0).  A cell's
    # gm counts every tuple the slot sampled while the cell was live —
    # *not* group-filtered — exactly the per-chunk sample size a dedicated
    # fan-out slot would carry, so cells live since admission are bit-exact
    # against the expand_group_by oracle.
    gm: jnp.ndarray              # (S, G, N) int32 per-cell sample sizes
    gys: jnp.ndarray             # (S, G, N) per-cell Σ x (group-masked)
    gyq: jnp.ndarray             # (S, G, N) per-cell Σ x²
    gps: jnp.ndarray             # (S, G, N) per-cell Σ p (base pred ∧ group)
    # per-queue heads under residency="sharded" ((0,) otherwise): positions
    # of queue q's schedule segment handed out; ``head`` is their sum
    qhead: jnp.ndarray           # (Q,) int32


class RoundReport(NamedTuple):
    estimate: jnp.ndarray        # (Q,)
    lo: jnp.ndarray              # (Q,)
    hi: jnp.ndarray              # (Q,)
    err: jnp.ndarray             # (Q,) error ratio (paper's metric)
    decided: jnp.ndarray         # (Q,) int8 HAVING verdict (-1/0/1)
    n_chunks: jnp.ndarray        # () chunks in sample
    m_tuples: jnp.ndarray        # () tuples in sample
    round_io_s: jnp.ndarray      # () modeled read seconds this round
    round_cpu_s: jnp.ndarray     # () modeled extract seconds this round
    tuples_round: jnp.ndarray    # ()
    bytes_round: jnp.ndarray     # ()
    all_stopped: jnp.ndarray     # () bool
    exhausted: jnp.ndarray       # () bool — every chunk closed
    # grouped plane (zero-width when the engine has max_groups == 0)
    g_est: jnp.ndarray           # (S, G) per-cell estimates
    g_lo: jnp.ndarray            # (S, G)
    g_hi: jnp.ndarray            # (S, G)
    g_err: jnp.ndarray           # (S, G) per-cell error ratio
    g_n: jnp.ndarray             # (S, G) int32 tuples in each cell's sample
    g_tal: jnp.ndarray           # (S, 3, H) per-round group-value tallies
                                 # [count, Σ value, Σ value²] per salted-hash
                                 # bucket of the slot's group column (base-
                                 # predicate-masked rows only) — the host
                                 # folds these into the SpaceSaving sketch
                                 # that discovers heavy-hitter groups online


class _Collectives:
    """Adapter between single-device and shard_map execution.

    ``gather_workers`` exposes every worker's flag in global worker order;
    ``merge`` sums contributions across devices; ``my_base`` is this device's
    first global worker id; ``local_chunk`` maps a global chunk id to a row
    of this device's raw-data block (the identity unless the block is this
    device's ``chunks_per_device`` range of a sharded table).  The
    single-device instance is the identity, so both modes run the *same*
    round body.
    """

    def __init__(self, axis_name: Optional[str] = None,
                 workers_per_device: Optional[int] = None,
                 chunks_per_device: Optional[int] = None):
        self.axis_name = axis_name
        self.wpd = workers_per_device
        self.cpd = chunks_per_device

    def gather_workers(self, x: jnp.ndarray) -> jnp.ndarray:
        if self.axis_name is None:
            return x
        g = jax.lax.all_gather(x, self.axis_name, axis=0)  # (D, W)
        return g.reshape((-1,) + x.shape[1:])

    def merge(self, tree):
        if self.axis_name is None:
            return tree
        return jax.lax.psum(tree, self.axis_name)

    def my_base(self) -> jnp.ndarray:
        if self.axis_name is None:
            return jnp.asarray(0, jnp.int32)
        return (jax.lax.axis_index(self.axis_name) * self.wpd).astype(jnp.int32)

    def local_chunk(self, j: jnp.ndarray) -> jnp.ndarray:
        if self.axis_name is None or self.cpd is None:
            return j
        base = (jax.lax.axis_index(self.axis_name) * self.cpd).astype(j.dtype)
        # an idle worker's chunk id may lie in another device's range; it
        # extracts nothing, but its gather must stay inside this block
        return jnp.clip(j - base, 0, self.cpd - 1)


class EngineProgram:
    """The jit-able round program, independent of host-side orchestration.

    Everything static lives here (schedule, seeds, query evaluator, cost
    model); per-round dynamic state is the :class:`EngineState` pytree.
    """

    def __init__(self, *, codec, queries: Sequence[Query] = (),
                 config: EngineConfig, n_chunks: int, m_max: int,
                 chunk_sizes: np.ndarray,
                 schedule: Optional[np.ndarray] = None,
                 max_slots: Optional[int] = None, confidence: float = 0.95,
                 queues: int = 1):
        self.codec = codec
        self.queries = list(queries)
        self.config = config
        self.n_chunks = int(n_chunks)
        self.m_max = int(m_max)
        self.max_slots = None if max_slots is None else int(max_slots)
        # claim queues (residency="sharded"): queue q owns the contiguous
        # chunk range of length N/Q at q·N/Q, and the schedule's segment
        # [q·N/Q, (q+1)·N/Q) is its committed random order
        self.sharded = config.residency == "sharded"
        self.queues = int(queues)
        assert self.queues >= 1 and (self.sharded or self.queues == 1), (
            "claim queues > 1 need residency='sharded'")
        if self.sharded:
            assert self.n_chunks % self.queues == 0, (
                f"{self.n_chunks} chunks do not split over {self.queues} "
                "queues")
            assert config.num_workers % self.queues == 0, (
                f"{config.num_workers} workers do not split over "
                f"{self.queues} queues")
        if schedule is None:
            schedule = random_chunk_order(config.seed, self.n_chunks)
        if self.sharded:
            schedule = queue_schedule(schedule, self.queues)
        self.schedule_np = np.asarray(schedule, np.int32)
        self.schedule = jnp.asarray(schedule, jnp.int32)
        self.seeds = chunk_seed(jnp.uint32(config.seed),
                                jnp.arange(self.n_chunks, dtype=jnp.uint32))
        self.chunk_sizes_np = np.asarray(chunk_sizes, np.int32)
        self.chunk_bytes = jnp.asarray(
            np.asarray(chunk_sizes, np.float32) * codec.record_bytes)
        if self.max_slots is None:
            assert self.queries, "frozen mode needs a non-empty query list"
            self.evaluate = compile_queries(self.queries)
            self.eps = jnp.asarray([q.epsilon for q in self.queries],
                                   jnp.float32)
            self.conf = float(self.queries[0].confidence)
        else:
            # slot-table mode: the query plane is a dynamic round argument;
            # confidence is per-slot (the table carries each slot's z), and
            # ``confidence`` here is only the default for reporting helpers.
            assert not self.queries, "slot mode takes queries via the table"
            self.evaluate = None
            self.eps = jnp.zeros((self.max_slots,), jnp.float32)
            self.conf = float(confidence)
        self.z = float(jax.scipy.special.ndtri((1.0 + self.conf) / 2.0))
        self.cost_per_tuple = float(codec.extract_cost_per_tuple())
        self.total_tuples = int(np.sum(chunk_sizes))
        self.num_cols = int(codec.num_cols)
        # grouped-plane sizing (static): G cells per slot incl. __other__,
        # H tally buckets for the online group-discovery sketch feed
        self.group_cells = (config.max_groups + 1) if config.max_groups > 0 else 0
        self.tally_buckets = TALLY_BUCKETS if self.group_cells else 0
        if self.group_cells and self.max_slots is None:
            raise ValueError(
                "max_groups > 0 requires slot-table mode (grouped queries "
                "run through the workload slot plane)")
        # EXTRACT backend resolution (static — baked into the jitted round).
        # The fused kernel parses fixed-width ASCII, needs linear+range
        # plans, and accumulates in float32: an explicit
        # "pallas"/"pallas-interpret" outside that raises here (not
        # mid-scan), while "auto" keeps the ref path — binary decode is
        # near-free anyway (those stores are IO-bound, not EXTRACT-bound),
        # Custom frozen queries have no coefficient form, and a non-f32
        # stats dtype must not be silently degraded to f32 sums.  "pallas"
        # is the compiled kernel and raises off-TPU; only "pallas-interpret"
        # runs the interpreter.  What "auto" chose is kept in
        # ``extract_backend``.
        kernel_ok = (getattr(codec, "name", "") == "ascii"
                     and jnp.dtype(config.stats_dtype) == jnp.float32)
        backend = config.extract_backend
        lp = None
        if backend == "auto":
            backend = ("pallas" if jax.default_backend() == "tpu" and kernel_ok
                       else "ref")
            if backend == "pallas" and self.max_slots is None:
                try:
                    lp = linear_plan(self.queries, self.num_cols)
                except ValueError:
                    backend = "ref"
        elif backend != "ref" and not kernel_ok:
            raise ValueError(
                f"extract_backend={backend!r} requires the fixed-width ASCII "
                "codec and float32 stats (the fused kernel parses ASCII "
                "records and accumulates its sums in f32)")
        elif backend == "pallas":
            kernel_ops.require_tpu(backend)
        self.extract_backend = backend
        self.extract_pallas = backend != "ref"
        if (self.group_cells and self.extract_pallas
                and config.residency == "stream"):
            raise ValueError(
                "grouped queries (max_groups > 0) support the fused Pallas "
                "kernel only under residency='packed'; use extract_backend="
                "'ref' for streaming/decoded rounds")
        if self.extract_pallas:
            if self.max_slots is None:
                # frozen plane: lower the query list to coefficient form once;
                # raises for queries outside linear+range (use 'ref' there)
                lp = lp or linear_plan(self.queries, self.num_cols)
                self._plan_coeffs = jnp.asarray(lp.coeffs)
                self._plan_lo = jnp.asarray(lp.lo)
                self._plan_hi = jnp.asarray(lp.hi)
                self._plan_is_count = jnp.asarray(
                    [1.0 if qq.agg == "count" else 0.0 for qq in self.queries],
                    jnp.float32)

    @property
    def q_dim(self) -> int:
        """Leading stats dimension: query count or slot count."""
        return self.max_slots if self.max_slots is not None else len(self.queries)

    # ------------------------------------------------------------ state ----
    def init_state(self, synopsis_seed: Optional[dict] = None) -> EngineState:
        cfg = self.config
        q = self.q_dim
        dtype = jnp.dtype(cfg.stats_dtype)
        sizes = jnp.asarray(self.chunk_sizes_np)
        stats = est.init_stats(sizes, query_shape=(q,), dtype=dtype,
                               m_total=self.total_tuples)
        if self.max_slots is not None:
            # per-slot sample sizes: each slot joined the scan at its own time
            assert synopsis_seed is None, (
                "slot mode seeds per-slot via the workload server")
            stats = stats._replace(
                m=jnp.zeros((q, self.n_chunks), jnp.int32))
        state = EngineState(
            stats=stats,
            scan_m=jnp.zeros((self.n_chunks,), jnp.int32),
            offset=jnp.zeros((self.n_chunks,), jnp.int32),
            closed=jnp.zeros((self.n_chunks,), bool),
            acc_met=jnp.zeros((self.n_chunks,), bool),
            head=jnp.asarray(0, jnp.int32),
            cur=jnp.full((cfg.num_workers,), IDLE, jnp.int32),
            budget=jnp.asarray(float(cfg.budget_init), jnp.float32),
            decay=jnp.asarray(1.0, jnp.float32),
            calib_sum=jnp.asarray(0.0, jnp.float32),
            calib_cnt=jnp.asarray(0.0, jnp.float32),
            first_est=jnp.asarray(False),
            stopped=jnp.zeros((q,), bool),
            round=jnp.asarray(0, jnp.int32),
            t_io=jnp.asarray(0.0, jnp.float32),
            t_cpu=jnp.asarray(0.0, jnp.float32),
            cpu_bound=jnp.asarray(False),
            cached_m=jnp.zeros((self.n_chunks,), jnp.int32),
            raw_touched=jnp.zeros((self.n_chunks,), bool),
            cache=jnp.zeros((self.n_chunks, cfg.cache_cap, self.num_cols),
                            jnp.float32),
            schedule=jnp.asarray(self.schedule_np),
            quarantined=jnp.zeros((self.n_chunks,), bool),
            gm=jnp.zeros((q, self.group_cells, self.n_chunks), jnp.int32),
            gys=jnp.zeros((q, self.group_cells, self.n_chunks), dtype),
            gyq=jnp.zeros((q, self.group_cells, self.n_chunks), dtype),
            gps=jnp.zeros((q, self.group_cells, self.n_chunks), dtype),
            qhead=jnp.zeros((self.queues if self.sharded else 0,), jnp.int32),
        )
        if synopsis_seed is not None:
            stats = state.stats._replace(
                m=jnp.asarray(synopsis_seed["m"], jnp.int32),
                ysum=jnp.asarray(synopsis_seed["ysum"], dtype),
                ysq=jnp.asarray(synopsis_seed["ysq"], dtype),
                psum=jnp.asarray(synopsis_seed["psum"], dtype),
            )
            state = state._replace(
                stats=stats,
                scan_m=jnp.asarray(synopsis_seed["m"], jnp.int32),
                offset=jnp.asarray(synopsis_seed["offset"], jnp.int32),
                closed=jnp.asarray(synopsis_seed.get(
                    "closed", np.zeros(self.n_chunks, bool))),
                cached_m=jnp.asarray(synopsis_seed["m"], jnp.int32),
            )
            if "cache" in synopsis_seed and cfg.cache_cap > 0:
                pre = jnp.asarray(synopsis_seed["cache"], jnp.float32)
                state = state._replace(
                    cache=state.cache.at[:, : pre.shape[1]].set(pre))
        return state

    def plan_claims(self, state: EngineState
                    ) -> tuple[np.ndarray, np.ndarray, int]:
        """Host-side replica of the round's CLAIM step (streaming residency).

        The claim rule is a pure function of ``(cur, head, state.schedule)``
        — no chunk content — so the slab pipeline can predict *exactly* which
        chunk each worker will hold this round and assemble the slab before
        the jitted step runs.  The schedule is read from *state* (not the
        program) so a scheduler-permuted claim order (see
        :class:`repro.sched.WorkloadScheduler`) is followed identically by
        the prediction and the in-jit CLAIM.  Returns ``(chunk_ids (P,),
        active (P,), new_head)`` in global worker order (``state.cur`` is
        host-gathered, so this works unchanged for the SPMD engines).
        """
        cur = np.asarray(state.cur).astype(np.int64)
        head = int(state.head)
        n = self.n_chunks
        schedule = np.asarray(state.schedule)
        idle = cur == IDLE
        ranks = np.cumsum(idle) - idle
        want = head + ranks
        got = idle & (want < n)
        cur_next = np.where(got, want, np.where(idle, EXHAUSTED, cur))
        j = schedule[np.clip(cur_next, 0, n - 1)]
        active = cur_next >= 0
        new_head = head + int(np.sum(idle & (want < n)))
        return j, active, new_head

    def open_heads(self, closed: np.ndarray, schedule: np.ndarray
                   ) -> tuple[int, np.ndarray]:
        """Host side: ``(head, qhead)`` rewound to each queue's first
        not-closed schedule position (a top-up pass re-claims from there).
        ``qhead`` is ``(Q,)`` under residency="sharded" and ``(0,)``
        otherwise."""
        done = np.asarray(closed)[np.asarray(schedule)].reshape(
            self.queues, -1)
        first = np.where(done.all(axis=1), done.shape[1],
                         np.argmax(~done, axis=1))
        qhead = first if self.sharded else first[:0]
        return int(first.sum()), qhead.astype(np.int32)

    def _closed_prefix_mask(self, closed: jnp.ndarray,
                            schedule: jnp.ndarray) -> jnp.ndarray:
        """Reordering barrier (§3): chunk-level estimation may only use the
        *closed prefix* of the schedule — the chunks up to the first not-yet
        -closed schedule position; with claim queues, the union of each
        queue's closed prefix of its own segment.  Returns the (N,) chunk
        mask."""
        n = self.n_chunks
        if self.sharded:
            done = closed[schedule].reshape(self.queues, -1)
            per = done.shape[1]
            prefix_len = jnp.where(jnp.all(done, axis=1), per,
                                   jnp.argmax(~done, axis=1))
            inside = (jnp.arange(per)[None, :] < prefix_len[:, None])
            return jnp.zeros((n,), bool).at[schedule].set(inside.reshape(-1))
        done_sched = closed[schedule]
        prefix_len = jnp.where(jnp.all(done_sched), n, jnp.argmax(~done_sched))
        return jnp.zeros((n,), bool).at[schedule].set(
            jnp.arange(n) < prefix_len)

    def _claim_queues(self, state: EngineState, coll: _Collectives):
        """CLAIM with one queue per chunk range: worker w claims from queue
        w // (W/Q), by a prefix sum over the idle flags of that queue's
        workers, the next positions of the queue's schedule segment.  On a
        mesh with one queue per device a queue's workers are all local, so
        no flags are gathered.  ``head >= N`` (nothing left anywhere) stops
        every claim.  Returns ``(cur, claimed (Q,))``; the claims are
        merged across devices with the round's deltas."""
        nq = self.queues
        per = self.n_chunks // nq
        w_local = state.cur.shape[0]
        ids = coll.my_base() + jnp.arange(w_local, dtype=jnp.int32)
        queue = ids // (self.config.num_workers // nq)           # (W,)
        mine = queue[:, None] == jnp.arange(nq, dtype=jnp.int32)[None, :]
        idle = state.cur == IDLE
        idle_q = (mine & idle[:, None]).astype(jnp.int32)        # (W, Q)
        rank = jnp.sum((jnp.cumsum(idle_q, axis=0) - idle_q)
                       * mine.astype(jnp.int32), axis=1)
        want = state.qhead[queue] + rank
        got = idle & (want < per) & (state.head < self.n_chunks)
        cur = jnp.where(got, queue * per + want,
                        jnp.where(idle, EXHAUSTED, state.cur))
        claimed = jnp.sum((mine & got[:, None]).astype(jnp.int32), axis=0)
        return cur, claimed

    def _round_tallies(self, colv: jnp.ndarray, pr: jnp.ndarray,
                       live: jnp.ndarray, rnd: jnp.ndarray,
                       dtype) -> jnp.ndarray:
        """Per-slot ``(S, 3, H)`` group-value tallies ``[count, Σv, Σv²]``,
        bucketed by a per-round salted hash of the group column.  ``pr`` is
        the fully-masked predicate indicator, so only counted base-predicate
        rows tally; ``live`` (S,) gates tallies to slots still discovering
        groups (the ``__other__`` cell's active flag — ungrouped slots would
        otherwise tally their clipped column).  The salt (round number)
        re-buckets every round: hash collisions are transient, and the
        host-side SpaceSaving fold only trusts buckets whose moments prove a
        single value (Σv²·n == (Σv)²).
        """
        s, w, b = colv.shape
        hbk = self.tally_buckets
        h = tally_hash(colv, rnd.astype(jnp.uint32), hbk)        # (S, W, B)
        flat = (jnp.arange(s, dtype=jnp.int32)[:, None, None] * hbk
                + h).reshape(-1)
        prf = (pr * live[:, None, None].astype(pr.dtype)
               ).reshape(-1).astype(dtype)
        cv = colv.reshape(-1).astype(dtype)
        cnt = jnp.zeros((s * hbk,), dtype).at[flat].add(prf)
        vsum = jnp.zeros((s * hbk,), dtype).at[flat].add(prf * cv)
        vsq = jnp.zeros((s * hbk,), dtype).at[flat].add(prf * cv * cv)
        return jnp.stack([cnt.reshape(s, hbk), vsum.reshape(s, hbk),
                          vsq.reshape(s, hbk)], axis=1)

    # ------------------------------------------------------------ round ----
    def round_body(self, state: EngineState, data: jnp.ndarray,
                   speeds: jnp.ndarray, b_static: int,
                   coll: _Collectives, slots: Optional[SlotTable] = None,
                   decoded_mode: str = "none",
                   ) -> tuple[EngineState, RoundReport]:
        """One engine round.  ``state.cur``/``speeds`` are *local* worker
        slices (the full arrays in single-device mode); everything else is
        replicated.  ``data`` is the raw byte source: the whole packed store
        ``(N, M_max, rec)`` under ``residency="packed"``, or this round's
        per-worker slab ``(W_local, rows_max, rec)`` under
        ``residency="stream"`` (worker w's chunk rows at ``data[w]``,
        assembled by the host from :meth:`plan_claims` — the in-jit CLAIM
        below recomputes the same assignment, so slab row w always holds the
        chunk worker w claims).

        ``decoded_mode`` (static; streaming + decoded-chunk cache only)
        selects the round variant: ``"none"`` is the classic raw-slab round,
        otherwise ``data`` is the ``(raw_slab, decoded_slab, is_decoded)``
        triple from the prefetcher — ``"all"`` skips tokenize/parse entirely
        (every active worker's chunk is decoded-cached), ``"mixed"`` splits
        the budget between the raw-EXTRACT and decoded-input kernels per the
        mask.  Every variant produces bit-identical statistics and modeled
        resource clock (decoded workers keep their as-if-raw cost), so scan
        decisions never diverge with the cache on or off.

        With ``slots`` (slot-table mode) the query plane is data-driven:
        evaluation, ε targets, plan policies, and HAVING verdicts all come
        from the table, and per-query arrays are sized ``max_slots``."""
        cfg = self.config
        streaming = cfg.residency == "stream"
        assert decoded_mode in ("none", "mixed", "all"), decoded_mode
        if decoded_mode != "none":
            assert streaming, "decoded rounds exist only under streaming"
            data, dec, is_dec = data
        n = self.n_chunks
        slot_mode = slots is not None
        grouped = slot_mode and self.group_cells > 0
        if slot_mode:
            assert slots.gval.shape[1] == self.group_cells, (
                "slot table group capacity != engine max_groups")
        q = self.q_dim
        dtype = state.stats.ysum.dtype
        sizes = state.stats.M

        # ---- 1. CLAIM: prefix-sum queue-head allocation -------------------
        if self.sharded:
            cur, claimed = self._claim_queues(state, coll)
        else:
            idle_local = state.cur == IDLE
            idle_all = coll.gather_workers(idle_local)           # (P,) global order
            ranks_all = (jnp.cumsum(idle_all.astype(jnp.int32))
                         - idle_all.astype(jnp.int32))
            w_local = state.cur.shape[0]
            my_ids = coll.my_base() + jnp.arange(w_local, dtype=jnp.int32)
            ranks = ranks_all[my_ids]
            want_pos = state.head + ranks
            got = idle_local & (want_pos < n)
            cur = jnp.where(got, want_pos,
                            jnp.where(idle_local, EXHAUSTED, state.cur))
            head = state.head + jnp.sum(idle_all & (state.head + ranks_all < n))

        active = cur >= 0
        j = state.schedule[jnp.clip(cur, 0, n - 1)]              # (W,) chunk ids
        jd = coll.local_chunk(j)                                 # row of `data`
        mj = sizes[j]
        off = state.offset[j]                                    # permutation cursor
        m_before = state.scan_m[j]                               # scan tuples so far

        # ---- 2. EXTRACT ----------------------------------------------------
        # remaining unsampled tuples bounds the budget (cursor may wrap when a
        # synopsis window started mid-permutation — Section 6.2 circular scan)
        b_eff = jnp.minimum(jnp.floor(b_static * speeds).astype(jnp.int32),
                            jnp.maximum(mj - m_before, 0))
        b_eff = jnp.where(active, b_eff, 0)
        # a quarantined chunk yields nothing: a worker that (still) holds one
        # extracts zero tuples this round and releases it below (quarantine
        # implies closed), so claims drain without a stall
        b_eff = jnp.where(state.quarantined[j], 0, b_eff)
        k = jnp.arange(b_static, dtype=jnp.int32)
        valid = k[None, :] < b_eff[:, None]                      # (W, B)
        if slot_mode:
            # fairness weights (scheduler, repro.sched.fairness): slot s may
            # *count* only the first ceil(weight_s · b_eff) tuples of each
            # worker window this round.  The scan still extracts the full
            # b_eff (cursors/READ accounting are scan-level); a weighted slot
            # samples a shorter prefix of the same permutation window, which
            # is still a uniform without-replacement subsample.  weight = 1
            # reproduces the unweighted round bit-for-bit.
            b_slot = jnp.minimum(
                jnp.ceil(slots.weight[:, None]
                         * b_eff[None, :].astype(jnp.float32)).astype(jnp.int32),
                b_eff[None, :])                                  # (S, W)

        def window(seed_j, off_j, mj_j):
            return permutation_window_dyn(seed_j, off_j, b_static, mj_j, self.m_max)

        idx = jax.vmap(window)(self.seeds[j], off, mj)           # (W, B)
        cap = cfg.cache_cap
        if self.extract_pallas:
            # Fused kernel: gather + parse + slot eval + per-(worker, slot)
            # partial stats in one pass — no (S, W, B) eval tensor and no
            # decoded (W, B, C) copy (the decoded slab is emitted only when
            # the synopsis extraction cache needs it).
            if slot_mode:
                coeffs, p_lo, p_hi = slots.coeffs, slots.lo, slots.hi
                isc = (slots.agg == AGG_COUNT).astype(jnp.float32)
                gate_v = slots.active.astype(jnp.float32)
                wts = slots.weight
            else:
                coeffs, p_lo, p_hi = (self._plan_coeffs, self._plan_lo,
                                      self._plan_hi)
                isc = self._plan_is_count
                gate_v = jnp.ones((q,), jnp.float32)
                wts = jnp.ones((q,), jnp.float32)
            cols = None
            cache_rows = None
            if streaming:
                # slab-streaming kernels: row tiles of the worker's slab, so
                # chunks larger than VMEM stream tile-by-tile.  cache_cap > 0
                # makes the kernel itself emit the synopsis-cache delta rows
                # (W, cap, C) — only O(cap·C) per worker reaches HBM, never
                # the whole decoded window.
                def _stream_raw(budgets):
                    return kernel_ops.slot_extract_stream(
                        data, idx, budgets, coeffs, p_lo, p_hi, isc, gate_v,
                        weights=wts, row_tile=cfg.slab_row_tile,
                        backend=self.extract_backend, cache_cap=cap,
                        m_before=m_before)

                def _stream_dec(budgets):
                    return kernel_ops.slot_eval_decoded(
                        dec, idx, budgets, coeffs, p_lo, p_hi, isc, gate_v,
                        weights=wts, row_tile=cfg.slab_row_tile,
                        backend=self.extract_backend, cache_cap=cap,
                        m_before=m_before)

                if decoded_mode == "all":
                    res = _stream_dec(b_eff)
                elif decoded_mode == "mixed":
                    # complementary budgets: a zero-budget worker contributes
                    # exact float zeros, so the two kernel outputs sum to the
                    # single-kernel result bit-for-bit
                    b_raw = jnp.where(is_dec, 0, b_eff)
                    r_raw = _stream_raw(b_raw)
                    r_dec = _stream_dec(b_eff - b_raw)
                    res = jax.tree.map(lambda a, b: a + b, r_raw, r_dec)
                else:
                    res = _stream_raw(b_eff)
                if cap > 0:
                    stats4, cache_rows = res
                else:
                    stats4 = res
            elif grouped:
                stats4, cols, gstats4, tal_w = kernel_ops.slot_extract(
                    data, jd, idx, b_eff, coeffs, p_lo, p_hi, isc, gate_v,
                    weights=wts,
                    return_cols=cap > 0, backend=self.extract_backend,
                    gcol=slots.gcol, gval=slots.gval, gact=slots.gact,
                    salt=state.round.astype(jnp.uint32),
                    tally_buckets=self.tally_buckets)
                # (W, S, G, 4) partials -> (S, G, W) sums; worker tallies
                # sum locally here (psum merges across devices below)
                g_sum_x = jnp.moveaxis(gstats4[..., 1].astype(dtype), 0, -1)
                g_sum_xx = jnp.moveaxis(gstats4[..., 2].astype(dtype), 0, -1)
                g_sum_p = jnp.moveaxis(gstats4[..., 3].astype(dtype), 0, -1)
                tal = jnp.sum(tal_w.astype(dtype), axis=0)       # (S, 3, H)
            else:
                stats4, cols = kernel_ops.slot_extract(
                    data, jd, idx, b_eff, coeffs, p_lo, p_hi, isc, gate_v,
                    weights=wts,
                    return_cols=cap > 0, backend=self.extract_backend)
            sum_x = stats4[..., 1].astype(dtype).T               # (Q|S, W)
            sum_xx = stats4[..., 2].astype(dtype).T
            sum_p = stats4[..., 3].astype(dtype).T
        else:
            cache_rows = None
            w_ids = jnp.arange(idx.shape[0], dtype=jnp.int32)[:, None]
            if decoded_mode == "all":
                # parse-once fast path: the whole window gathers from the
                # decoded slab — no tokenize/parse at all
                cols = dec[w_ids, idx]                           # (W, B, C)
            else:
                if streaming:
                    raw = jax.vmap(lambda sw, ii: sw[ii])(data, idx)   # (W, B, rec)
                else:
                    raw = jax.vmap(lambda jj, ii: data[jj][ii])(jd, idx)  # (W, B, rec)
                cols = jax.vmap(self.codec.decode_ref)(raw)      # (W, B, C)
                if decoded_mode == "mixed":
                    # decode_ref is row-elementwise, so decoded-slab gathers
                    # equal gather-then-decode bit-for-bit
                    cols = jnp.where(is_dec[:, None, None], dec[w_ids, idx],
                                     cols)
            if slot_mode:
                x, pr = slot_evaluate(slots, cols)               # (S, W, B)
                gate = slots.active.astype(dtype)[:, None, None]
                # per-slot window prefix (fairness): k < b_slot[s, w]
                vf = (k[None, None, :] < b_slot[:, :, None]).astype(dtype)
            else:
                x, pr = jax.vmap(self.evaluate, in_axes=0, out_axes=1)(cols)  # (Q, W, B)
                gate = jnp.ones((), dtype)
                vf = valid.astype(dtype)[None]
            x = x.astype(dtype) * vf * gate
            pr = pr.astype(dtype) * vf * gate
            sum_x = jnp.sum(x, -1)                               # (Q|S, W)
            sum_xx = jnp.sum(x * x, -1)
            sum_p = jnp.sum(pr, -1)
            if grouped:
                # per-cell accumulation from the materialized columns.  All
                # mask factors are exact 0/1 floats, so multiplying them in
                # any order is IEEE-exact — a tracked cell's products equal
                # the expand_group_by fan-out slot's (expr · p · valid ·
                # gate) bit-for-bit, which is the oracle the grouped plane
                # is gated on.  A row matches at most one tracked value, so
                # the __other__ spill indicator is the complement of the
                # tracked-cell sum.
                gcol_c = jnp.clip(slots.gcol, 0, self.num_cols - 1)
                colv = jnp.moveaxis(cols, -1, 0)[gcol_c]         # (S, W, B)
                gvals = slots.gval.astype(dtype)
                gactf = slots.gact.astype(dtype)
                eq = (colv[:, None] == gvals[:, :, None, None]).astype(dtype)
                trk = eq * gactf[:, :, None, None]               # (S, G, W, B)
                other = ((1.0 - jnp.sum(trk[:, :-1], axis=1))
                         * gactf[:, -1][:, None, None])          # (S, W, B)
                ind = jnp.concatenate([trk[:, :-1], other[:, None]], axis=1)
                gx = ind * x[:, None]                            # (S, G, W, B)
                gp = ind * pr[:, None]
                g_sum_x = jnp.sum(gx, -1)                        # (S, G, W)
                g_sum_xx = jnp.sum(gx * gx, -1)
                g_sum_p = jnp.sum(gp, -1)
                tal = self._round_tallies(colv, pr, gactf[:, -1],
                                          state.round, dtype)

        # ---- 3. MERGE -------------------------------------------------------
        af = active.astype(jnp.int32)
        deltas = dict(
            dm=jnp.zeros((n,), jnp.int32).at[j].add(b_eff * af),
            dys=jnp.zeros((q, n), dtype).at[:, j].add(sum_x * af),
            dyq=jnp.zeros((q, n), dtype).at[:, j].add(sum_xx * af),
            dps=jnp.zeros((q, n), dtype).at[:, j].add(sum_p * af),
        )
        if slot_mode:
            # per-slot sample-size deltas honor the fairness budgets (== dm
            # broadcast when every weight is 1)
            deltas["dmq"] = jnp.zeros((q, n), jnp.int32).at[:, j].add(
                b_slot * af[None, :])
        if grouped:
            gcells = self.group_cells
            deltas["dgys"] = jnp.zeros((q, gcells, n), dtype).at[:, :, j].add(
                g_sum_x * af)
            deltas["dgyq"] = jnp.zeros((q, gcells, n), dtype).at[:, :, j].add(
                g_sum_xx * af)
            deltas["dgps"] = jnp.zeros((q, gcells, n), dtype).at[:, :, j].add(
                g_sum_p * af)
            deltas["gtal"] = tal
        if self.sharded:
            deltas["claims"] = claimed
        deltas = coll.merge(deltas)
        if self.sharded:
            qhead = state.qhead + deltas["claims"]
            head = state.head + jnp.sum(deltas["claims"])
        else:
            qhead = state.qhead
        if slot_mode:
            # a slot only counts tuples extracted while it is active
            dm_q = slots.active.astype(jnp.int32)[:, None] * deltas["dmq"]
        else:
            dm_q = deltas["dm"]
        stats = state.stats._replace(
            m=state.stats.m + dm_q,
            ysum=state.stats.ysum + deltas["dys"],
            ysq=state.stats.ysq + deltas["dyq"],
            psum=state.stats.psum + deltas["dps"])
        if grouped:
            # a cell's m counts every tuple the slot sampled while the cell
            # was live — not group-filtered — matching the per-chunk sample
            # size a dedicated fan-out slot would carry (predicate-
            # independent), so cells live since admission are bit-exact
            # against the fan-out oracle.  Cells activated mid-scan
            # accumulate from activation: any contiguous window of a chunk's
            # committed random permutation is still a uniform without-
            # replacement sample.
            gact_i = slots.gact.astype(jnp.int32)
            gm_new = state.gm + dm_q[:, None, :] * gact_i[:, :, None]
            gys_new = state.gys + deltas["dgys"]
            gyq_new = state.gyq + deltas["dgyq"]
            gps_new = state.gps + deltas["dgps"]
            g_tal = deltas["gtal"]
        else:
            gm_new, gys_new = state.gm, state.gys
            gyq_new, gps_new = state.gyq, state.gps
            g_tal = jnp.zeros((q, 3, self.tally_buckets), dtype)
        scan_m = state.scan_m + deltas["dm"]
        offset = state.offset + deltas["dm"]

        # READ accounting: a chunk costs its full raw bytes the first time it
        # is extracted *beyond* what the synopsis supplied (Section 6.3 —
        # in-memory chunks only trigger a read when topped up from raw).
        needs_raw = active & (b_eff > 0) & (m_before >= state.cached_m[j])
        newly_raw = needs_raw & ~state.raw_touched[j]
        raw_touched = state.raw_touched | (coll.merge(
            jnp.zeros((n,), jnp.int32).at[j].add(newly_raw.astype(jnp.int32))) > 0)
        bytes_round = coll.merge(
            jnp.sum(jnp.where(newly_raw, self.chunk_bytes[j], 0.0)))

        # extracted-tuple cache for synopsis construction: row r of chunk j
        # holds the r-th tuple of its permutation window (append-only; the
        # maintenance pass shrinks windows host-side).  OOB rows are dropped.
        if cap > 0:
            if cache_rows is not None:
                # streaming kernels already emitted the (W, cap, C) delta
                # rows (zeros off-window, so inactive workers are no-ops)
                cache_delta = jnp.zeros_like(state.cache).at[j].add(cache_rows)
            else:
                kk = jnp.arange(b_static, dtype=jnp.int32)
                rows = m_before[:, None] + kk[None, :]           # (W, B) ordinals
                writable = (kk[None, :] < b_eff[:, None]) & active[:, None]
                rows = jnp.where(writable, rows, cap)            # cap == OOB -> drop
                cache_delta = jnp.zeros_like(state.cache).at[
                    j[:, None], rows].add(cols * writable[..., None], mode="drop")
            cache = state.cache + coll.merge(cache_delta)
        else:
            cache = state.cache

        # ---- 4. DECIDE -------------------------------------------------------
        # per-slot sample sizes: (W,) in frozen mode, (S, W) in slot mode
        mj_new = jnp.take(stats.m, j, axis=-1).astype(dtype)
        scan_mj = scan_m[j].astype(dtype)                        # (W,) scan-level
        big_m = sizes[j].astype(dtype)
        scale = big_m / jnp.maximum(mj_new, 1.0)
        ys_j = stats.ysum[:, j]                                  # (Q|S, W)
        yq_j = stats.ysq[:, j]
        ss = yq_j - ys_j * ys_j / jnp.maximum(mj_new, 1.0)
        fpc = (big_m - mj_new) / jnp.maximum(mj_new - 1.0, 1.0)
        v_local = scale * fpc * jnp.maximum(ss, 0.0)             # Eq. (5) LHS
        yhat_local = scale * ys_j
        tiny = jnp.asarray(1e-12, dtype)
        eps_vec = slots.eps.astype(dtype) if slot_mode else self.eps.astype(dtype)
        # per-slot confidence: each slot carries its own z (frozen mode bakes
        # in the query list's shared confidence level)
        z_q = slots.z.astype(dtype)[:, None] if slot_mode else self.z
        # slots that are retired/not-yet-admitted never hold a chunk open
        stopped_mask = (state.stopped | ~slots.active) if slot_mode else state.stopped
        # ε_j = ε rule (Theorem 3), in error-ratio form: 2 z √v_j <= ε |ŷ_j|
        local_ok_q = 2.0 * z_q * jnp.sqrt(jnp.maximum(v_local, 0.0)) <= (
            eps_vec[:, None] * jnp.maximum(jnp.abs(yhat_local), tiny))
        if slot_mode:
            # per-slot m: each live slot needs >= 2 of its own tuples
            local_ok = jnp.all((local_ok_q & (mj_new >= 2.0))
                               | stopped_mask[:, None], axis=0)
        else:
            local_ok = jnp.all(local_ok_q | stopped_mask[:, None], axis=0)
            local_ok = local_ok & (mj_new >= 2.0)
        # a quarantined chunk counts as exhausted: whoever holds it closes it
        # immediately (it contributed b_eff == 0 above)
        exhausted_w = (scan_m[j] >= sizes[j]) | state.quarantined[j]
        newly_acc = active & local_ok & ~state.acc_met[j]

        if slot_mode:
            # a chunk may close before exhaustion only if every live slot's
            # plan permits early close (single-pass semantics, or
            # resource-aware while the monitor says CPU-bound)
            allow_early = (slots.plan == PLAN_SINGLE_PASS) | (
                (slots.plan == PLAN_RESOURCE_AWARE) & state.cpu_bound)
            early_ok = jnp.all(allow_early | stopped_mask)
            close_w = exhausted_w | (local_ok & early_ok)
        else:
            strategy = cfg.strategy
            if strategy in ("chunk_level", "chunk_level_unordered", "holistic"):
                close_w = exhausted_w
            elif strategy == "single_pass":
                close_w = exhausted_w | local_ok
            else:  # resource_aware
                close_w = exhausted_w | (local_ok & state.cpu_bound)
        close_w = close_w & active

        flag_deltas = coll.merge(dict(
            acc=jnp.zeros((n,), jnp.int32).at[j].add((local_ok & active).astype(jnp.int32)),
            cls=jnp.zeros((n,), jnp.int32).at[j].add(close_w.astype(jnp.int32)),
            calib_sum=jnp.sum(jnp.where(newly_acc, scan_mj, 0.0)),
            calib_cnt=jnp.sum(newly_acc.astype(dtype)),
            b_eff_total=jnp.sum(b_eff),
        ))
        acc_met = state.acc_met | (flag_deltas["acc"] > 0)
        closed = state.closed | (flag_deltas["cls"] > 0)
        cur = jnp.where(close_w, IDLE, cur)
        calib_sum = state.calib_sum + flag_deltas["calib_sum"].astype(jnp.float32)
        calib_cnt = state.calib_cnt + flag_deltas["calib_cnt"].astype(jnp.float32)

        # resource monitor: Eq. (4)'s two cost terms for this round
        p_total = cfg.num_workers
        round_cpu = (flag_deltas["b_eff_total"].astype(jnp.float32)
                     * self.cost_per_tuple / cfg.cpu_tuple_ops_per_sec / p_total)
        round_io = bytes_round.astype(jnp.float32) / cfg.io_bytes_per_sec
        cpu_bound = round_cpu > round_io

        # budget (t_eval) update — §5.4 rules
        any_acc = flag_deltas["calib_cnt"] > 0
        halve = jnp.where(cpu_bound, state.first_est, any_acc)
        decay = jnp.where(halve, state.decay * 0.5,
                          jnp.minimum(state.decay * 2.0, 1.0))
        base = jnp.where(calib_cnt > 0, calib_sum / jnp.maximum(calib_cnt, 1.0),
                         jnp.asarray(float(cfg.budget_init), jnp.float32))
        budget = jnp.clip(base * decay, float(cfg.budget_min), float(cfg.budget_max))
        if slot_mode:
            # adapt t_eval iff some live slot runs the resource-aware plan
            use_adapt = jnp.any(slots.active & ~state.stopped
                                & (slots.plan == PLAN_RESOURCE_AWARE))
            budget = jnp.where(use_adapt, budget, state.budget)
            decay = jnp.where(use_adapt, decay, state.decay)
        elif cfg.strategy != "resource_aware":
            budget = state.budget      # fixed t_eval for the simpler strategies
            decay = state.decay

        # ---- 5. ESTIMATE -----------------------------------------------------
        if slot_mode:
            # per-slot estimation mask (S, N): chunk-level slots see only the
            # closed schedule prefix (reordering barrier); everything else
            # sees all chunks the slot has sampled
            base_mask = stats.m > 0                              # (S, N)
            est_mask = jnp.where(
                (slots.plan == PLAN_CHUNK_LEVEL)[:, None],
                base_mask & self._closed_prefix_mask(
                    closed, state.schedule)[None], base_mask)
        else:
            strategy = cfg.strategy
            if strategy == "chunk_level":
                est_mask = self._closed_prefix_mask(closed, state.schedule)
            elif strategy == "chunk_level_unordered":
                est_mask = closed                  # inspection-paradox-vulnerable
            else:
                est_mask = stats.m > 0
        # coverage-adjusted population: quarantined chunks leave the sample
        # *and* the universe — the bi-level estimator's chunk count |U| and
        # tuple total M shrink to the survivors, so the N/n scale-up and the
        # FPC price exactly the population an answer can still speak for
        # (CIs widen; masked stats over N slots equal a compact scan over
        # the survivors bit-for-bit, since the dropped columns are zero).
        alive = ~state.quarantined
        est_mask = est_mask & alive
        strata = self.queues
        if strata > 1:
            # stratified over the claim queues' chunk ranges: each range is
            # a population of its own (quarantine shrinks only its range)
            n_eff, m_eff = est.stratum_totals(sizes, state.quarantined,
                                              strata)
        else:
            n_eff = (jnp.asarray(stats.n_total, jnp.int32)
                     - jnp.sum(state.quarantined.astype(jnp.int32)))
            m_eff = (jnp.asarray(stats.m_total, jnp.int32)
                     - jnp.sum(jnp.where(state.quarantined, sizes, 0)))
        # (N,) masks broadcast over the leading query dim; (S, N) are per-slot
        stats_est = stats._replace(
            m=jnp.where(est_mask, stats.m, 0),
            ysum=jnp.where(est_mask, stats.ysum, 0),
            ysq=jnp.where(est_mask, stats.ysq, 0),
            psum=jnp.where(est_mask, stats.psum, 0),
            n_total=n_eff, m_total=m_eff)

        need_avg = slot_mode or any(qq.agg == "avg" for qq in self.queries)
        sum_t, sum_v, cnt_t, cnt_v, avg_t, avg_v = est.bilevel_estimates(
            stats_est, strata,
            aggs=("sum", "count", "avg") if need_avg else ("sum", "count"))

        if slot_mode:
            agg = slots.agg
            estimate = jnp.where(agg == AGG_SUM, sum_t,
                                 jnp.where(agg == AGG_COUNT, cnt_t, avg_t))
            variance = jnp.where(agg == AGG_SUM, sum_v,
                                 jnp.where(agg == AGG_COUNT, cnt_v, avg_v))
            # per-slot confidence bounds: estimate ± z_s √var
            half = slots.z.astype(dtype) * jnp.sqrt(jnp.maximum(variance, 0.0))
            lo, hi = estimate - half, estimate + half
            err = est.error_ratio(estimate, lo, hi)

            # vectorized HAVING verdicts over the per-slot code columns
            op = slots.having_op
            decided = est.having_decision_coded(
                lo, hi, op, slots.having_thr.astype(dtype))
            stop_now = (err <= eps_vec) | (
                (op != HAVING_NONE) & (decided != -1))
            if grouped:
                # per-cell estimates over the (S, G, N) stat rows — the
                # bi-level estimators broadcast over arbitrary leading dims,
                # and a cell with gm == 0 on a chunk simply isn't in that
                # cell's sample (self-masking), so the slot-level chunk
                # eligibility mask is the only extra gating needed
                gmask = est_mask[:, None, :]
                gstats_est = BiLevelStats(
                    M=stats.M, m=jnp.where(gmask, gm_new, 0),
                    ysum=jnp.where(gmask, gys_new, 0),
                    ysq=jnp.where(gmask, gyq_new, 0),
                    psum=jnp.where(gmask, gps_new, 0),
                    n_total=n_eff, m_total=m_eff)
                (g_sum_t, g_sum_v, g_cnt_t, g_cnt_v, g_avg_t,
                 g_avg_v) = est.bilevel_estimates(gstats_est, strata)
                agg_b = agg[:, None]
                g_est = jnp.where(agg_b == AGG_SUM, g_sum_t,
                                  jnp.where(agg_b == AGG_COUNT, g_cnt_t,
                                            g_avg_t))
                g_var = jnp.where(agg_b == AGG_SUM, g_sum_v,
                                  jnp.where(agg_b == AGG_COUNT, g_cnt_v,
                                            g_avg_v))
                g_half = (slots.z.astype(dtype)[:, None]
                          * jnp.sqrt(jnp.maximum(g_var, 0.0)))
                g_lo, g_hi = g_est - g_half, g_est + g_half
                g_err = est.error_ratio(g_est, g_lo, g_hi)
                g_n = jnp.sum(jnp.where(gmask, gm_new, 0), axis=-1)
                # grouped stop: the slot's top-K live cells (by |estimate|)
                # must all meet its eps.  lax.top_k needs a static k, so
                # rank by double argsort and compare against per-slot gtopk.
                cell_ok = (slots.gact > 0) & (g_n > 0)
                scores = jnp.where(cell_ok, jnp.abs(g_est), -jnp.inf)
                ranks = jnp.argsort(jnp.argsort(-scores, axis=-1), axis=-1)
                need_cell = cell_ok & (ranks < slots.gtopk[:, None])
                # discovery guard: with fewer than top_k live cells the
                # top-K rule would be vacuously satisfied (a fresh slot has
                # only __other__ live, which converges long before online
                # discovery has promoted anything) — such a slot keeps
                # scanning; stores with fewer true groups than top_k run to
                # exhaustion and retire on the census
                n_live = jnp.sum(cell_ok.astype(jnp.int32), axis=-1)
                grouped_ok = (jnp.all(~need_cell | (g_err <= eps_vec[:, None]),
                                      axis=-1)
                              & (n_live >= slots.gtopk))
                # grouped slots retire on the grouped rule alone (the scalar
                # err describes the base-predicate population; per-cell
                # HAVING verdicts are assembled host-side at retire)
                stop_now = jnp.where(slots.gcol >= 0, grouped_ok, stop_now)
            stopped = state.stopped | stop_now
            all_stopped = jnp.all(stopped | ~slots.active)
            n_chunks_rep = jnp.sum((scan_m > 0).astype(jnp.int32))
            m_tuples_rep = jnp.sum(scan_m)
        else:
            estimate = jnp.zeros((q,), dtype)
            variance = jnp.zeros((q,), dtype)
            for qi, qq in enumerate(self.queries):
                t_, v_ = {"sum": (sum_t, sum_v), "count": (cnt_t, cnt_v),
                          "avg": (avg_t, avg_v) if need_avg else (sum_t, sum_v)}[qq.agg]
                estimate = estimate.at[qi].set(t_[qi])
                variance = variance.at[qi].set(v_[qi])
            lo, hi = est.confidence_bounds(estimate, variance, self.conf)
            err = est.error_ratio(estimate, lo, hi)

            decided = jnp.full((q,), -1, jnp.int8)
            stop_now = err <= self.eps.astype(dtype)
            for qi, qq in enumerate(self.queries):
                if qq.having is not None:
                    d = est.having_decision(lo[qi], hi[qi], qq.having.op,
                                            qq.having.threshold)
                    decided = decided.at[qi].set(d)
                    stop_now = stop_now.at[qi].set(stop_now[qi] | (d != -1))
            stopped = state.stopped | stop_now
            all_stopped = jnp.all(stopped)
            n_chunks_rep = stats_est.n
            m_tuples_rep = jnp.sum(stats_est.m)

        if not grouped:
            g_est = g_lo = g_hi = g_err = jnp.zeros(
                (q, self.group_cells), dtype)
            g_n = jnp.zeros((q, self.group_cells), jnp.int32)

        all_closed = jnp.all(closed) & (head >= n)
        new_state = EngineState(
            stats=stats, scan_m=scan_m, offset=offset, closed=closed,
            acc_met=acc_met, head=head, cur=cur, budget=budget, decay=decay,
            calib_sum=calib_sum, calib_cnt=calib_cnt,
            first_est=jnp.asarray(True), stopped=stopped,
            round=state.round + 1, t_io=state.t_io + round_io,
            t_cpu=state.t_cpu + round_cpu, cpu_bound=cpu_bound,
            cached_m=state.cached_m, raw_touched=raw_touched, cache=cache,
            schedule=state.schedule, quarantined=state.quarantined,
            gm=gm_new, gys=gys_new, gyq=gyq_new, gps=gps_new, qhead=qhead)
        report = RoundReport(
            estimate=estimate, lo=lo, hi=hi, err=err, decided=decided,
            n_chunks=n_chunks_rep, m_tuples=m_tuples_rep,
            round_io_s=round_io, round_cpu_s=round_cpu,
            tuples_round=flag_deltas["b_eff_total"], bytes_round=bytes_round,
            all_stopped=all_stopped, exhausted=all_closed,
            g_est=g_est, g_lo=g_lo, g_hi=g_hi, g_err=g_err, g_n=g_n,
            g_tal=g_tal)
        return new_state, report


def queue_schedule(order, queues: int) -> np.ndarray:
    """A claim order split into ``queues`` segments: segment q holds the
    chunks of range q (ids ``[q·N/Q, (q+1)·N/Q)``) in the order ``order``
    gives them, so each queue keeps a uniformly random order of its own
    range, and one queue keeps ``order`` itself."""
    order = np.asarray(order, np.int32)
    per = len(order) // queues
    return order[np.argsort(order // per, kind="stable")]


def budget_ladder(config: EngineConfig, m_max: int, b: float) -> int:
    """Snap a fractional t_eval budget to the power-of-two compile ladder."""
    b = float(np.clip(b, config.budget_min, min(config.budget_max, m_max)))
    return int(2 ** int(np.ceil(np.log2(max(b, 1.0)))))


# ---------------------------------------------------------------------------
# Slot retire / re-admit helpers (workload serving; preemption support)
# ---------------------------------------------------------------------------

def slot_stats_snapshot(state: EngineState, s: int) -> dict:
    """Host-side copy of slot ``s``'s sufficient-statistics row.

    The dict has the same ``{m, ysum, ysq, psum}`` shape contract as
    :meth:`~repro.core.synopsis.BiLevelSynopsis.seed_slot`, so a preempted
    query's snapshot slots straight back into the admission seeding path
    (:func:`slot_stats_write`) when it is re-admitted.  It is a *richer*
    seed than the synopsis — every tuple the slot already counted, at full
    per-chunk resolution — and it remains statistically valid because each
    chunk's tuples were drawn as a prefix of that chunk's committed random
    permutation, a property re-admission preserves (the scan's cursors
    never rewind).
    """
    stats = state.stats
    return dict(
        m=np.asarray(stats.m[s]),
        ysum=np.asarray(stats.ysum[s]),
        ysq=np.asarray(stats.ysq[s]),
        psum=np.asarray(stats.psum[s]),
    )


def slot_stats_fold(state: EngineState, slot_ids) -> dict:
    """Batched host-side fold-out of several slots' sufficient-statistics
    rows: ``{s: {m, ysum, ysq, psum}}`` with the same row contract as
    :func:`slot_stats_snapshot`.

    This is the rollup tier's per-round maintenance hook (see
    ``repro.serve.rollup``): after each engine round the server folds the
    resident slots whose query pattern is promoted into their rollup
    cells.  One device→host transfer per statistics array covers *all*
    requested rows (vs one transfer per slot through repeated
    :func:`slot_stats_snapshot` calls), and the empty-``slot_ids`` case —
    the common one, when no promoted pattern is resident — returns without
    touching the device at all.
    """
    slot_ids = list(slot_ids)
    if not slot_ids:
        return {}
    stats = state.stats
    m = np.asarray(stats.m)
    ysum = np.asarray(stats.ysum)
    ysq = np.asarray(stats.ysq)
    psum = np.asarray(stats.psum)
    return {s: dict(m=m[s], ysum=ysum[s], ysq=ysq[s], psum=psum[s])
            for s in slot_ids}


def slot_stats_write(stats: BiLevelStats, s: int, seed: Optional[dict],
                     n_chunks: int) -> tuple[BiLevelStats, int]:
    """Functional write of slot ``s``'s statistics row from a seed dict
    (synopsis seed or preemption snapshot) — zeros when ``seed`` is None.
    Returns ``(new_stats, seeded_tuple_count)``.  Host-side, between
    rounds; the engine round step never mutates rows of retired slots, so
    the write is race-free by construction."""
    dtype = stats.ysum.dtype
    if seed is None:
        m_row = jnp.zeros((n_chunks,), jnp.int32)
        zs = jnp.zeros((n_chunks,), dtype)
        ys_row, yq_row, ps_row = zs, zs, zs
        seeded = 0
    else:
        m_row = jnp.asarray(seed["m"], jnp.int32)
        ys_row = jnp.asarray(seed["ysum"], dtype)
        yq_row = jnp.asarray(seed["ysq"], dtype)
        ps_row = jnp.asarray(seed["psum"], dtype)
        seeded = int(np.asarray(seed["m"]).sum())
    return stats._replace(
        m=stats.m.at[s].set(m_row),
        ysum=stats.ysum.at[s].set(ys_row),
        ysq=stats.ysq.at[s].set(yq_row),
        psum=stats.psum.at[s].set(ps_row)), seeded


def zero_group_cells(state: EngineState, s: int,
                     cells=None) -> EngineState:
    """Zero slot ``s``'s per-group sufficient-stat rows (all cells, or the
    given cell indices).  Host-side, between rounds — a no-op on ungrouped
    engines.

    Used at admission (a fresh occupant must not inherit the previous
    query's cells) and by online discovery: promoting a value out of
    ``__other__`` changes what the spill cell means, so its stats restart.
    A restarted cell's sample is the post-restart window of each chunk's
    committed permutation — a contiguous window of a uniform random
    permutation, hence still a uniform without-replacement sample.
    """
    if state.gm.shape[1] == 0:
        return state
    sel = slice(None) if cells is None else np.asarray(list(cells), np.int64)
    gm = np.asarray(state.gm).copy()
    gys = np.asarray(state.gys).copy()
    gyq = np.asarray(state.gyq).copy()
    gps = np.asarray(state.gps).copy()
    gm[s, sel] = 0
    gys[s, sel] = 0
    gyq[s, sel] = 0
    gps[s, sel] = 0
    return state._replace(gm=jnp.asarray(gm), gys=jnp.asarray(gys),
                          gyq=jnp.asarray(gyq), gps=jnp.asarray(gps))


def slot_group_rows(state: EngineState, s: int) -> dict:
    """Host-side copy of slot ``s``'s per-cell stat rows
    ``{gm, gys, gyq, gps}`` (each ``(G, N)``).  Per-cell counterpart of
    :func:`slot_stats_snapshot`: each cell's row has the same
    ``{m, ysum, ysq, psum}`` contract, so the rollup tier folds tracked
    cells through the exact same cell-fold path as scalar slots."""
    return dict(
        gm=np.asarray(state.gm[s]),
        gys=np.asarray(state.gys[s]),
        gyq=np.asarray(state.gyq[s]),
        gps=np.asarray(state.gps[s]),
    )


def quarantine_chunks(state: EngineState, chunk_ids) -> EngineState:
    """Host-side quarantine write (between rounds, like the scheduler's
    claim reorder): mark chunks quarantined + closed and zero their
    statistics columns.

    With the columns zeroed and the round's ESTIMATE stage substituting the
    surviving chunk count / tuple total, the masked N-slot estimator sums
    are *bit-for-bit* what a fresh scan over only the surviving chunks
    would compute (adding float zeros is IEEE-exact) — the oracle property
    gated in ``tests/test_faults.py``.  A worker currently holding a
    quarantined chunk extracts zero tuples next round and releases it
    (quarantine implies exhausted), so the scan never stalls.
    """
    ids = np.asarray(sorted({int(c) for c in chunk_ids}), np.int64)
    if ids.size == 0:
        return state
    q = np.asarray(state.quarantined).copy()
    ids = ids[~q[ids]]
    if ids.size == 0:
        return state
    q[ids] = True
    closed = np.asarray(state.closed).copy()
    closed[ids] = True
    stats = state.stats
    m = np.asarray(stats.m).copy()
    ysum = np.asarray(stats.ysum).copy()
    ysq = np.asarray(stats.ysq).copy()
    psum = np.asarray(stats.psum).copy()
    m[..., ids] = 0
    ysum[..., ids] = 0
    ysq[..., ids] = 0
    psum[..., ids] = 0
    cached_m = np.asarray(state.cached_m).copy()
    cached_m[ids] = 0
    state = state._replace(
        quarantined=jnp.asarray(q),
        closed=jnp.asarray(closed),
        cached_m=jnp.asarray(cached_m),
        stats=stats._replace(
            m=jnp.asarray(m), ysum=jnp.asarray(ysum),
            ysq=jnp.asarray(ysq), psum=jnp.asarray(psum)))
    if state.gm.shape[1] > 0:
        gm = np.asarray(state.gm).copy()
        gys = np.asarray(state.gys).copy()
        gyq = np.asarray(state.gyq).copy()
        gps = np.asarray(state.gps).copy()
        gm[..., ids] = 0
        gys[..., ids] = 0
        gyq[..., ids] = 0
        gps[..., ids] = 0
        state = state._replace(gm=jnp.asarray(gm), gys=jnp.asarray(gys),
                               gyq=jnp.asarray(gyq), gps=jnp.asarray(gps))
    return state


class _ResidencyMixin:
    """Host-side raw-data feed shared by every engine.

    ``round_data(state)`` is what drivers pass as the round step's ``data``
    argument: the resident packed view under ``residency="packed"``, or a
    freshly assembled bounded slab under ``residency="stream"`` (claim
    prediction → prefetcher assemble → read-ahead hint for the next schedule
    positions, overlapping disk READ with this round's device compute).  It
    returns ``(state, data)``: streaming assembly is where permanent read
    failures surface, and each one quarantines the lost chunk in the
    returned state instead of raising into the driver loop.
    """

    pipeline = None
    #: Span tracer for the host-side round feed (claims prediction + slab
    #: assembly).  Default is the shared no-op; :meth:`set_tracer` swaps in
    #: the server's and propagates it to the prefetcher so ``ola.read``
    #: spans land in the same trace on the reader thread.
    tracer = NULL_TRACER

    def set_tracer(self, tracer) -> None:
        self.tracer = tracer
        if self.pipeline is not None:
            self.pipeline.tracer = tracer

    def _init_residency(self, store, config: EngineConfig, slab_put=None,
                        packed_put=None, shards_put=None) -> np.ndarray:
        """Set up ``self.packed``/``self.pipeline`` per the configured
        residency; returns the chunk-size vector.  ``slab_put``/``packed_put``
        let the SPMD engines place buffers with mesh shardings, and
        ``shards_put(store)`` places a sharded table range by range (one
        device holds a sharded table whole, as packed)."""
        self.quarantine_log: list[int] = []
        if config.residency == "sharded" and shards_put is not None:
            self.pipeline = None
            with self.tracer.span("ola.shard_place"):
                self.packed = shards_put(store)
            return store.chunk_sizes
        if config.residency == "stream":
            from repro.data.pipeline import SlabPrefetcher

            self.packed = None
            self.pipeline = SlabPrefetcher(
                store, num_workers=config.num_workers,
                row_multiple=config.slab_row_tile,
                lookahead=config.prefetch_lookahead, device_put=slab_put,
                adaptive=config.prefetch_adaptive,
                decoded_cache_bytes=config.decoded_cache_bytes)
            return store.chunk_sizes
        # rows padded to the fused kernel's aligned gather tile, so the
        # kernel never re-pads (copies) the resident store per round
        packed, sizes = store.packed_device_view(row_multiple=GATHER_ROWS)
        self.packed = (jnp.asarray(packed) if packed_put is None
                       else packed_put(packed))
        return sizes

    @property
    def resident_bytes(self) -> int:
        """Raw-data bytes the busiest device holds: its chunk range of a
        sharded table, else the whole padded table (0 when streaming)."""
        if self.packed is None:
            return 0
        return max(s.data.nbytes for s in self.packed.addressable_shards)

    def round_data(self, state: EngineState) -> tuple[EngineState, object]:
        if self.pipeline is None:
            return state, self.packed
        with self.tracer.span("ola.assemble"):
            while True:
                j, active, new_head = self.program.plan_claims(state)
                qn = np.asarray(state.quarantined)
                # never read a quarantined chunk: its worker still claims it
                # in-jit but extracts b_eff == 0 from a zero slab row
                active = np.asarray(active) & ~qn[np.asarray(j)]
                try:
                    slab = self.pipeline.assemble(j, active)
                except FaultError as e:
                    if e.chunk_id is None:
                        raise
                    # retries exhausted / CRC mismatch / permanent loss: drop
                    # the chunk from the population and re-plan.  Progress is
                    # monotone (each pass quarantines one more chunk), so this
                    # loop is bounded by the chunk count.  The decoded-chunk
                    # cache drops the chunk too: a block decoded from bytes
                    # the scan no longer trusts must not keep serving hits.
                    state = quarantine_chunks(state, [e.chunk_id])
                    self.drop_decoded_chunks([e.chunk_id])
                    self.quarantine_log.append(int(e.chunk_id))
                    continue
                # read-ahead follows the *state* schedule, so a scheduler-
                # permuted claim order (repro.sched) is what the reader
                # thread warms up; quarantined chunks are skipped
                nxt = np.asarray(state.schedule)[new_head:new_head
                                                 + self.pipeline.lookahead]
                self.pipeline.prefetch(int(p) for p in nxt if not qn[p])
                return state, slab

    def drop_decoded_chunks(self, chunk_ids) -> int:
        """Evict chunks from the prefetcher's decoded cache (quarantine /
        invalidation hook); returns the number actually dropped."""
        if self.pipeline is None or self.pipeline.decoded is None:
            return 0
        return self.pipeline.drop_decoded(chunk_ids)

    def decoded_fraction(self) -> float:
        """Fraction of the store's tuples with decoded blocks cached (the
        Eq. (4) CPU-cost discount input); 0.0 without a decoded cache."""
        if self.pipeline is None:
            return 0.0
        return self.pipeline.decoded_fraction()

    @staticmethod
    def data_mode(data) -> tuple[str, object]:
        """Split :meth:`round_data`'s result into the static round variant
        and the jit-able data argument: the prefetcher's decoded 4-tuple
        carries a host-side all-decoded flag that picks ``"all"`` vs
        ``"mixed"``; anything else is the classic ``"none"`` round."""
        if isinstance(data, tuple) and len(data) == 4:
            raw, dec_slab, mask, all_dec = data
            return ("all" if all_dec else "mixed"), (raw, dec_slab, mask)
        return "none", data

    def close(self) -> None:
        if self.pipeline is not None:
            self.pipeline.close()


class OLAEngine(_ResidencyMixin):
    """Host-facing single-process engine: owns device buffers + jitted rounds.
    ``queues`` claim queues under residency="sharded": one device with Q
    queues runs the program a Q-device mesh runs, round for round."""

    def __init__(self, store, queries: Sequence[Query], config: EngineConfig,
                 schedule: Optional[np.ndarray] = None, queues: int = 1):
        self.store = store
        self.config = config
        sizes = self._init_residency(store, config)
        self.program = EngineProgram(
            codec=store.codec, queries=queries, config=config,
            n_chunks=store.num_chunks, m_max=store.max_chunk_tuples,
            chunk_sizes=sizes, schedule=schedule, queues=queues)
        speeds = config.worker_speed or (1.0,) * config.num_workers
        assert len(speeds) == config.num_workers
        self.speeds = jnp.asarray(speeds, jnp.float32)
        self._round_fns: dict[tuple, callable] = {}
        self.m_max = int(store.max_chunk_tuples)

    @property
    def queries(self):
        return self.program.queries

    def init_state(self, synopsis_seed: Optional[dict] = None) -> EngineState:
        return self.program.init_state(synopsis_seed)

    def round_fn(self, b_static: int, decoded_mode: str = "none"):
        key = (b_static, decoded_mode)
        if key not in self._round_fns:
            coll = _Collectives()

            def step(state, packed, speeds):
                return self.program.round_body(state, packed, speeds, b_static,
                                               coll, decoded_mode=decoded_mode)

            self._round_fns[key] = jax.jit(step, donate_argnums=(0,))
        return self._round_fns[key]

    def budget_ladder(self, b: float) -> int:
        return budget_ladder(self.config, self.m_max, b)

    def run(self, max_rounds: int = 100_000, wall_timeout_s: float = 300.0,
            synopsis_seed: Optional[dict] = None, collect_history: bool = True):
        """Bare driver loop (the δ-interval reporting controller wraps this)."""
        state = self.init_state(synopsis_seed)
        history = []
        t0 = time.perf_counter()
        for _ in range(max_rounds):
            b = self.budget_ladder(float(state.budget))
            state, data = self.round_data(state)
            mode, data = self.data_mode(data)
            state, rep = self.round_fn(b, mode)(state, data, self.speeds)
            if collect_history:
                history.append(jax.tree.map(np.asarray, rep))
            if bool(rep.all_stopped) or bool(rep.exhausted):
                break
            if time.perf_counter() - t0 > wall_timeout_s:
                break
        return state, history


class SlotOLAEngine(_ResidencyMixin):
    """Host-facing engine whose query plane is a dynamic slot table.

    Mirrors :class:`OLAEngine` but the jitted round takes a
    :class:`~repro.core.queries.SlotTable` as a *data* argument: admitting a
    query mid-scan, retiring one early, or changing a slot's ε/plan is a
    host-side row write between rounds, with no recompilation and no
    disturbance to the other slots' statistics.  The workload server
    (``repro.serve.ola_server.OLAWorkloadServer``) owns admission policy,
    synopsis seeding, and top-up passes; this class owns device buffers and
    the jitted step.  ``queues`` as in :class:`OLAEngine`.
    """

    def __init__(self, store, max_slots: int, config: EngineConfig,
                 schedule: Optional[np.ndarray] = None,
                 confidence: float = 0.95, queues: int = 1):
        self.store = store
        self.config = config
        sizes = self._init_residency(store, config)
        self.program = EngineProgram(
            codec=store.codec, config=config, n_chunks=store.num_chunks,
            m_max=store.max_chunk_tuples, chunk_sizes=sizes,
            schedule=schedule, max_slots=max_slots, confidence=confidence,
            queues=queues)
        speeds = config.worker_speed or (1.0,) * config.num_workers
        assert len(speeds) == config.num_workers
        self.speeds = jnp.asarray(speeds, jnp.float32)
        self._round_fns: dict[tuple, callable] = {}
        self.m_max = int(store.max_chunk_tuples)

    @property
    def max_slots(self) -> int:
        return self.program.max_slots

    def init_state(self) -> EngineState:
        return self.program.init_state()

    def round_fn(self, b_static: int, decoded_mode: str = "none"):
        key = (b_static, decoded_mode)
        if key not in self._round_fns:
            coll = _Collectives()

            def step(state, table, packed, speeds):
                return self.program.round_body(state, packed, speeds,
                                               b_static, coll, slots=table,
                                               decoded_mode=decoded_mode)

            self._round_fns[key] = jax.jit(step, donate_argnums=(0,))
        return self._round_fns[key]

    def budget_ladder(self, b: float) -> int:
        return budget_ladder(self.config, self.m_max, b)
