"""Tier-1 rollup answer cache in front of the OLA workload server.

OLA-RAW's central economy is never paying the scan/tokenize/parse cost
twice — yet a *repeated* query pattern still costs scan rounds every time
it arrives.  This module adds the two-tier shape production OLAP serving
converges on (pre-aggregated rollup cells answer the hot patterns
instantly; the shared raw scan serves only the long tail):

* **pattern mining** — every completed query's ``(measure,
  predicate-template)`` pattern is logged; a pattern observed
  ``promote_hits`` times inside the sliding mining window is *promoted* to
  a rollup cell (query-feedback-driven refinement: the workload itself
  decides what is worth materializing);
* **incremental maintenance** — a promoted cell holds per-chunk sufficient
  statistics ``{m, ysum, ysq, psum}`` (the same ``(N,)``-row contract as
  :meth:`~repro.core.synopsis.BiLevelSynopsis.seed_slot` and
  :func:`~repro.core.engine.slot_stats_snapshot`), folded from the rows
  the engine already emits: resident slots running the pattern fold out
  once per round (:func:`~repro.core.engine.slot_stats_fold`, a near-free
  hook — one batched device→host copy, empty in the common case), and
  every retirement folds the final row.  Folding is *replacement by
  larger per-chunk sample*: each slot row is a union of windows of the
  chunk's committed random permutation, so the bigger row subsumes the
  smaller one and stays a valid uniform without-replacement sample —
  never added, never double counted;
* **tiered answers** — a cell answers through the engine's bi-level
  estimators: chunks with ``m == M_j`` are fully covered and contribute
  *exactly* (the FPC zeroes their within-chunk variance), the remainder
  contributes a synopsis-style CI.  A fully-covered cell's answer is
  bit-identical to a fresh census scan of the pattern;
* **cost-model routing** — the server routes each admission Tier-1 vs
  Tier-2 with the Eq. (4) terms: a rollup answer that meets the query's
  accuracy target costs zero scan seconds and beats any admit/queue/shed
  plan (:data:`repro.sched.admission.TIER1`, checked before the
  feasibility triage); when the cell alone cannot meet ε it still
  discounts the Tier-2 plan as a seed richer than the synopsis (CLT
  ``err ∝ 1/√m`` — fewer tuples left to scan);
* **invalidation / demotion** — cells pin the
  :attr:`~repro.data.chunkstore.ChunkStore.content_version` they were
  built over and are dropped wholesale when the raw bytes change; cold
  patterns (no hit for ``cold_after_s`` modeled seconds) are demoted, and
  the cell store is LRU-bounded at ``max_cells``.

Statistical validity: every row folded into a cell describes tuples drawn
from windows of each chunk's committed permutation that lie inside the
scan's already-extracted prefix (synopsis windows and slot deltas both
are).  Future scan extraction continues past the scan cursor, so a cell
row used as an admission seed composes with later round deltas without
overlap — the same argument that makes preemption snapshots re-seedable.
"""

from __future__ import annotations

import dataclasses
import math
from collections import deque
from typing import Optional

import numpy as np

from repro.core.queries import Query, linear_plan


def pattern_key(query: Query, num_cols: int) -> Optional[tuple]:
    """Canonical ``(measure, predicate-template)`` cell key for a query.

    The key is the slot-encodable coefficient form — aggregate kind plus
    the exact f32 ``coeffs/lo/hi`` lowering of :func:`linear_plan` — so
    textually different but semantically identical predicates collide.
    Accuracy parameters (ε, confidence) and HAVING are deliberately *not*
    part of the key: repeats of the same measure at different targets
    share one cell and re-judge the answer against their own target.
    Returns ``None`` for queries outside the linear+range form (those are
    never cacheable and always route Tier-2).
    """
    try:
        plan = linear_plan([query], num_cols)
    except ValueError:
        return None
    return (query.agg, plan.coeffs[0].tobytes(), plan.lo[0].tobytes(),
            plan.hi[0].tobytes())


@dataclasses.dataclass(frozen=True)
class RollupConfig:
    """Promotion/demotion policy knobs for the Tier-1 cell store."""

    # completions of a pattern (inside the mining window) before promotion
    promote_hits: int = 2
    # LRU capacity of the cell store
    max_cells: int = 64
    # demote a cell untouched for this many modeled seconds (inf = never)
    cold_after_s: float = math.inf
    # sliding completed-query log length the pattern miner counts over
    mine_window: int = 256

    def __post_init__(self):
        assert self.promote_hits >= 1, self.promote_hits
        assert self.max_cells >= 1, self.max_cells
        assert self.mine_window >= 1, self.mine_window


class RollupCell:
    """One promoted pattern's partial aggregate: per-chunk sufficient
    statistics over the chunks the scan has covered for it so far."""

    def __init__(self, key: tuple, query: Query, n_chunks: int,
                 now: float, content_version: int):
        self.key = key
        self.query = query              # exemplar (ε/HAVING ignored at answer)
        self.content_version = content_version
        self.created_t = now
        self.last_hit_t = now
        self.hits = 0                   # Tier-1 answers served from this cell
        self.folds = 0
        self.m = np.zeros(n_chunks, np.int64)
        self.ysum = np.zeros(n_chunks, np.float64)
        self.ysq = np.zeros(n_chunks, np.float64)
        self.psum = np.zeros(n_chunks, np.float64)

    def fold(self, row: dict) -> int:
        """Merge one engine stats row (``slot_stats_snapshot`` /
        ``seed_slot`` contract) into the cell: per chunk, the row with the
        larger sample *replaces* the cell's (both are unions of windows of
        the same committed permutation — the larger subsumes the smaller;
        adding would double count).  Returns the number of chunks
        upgraded."""
        m = np.asarray(row["m"], np.int64)
        take = m > self.m
        n = int(take.sum())
        if n:
            self.m[take] = m[take]
            self.ysum[take] = np.asarray(row["ysum"], np.float64)[take]
            self.ysq[take] = np.asarray(row["ysq"], np.float64)[take]
            self.psum[take] = np.asarray(row["psum"], np.float64)[take]
            self.folds += 1
        return n

    def seed_dict(self) -> dict:
        """The cell as a ``{m, ysum, ysq, psum}`` seed row — drop-in for
        the server's slot write (:mod:`repro.serve.slot_writes`) and its
        ``_seed_answer`` (the same contract the synopsis emits)."""
        return dict(m=self.m.copy(), ysum=self.ysum.copy(),
                    ysq=self.ysq.copy(), psum=self.psum.copy())

    def covered(self, chunk_sizes: np.ndarray) -> np.ndarray:
        """Fully-covered mask (the exact part of a tiered answer)."""
        return self.m >= np.asarray(chunk_sizes, np.int64)

    def touch(self, now: float) -> None:
        self.hits += 1
        self.last_hit_t = max(self.last_hit_t, now)


class RollupTier:
    """The Tier-1 cell store + pattern miner (see module docstring).

    Host-side and engine-free: the server owns answer construction (it
    reuses the estimator stack on :meth:`RollupCell.seed_dict` rows) and
    feeds completions/fold rows in; this class owns which patterns are
    materialized and when cells die.
    """

    def __init__(self, store, config: RollupConfig = RollupConfig(),
                 num_cols: Optional[int] = None):
        self.store = store
        self.config = config
        self.num_cols = (store.codec.num_cols if num_cols is None
                         else int(num_cols))
        self.n_chunks = store.num_chunks
        self.content_version = int(getattr(store, "content_version", 0))
        self.cells: dict[tuple, RollupCell] = {}
        self._log: deque[tuple] = deque()   # completed-query pattern log
        self._counts: dict[tuple, int] = {}
        # observability counters (surfaced by benchmarks/bench_workload.py
        # and the server's metrics registry via counters()/bind_metrics)
        self.tier1_hits = 0
        self.promotions = 0
        self.demotions = 0
        self.invalidations = 0

    COUNTER_FIELDS = ("tier1_hits", "promotions", "demotions",
                      "invalidations")

    def counters(self) -> dict:
        """Point-in-time snapshot of the tier's monotone counters plus the
        current cell population."""
        out = {f: int(getattr(self, f)) for f in self.COUNTER_FIELDS}
        out["cells"] = len(self.cells)
        return out

    def bind_metrics(self, registry, prefix: str = "rollup") -> None:
        """Register pull gauges for every counter on a
        :class:`~repro.obs.metrics.MetricsRegistry` (read at snapshot
        time, zero hot-path writes)."""
        for f in self.COUNTER_FIELDS:
            registry.gauge(f"{prefix}_{f}",
                           help=f"RollupTier.{f} (cumulative)",
                           fn=(lambda f=f: getattr(self, f)))
        registry.gauge(f"{prefix}_cells", help="materialized rollup cells",
                       fn=lambda: len(self.cells))

    # ----------------------------------------------------------- mining ----
    def observe(self, query: Query, key: Optional[tuple],
                now: float) -> Optional[RollupCell]:
        """Log one completed query.  Returns the cell iff this completion
        *newly promoted* the pattern (the caller seeds/folds it); already-
        promoted patterns just refresh their recency."""
        if key is None:
            return None
        self._log.append(key)
        self._counts[key] = self._counts.get(key, 0) + 1
        while len(self._log) > self.config.mine_window:
            old = self._log.popleft()
            self._counts[old] = max(self._counts.get(old, 1) - 1, 0)
        cell = self.cells.get(key)
        if cell is not None:
            cell.last_hit_t = max(cell.last_hit_t, now)
            return None
        if self._counts[key] < self.config.promote_hits:
            return None
        cell = RollupCell(key, query, self.n_chunks, now,
                          self.content_version)
        self.cells[key] = cell
        self.promotions += 1
        self._evict_lru()
        return cell

    def _evict_lru(self) -> None:
        while len(self.cells) > self.config.max_cells:
            lru = min(self.cells.values(), key=lambda c: c.last_hit_t)
            self._demote(lru.key)

    def _demote(self, key: tuple) -> None:
        self.cells.pop(key, None)
        # demand fresh evidence before re-promoting: a demoted pattern's
        # stale log entries must not instantly resurrect the cell
        self._counts[key] = 0
        self.demotions += 1

    # ------------------------------------------------------- maintenance ----
    def maintain(self, now: float) -> None:
        """Invalidate on store content change, demote cold cells.  Called
        by the server once per intake pass (cheap: O(cells))."""
        version = int(getattr(self.store, "content_version", 0))
        if version != self.content_version:
            # the raw bytes changed under the cells: every partial
            # aggregate is stale — drop them all, keep the miner's log
            # (the patterns are still hot; they re-promote and rebuild
            # over the new content)
            if self.cells:
                self.invalidations += len(self.cells)
                self.cells.clear()
            self.content_version = version
        if math.isfinite(self.config.cold_after_s):
            cold = [k for k, c in self.cells.items()
                    if now - c.last_hit_t > self.config.cold_after_s]
            for k in cold:
                self._demote(k)

    def invalidate_chunks(self, chunk_ids) -> int:
        """Drop every cell whose partial aggregate covers a quarantined
        chunk: the cell's answer counts tuples that left the surviving
        population, so it can no longer serve Tier-1 (or seed Tier-2).
        Cells with zero sample over the quarantined ids keep serving —
        their statistics already describe only surviving chunks.  Returns
        the number of cells invalidated; the miner's pattern log survives
        (hot patterns re-promote and rebuild over the survivors)."""
        ids = [int(j) for j in chunk_ids]
        if not ids:
            return 0
        stale = [k for k, c in self.cells.items()
                 if int(c.m[ids].sum()) > 0]
        for k in stale:
            self.cells.pop(k, None)
        self.invalidations += len(stale)
        return len(stale)

    # ------------------------------------------------------------ lookup ----
    def get(self, key: Optional[tuple]) -> Optional[RollupCell]:
        """The promoted cell for a pattern key, or None.  Callers run
        :meth:`maintain` at intake, so a returned cell is content-current."""
        if key is None:
            return None
        return self.cells.get(key)

    def fold(self, key: Optional[tuple], row: dict) -> None:
        cell = self.get(key)
        if cell is not None:
            cell.fold(row)
