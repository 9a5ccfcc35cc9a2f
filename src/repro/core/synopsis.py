"""Memory-resident bi-level sample synopsis — paper Section 6.

The synopsis caches, under a tuple budget ``B``, a *circular window* into each
chunk's keyed permutation together with the extracted column values, so that
subsequent queries can be estimated without touching raw data.  Because the
window is a contiguous run of the chunk's random order, whatever survives
shrinking is still a uniform without-replacement sample — the synopsis is a
valid bi-level sample *at every instant* (Section 6.1), and degenerates to a
stratified sample once every chunk is represented.

Construction/maintenance follow the paper's variance-driven strategy:

* chunks are admitted in extraction order (reservoir-style: everything fits
  until budget pressure appears);
* on pressure, the budget is split across chunks **proportionally to their
  within-chunk variance for the current query**; shrinking drops tuples from
  the *front* of the window (``start += excess``) so the survivor set remains
  a permutation window;
* on resampling, new tuples extend the window at the *end* (the engine's
  cursor continues from ``start+count``, wrapping circularly — Section 6.2),
  and the merged window is re-fit to the chunk's allocation with the same
  keep-the-tail rule.

Maintenance is a between-queries host-side pass (numpy) over the engine's
device-built extraction cache; estimation seeding evaluates the *new* query
on the cached tuples, which is what lets a different expression/predicate
reuse the same sample (Section 6.3).

Under the workload server the same machinery runs *mid-scan*: the synopsis
absorbs the shared scan's extraction cache on demand, and :meth:`seed_slot`
produces per-slot stats rows for a query admitted while the scan is running.
Because every cached window lies inside the already-scanned prefix of each
chunk's permutation (the scan cursor is at or past the window end), a seeded
window and the slot's future extraction are disjoint index sets of one keyed
permutation — their union is still a uniform without-replacement sample.
The scan *top-up* (re-opening early-closed chunks when a later query needs
more tuples) is driven by the server; the synopsis only guarantees window
alignment.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.queries import Query, compile_queries, linear_plan


@dataclasses.dataclass
class SynopsisChunk:
    start: int                 # window start in the chunk's permutation order
    values: np.ndarray         # (count, C) extracted tuples, window order

    @property
    def count(self) -> int:
        return int(self.values.shape[0])


def _evaluate(queries: Sequence[Query], cols: np.ndarray, num_cols: int):
    """``cols (T, C) float32 -> (x, p) (Q, T)``, the semantics of
    ``slot_evaluate``: ``lo <= c < hi`` on every column, ``x = (cols @
    coeffs) · p``, ``x = p`` for COUNT.  Runs in numpy at float32: the
    synopsis is host memory, and the work is far below one device round
    trip.  A query outside the coefficient form (``Custom``) takes the JAX
    evaluator, once, on the whole array."""
    try:
        lp = linear_plan(queries, num_cols)
    except ValueError:
        x, p = compile_queries(queries)(jnp.asarray(cols))
        return np.asarray(x), np.asarray(p)
    c = cols[:, None, :]                                    # (T, 1, C)
    p = np.all((c >= lp.lo) & (c < lp.hi), axis=-1).T.astype(np.float32)
    count = np.asarray([q.agg == "count" for q in queries])[:, None]
    return np.where(count, np.float32(1), lp.coeffs @ cols.T) * p, p


class BiLevelSynopsis:
    """Budgeted cache of per-chunk permutation windows."""

    def __init__(self, n_chunks: int, num_cols: int, budget_tuples: int,
                 chunk_sizes: np.ndarray):
        self.n_chunks = int(n_chunks)
        self.num_cols = int(num_cols)
        self.budget = int(budget_tuples)
        self.chunk_sizes = np.asarray(chunk_sizes, np.int64)
        self.chunks: dict[int, SynopsisChunk] = {}
        self.origin_schedule: Optional[np.ndarray] = None
        self.columns_cached: frozenset = frozenset(range(num_cols))
        self.rebuilds = 0

    # ------------------------------------------------------------ queries --
    def supports(self, queries: Sequence[Query]) -> bool:
        """A query sequence can reuse the synopsis iff its column support is
        cached (Section 6: otherwise a full rebuild is triggered)."""
        need = set()
        for q in queries:
            need |= set(q.columns_used)
        if -1 in need:  # unknown support (Custom expression) -> all columns
            need = set(range(self.num_cols))
        return need <= set(self.columns_cached)

    @property
    def total_tuples(self) -> int:
        return sum(c.count for c in self.chunks.values())

    @property
    def coverage(self) -> float:
        return len(self.chunks) / max(self.n_chunks, 1)

    # -------------------------------------------------------------- build --
    def update_from_engine(self, state, schedule: np.ndarray,
                           query_variances: np.ndarray) -> None:
        """Absorb an engine run's extraction cache (Section 6.1/6.2).

        ``query_variances`` is the per-chunk within-variance proxy for the
        *current* (origin) query — the allocation driver.  Chunks are visited
        in schedule order (= extraction order); windows merge with any
        existing window for the same chunk (engine cursors continued from the
        synopsis window end, so cached rows align with window ordinals).

        ``state`` may come from a frozen-query engine or the slot-table
        engine — extraction counts are read from the scan-level ``scan_m``
        (identical to ``stats.m`` in frozen mode, shared across slots in
        slot mode).
        """
        cache = np.asarray(state.cache)          # (N, cap, C)
        m = np.asarray(state.scan_m)             # (N,) scan-level
        cached_m = np.asarray(state.cached_m)
        offset = np.asarray(state.offset)
        cap = cache.shape[1]
        if self.origin_schedule is None:
            self.origin_schedule = np.asarray(schedule).copy()

        for j in np.asarray(schedule):
            j = int(j)
            mj = int(m[j])
            if mj <= 0:
                continue
            have = self.chunks.get(j)
            rows = min(mj, cap)
            vals = cache[j, :rows]
            if have is not None and int(cached_m[j]) > 0:
                # engine was seeded from this window; cache rows [0, cached_m)
                # duplicate it only if the engine re-wrote them (it does not),
                # so splice: existing window + newly extracted tail.
                new_rows = cache[j, int(cached_m[j]):rows]
                vals = np.concatenate([have.values, new_rows], axis=0)
                start = have.start
            else:
                start = int(offset[j]) - mj if int(offset[j]) >= mj else 0
            self.chunks[j] = SynopsisChunk(start=start, values=np.asarray(vals))

        self._fit_budget(query_variances)

    def _fit_budget(self, variances: np.ndarray) -> None:
        """Variance-proportional allocation + keep-the-tail shrinking."""
        if self.total_tuples <= self.budget:
            return
        js = sorted(self.chunks.keys())
        v = np.maximum(np.asarray([variances[j] for j in js], np.float64), 1e-12)
        alloc = np.floor(self.budget * v / v.sum()).astype(np.int64)
        alloc = np.maximum(alloc, 1)  # every admitted chunk keeps >= 1 tuple
        # trim overshoot from the largest allocations
        while alloc.sum() > self.budget:
            k = int(np.argmax(alloc))
            alloc[k] -= 1
        for idx, j in enumerate(js):
            ch = self.chunks[j]
            keep = int(min(alloc[idx], ch.count))
            if keep < ch.count:
                drop = ch.count - keep
                # drop the *front* of the random permutation (paper Fig. 6)
                self.chunks[j] = SynopsisChunk(
                    start=(ch.start + drop) % max(int(self.chunk_sizes[j]), 1),
                    values=ch.values[drop:])

    # ---------------------------------------------------------- estimation --
    def within_variances(self, state) -> np.ndarray:
        """Per-chunk within-variance proxy from engine stats (allocation key).

        Frozen mode keys the allocation on the origin (first) query, as
        before.  In slot mode ``stats.m`` is per-slot ``(S, N)``; the
        allocation driver is the worst case (max) across slots, so the
        budget favors chunks that are high-variance for *any* live query.
        """
        m = np.asarray(state.stats.m, np.float64)
        ys = np.asarray(state.stats.ysum).astype(np.float64)
        yq = np.asarray(state.stats.ysq).astype(np.float64)
        if m.ndim == 1:
            ys, yq = ys[0], yq[0]
            ss = yq - np.where(m > 0, ys * ys / np.maximum(m, 1.0), 0.0)
            return np.maximum(ss / np.maximum(m - 1.0, 1.0), 0.0)
        ss = yq - np.where(m > 0, ys * ys / np.maximum(m, 1.0), 0.0)
        v = np.maximum(ss / np.maximum(m - 1.0, 1.0), 0.0)
        return v.max(axis=0)

    def _window_stats(self, queries: Sequence[Query]):
        """Per-chunk sufficient statistics of ``queries`` over every cached
        window (Section 6.3), in one pass: the windows are stacked into one
        ``(T, C)`` float32 array, evaluated once, and summed by chunk id in
        float64.  Returns ``(m (N,) int32, ysum, ysq, psum (Q, N) float32)``.
        """
        n = self.n_chunks
        ids = np.fromiter(self.chunks, np.int64, len(self.chunks))
        windows = [ch.values for ch in self.chunks.values()]
        seg = np.repeat(ids, [len(v) for v in windows])
        cols = (np.concatenate(windows) if windows
                else np.zeros((0, self.num_cols)))
        x, p = _evaluate(queries, cols.astype(np.float32, copy=False),
                         self.num_cols)
        x = x.astype(np.float64)

        def by_chunk(w):                      # (Q, T) -> (Q, N)
            return np.stack([np.bincount(seg, wq, n) for wq in w]
                            ).astype(np.float32)

        return (np.bincount(seg, minlength=n).astype(np.int32),
                by_chunk(x), by_chunk(x * x), by_chunk(p))

    def seed(self, queries: Sequence[Query], cache_cap: int) -> dict:
        """Engine seed for a follow-up query (Section 6.3): evaluate the new
        queries over the cached tuples and pre-fill stats + cursors."""
        n = self.n_chunks
        m, ysum, ysq, psum = self._window_stats(queries)
        offset = np.zeros(n, np.int32)
        cache = np.zeros((n, cache_cap, self.num_cols), np.float32)
        for j, ch in self.chunks.items():
            if ch.count == 0:
                continue
            offset[j] = ch.start + ch.count   # cursor continues past the window
            rows = min(ch.count, cache_cap)
            cache[j, :rows] = ch.values[:rows]
        return dict(m=m, ysum=ysum, ysq=ysq, psum=psum, offset=offset,
                    cache=cache)

    def seed_slot(self, query: Query) -> Optional[dict]:
        """Per-slot sufficient-statistics rows for one mid-scan admission.

        Evaluates ``query`` over every cached window and returns
        ``dict(m (N,), ysum (N,), ysq (N,), psum (N,))`` — the slot's seed
        sample over the already-started chunk set.  Returns ``None`` when the
        synopsis is empty or cannot serve the query's column support (the
        slot then starts cold and only accumulates from future rounds).

        The window/cursor alignment argument from the module docstring makes
        the seeded sample and the scan's future extraction disjoint, so the
        engine can simply keep adding round deltas on top of these rows.
        """
        if not self.chunks or not self.supports([query]):
            return None
        m, ysum, ysq, psum = self._window_stats([query])
        return dict(m=m, ysum=ysum[0], ysq=ysq[0], psum=psum[0])

    def plan_schedule(self, base_schedule: np.ndarray,
                      by_variance: Optional[np.ndarray] = None) -> np.ndarray:
        """Chunk order for a follow-up query (Section 6.3).

        If some chunks are missing from the synopsis, they go *first* in their
        original order (new chunks have "infinite variance"); cached chunks
        follow, also in original order.  If everything is cached, the synopsis
        is a stratified sample and the order may be optimized to decreasing
        chunk variance (pass ``by_variance``).
        """
        base = np.asarray(base_schedule)
        cached = np.asarray([j in self.chunks for j in base])
        if not cached.all():
            return np.concatenate([base[~cached], base[cached]]).astype(np.int32)
        if by_variance is not None:
            order = np.argsort(-by_variance[base], kind="stable")
            return base[order].astype(np.int32)
        return base.astype(np.int32)

    def rebuild(self) -> None:
        """Full reset (Section 6: a query the synopsis cannot serve triggers
        an automatic rebuild)."""
        self.chunks.clear()
        self.origin_schedule = None
        self.rebuilds += 1

    def drop_chunks(self, chunk_ids) -> int:
        """Forget windows over quarantined chunks: a lost/corrupt chunk is
        out of the surviving population, so its cached tuples must stop
        seeding estimates.  Returns the number of windows dropped."""
        n = 0
        for j in chunk_ids:
            if self.chunks.pop(int(j), None) is not None:
                n += 1
        return n
