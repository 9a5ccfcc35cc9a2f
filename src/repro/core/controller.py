"""Estimation controller — the paper's δ-interval reporting loop (Section 7.1
"implementation") plus the query-sequence / verification workflows.

The controller owns: the modeled wall clock (Eq. 4 — READ and EXTRACT are
overlapped, so a round costs ``max(t_io, t_cpu)``), the δ-interval estimate
reports, the HAVING-sequence early-outs (the PTF workflow of Section 1), and
the synopsis life-cycle across a query sequence (build → reuse → top-up →
rebuild).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core.engine import EngineConfig, OLAEngine
from repro.core.queries import Query
from repro.core.synopsis import BiLevelSynopsis


@dataclasses.dataclass
class EstimateReport:
    """One user-visible estimate row (emitted every δ of modeled time)."""

    t_model: float            # modeled seconds since query start (Eq. 4 clock)
    t_wall: float             # measured wall seconds (CPU host, for reference)
    estimate: np.ndarray      # (Q,)
    lo: np.ndarray
    hi: np.ndarray
    err: np.ndarray           # (Q,) error ratio
    n_chunks: int
    m_tuples: int
    io_busy: float            # utilization trace for the Fig. 14 analogue
    cpu_busy: float


@dataclasses.dataclass
class QueryResult:
    reports: list[EstimateReport]
    final_estimate: np.ndarray
    final_err: np.ndarray
    decisions: np.ndarray     # (Q,) int8 HAVING verdicts
    stopped: np.ndarray       # (Q,) bool
    rounds: int
    t_model_total: float
    t_wall_total: float
    tuples_ratio: float       # fraction of the table's tuples extracted
    chunks_ratio: float       # fraction of chunks read from raw
    from_synopsis: bool = False


class EstimationController:
    """Drives an engine round loop with δ-interval reporting and synopsis reuse."""

    def __init__(self, store, config: EngineConfig, delta_model_s: float = 1.0,
                 synopsis_budget_tuples: int = 0, engine_cls=OLAEngine,
                 engine_kwargs: Optional[dict] = None):
        self.store = store
        self.config = config
        self.delta = float(delta_model_s)
        self.engine_cls = engine_cls
        self.engine_kwargs = engine_kwargs or {}
        self.synopsis: Optional[BiLevelSynopsis] = None
        if synopsis_budget_tuples > 0:
            self.synopsis = BiLevelSynopsis(
                n_chunks=store.num_chunks, num_cols=store.codec.num_cols,
                budget_tuples=synopsis_budget_tuples,
                chunk_sizes=store.chunk_sizes)

    # ----------------------------------------------------------------- run --
    def run_query(self, queries: Sequence[Query], max_rounds: int = 200_000,
                  wall_timeout_s: float = 600.0) -> QueryResult:
        queries = list(queries)
        cfg = self.config
        use_syn = (self.synopsis is not None and len(self.synopsis.chunks) > 0
                   and self.synopsis.supports(queries))
        if self.synopsis is not None and not use_syn and len(self.synopsis.chunks) > 0:
            # unservable query -> automatic rebuild (Section 6)
            self.synopsis.rebuild()

        cache_cap = cfg.cache_cap
        if self.synopsis is not None and cache_cap == 0:
            # need the extraction cache to build/maintain the synopsis
            cache_cap = max(64, int(np.ceil(
                4 * self.synopsis.budget / max(self.store.num_chunks, 1))))
            cfg = dataclasses.replace(cfg, cache_cap=cache_cap)

        schedule = None
        seed = None
        if use_syn:
            from repro.sampling.permutation import random_chunk_order

            base = random_chunk_order(cfg.seed, self.store.num_chunks)
            if self.synopsis.origin_schedule is not None:
                base = self.synopsis.origin_schedule
            schedule = self.synopsis.plan_schedule(base)
            seed = self.synopsis.seed(queries, cache_cap)

        engine = self.engine_cls(self.store, queries, cfg, schedule=schedule,
                                 **self.engine_kwargs)
        state = engine.init_state(seed)

        if seed is not None:
            zero = self._try_answer_from_seed(engine, queries, seed)
            if zero is not None:
                if self.synopsis is not None:
                    # refresh variances for subsequent allocation decisions
                    pass
                engine.close()
                return zero

        reports: list[EstimateReport] = []
        t_model = 0.0
        next_report = 0.0
        io_busy_acc = cpu_busy_acc = 0.0
        t0 = time.perf_counter()
        rounds = 0
        last = None
        for _ in range(max_rounds):
            b = engine.budget_ladder(float(state.budget))
            state, data = engine.round_data(state)
            mode, data = engine.data_mode(data)
            state, rep = engine.round_fn(b, mode)(state, data, engine.speeds)
            rounds += 1
            io_s = float(rep.round_io_s)
            cpu_s = float(rep.round_cpu_s)
            # Eq. 4 overlapped-pipeline clock
            t_model = max(float(state.t_io), float(state.t_cpu))
            step_t = max(io_s, cpu_s)
            if step_t > 0:
                io_busy_acc += io_s
                cpu_busy_acc += cpu_s
            last = rep
            if t_model >= next_report or bool(rep.all_stopped) or bool(rep.exhausted):
                reports.append(EstimateReport(
                    t_model=t_model, t_wall=time.perf_counter() - t0,
                    estimate=np.asarray(rep.estimate), lo=np.asarray(rep.lo),
                    hi=np.asarray(rep.hi), err=np.asarray(rep.err),
                    n_chunks=int(rep.n_chunks), m_tuples=int(rep.m_tuples),
                    io_busy=io_s / max(step_t, 1e-12),
                    cpu_busy=cpu_s / max(step_t, 1e-12)))
                next_report = t_model + self.delta
            if bool(rep.all_stopped) or bool(rep.exhausted):
                break
            if time.perf_counter() - t0 > wall_timeout_s:
                break

        # synopsis maintenance from this run's extraction cache
        if self.synopsis is not None:
            variances = self.synopsis.within_variances(state)
            self.synopsis.update_from_engine(
                state, np.asarray(engine.program.schedule), variances)

        # one engine per query: release its prefetcher (stream residency)
        engine.close()

        chunks_raw = int(np.asarray(state.raw_touched).sum())
        return QueryResult(
            reports=reports,
            final_estimate=np.asarray(last.estimate),
            final_err=np.asarray(last.err),
            decisions=np.asarray(last.decided),
            stopped=np.asarray(state.stopped),
            rounds=rounds,
            t_model_total=t_model,
            t_wall_total=time.perf_counter() - t0,
            tuples_ratio=float(int(last.m_tuples) / max(engine.program.total_tuples, 1)),
            chunks_ratio=chunks_raw / max(engine.program.n_chunks, 1),
            from_synopsis=use_syn,
        )

    def _try_answer_from_seed(self, engine, queries, seed):
        """Section 6.3 best case: the query is answered exclusively from the
        memory-resident synopsis — zero raw access, zero modeled time."""
        import numpy as np

        from repro.core import estimators as E

        est_v, lo, hi, err = _answer_from_stats(
            queries, engine.init_state(seed).stats)
        import jax.numpy as jnp

        decided = np.full(len(queries), -1, np.int8)
        stop = np.asarray(err) <= np.asarray([q.epsilon for q in queries])
        for qi, q in enumerate(queries):
            if q.having is not None:
                d = int(E.having_decision(lo[qi], hi[qi], q.having.op,
                                          q.having.threshold))
                decided[qi] = d
                stop[qi] |= d != -1
        if not stop.all():
            return None
        return QueryResult(
            reports=[EstimateReport(
                t_model=0.0, t_wall=0.0, estimate=np.asarray(est_v),
                lo=np.asarray(lo), hi=np.asarray(hi), err=np.asarray(err),
                n_chunks=int(np.sum(np.asarray(seed["m"]) > 0)),
                m_tuples=int(np.sum(seed["m"])), io_busy=0.0, cpu_busy=0.0)],
            final_estimate=np.asarray(est_v), final_err=np.asarray(err),
            decisions=decided, stopped=stop, rounds=0, t_model_total=0.0,
            t_wall_total=0.0,
            tuples_ratio=float(np.sum(seed["m"]) / max(self.store.num_tuples, 1)),
            chunks_ratio=0.0, from_synopsis=True)

    # -------------------------------------------------- verification chain --
    def run_verification(self, queries: Sequence[Query],
                         max_rounds: int = 200_000) -> list[QueryResult]:
        """The PTF workflow (Section 1): execute HAVING queries in sequence;
        a query runs only if every previous one passed.  Each query reuses
        (and refreshes) the synopsis."""
        results = []
        for q in queries:
            assert q.having is not None, "verification queries need HAVING"
            res = self.run_query([q], max_rounds=max_rounds)
            results.append(res)
            verdict = int(res.decisions[0])
            passed = verdict == 1 or (verdict == -1 and _having_exact_pass(q, res))
            if not passed:
                break  # batch rejected: skip the rest (the whole point of OLA)
        return results


def _answer_from_stats(queries, stats):
    import jax.numpy as jnp

    from repro.core import estimators as E

    sum_t, sum_v, cnt_t, cnt_v, avg_t, avg_v = E.bilevel_estimates(
        stats, aggs={q.agg for q in queries})
    pick = {"sum": (sum_t, sum_v), "count": (cnt_t, cnt_v),
            "avg": (avg_t, avg_v)}
    est_v = jnp.stack([pick[q.agg][0][qi] for qi, q in enumerate(queries)])
    var_v = jnp.stack([pick[q.agg][1][qi] for qi, q in enumerate(queries)])
    lo, hi = E.confidence_bounds(est_v, var_v, queries[0].confidence)
    err = E.error_ratio(est_v, lo, hi)
    return est_v, lo, hi, err


def _having_exact_pass(q: Query, res: QueryResult) -> bool:
    """If the engine exhausted the data the estimate is exact — decide directly."""
    est = float(res.final_estimate[0])
    t = q.having.threshold
    return {"<": est < t, "<=": est <= t, ">": est > t, ">=": est >= t}[q.having.op]
