"""Shared helpers for the benchmark modules."""

from __future__ import annotations

import numpy as np

from repro.core.engine import EngineConfig, OLAEngine
from repro.core.queries import Linear, Query, Range, TRUE
from repro.data.generator import (
    make_ptf_like, make_synthetic_zipf, make_wiki_like, store_dataset,
)

SYN_COEF16 = tuple(1.0 / (k + 1) for k in range(16))
PTF_COEF = (0.0, 0.0, 0.0, 1.0, 2.0, 1.5, 0.0, 0.0)  # mag/err/flux expression


def bench_output_paths(name: str) -> tuple:
    """Result-file path(s) anchored to the repo root, not the process CWD —
    the server's ``default_rates_path`` reads from the same anchor, so the
    calibration round-trips no matter where either process was started.
    ``BENCH_<name>.json`` at the root is the single canonical artifact (the
    committed baseline the CI gate diffs against); the old
    ``results/bench_<name>.json`` mirror is gone — it was gitignored, went
    stale the moment a lane ran from another CWD, and nothing read it."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return (os.path.join(root, f"BENCH_{name}.json"),)


def runner_fingerprint() -> dict:
    """Identity of the machine/toolchain a benchmark ran on — written into
    every BENCH_*.json so the regression gate (``scripts/
    check_bench_regression.py``) can tell whether a committed baseline came
    from a comparable runner.  Machine-dependent checks (RSS) are skipped on
    mismatch instead of failing spuriously; the Eq. (4) modeled-clock
    metrics are machine-independent and stay gated regardless."""
    import os
    import platform

    cpu_model = None
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.lower().startswith("model name"):
                    cpu_model = line.split(":", 1)[1].strip()
                    break
    except OSError:
        pass
    if not cpu_model:
        cpu_model = platform.processor() or platform.machine()
    import jax

    return {
        "cpu_model": cpu_model,
        "cpu_count": os.cpu_count(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "platform": platform.platform(),
    }


def memory_report() -> dict:
    """Peak host RSS + resident device bytes for BENCH_*.json outputs.

    ``device_raw_bytes`` counts only uint8 arrays — the packed views / slabs
    whose footprint the streaming residency bounds; ``device_total_bytes``
    adds the f32 state pytrees."""
    from repro.data.pipeline import device_resident_bytes, peak_host_rss_bytes

    return {
        "peak_host_rss_bytes": peak_host_rss_bytes(),
        "device_raw_bytes": device_resident_bytes(np.uint8),
        "device_total_bytes": device_resident_bytes(),
    }


def latency_stats(results) -> dict:
    """Latency percentiles + SLO-hit rate for BENCH_*.json outputs.

    ``results`` are :class:`~repro.serve.ola_server.WorkloadResult`\\ s.
    ``slo_hit_rate`` averages over the queries that carried an SLO
    (``slo_met is not None``); it is ``None`` when none did.  Outcome counts
    split scan-served answers from queued/shed ones.
    """
    lat = np.asarray([r.latency_model_s for r in results], float)
    out = {
        "p50_latency_s": float(np.percentile(lat, 50)) if len(lat) else None,
        "p95_latency_s": float(np.percentile(lat, 95)) if len(lat) else None,
        "p99_latency_s": float(np.percentile(lat, 99)) if len(lat) else None,
        "mean_latency_s": float(lat.mean()) if len(lat) else None,
        "mean_queue_wait_s": float(np.mean(
            [r.queue_wait_model_s for r in results])) if results else None,
        "outcomes": {
            k: sum(r.sched_outcome == k for r in results)
            for k in ("admitted", "queued", "preempted", "shed", "tier1")},
    }
    hits = [r.slo_met for r in results if r.slo_met is not None]
    out["slo_hit_rate"] = float(np.mean(hits)) if hits else None
    return out


def latency_stats_by_class(results) -> dict:
    """Per-priority-class latency percentiles + SLO-hit rate.

    Groups :class:`~repro.serve.ola_server.WorkloadResult`\\ s by their
    ``priority`` field (the SLO class) — the per-class p99-vs-offered-load
    curves in ``bench_workload``'s full lane are built from this.  Classes
    with no queries are simply absent.
    """
    by: dict = {}
    for r in results:
        by.setdefault(r.priority, []).append(r)
    return {cls: latency_stats(rs) for cls, rs in sorted(by.items())}


def trace_summary(tracer) -> dict:
    """Compact per-span-name summary of a :class:`~repro.obs.trace.
    SpanTracer` buffer for BENCH_*.json artifacts: event/drop counts,
    per-name span counts with total seconds, and any chrome-trace schema
    problems the validator found (empty list = valid)."""
    from repro.obs.trace import validate_chrome_trace

    doc = tracer.to_chrome_trace()
    spans: dict = {}
    for ev in doc["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        d = spans.setdefault(ev["name"], {"count": 0, "total_s": 0.0})
        d["count"] += 1
        d["total_s"] += float(ev.get("dur", 0.0)) / 1e6
    return {
        "events": len(doc["traceEvents"]),
        "dropped": int(getattr(tracer, "dropped", 0)),
        "spans": spans,
        "schema_problems": validate_chrome_trace(doc),
    }


def datasets(fast: bool):
    t = 8192 if fast else 16384
    chunks = 32 if fast else 64
    out = {
        "synthetic": store_dataset(make_synthetic_zipf(t, 16, 0), chunks,
                                   "ascii"),
        "ptf-ascii": store_dataset(make_ptf_like(t, chunks, 0), chunks,
                                   "ascii"),
        "ptf-binary": store_dataset(make_ptf_like(t, chunks, 0), chunks,
                                    "binary"),
    }
    w, _ = make_wiki_like(t, 30, 0)
    out["wiki"] = store_dataset(w, max(chunks // 3, 8), "ascii")
    return out


def selectivity_query(dataset: str, selectivity: float,
                      epsilon: float = 0.05) -> Query:
    if dataset.startswith("ptf"):
        # range predicate on ra (col 0) covering x% of [0, 360)
        return Query(agg="sum", expr=Linear(PTF_COEF),
                     pred=Range(0, 0.0, 360.0 * selectivity) if selectivity < 1
                     else TRUE, epsilon=epsilon)
    if dataset == "wiki":
        # per-language count: language 0 is 'en'
        return Query(agg="count", pred=Range(0, -0.5, 0.5), epsilon=epsilon)
    return Query(agg="sum", expr=Linear(SYN_COEF16),
                 pred=Range(0, 0.0, 1e8 * selectivity) if selectivity < 1
                 else TRUE, epsilon=epsilon)


def run_curve(store, query: Query, strategy: str, workers: int,
              seed: int = 0, max_rounds: int = 20000):
    """-> (times, errs, final) with the Eq. 4 modeled clock."""
    eng = OLAEngine(store, [query],
                    EngineConfig(num_workers=workers, strategy=strategy,
                                 budget_init=64, seed=seed))
    state = eng.init_state()
    times, errs = [], []
    rep = None
    for _ in range(max_rounds):
        b = eng.budget_ladder(float(state.budget))
        state, rep = eng.round_fn(b)(state, eng.packed, eng.speeds)
        # Eq. 4: READ and EXTRACT are overlapped pipelines — wall time is
        # the max of the cumulative busy times, not a per-round barrier
        times.append(max(float(state.t_io), float(state.t_cpu)))
        errs.append(float(rep.err[0]))
        if bool(rep.all_stopped) or bool(rep.exhausted):
            break
    t = times[-1] if times else 0.0
    return np.asarray(times), np.asarray(errs), {
        "t_model": t,
        "tuples_ratio": float(int(rep.m_tuples) / eng.program.total_tuples),
        "chunks_ratio": float(np.asarray(state.raw_touched).sum()
                              / eng.program.n_chunks),
        "estimate": float(rep.estimate[0]),
        "stopped": bool(rep.all_stopped),
    }


def ext_baseline_time(store, workers: int,
                      io_bps: float = 565e6, cpu_ops: float = 2.0e9) -> float:
    """External tables: exact answer = one full sequential scan (Eq. 4)."""
    total_bytes = float(store.chunk_sizes.sum()) * store.codec.record_bytes
    total_tuples = float(store.num_tuples)
    t_io = total_bytes / io_bps
    t_cpu = total_tuples * store.codec.extract_cost_per_tuple() / cpu_ops / workers
    return max(t_io, t_cpu)
