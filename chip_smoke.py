"""Chip smoke test: the OLA workload server end to end on one TPU.

    python chip_smoke.py [--seed N]             # one chip: serve + parity
    python chip_smoke.py --chips 4 [--seed N]   # only the SPMD slot path

Builds a 2**23-tuple, 16-column Zipf table from ``--seed`` and stores it as
fixed-width ASCII in 512 chunks of 16384 tuples: 2 GiB of raw bytes, resident
on the device under packed residency.  Phases:

* ``serve`` — SUM, COUNT, AVG, HAVING and grouped top-K queries through
  :class:`OLAWorkloadServer` on the compiled fused kernel
  (``extract_backend="pallas"``), two of them submitted after the first
  round.  Every
  answer must lie within 3·ε·|exact| of the exact full-scan answer, computed
  in float64 with numpy; the grouped query must find the exact top-K values.
* ``parity`` — one slot table for a fixed number of rounds on ``"pallas"``
  and on ``"ref"``: identical chunk hand-out and sample sizes, sums equal to
  fp32 tolerance.
* ``spmd`` (``--chips 4`` only) — the serve workload on a 4-device data mesh
  against a single-device server in the same process: identical hand-out
  and sample sizes, estimates equal to fp32 tolerance.

It exits non-zero when JAX finds no TPU or any check fails.  The last line
of standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402

from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.engine import EngineConfig, SlotOLAEngine  # noqa: E402
from repro.core.queries import (  # noqa: E402
    GroupBy,
    Having,
    Linear,
    Query,
    Range,
    empty_slot_table,
    encode_slot,
    slot_table_set,
)
from repro.data.generator import make_synthetic_zipf, store_dataset  # noqa: E402
from repro.serve.ola_server import OLAWorkloadServer, ServerOptions  # noqa: E402

TUPLES = 2 ** 23
CHUNKS = 512
COLS = 16
WORKERS = 8
SLOTS = 8
BUDGET_MAX = 4096        # the largest budget rung: idx is (W, 4096) in SMEM
EPS = 0.05
BOUND = 3.0              # answers lie within BOUND·ε·|exact|
GROUP_COL = 15           # Zipf s = 3.75, the most skewed column
MAX_GROUPS = 8
TOP_K = 4
COEF = tuple(1.0 / (k + 1) for k in range(COLS))


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def unit(col: int) -> Linear:
    return Linear(tuple(1.0 if k == col else 0.0 for k in range(COLS)))


# ---------------------------------------------------------------- data ----

def make_table(seed: int, tuples: int, chunks: int):
    """-> (values (T, C) float64 as the store holds them, ChunkStore)."""
    values = make_synthetic_zipf(tuples, COLS, seed=seed)
    store = store_dataset(values, chunks, fmt="ascii")
    # the codec keeps 6 fraction digits (formats.AsciiFixedFormat.encode)
    ip = np.floor(np.abs(values))
    frac = np.rint((np.abs(values) - ip) * 1e6) / 1e6
    return np.copysign(ip + frac, values), store


def _terms(values: np.ndarray, q: Query):
    """-> (x, p) float64: the expression and the 0/1 predicate per row."""
    if q.agg == "count":
        x = np.ones(len(values))
    else:
        x = values[:, :len(q.expr.coeffs)] @ np.asarray(q.expr.coeffs)
    p = np.ones(len(values), bool)
    if isinstance(q.pred, Range):
        col = values[:, q.pred.col]
        p = (col >= q.pred.lo) & (col < q.pred.hi)
    return x, p


def _aggregate(agg: str, xs: np.ndarray, ps: np.ndarray) -> float:
    s = float(np.sum(xs * ps))
    return s / float(np.sum(ps)) if agg == "avg" else s


def exact_answer(values: np.ndarray, q: Query) -> float:
    """The exact full-scan answer of an ungrouped query, in float64."""
    return _aggregate(q.agg, *_terms(values, q))


def exact_groups(values: np.ndarray, q: Query):
    """-> (keys, answers): the exact per-group answers, in float64."""
    x, p = _terms(values, q)
    keys, inv = np.unique(values[:, q.group_by.col], return_inverse=True)
    s = np.bincount(inv, weights=x * p, minlength=len(keys))
    if q.agg == "avg":
        s = s / np.maximum(np.bincount(inv, weights=p, minlength=len(keys)), 1)
    return keys, s


def base_queries() -> list[Query]:
    """The ungrouped queries that need no knowledge of the data."""
    return [
        Query(agg="sum", expr=Linear(COEF), pred=Range(0, 0.0, 5e7),
              epsilon=EPS, name="sum_range"),
        Query(agg="count", pred=Range(2, 0.0, 1e6), epsilon=EPS,
              name="count_range"),
        Query(agg="avg", expr=Linear(COEF), epsilon=EPS, name="avg_all"),
    ]


def workload(values: np.ndarray) -> list[tuple[Query, bool]]:
    """-> [(query, mid_scan)]: the serve phase's queries."""
    sum3 = Query(agg="sum", expr=unit(3), epsilon=EPS)
    # a verdict needs the CI to clear a threshold 2ε above the answer
    having = Having("<", exact_answer(values, sum3) * (1.0 + 2.0 * EPS))
    sum_range, count_range, avg_all = base_queries()
    return [
        (sum_range, False),
        (count_range, False),
        (Query(agg="sum", expr=unit(3), having=having, epsilon=EPS,
               name="having_sum"), False),
        (avg_all, True),
        (Query(agg="sum", expr=unit(1), epsilon=EPS, name="topk_groups",
               group_by=GroupBy(col=GROUP_COL, max_groups=MAX_GROUPS,
                                top_k=TOP_K)), True),
    ]


# --------------------------------------------------------------- checks ---

def _tolerance(err: float, met: bool, strict: bool, what: str) -> float:
    """The relative bound on an answer: BOUND·ε.  Strict, the answer must
    also have met its ε (``met``).  Otherwise (a table so small that the
    scan can end first) BOUND times its own relative CI half-width ``err``
    where that is wider."""
    if strict:
        check(met, f"{what}: retired at err {err} without meeting ε={EPS}")
        return BOUND * EPS
    return BOUND * max(EPS, err)


def check_answer(values: np.ndarray, q: Query, r, strict: bool = True
                 ) -> None:
    exact = exact_answer(values, q)
    rel = abs(r.estimate - exact) / abs(exact)
    say(f"  {q.name}: estimate={r.estimate!r} exact={exact!r} "
        f"rel_err={rel!r} err={r.err!r} rounds={r.rounds_resident} "
        f"tuples={r.tuples_seen} decision={r.decision}")
    decided = q.having is not None and r.decision != -1
    tol = _tolerance(r.err, r.err <= EPS or decided, strict, q.name)
    check(rel <= tol, f"{q.name}: estimate {r.estimate} is {rel} off "
          f"{exact}, bound {tol}")
    if decided:
        t = q.having.threshold
        truth = {"<": exact < t, "<=": exact <= t,
                 ">": exact > t, ">=": exact >= t}[q.having.op]
        check(r.decision == int(truth),
              f"{q.name}: HAVING verdict {r.decision}, exact {truth}")


def check_groups(values: np.ndarray, q: Query, r, strict: bool = True
                 ) -> None:
    keys, answers = exact_groups(values, q)
    k = q.group_by.top_k
    top = keys[np.argsort(-np.abs(answers), kind="stable")[:k]]
    cells = sorted((g for g in r.groups if not g.is_other),
                   key=lambda g: -abs(g.estimate))[:k]
    say(f"  {q.name}: {len(keys)} distinct values in column "
        f"{q.group_by.col}, exact top-{k} {top.tolist()}, "
        f"rounds={r.rounds_resident}")
    check(len(cells) == k, f"{q.name}: {len(cells)} tracked cells < top-{k}")
    for g in cells:
        i = int(np.argmin(np.abs(keys - g.value)))
        rel = abs(g.estimate - answers[i]) / abs(answers[i])
        say(f"    group {g.value!r}: estimate={g.estimate!r} "
            f"exact={answers[i]!r} rel_err={rel!r} err={g.err!r} n={g.n}")
        check(np.isclose(keys[i], g.value, rtol=1e-6, atol=1e-3),
              f"{q.name}: cell value {g.value} is no value of the column")
        check(keys[i] in top, f"{q.name}: cell {keys[i]} is not in the top-{k}")
        tol = _tolerance(g.err, g.err <= EPS, strict, f"{q.name}[{keys[i]}]")
        check(rel <= tol, f"{q.name}: group {keys[i]} estimate {g.estimate} "
              f"is {rel} off {answers[i]}, bound {tol}")


# --------------------------------------------------------------- phases ---

def serve_phase(store, values, backend: str = "pallas", mesh=None,
                seed: int = 0, budget: int = BUDGET_MAX,
                strict: bool = True) -> dict:
    """Serve the workload; check every answer against the exact one (see
    :func:`_tolerance` for ``strict``).

    Returns the per-round chunk hand-out (each worker's schedule position),
    the final per-slot sample sizes and the results.
    """
    # every round at one rung: single_pass keeps t_eval fixed (the
    # resource-aware plan's modeled clock shrinks it to budget_min on ASCII)
    cfg = EngineConfig(num_workers=WORKERS, seed=seed, budget_init=budget,
                       budget_max=budget, extract_backend=backend,
                       residency="packed", max_groups=MAX_GROUPS)
    handout = []
    with OLAWorkloadServer(store, cfg, options=ServerOptions(
            max_slots=SLOTS, mesh=mesh)) as srv:
        prog = srv.engine.program
        say(f"  engine={type(srv.engine).__name__} "
            f"extract_backend={prog.extract_backend} "
            f"interpret={prog.extract_backend == 'pallas-interpret'} "
            f"raw_bytes_resident={srv.engine.packed.nbytes}")
        # "pallas" resolves to the compiled kernel or raises: never the
        # interpreter, never the ref path
        check(prog.extract_pallas and prog.extract_backend == backend,
              f"backend {backend!r} resolved to {prog.extract_backend!r}")
        queries = workload(values)
        for q, mid in queries:
            if not mid:
                srv.submit(q, arrival_t=0.0, plan="single_pass")
        srv.step()
        handout.append(np.asarray(srv.state.cur))
        check(srv.rounds == 1, "no round ran before the mid-scan submissions")
        say("  mid-scan submissions after round 1")
        for q, mid in queries:
            if mid:
                srv.submit(q, plan="single_pass")
        results = srv.run(wall_timeout_s=900.0, on_round=lambda s: (
            handout.append(np.asarray(s.state.cur))))
        check(not srv.truncated, "the server run was cut short")
        rounds = srv.rounds
        m = np.asarray(srv.state.stats.m)
    check(len(results) == len(queries),
          f"{len(results)} answers for {len(queries)} queries")
    say(f"  {rounds} rounds")
    for (q, _), r in zip(queries, results):
        check(r.name == q.name, f"answer {r.name} out of order")
        if q.group_by is None:
            check_answer(values, q, r, strict)
        else:
            check_groups(values, q, r, strict)
    return {"handout": np.stack(handout), "m": m, "results": results}


def parity_phase(store, backend: str = "pallas", rounds: int = 6,
                 budget: int = BUDGET_MAX, seed: int = 0) -> dict:
    """One slot table for ``rounds`` rounds on ``backend`` and on ``"ref"``,
    with a mid-scan admission: identical hand-out and sample sizes, sums and
    estimates equal to within fp32 rounding of ``budget``-row sums."""
    queries = base_queries()
    cols = store.codec.num_cols
    runs = {}
    for be in (backend, "ref"):
        cfg = EngineConfig(num_workers=WORKERS, strategy="single_pass",
                           seed=seed, budget_init=budget, budget_min=budget,
                           budget_max=budget, extract_backend=be)
        eng = SlotOLAEngine(store, len(queries), cfg)
        table = empty_slot_table(len(queries), cols)
        for i, q in enumerate(queries[:-1]):
            table = slot_table_set(table, i, encode_slot(
                q, cols, plan="single_pass"))
        state = eng.init_state()
        curs, ests = [], []
        for r in range(rounds):
            if r == rounds // 2:
                table = slot_table_set(table, len(queries) - 1, encode_slot(
                    queries[-1], cols, plan="single_pass"))
            b = eng.budget_ladder(float(state.budget))
            state, data = eng.round_data(state)
            state, rep = eng.round_fn(b)(state, table, data, eng.speeds)
            curs.append(np.asarray(state.cur))
            ests.append(np.asarray(rep.estimate))
        runs[be] = {"cur": np.stack(curs), "est": np.stack(ests),
                    **{k: np.asarray(getattr(state.stats, k))
                       for k in ("m", "ysum", "ysq", "psum")}}
        del eng, state, data
        gc.collect()
    a, b = runs[backend], runs["ref"]
    # recursive summation of n same-signed f32 terms errs by at most n·u
    rtol = (budget + rounds) * float(np.finfo(np.float32).eps) / 2
    diffs = {k: float(np.max(np.abs(a[k] - b[k])
                             / np.maximum(np.abs(b[k]), 1e-30)))
             for k in ("ysum", "ysq", "psum", "est")}
    say(f"  {rounds} rounds at budget {budget}: hand-out identical="
        f"{np.array_equal(a['cur'], b['cur'])} m identical="
        f"{np.array_equal(a['m'], b['m'])} max rel diff {diffs} "
        f"(tolerance {rtol!r})")
    check(np.array_equal(a["cur"], b["cur"]), "parity: chunk hand-out differs")
    check(np.array_equal(a["m"], b["m"]), "parity: sample sizes differ")
    for k, d in diffs.items():
        check(d <= rtol, f"parity: {k} differs by {d} > {rtol}")
    return runs


def spmd_phase(store, values, mesh, backend: str = "pallas",
               seed: int = 0, budget: int = BUDGET_MAX,
               strict: bool = True) -> None:
    """The serve workload on ``mesh`` against one device, in one process."""
    say("  single device")
    one = serve_phase(store, values, backend, seed=seed, budget=budget,
                      strict=strict)
    gc.collect()
    say(f"  mesh {dict(mesh.shape)}")
    many = serve_phase(store, values, backend, mesh=mesh, seed=seed,
                       budget=budget, strict=strict)
    rtol = budget * float(np.finfo(np.float32).eps)
    est_diff = max(abs(a.estimate - b.estimate) / abs(a.estimate)
                   for a, b in zip(one["results"], many["results"]))
    say(f"  hand-out identical={np.array_equal(one['handout'], many['handout'])}"
        f" m identical={np.array_equal(one['m'], many['m'])} "
        f"max estimate rel diff {est_diff!r} (tolerance {rtol!r})")
    check(np.array_equal(one["handout"], many["handout"]),
          "spmd: chunk hand-out differs from one device")
    check(np.array_equal(one["m"], many["m"]),
          "spmd: sample sizes differ from one device")
    check(est_diff <= rtol, f"spmd: estimates differ by {est_diff} > {rtol}")


# ------------------------------------------------------------------ main ---

class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (reads of the
    persistent cache included), from its monitoring events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration

    def _event(self, event: str, **kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if name in self.cache:
            self.cache[name] += 1


def timed(name: str, clock: CompileClock, fn, *args, **kw):
    say(f"phase {name}:")
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn(*args, **kw)
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    say(f"phase {name}: ok wall_s={wall!r} compile_s={comp!r} "
        f"serve_s={wall - comp!r}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the SPMD phase on a 4-device mesh")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    clock = CompileClock()
    say(f"device kind={dev.device_kind!r} count={len(devices)} "
        f"compile_cache={cache_dir}")

    values, store = timed("make_table", clock, make_table, args.seed,
                          TUPLES, CHUNKS)
    say(f"table: {store.num_tuples} tuples x {COLS} columns in "
        f"{store.num_chunks} chunks, raw bytes "
        f"{store.num_tuples * store.codec.record_bytes}")
    if args.chips == 4:
        mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
        timed("spmd", clock, spmd_phase, store, values, mesh, seed=args.seed)
    else:
        timed("serve", clock, serve_phase, store, values, seed=args.seed)
        gc.collect()
        timed("parity", clock, parity_phase, store, seed=args.seed)
    stats = dev.memory_stats() or {}
    say(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')} "
        f"compile_s_total={clock.seconds!r} persistent_cache={clock.cache}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
