"""Closed-loop exploration sessions against the OLA workload server.

One run of one cell: generate the configuration's table from its fixed
data seed, build the engine and warm every program the window uses, then
measure a window of closed-loop sessions: each session submits its next
query as soon as its previous one is answered.  The run's seed deals the
mix's streams of queries to the sessions (:class:`perfbench.traffic.Sessions`),
so every seed asks for the same work in another order.  The window drives ``OLAWorkloadServer.submit``
and ``.step`` and nothing else of the program.

A server serves one pass over the table.  When every chunk is fully
extracted the server force-retires what is resident; a query retired without
meeting its stop rule is resubmitted first thing to the next pass, a new
server built on the same engine, and its clock keeps running from its first
submission.  The drain and rebuild at a pass boundary stay in the window.

After the window closes, the queries still open are served on (up to
``drain_s`` seconds past the close) so that each has an answer to check, the
device's peak memory is read, the program's state is freed, and every answer
and a seeded sample of rounds are compared with the plain reference
(:mod:`perfbench.reference`).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from perfbench import reference, tablegen, traffic

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent
ANNOTATIONS = ("bench.window", "bench.submit", "ola.step",
               "bench.collect", "bench.pass_rebuild")


def say(*parts) -> None:
    print(*parts, flush=True)


# ------------------------------------------------------------ the cell ----

@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list
    per_layer: list
    limits: dict


def load_cell(workload: str, root: Path = CHECKOUT) -> Cell:
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / cfg["file"]) as f:
        config = json.load(f)
    mix = traffic.load_mix(w["traffic"], root / "perfbench" / "mixes")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    with open(root / "perfbench" / "limits" / f"{workload}.json") as f:
        limits = json.load(f)
    return Cell(name=workload, config_name=w["config"], config=config,
                mix=mix, chips=int(w["chips"]),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                limits=limits)


# ----------------------------------------------------- compile clock ----

class CompileClock:
    """Seconds and count of JAX compiles, and persistent-cache hits and
    misses, from JAX's monitoring events (after ``chip_smoke.py``)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.names: list[str] = []
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.EVENTS[-1]:
            self.compiles += 1
            self.names.append(str(kw.get("fun_name", "?")))

    def _event(self, event: str, **kw) -> None:
        name = event.rsplit("/", 1)[-1]
        if name in self.cache:
            self.cache[name] += 1


def profile_options():
    """Device activity and the harness's own annotations; no Python
    function tracing, which would slow the host it measures."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


def use_compile_cache() -> str:
    """Persistent compile cache at a fixed path inside the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one.  Every program is cached, the
    small eager ones too, so that only a cell's first run compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------- the program's view ----

def to_query(t: reference.Template, num_cols: int):
    """A template as the program's :class:`repro.core.queries.Query`."""
    from repro.core.queries import TRUE, GroupBy, Having, Linear, Query, Range

    pred = TRUE if t.pred_col < 0 else Range(t.pred_col, t.lo, t.hi)
    return Query(
        agg=t.agg, expr=Linear(tuple(t.coeffs)), pred=pred,
        having=None if t.having is None else Having(*t.having),
        epsilon=t.epsilon, name=f"t{t.tid}",
        group_by=(GroupBy(col=t.group_col, max_groups=t.max_groups,
                          top_k=t.top_k) if t.grouped else None))


def stop_rule_met(t: reference.Template, r) -> bool:
    """Whether a retirement is an answer: the server's own stop rule (ε met,
    or a HAVING verdict), or for a grouped query its top-K cells at ε."""
    if r.unserved or not np.isfinite(r.estimate):
        return False
    if t.grouped:
        cells = top_cells(r, t.top_k)
        return len(cells) == t.top_k and all(g.err <= t.epsilon
                                             for g in cells)
    return r.err <= t.epsilon or (t.having is not None and r.decision != -1)


def top_cells(r, k: int) -> list:
    cells = [g for g in (r.groups or []) if not g.is_other]
    return sorted(cells, key=lambda g: -abs(g.estimate))[:k]


def _snap_pre(state, table):
    st = state.stats
    return dict(offset=state.offset, scan_m=state.scan_m, m=st.m,
                ysum=st.ysum, ysq=st.ysq, psum=st.psum, gys=state.gys,
                gyq=state.gyq, gps=state.gps, gval=table.gval,
                gact=table.gact)


def _snap_post(state):
    st = state.stats
    return dict(scan_m=state.scan_m, m=st.m, ysum=st.ysum, ysq=st.ysq,
                psum=st.psum, gys=state.gys, gyq=state.gyq, gps=state.gps)


# fresh copies: the round donates the state it is given
_copy_pre = jax.jit(lambda s, t: jax.tree.map(lambda x: x + 0,
                                              _snap_pre(s, t)))
_copy_post = jax.jit(lambda s: jax.tree.map(lambda x: x + 0, _snap_post(s)))


class RoundSampler:
    """Keeps copies of the engine state around a seeded sample of rounds,
    for the EXTRACT check.  Installed as a thin wrapper of the engine's
    ``round_fn``; a round that is not sampled costs one counter increment.
    The copies stay on the device until the window has closed."""

    def __init__(self, engine, sample: set):
        self.sample = sample
        self.ordinal = 0
        self.server = None
        self.lookup: dict = {}
        self.records: list[dict] = []
        self.engine = engine
        self.orig = engine.round_fn
        engine.round_fn = self.round_fn

    def remove(self) -> None:
        self.engine.round_fn = self.orig

    def round_fn(self, b, mode="none"):
        fn = self.orig(b, mode)
        k = self.ordinal
        self.ordinal += 1
        if self.sample is not None and k not in self.sample:
            return fn

        def sampled(state, table, data, speeds):
            before = _copy_pre(state, table)
            slots = [None if w is None or w.qid not in self.lookup
                     else self.lookup[w.qid].template
                     for w in self.server.slot_wq]
            state, rep = fn(state, table, data, speeds)
            self.records.append(dict(pre=before, post=_copy_post(state),
                                     slots=slots))
            return state, rep

        return sampled

    def host_records(self) -> list[dict]:
        out = []
        for r in self.records:
            pre = {k: np.asarray(v) for k, v in r["pre"].items()}
            post = {k: np.asarray(v) for k, v in r["post"].items()}
            out.append(dict(pre=pre, post=post, slots=r["slots"],
                            gval=pre["gval"], gact=pre["gact"]))
        return out


def sample_rounds(seed_word: int, octaves: int = 16, per: int = 3) -> set:
    """Round ordinals to check: ``per`` drawn from each octave
    ``[2^o, 2^(o+1))``, so a run checks some tens of rounds whatever its
    speed."""
    rng = np.random.default_rng([int(seed_word), 3])
    out = {0}
    for o in range(octaves):
        lo, hi = 1 << o, 1 << (o + 1)
        out.update(int(v) for v in rng.integers(lo, hi, per))
    return out


# ------------------------------------------------------------- set-up ----

@dataclasses.dataclass
class Setup:
    cell: Cell
    spec: tablegen.TableSpec
    table: tablegen.Table
    store: object
    exact: reference.Exact
    templates: list
    engine: object
    engine_seed: int
    traffic_word: int
    sample_word: int
    parts: dict
    warm_parts: dict = dataclasses.field(default_factory=dict)

    def reseed(self, seed: int) -> None:
        """The run's seed: it deals the mix's streams to the sessions and
        picks the rounds the EXTRACT check samples.  The table, the engine's
        sampling order and the template pool are the configuration's and
        the mix's own, the same for every seed, so that every seed asks for
        the same work."""
        w = tablegen.seed_words(seed, 2)
        self.traffic_word, self.sample_word = int(w[0]), int(w[1])


def _server_options(cell: Cell, engine=None, mesh=None):
    from repro.serve.ola_server import ServerOptions

    srv = cell.config["server"]
    return ServerOptions(max_slots=int(srv["max_slots"]),
                         synopsis_budget_tuples=int(
                             srv["synopsis_budget_tuples"]),
                         confidence=float(srv["confidence"]),
                         engine=engine, mesh=mesh)


def _engine_config(cell: Cell, engine_seed: int, backend: Optional[str]):
    from repro.core.engine import EngineConfig

    e = cell.config["engine"]
    return EngineConfig(num_workers=int(e["num_workers"]), seed=engine_seed,
                        budget_init=int(e["budget"]),
                        budget_max=int(e["budget"]),
                        extract_backend=backend or e["extract_backend"],
                        residency=e["residency"],
                        max_groups=int(e["max_groups"]))


def build_store(table: tablegen.Table):
    from repro.data.chunkstore import ChunkStore
    from repro.data.formats import AsciiFixedFormat, BinaryBigEndianFormat

    spec = table.spec
    codec = (AsciiFixedFormat(spec.num_cols) if spec.format == "ascii"
             else BinaryBigEndianFormat(spec.num_cols))
    store = ChunkStore.create(name="synth", codec=codec)
    for raw in table.chunks:
        store.append_chunk(raw, num_tuples=raw.shape[0])
    store.finalize()
    return store


def _mesh(cell: Cell):
    n = int(cell.config.get("mesh_devices", 1))
    if n <= 1:
        return None

    return jax.make_mesh((n,), ("data",), devices=jax.devices()[:n])


def _warm_templates(templates: list) -> list:
    """One template of each kind the mix has."""
    kinds: dict = {}
    for t in templates:
        kinds.setdefault((t.agg, t.having is not None, t.grouped,
                          t.pred_col >= 0), t)
    return list(kinds.values())


def warm_up(setup: Setup, clock: CompileClock, rounds: int = 6) -> None:
    """Run every program the window runs, at the window's shapes: rounds
    with admissions, synopsis seeds, retirements and group promotion; the
    synopsis seed evaluation at every window size; a top-up pass and a
    census at exhaustion that retires every kind of query; a pass rebuild
    on the same engine; the EXTRACT check's state copies."""
    import jax.numpy as jnp

    from repro.core.engine import IDLE
    from repro.core.queries import compile_queries
    from repro.serve.ola_server import OLAWorkloadServer

    cell, store, engine = setup.cell, setup.store, setup.engine
    num_cols = setup.spec.num_cols
    plan = cell.config["server"]["plan"]
    kinds = _warm_templates(setup.templates)
    feed = traffic.Sessions(setup.templates, cell.mix, 0)
    sampler = RoundSampler(engine, None)

    def server():
        srv = OLAWorkloadServer(store, engine.config,
                                options=_server_options(cell, engine))
        sampler.server = srv
        return srv

    def serve(srv, n_rounds):
        # each kind is admitted once on an empty synopsis and once seeded
        for k in range(2 * n_rounds):
            if k in (0, n_rounds):
                for t in kinds:
                    srv.submit(to_query(t, num_cols), plan=plan)
            n0 = len(srv.results)
            srv.step()
            for _ in srv.results[n0:]:
                srv.submit(to_query(feed.next(0), num_cols), plan=plan)

    marks = [("start", time.perf_counter(), clock.compiles)]

    def mark(name):
        marks.append((name, time.perf_counter(), clock.compiles))

    srv = server()
    serve(srv, rounds)
    srv.close()
    mark("rounds")
    # the synopsis evaluates a newcomer over each cached window, one window
    # size at a time
    for t in kinds:
        ev = compile_queries([to_query(t, num_cols)])
        for c in range(1, engine.config.cache_cap + 1):
            x, p = ev(jnp.zeros((c, num_cols), jnp.float32))
            np.asarray(x), np.asarray(p)
    mark("synopsis_shapes")
    # one kind per slot resident, then a top-up pass and the census
    srv = server()
    for t in kinds[:srv.max_slots]:
        srv.submit(to_query(t, num_cols), plan=plan)
    srv.step()
    n = store.num_chunks
    sizes = jnp.asarray(store.chunk_sizes)
    short = jnp.zeros((n,), jnp.int32).at[0].set(1)
    srv.state = srv.state._replace(
        scan_m=sizes - short, offset=sizes - short,
        closed=jnp.ones((n,), bool), head=jnp.asarray(n, jnp.int32),
        cur=jnp.full_like(srv.state.cur, IDLE))
    for _ in range(3):
        srv.step()
    srv.tuples_scanned
    srv.close()
    # a grouped retirement reads one slot's row of the round report
    g = engine.config.max_groups + 1
    for dt in (jnp.float32, jnp.int32):
        np.asarray(jnp.zeros((engine.max_slots, g), dt)[0])
    mark("census")
    # the next pass: a new server on the same engine
    srv = server()
    serve(srv, 2)
    srv.close()
    sampler.host_records()
    sampler.remove()
    del srv
    gc.collect()
    mark("next_pass")
    setup.warm_parts = {b[0]: {"s": b[1] - a[1], "compiles": b[2] - a[2]}
                        for a, b in zip(marks, marks[1:])}


def setup_run(cell: Cell, seed: int, clock: CompileClock,
              backend: Optional[str] = None) -> Setup:
    from repro.serve.ola_server import OLAWorkloadServer

    spec = tablegen.TableSpec.from_dict(cell.config["table"])
    words = tablegen.seed_words(int(cell.config["data_seed"]), 2)
    parts = {}

    def part(name, fn, *a):
        c0, t0 = clock.seconds, time.perf_counter()
        out = fn(*a)
        parts[name] = {"s": time.perf_counter() - t0,
                       "compile_s": clock.seconds - c0}
        return out

    table = part("generate", tablegen.generate, spec, int(words[0]))
    store = part("store", build_store, table)
    exact = part("reference", reference.Exact, spec, table.ranks)
    templates = part("templates", traffic.build_templates, cell.mix, exact)
    engine_seed = int(words[1] & 0x7FFFFFFF)
    cfg = _engine_config(cell, engine_seed, backend)

    def build_engine():
        srv = OLAWorkloadServer(store, cfg, options=_server_options(
            cell, mesh=_mesh(cell)))
        eng = srv.engine
        srv.close()
        return eng

    engine = part("engine", build_engine)
    setup = Setup(cell=cell, spec=spec, table=table, store=store,
                  exact=exact, templates=templates, engine=engine,
                  engine_seed=engine_seed, traffic_word=0, sample_word=0,
                  parts=parts)
    setup.reseed(seed)
    part("warm_up", warm_up, setup, clock)
    return setup


# ------------------------------------------------------------- window ----

@dataclasses.dataclass
class Request:
    session: int
    template: reference.Template
    t_first: float
    t_answer: Optional[float] = None
    result: object = None
    passes: int = 1


@dataclasses.dataclass
class Window:
    requests: list
    rounds: int
    passes: int
    rebuild_s: float
    rebuilds: list
    tuples_scanned: int
    seconds: float
    t_end: float
    t_drained: float
    drain_s: float
    compiles: list
    records: list


class _Loop:
    """The closed loop's bookkeeping across passes."""

    def __init__(self, setup: Setup, sampler: RoundSampler):
        self.setup = setup
        self.sampler = sampler
        self.num_cols = setup.spec.num_cols
        self.plan = setup.cell.config["server"]["plan"]
        self.total = setup.store.num_tuples
        self.srv = None
        self.by_qid: dict = {}
        self.seen = 0
        self.passes = 0
        self.rebuild_s = 0.0
        self.rebuilds: list[float] = []
        self.scanned = 0

    def new_pass(self, carry: list) -> None:
        from repro.serve.ola_server import OLAWorkloadServer

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.pass_rebuild"):
            if self.srv is not None:
                self.scanned += self.srv.tuples_scanned
                self.srv.close()
            self.srv = OLAWorkloadServer(
                self.setup.store, self.setup.engine.config,
                options=_server_options(self.setup.cell, self.setup.engine))
            self.sampler.lookup = self.by_qid
            self.sampler.server = self.srv
            self.by_qid.clear()
            self.seen = 0
            for req in carry:
                req.passes += 1
                self.submit(req)
        self.passes += 1
        self.rebuild_s += time.perf_counter() - t0
        self.rebuilds.append(t0)

    def submit(self, req: Request) -> None:
        qid = self.srv.submit(to_query(req.template, self.num_cols),
                              plan=self.plan)
        self.by_qid[qid] = req

    def step(self) -> list:
        """One server step; returns the requests answered in it, with the
        answer time set.  A retirement that is no answer goes to the next
        pass when the scan is exhausted, else back into the queue."""

        with jax.profiler.TraceAnnotation("ola.step"):
            self.srv.step()
        with jax.profiler.TraceAnnotation("bench.collect"):
            now = time.perf_counter()
            done, carry = [], []
            new = self.srv.results[self.seen:]
            self.seen = len(self.srv.results)
            for r in new:
                req = self.by_qid.pop(r.qid)
                if stop_rule_met(req.template, r):
                    req.t_answer, req.result = now, r
                    done.append(req)
                else:
                    carry.append(req)
            exhausted = self.srv.tuples_scanned >= self.total
        if exhausted:
            self.new_pass(carry + list(self.by_qid.values()))
        else:
            for req in carry:
                self.submit(req)
        return done

    @property
    def tuples_scanned(self) -> int:
        return self.scanned + self.srv.tuples_scanned


def serve_window(setup: Setup, seconds: float, clock: CompileClock,
                 drain_s: float = 60.0, on_window_end=None) -> Window:
    """Closed-loop sessions for ``seconds``, then the drain."""

    subs = traffic.Sessions(setup.templates, setup.cell.mix,
                            setup.traffic_word)
    sampler = RoundSampler(setup.engine, sample_rounds(setup.sample_word))
    loop = _Loop(setup, sampler)
    requests: list[Request] = []
    c0 = clock.compiles
    t0 = time.perf_counter()
    t_end = t0 + seconds
    with jax.profiler.TraceAnnotation("bench.window"):
        loop.new_pass([])
        loop.passes, loop.rebuild_s, loop.rebuilds = 1, 0.0, []
        with jax.profiler.TraceAnnotation("bench.submit"):
            for s in range(int(setup.cell.mix["sessions"])):
                requests.append(Request(s, subs.next(s), t0))
                loop.submit(requests[-1])
        now = t0
        while now < t_end:
            done = loop.step()
            now = time.perf_counter()
            if done and now < t_end:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    for d in done:
                        requests.append(Request(
                            d.session, subs.next(d.session), d.t_answer))
                        loop.submit(requests[-1])
        rounds, scanned = sampler.ordinal, loop.tuples_scanned
        passes, rebuild_s = loop.passes, loop.rebuild_s
    compiles = clock.names[c0:clock.compiles]
    if on_window_end is not None:
        on_window_end()
    t_close = time.perf_counter()
    while (any(r.t_answer is None for r in requests)
           and time.perf_counter() - t_close <= drain_s):
        loop.step()
    loop.srv.close()
    sampler.remove()
    return Window(requests=requests, rounds=rounds, passes=passes,
                  rebuild_s=rebuild_s, rebuilds=loop.rebuilds,
                  tuples_scanned=scanned,
                  seconds=seconds, t_end=t_end, t_drained=time.perf_counter(),
                  drain_s=time.perf_counter() - t_close,
                  compiles=compiles, records=sampler.host_records())


# ------------------------------------------------------------ checking ----

def _err_eps(est: float, exact: float, eps: float) -> float:
    """|estimate - exact| in units of ε·|exact|."""
    if not np.isfinite(est):
        return float("inf")
    if exact == 0:
        return 0.0 if est == 0 else float("inf")
    return abs(est - exact) / (eps * abs(exact))


def _holds(op: str, value: float, threshold: float) -> bool:
    return {"<": value < threshold, ">": value > threshold,
            "<=": value <= threshold, ">=": value >= threshold}[op]


def check_answers(setup: Setup, window: Window) -> dict:
    """Every answer of a query first submitted in the window, against the
    exact float64 answer:

    * an answer that met its ε, and each top-K cell of a grouped answer:
      the estimate's error in units of ε (``answer_err_eps``);
    * a grouped answer: whether its top-K cells are the exact top-K
      (``topk_wrong``);
    * a HAVING verdict: whether it agrees with the answer's own estimate
      against the threshold (``verdict_self``: the interval that decided it
      holds the estimate, so this is exact), and the share of verdicts that
      disagree with the exact answer (``verdict_wrong``).  A verdict
      retires its query before ε; the verdicts hold such an answer."""
    exact = setup.exact
    lim = setup.cell.limits.get("answer_err_eps", float("inf"))
    worst, topk, unanswered, bad = 0.0, 0, 0, 0
    decided, self_bad, verdict_bad = 0, 0, 0
    where = ""
    for req in window.requests:
        t, r = req.template, req.result
        if r is None:
            unanswered += 1
            bad += 1
            continue
        errs, wrong = [], False
        if t.grouped:
            keys, answers = exact.groups(t)
            order = np.argsort(-np.abs(answers), kind="stable")[:t.top_k]
            want = {float(np.float32(keys[i])) for i in order}
            cells = top_cells(r, t.top_k)
            if {float(np.float32(g.value)) for g in cells} != want:
                topk += 1
                wrong = True
            f32 = np.float32(keys)
            for g in cells:
                hit = np.flatnonzero(f32 == np.float32(g.value))
                errs.append(_err_eps(g.estimate, answers[hit[0]], t.epsilon)
                            if len(hit) else float("inf"))
        else:
            want = exact.answer(t)
            if r.err <= t.epsilon:
                errs.append(_err_eps(r.estimate, want, t.epsilon))
            if t.having is not None and r.decision != -1:
                op, thr = t.having
                decided += 1
                own = r.decision != int(_holds(op, r.estimate,
                                               float(np.float32(thr))))
                true = r.decision != int(_holds(op, want, thr))
                self_bad += own
                verdict_bad += true
                wrong = wrong or own or true
        if errs and max(errs) > worst:
            worst = max(errs)
            where = (f"t{t.tid} {t.agg} eps={t.epsilon} estimate="
                     f"{r.estimate!r} err={r.err!r} exact="
                     f"{exact.answer(t)!r} passes={req.passes}")
        bad += wrong or any(e > lim for e in errs)
    return {"answer_err_eps": worst, "topk_wrong": topk,
            "unanswered": unanswered, "verdict_self": self_bad,
            "verdict_wrong": verdict_bad / decided if decided else 0.0,
            "verdicts": decided, "failed": bad, "worst": where}


def check_extract(setup: Setup, window: Window, bf16: bool = False,
                  stats: tuple = ()) -> tuple[float, int, str]:
    """(largest relative error, sums compared, which) of the sampled rounds'
    per-(slot, chunk) sums against the reference over the same raw rows;
    ``stats`` keeps only the sums of the statistics it names."""
    errs = []
    for rec in window.records:
        errs += reference.extract_errors(
            setup.spec, setup.table.chunks, rec, setup.engine_seed,
            setup.store.max_chunk_tuples, bf16=bf16)
    if stats:
        errs = [e for e in errs if e[1].split(" ", 1)[0] in stats]
    if not errs:
        return float("inf"), 0, "no sums compared"
    worst = max(errs, key=lambda e: e[0])
    return float(worst[0]), len(errs), worst[1]


def checks(setup: Setup, window: Window) -> tuple[dict, dict]:
    """-> (the numbers the cell's limits name, each ``{"value", "limit"}``,
    in the limits file's order; counts)."""
    a = check_answers(setup, window)
    ext, n_ext, ext_at = check_extract(setup, window)
    numbers = {**a, "extract_sum_err": ext}
    out = {k: {"value": numbers[k], "limit": v}
           for k, v in setup.cell.limits.items()}
    return out, {"failed": a["failed"], "extract_sums": n_ext,
                 "verdicts": a["verdicts"],
                 "extract_worst": ext_at, "answer_worst": a["worst"]}


# ------------------------------------------------------------ metrics ----

def end_to_end(window: Window) -> dict:
    # a query never answered enters with its time until the drain ended
    lat = [(r.t_answer if r.t_answer is not None else window.t_drained)
           - r.t_first for r in window.requests]
    answers = sum(1 for r in window.requests
                  if r.t_answer is not None and r.t_answer < window.t_end)
    lat_arr = np.asarray(lat)
    p50, p95 = (np.percentile(lat_arr, [50, 95], method="linear")
                if len(lat_arr) else (float("nan"), float("nan")))
    return {"answers_per_s": answers / window.seconds,
            "answer_p50_s": float(p50), "answer_p95_s": float(p95),
            "samples": len(lat), "answers": answers}


def load_reader(name: str):
    path = BENCH_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench.layer_metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
