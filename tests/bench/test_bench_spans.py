"""The program's own spans in the profiler's trace.

A live CPU trace of a tiny server run checks that every span the server
emits is one of ``PROGRAM_SPANS``, one ``ola.round`` per round, and that
the server's spans nest inside a harness-style ``ola.step``.  A small trace
recorded on one TPU v5e (a tiny ASCII cell, 0.88 s of window:
``data/tiny-ascii-spans.xplane.pb``; of the host plane only the Python
thread's line is kept, the runtime's threads are dropped for size) checks
that the top-level spans cover the step and name the device's idle gaps.
"""

from __future__ import annotations

from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import harness, tracing

DATA = Path(__file__).parent / "data"
SPANS_TRACE = DATA / "tiny-ascii-spans.xplane.pb"
# every span the server opens on its own thread; ola.read is left out, as
# it also runs on the prefetcher's thread
PROGRAM_SPANS = (
    "ola.submit", "ola.admit", "ola.admit_query", "ola.synopsis_refresh",
    "ola.seed", "ola.slot_write", "ola.seed_retire", "ola.decide",
    "ola.shed", "ola.evict", "ola.round", "ola.schedule",
    "ola.claims", "ola.assemble", "ola.prefetch", "ola.quarantine",
    "ola.dispatch", "ola.device_wait", "ola.merge", "ola.retire",
    "ola.report", "ola.retire_slots", "ola.retire_query", "ola.groups",
    "ola.group_promote", "ola.topup")
SPAN_ANNOTATIONS = harness.ANNOTATIONS + PROGRAM_SPANS
# what a NEUTRAL-scheduled run with queueing, seeds and no faults emits
EXPECTED = {"ola.submit", "ola.admit", "ola.admit_query",
            "ola.synopsis_refresh", "ola.seed", "ola.slot_write",
            "ola.seed_retire", "ola.decide", "ola.round", "ola.schedule",
            "ola.claims", "ola.dispatch", "ola.device_wait", "ola.merge",
            "ola.retire", "ola.report", "ola.retire_slots",
            "ola.retire_query", "ola.groups", "ola.topup"}


def _host_events(path) -> list:
    """(name, start_ns, end_ns) of every event on the host plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.end_ns)
            for pl in pd.planes if pl.name == tracing.HOST_PLANE
            for ln in pl.lines for ev in ln.events]


def _inside(inner: np.ndarray, outer: np.ndarray) -> np.ndarray:
    """Whether each interval of ``inner`` lies inside one of ``outer``."""
    outer = outer[np.argsort(outer[:, 0], kind="stable")]
    at = np.searchsorted(outer[:, 0], inner[:, 0], "right") - 1
    return (at >= 0) & (inner[:, 1] <= outer[np.maximum(at, 0), 1])


@pytest.fixture(scope="module")
def live(tmp_path_factory):
    """A NEUTRAL-scheduled server on a tiny store, two slots for six
    queries, each step under ``ola.step``, traced by the JAX profiler."""
    from repro.core.engine import EngineConfig
    from repro.core.queries import Linear, Query, Range
    from repro.data.generator import make_synthetic_zipf, store_dataset
    from repro.sched import WorkloadScheduler
    from repro.sched.scheduler import NEUTRAL
    from repro.serve.ola_server import OLAWorkloadServer, ServerOptions

    store = store_dataset(make_synthetic_zipf(2048, 8, seed=3), 16, "ascii")
    coef = tuple(1.0 / (k + 1) for k in range(8))
    srv = OLAWorkloadServer(store, EngineConfig(num_workers=2, seed=5),
                            options=ServerOptions(
                                max_slots=2, synopsis_budget_tuples=512,
                                scheduler=WorkloadScheduler(NEUTRAL)))
    out = tmp_path_factory.mktemp("live-trace")
    jax.profiler.start_trace(str(out),
                             profiler_options=harness.profile_options())
    try:
        for i in range(6):
            srv.submit(Query(agg="sum", expr=Linear(coef),
                             pred=Range(0, 0.0, 6e7 + 1e6 * i),
                             epsilon=0.1, name=f"q{i}"),
                       arrival_t=1e-5 * i)
        while srv.queue or srv._any_active():
            with jax.profiler.TraceAnnotation("ola.step"):
                srv.step()
    finally:
        jax.profiler.stop_trace()
        srv.close()
    path = tracing.find_xplane(str(out))
    return srv, path, tracing.reduce(path, SPAN_ANNOTATIONS)


def test_every_program_span_is_collected(live):
    _, path, summary = live
    assert EXPECTED <= set(summary.spans)
    emitted = {n for n, _, _ in _host_events(path) if n.startswith("ola.")}
    assert emitted >= EXPECTED
    # the list names every span the server emits
    assert emitted <= set(PROGRAM_SPANS) | {"ola.step", "ola.read"}


def test_one_round_span_per_round(live):
    srv, _, summary = live
    assert srv.rounds > 0
    assert summary.span_seconds("ola.round")[1] == srv.rounds
    assert summary.span_seconds("ola.dispatch")[1] == srv.rounds
    assert summary.span_seconds("ola.device_wait")[1] == srv.rounds


def test_program_spans_nest_inside_the_step(live):
    _, _, summary = live
    steps = summary.spans["ola.step"]
    for name in EXPECTED - {"ola.submit"}:
        assert _inside(summary.spans[name], steps).all(), name
    rounds = summary.spans["ola.round"]
    for name in ("ola.claims", "ola.dispatch", "ola.device_wait",
                 "ola.merge", "ola.retire", "ola.retire_slots"):
        assert _inside(summary.spans[name], rounds).all(), name
    admits = summary.spans["ola.admit"]
    for name in ("ola.admit_query", "ola.synopsis_refresh", "ola.seed",
                 "ola.slot_write", "ola.decide"):
        assert _inside(summary.spans[name], admits).all(), name


# --------------------------------------------------- the chip's trace ----

@pytest.fixture(scope="module")
def chip():
    return tracing.reduce(str(SPANS_TRACE), SPAN_ANNOTATIONS)


def test_program_spans_cover_the_step(chip):
    step, _ = chip.span_seconds("ola.step")
    top = sum(chip.span_seconds(n)[0] for n in ("ola.admit", "ola.round"))
    assert 0.95 * step <= top <= step


def test_program_spans_name_the_idle_gaps(chip):
    gaps = dict(chip.idle_gaps(k=len(chip.spans) + 1))
    program = sum(v for n, v in gaps.items() if n in PROGRAM_SPANS)
    assert program > 0.5 * sum(gaps.values())
    assert program > 10 * gaps.get("ola.step", 0.0)
