"""Span tracers on the JAX profiler's clock, with chrome-trace export.

The OLA query lifecycle is a pipeline the user is supposed to *watch*:
submit, admission (synopsis refresh, seed, slot write), then per round
claims, dispatch, device wait, merge and retirement, with the scan plane's
READ / prefetch overlap running underneath on the reader thread.  Call
sites open spans through one protocol, ``tracer.span(name, **args)`` and
``tracer.round(n)``; the query a span works for travels in its args
(``qid``, ``slot``, ``plan``, ``outcome``), so the spans of one query share
its ``qid``.

* :class:`ProfilerTracer`, every server's default, emits each span as a
  ``jax.profiler.TraceAnnotation`` and each round as a
  ``StepTraceAnnotation``.  The profiler keeps them, while a trace is being
  recorded, on the same clock as the device's operations, so an idle gap
  of the device can be put down to the host work under it.  With no trace
  recording an annotation costs about a microsecond.
* :class:`SpanTracer` does the same and also records each span into its
  own bounded buffer, exported as chrome-trace JSON (``traceEvents`` with
  complete ``"X"`` events) that https://ui.perfetto.dev or
  ``chrome://tracing`` open directly.
* :data:`NULL_TRACER` is tracing off: ``span()`` returns one shared no-op
  context manager.

Every span wraps host calls only; nothing jit-visible changes, so a traced
run is round-for-round bit-exact with an untraced one.  ``jax`` is imported
when a tracer is built, not when this module is, so ``repro.obs`` stays
importable without it.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Callable, Optional

#: Name of the span around one engine round (a profiler step).
ROUND_SPAN = "ola.round"


class _NullSpan:
    """Shared no-op context manager: the entire cost of disabled tracing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Tracing disabled: every call returns the shared no-op span."""

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def round(self, n: int) -> _NullSpan:
        return _NULL_SPAN


#: Module-level singleton: pass it as ``tracer`` to turn tracing off.
NULL_TRACER = NullTracer()


class ProfilerTracer:
    """Spans as JAX profiler annotations (see module docstring)."""

    def __init__(self):
        from jax import profiler

        self._annotation = profiler.TraceAnnotation
        self._step = profiler.StepTraceAnnotation

    def span(self, name: str, **args):
        """Context manager around host work; ``args`` become the
        annotation's stats (keep them small scalars)."""
        return self._annotation(name, **args)

    def round(self, n: int):
        """Context manager around engine round ``n``."""
        return self._step(ROUND_SPAN, step_num=n)


class _Span:
    __slots__ = ("tracer", "name", "args", "inner", "t0", "depth")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict, inner):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.inner = inner

    def __enter__(self):
        self.inner.__enter__()
        tr = self.tracer
        stack = tr._stack()
        self.depth = len(stack)
        stack.append(self)
        self.t0 = tr.clock()
        return self

    def __exit__(self, *exc):
        tr = self.tracer
        t1 = tr.clock()
        tr._stack().pop()
        tr._record(self.name, self.t0, t1 - self.t0, self.depth, self.args)
        return self.inner.__exit__(*exc)


class SpanTracer(ProfilerTracer):
    """Profiler annotations plus a chrome-trace buffer (see module
    docstring).

    ``clock`` must be monotone (defaults to :func:`time.perf_counter`);
    timestamps are recorded relative to the tracer's construction so the
    exported trace starts near zero.  Spans from several threads are safe:
    events carry a small per-thread tid, appends are lock-protected and
    nesting state is thread-local.  At ``max_events`` the buffer stops
    recording and counts drops (``dropped``), which the export stamps into
    the trace metadata.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 max_events: int = 1_000_000):
        super().__init__()
        self.clock = clock if clock is not None else time.perf_counter
        self.max_events = int(max_events)
        self.events: list[tuple] = []   # (name, ts, dur, tid, depth, args)
        self.dropped = 0
        self._t0 = self.clock()
        self._lock = threading.Lock()
        self._local = threading.local()
        self._tids: dict[int, int] = {}   # thread ident -> small stable tid

    # ------------------------------------------------------------ record ----
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids)
        return tid

    def _record(self, name: str, t0: float, dur: float, depth: int,
                args: dict) -> None:
        with self._lock:
            if len(self.events) >= self.max_events:
                self.dropped += 1
                return
            self.events.append(
                (name, t0 - self._t0, max(dur, 0.0), self._tid(), depth,
                 args))

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args, super().span(name, **args))

    def round(self, n: int) -> _Span:
        return _Span(self, ROUND_SPAN, {"step_num": n}, super().round(n))

    def clear(self) -> None:
        with self._lock:
            self.events.clear()
            self.dropped = 0
            self._t0 = self.clock()

    # ------------------------------------------------------------ export ----
    def to_chrome_trace(self, process_name: str = "ola-server") -> dict:
        """Chrome-trace JSON object: complete ``"X"`` events in
        microseconds, one chrome 'thread' per real thread (tid 0 is the
        server loop, higher tids are reader threads)."""
        with self._lock:
            events = list(self.events)
            dropped = self.dropped
            tids = dict(self._tids)
        out = [{
            "name": "process_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": process_name},
        }]
        for ident, tid in sorted(tids.items(), key=lambda kv: kv[1]):
            out.append({
                "name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                "args": {"name": "server-loop" if tid == 0
                         else f"reader-{tid}"},
            })
        for name, ts, dur, tid, depth, args in events:
            ev = {"name": name, "ph": "X", "pid": 0, "tid": tid,
                  "ts": ts * 1e6, "dur": dur * 1e6, "cat": "ola"}
            if args or depth:
                ev["args"] = dict(args, depth=depth) if depth else dict(args)
            out.append(ev)
        doc = {"traceEvents": out, "displayTimeUnit": "ms"}
        if dropped:
            doc["otherData"] = {"dropped_events": dropped}
        return doc

    def save(self, path: str, process_name: str = "ola-server") -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(process_name), f)


def validate_chrome_trace(doc: dict) -> list[str]:
    """Schema/consistency check for an exported chrome trace; returns the
    list of problems (empty = valid).  Checks: ``traceEvents`` is a list
    of well-formed events, durations are non-negative and finite, and the
    ``"X"`` spans of each (pid, tid) nest properly — every span is either
    disjoint from or fully contained in any span it overlaps (the
    invariant a stack-shaped tracer must produce).  The CI observability
    smoke step runs this over the workload bench's trace."""
    problems: list[str] = []
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return ["traceEvents missing or not a list"]
    spans: dict[tuple, list] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i}: not an object")
            continue
        ph = ev.get("ph")
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            problems.append(f"event {i}: missing name")
        if ph not in ("X", "M", "B", "E", "i", "I"):
            problems.append(f"event {i}: unknown phase {ph!r}")
            continue
        if ph != "X":
            continue
        ts, dur = ev.get("ts"), ev.get("dur")
        if not isinstance(ts, (int, float)) or ts != ts:
            problems.append(f"event {i} ({ev.get('name')}): bad ts {ts!r}")
            continue
        if (not isinstance(dur, (int, float)) or dur != dur
                or dur < 0 or dur == float("inf")):
            problems.append(
                f"event {i} ({ev.get('name')}): bad duration {dur!r}")
            continue
        spans.setdefault((ev.get("pid", 0), ev.get("tid", 0)), []).append(
            (float(ts), float(ts) + float(dur), ev.get("name", "")))
    for key, ss in spans.items():
        # sort by start asc, end desc: a parent sorts before its children
        ss.sort(key=lambda s: (s[0], -s[1]))
        stack: list = []
        for t0, t1, name in ss:
            while stack and t0 >= stack[-1][1]:
                stack.pop()
            if stack and t1 > stack[-1][1] + 1e-9:
                problems.append(
                    f"tid {key}: span {name!r} [{t0}, {t1}] overlaps "
                    f"{stack[-1][2]!r} [{stack[-1][0]}, {stack[-1][1]}] "
                    "without nesting")
                continue
            stack.append((t0, t1, name))
    return problems
