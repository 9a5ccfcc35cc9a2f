"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:<KIND>:<n>``; their ``XLA Ops`` line holds one event per operation
run on the device.  The host plane ``/host:CPU`` holds the harness's
``TraceAnnotation`` spans on the Python thread's line, on the same clock.

Busy time is the union of a device's operation intervals inside the window
(the ``bench.window`` span); an idle gap is the complement, named after the
innermost harness span open on the host at the gap's midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
import numpy as np

OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _overlap(union: np.ndarray, lo: float, hi: float) -> float:
    return float(np.sum(np.diff(_clip(union, lo, hi), axis=1)))


@dataclasses.dataclass
class Device:
    name: str
    ops: list            # [(name, start_ns, end_ns)]
    busy: np.ndarray     # disjoint busy intervals inside the window


@dataclasses.dataclass
class TraceSummary:
    window: tuple                      # (start_ns, end_ns)
    devices: list
    spans: dict                        # annotation -> (n, 2) intervals

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        return float(np.mean([np.sum(np.diff(d.busy, axis=1))
                              for d in self.devices])) * 1e-9

    def op_seconds(self, pattern: str) -> tuple[float, int]:
        """(seconds, events) of the device operations whose name matches
        ``pattern``, inside the window, averaged over the devices."""
        rx = re.compile(pattern)
        tot, cnt = 0.0, 0
        lo, hi = self.window
        for d in self.devices:
            for name, s, e in d.ops:
                if rx.search(name):
                    s, e = max(s, lo), min(e, hi)
                    if e > s:
                        tot += e - s
                        cnt += 1
        n = max(len(self.devices), 1)
        return tot * 1e-9 / n, cnt // n

    def busy_in(self, annotation: str) -> float:
        """Device-busy seconds inside the spans of ``annotation``, averaged
        over the devices."""
        spans = self.spans.get(annotation, np.zeros((0, 2)))
        if not self.devices:
            return 0.0
        per = [sum(_overlap(d.busy, s, e) for s, e in spans)
               for d in self.devices]
        return float(np.mean(per)) * 1e-9

    def span_seconds(self, annotation: str) -> tuple[float, int]:
        spans = self.spans.get(annotation, np.zeros((0, 2)))
        return float(np.sum(np.diff(spans, axis=1))) * 1e-9, len(spans)

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` operations with most device time, by the name left of
        ``=`` in the HLO text the trace gives them."""
        tot: dict = defaultdict(float)
        lo, hi = self.window
        for d in self.devices:
            for name, s, e in d.ops:
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    short = name.split(" = ", 1)[0]
                    tot[short] += (e - s) * 1e-9 / len(self.devices)
        return sorted(([n, v] for n, v in tot.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle seconds of the first device inside the window, summed by
        the innermost harness span open on the host at each gap."""
        if not self.devices:
            return []
        lo, hi = self.window
        busy = self.devices[0].busy
        edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        mid = gaps.mean(axis=1)
        width = np.full(len(gaps), np.inf)
        label = np.full(len(gaps), -1)
        names = sorted(self.spans)
        for i, name in enumerate(names):
            iv = self.spans[name]
            if not len(iv):
                continue
            iv = iv[np.argsort(iv[:, 0])]
            at = np.searchsorted(iv[:, 0], mid, "right") - 1
            ok = at >= 0
            at = np.maximum(at, 0)
            w = iv[at, 1] - iv[at, 0]
            inner = ok & (mid < iv[at, 1]) & (w < width)
            width[inner] = w[inner]
            label[inner] = i
        named: dict = defaultdict(float)
        for lab, (s, e) in zip(label, gaps):
            named[names[lab] if lab >= 0 else "outside harness spans"] += (
                (e - s) * 1e-9)
        return sorted(([n, v] for n, v in named.items()),
                      key=lambda x: -x[1])[:k]


def reduce(path: str, annotations: tuple,
           device_prefix: str = "/device:") -> TraceSummary:
    """Read one ``.xplane.pb`` into a :class:`TraceSummary`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: dict = defaultdict(list)
    raw_devices = []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in annotations:
                        spans[ev.name].append((ev.start_ns, ev.end_ns))
        elif plane.name.startswith(device_prefix):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.name, ev.start_ns, ev.end_ns)
                           for ev in line.events]
            if ops:
                raw_devices.append((plane.name, ops))
    spans = {k: np.asarray(v, np.float64).reshape(-1, 2)
             for k, v in spans.items()}
    win = spans.get("bench.window")
    if win is not None and len(win):
        window = (float(win[0, 0]), float(win[0, 1]))
    else:
        ts = [t for _, ops in raw_devices for _, s, e in ops for t in (s, e)]
        window = (float(min(ts)), float(max(ts))) if ts else (0.0, 0.0)
    devices = []
    for name, ops in raw_devices:
        iv = np.asarray([(s, e) for _, s, e in ops], np.float64)
        devices.append(Device(name=name, ops=ops,
                              busy=_union(_clip(iv, *window))))
    return TraceSummary(window=window, devices=devices, spans=spans)
