"""The trace reduction, on a small profiler trace recorded on one TPU v5e
(a tiny ASCII cell, half a second of window: ``data/tiny-ascii.xplane.pb``),
and the roofline's byte count and peak table."""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, kernel_cost, tracing
from perfbench.layer_metrics import extract_kernel_ms

TRACE = Path(__file__).parent / "data" / "tiny-ascii.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return tracing.reduce(str(TRACE), harness.ANNOTATIONS)


@pytest.fixture(scope="module")
def raw():
    """The trace read independently of the reduction: each device's op
    intervals and the host annotations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(TRACE))
    dev, host = {}, {}
    for pl in pd.planes:
        if pl.name.startswith("/device:"):
            for ln in pl.lines:
                if ln.name == "XLA Ops":
                    dev[pl.name] = [(e.name, e.start_ns, e.end_ns)
                                    for e in ln.events]
        if pl.name == "/host:CPU":
            for ln in pl.lines:
                for e in ln.events:
                    if e.name in harness.ANNOTATIONS:
                        host.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    return dev, host


def _busy_by_sweep(ops, lo, hi) -> float:
    """Busy nanoseconds by a sweep over sorted start/end points."""
    pts = sorted([(max(s, lo), 1) for _, s, e in ops if e > lo and s < hi]
                 + [(min(e, hi), -1) for _, s, e in ops if e > lo and s < hi])
    busy, depth, last = 0.0, 0, None
    for t, d in pts:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_window_is_the_harness_span(summary, raw):
    _, host = raw
    (s, e), = host["bench.window"]
    assert summary.window == (float(s), float(e))
    assert 0.4 < summary.window_s < 5.0


def test_busy_union_and_idle_share(summary, raw):
    dev, _ = raw
    assert summary.devices, "the recorded trace has a device plane"
    lo, hi = summary.window
    want = np.mean([_busy_by_sweep(ops, lo, hi) for ops in dev.values()])
    assert summary.busy_s == pytest.approx(want * 1e-9, rel=1e-9)
    assert 0.0 < summary.busy_s < summary.window_s


def test_kernel_time_by_name(summary, raw):
    dev, _ = raw
    lo, hi = summary.window
    ops = next(iter(dev.values()))
    want = sum(min(e, hi) - max(s, lo) for n, s, e in ops
               if "slot_extract" in n and e > lo and s < hi)
    got, calls = summary.op_seconds(extract_kernel_ms.KERNEL)
    assert calls > 0
    assert got == pytest.approx(want * 1e-9, rel=1e-9)
    assert got <= summary.busy_s


def test_idle_gaps_are_named_by_the_host_span(summary):
    gaps = summary.idle_gaps()
    names = {n for n, _ in gaps}
    assert names <= set(harness.ANNOTATIONS) | {"outside harness spans"}
    idle = summary.window_s - summary.busy_s
    assert sum(v for _, v in gaps) == pytest.approx(idle, rel=1e-6)
    # host time inside steps is attributed to the step span
    assert "ola.step" in names


def test_union_merges_overlaps():
    iv = np.asarray([[0, 2], [1, 3], [5, 6], [6, 7], [9, 10]], float)
    assert tracing._union(iv).tolist() == [[0, 3], [5, 7], [9, 10]]


def test_needed_bytes_by_hand():
    # 2 calls sampling 1000 rows of 256 bytes; W=2 workers, B=8, S=3
    # slots, C=4 columns, ungrouped:
    #   rows: 1000 * 256 = 256000
    #   per call: idx+ids+b_eff 2*(8+2)*4 = 80; plan 3*3*4*4 + 3*3*4 = 180;
    #   partials 2*3*4*4 = 96  -> 356, twice = 712
    assert kernel_cost.extract_bytes(
        1000, 2, record_bytes=256, workers=2, budget=8, slots=3, cols=4,
        groups=0) == 256000 + 712
    # grouped, G=2 cells: + one-hot 3*4*4 = 48, gval/gact 2*3*2*4 = 48,
    # cell partials 2*3*2*4*4 = 192, tallies 2*3*3*128*4 = 9216
    assert kernel_cost.extract_bytes(
        1000, 1, record_bytes=256, workers=2, budget=8, slots=3, cols=4,
        groups=2) == 256000 + 356 + 48 + 48 + 192 + 9216


def test_peaks_refuse_an_unknown_device():
    assert kernel_cost.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        kernel_cost.peaks("TPU v9 imaginary")
