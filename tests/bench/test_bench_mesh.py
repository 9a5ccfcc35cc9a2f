"""The four-chip cell on four virtual CPU devices, and its readers.

``ascii-scan-4chip`` shards its table over a 4-device ``data`` mesh.  A
tiny copy of it (the repository's configuration and limits, the table cut
to CPU size, the tiny mix, the EXTRACT kernel in interpret mode) is
``correct``; the same run with the exchange between chips left out
(``_Collectives.merge`` made the identity) is not.  The devices are forced
in a subprocess, as XLA_FLAGS must be set before jax initializes.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from perfbench import kernel_cost, tracing
from perfbench.layer_metrics import (extract_chip_hbm_roofline,
                                     extract_hbm_roofline, extract_kernel_ms,
                                     merge_ms_per_round)

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import tempfile, types
from pathlib import Path
import jax
import bench_tiny as tiny
from perfbench import run as bench_run
from repro.core import engine

cell_def = {"name": "tiny-ascii-4chip", "config": "synth16-ascii-sharded4",
            "traffic": "tiny", "chips": 4, "why": "CPU rehearsal"}
root = tiny.make_root(Path(tempfile.mkdtemp()),
                      cells=tiny.TINY_CELLS + [cell_def])
cell = tiny.tiny_cell(root, "tiny-ascii-4chip", workers=8)
args = types.SimpleNamespace(seed=2**31 + 77, seconds=4.0, trace=0)
for fault, drain_s in (("none", 15.0), ("merge_left_out", 5.0)):
    if fault == "merge_left_out":
        engine._Collectives.merge = lambda self, tree: tree
    rc = bench_run.run(cell, args, jax.devices(), backend="pallas-interpret",
                       drain_s=drain_s, cache=False)
    assert rc == 0
"""


def _runs() -> list:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(CHECKOUT), str(CHECKOUT / "src"), str(HERE)])
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1200,
                         cwd=str(CHECKOUT))
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]


def test_tiny_four_chip_run_is_correct_and_the_merge_fault_is_not():
    sound, fault = _runs()
    assert sound["device"]["count"] == 4
    assert sound["correct"] is True, sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert fault["correct"] is False, fault["checks"]


def _summary(ops_per_device: list, window=(0.0, 1e9)) -> tracing.TraceSummary:
    devices = []
    for i, ops in enumerate(ops_per_device):
        iv = np.asarray([(s, e) for _, s, e in ops], np.float64).reshape(-1, 2)
        devices.append(tracing.Device(name=f"/device:TPU:{i}", ops=ops,
                                      busy=tracing._union(iv)))
    return tracing.TraceSummary(window=window, devices=devices, spans={})


def test_merge_reader_sums_collectives_per_chip_per_round():
    fusion = "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.2), kind=kLoop"
    dev0 = [
        ("%all-reduce.2 = (f32[8,64]{1,0}, s32[64]{0}) all-reduce(%a, %b), "
         "channel_id=1", 100.0, 400.0),
        ("%all-gather-start = s32[8]{0} all-gather-start(%c)", 500.0, 600.0),
        ("%all-gather-done = s32[32]{0} all-gather-done(%all-gather-start)",
         600.0, 700.0),
        (fusion, 700.0, 2700.0),
        ("%collective-permute.1 = f32[4]{0} collective-permute(%d)",
         3000.0, 3100.0),
    ]
    dev1 = [("%reduce-scatter.4 = f32[2]{0} reduce-scatter(%e)", 0.0, 700.0),
            (fusion, 700.0, 900.0)]
    # the window clips an operation that runs past it
    ctx = {"trace": _summary([dev0, dev1], window=(0.0, 3050.0)),
           "rounds": 2}
    per_chip_ns = ((300 + 100 + 100 + 50) + 700) / 2
    assert merge_ms_per_round.read(ctx) == per_chip_ns * 1e-9 / 2 * 1e3


def test_merge_reader_reads_nothing_without_collectives():
    ctx = {"trace": _summary([[("%fusion.1 = f32[8] fusion(%x)", 0.0, 9.0)]]),
           "rounds": 3}
    assert merge_ms_per_round.read(ctx) is None
    ctx = {"trace": _summary([]), "rounds": 3}
    assert merge_ms_per_round.read(ctx) is None


def _kernel_ctx(chips: int, kernel_ns: list) -> dict:
    """Two rounds: one fused-kernel call a round on each chip, each call
    lasting the chip's entry of ``kernel_ns``."""
    name = "%slot_extract_grouped_pallas.1 = f32[8] custom-call(%a)"
    devs = [[(name, 0.0, ns), (name, 1e6, 1e6 + ns)] for ns in kernel_ns]
    return {"trace": _summary(devs), "rounds": 2, "tuples_scanned": 262144,
            "record_bytes": 256, "device_kind": "TPU v5 lite",
            "config": {"mesh_devices": chips,
                       "engine": {"num_workers": 8 * chips, "budget": 4096,
                                  "max_groups": 8},
                       "table": {"num_cols": 16},
                       "server": {"max_slots": 8}}}


def test_chip_roofline_reads_each_chip_against_its_own_bytes():
    """Four chips, each extracting a quarter of the rows with its 8
    workers, read as one chip extracting that quarter: the kernel time and
    calls are averaged over the chips, and so are the bytes."""
    four = _kernel_ctx(4, [4e5, 5e5, 6e5, 5e5])
    got = extract_chip_hbm_roofline.read(four)
    need = kernel_cost.extract_bytes(
        262144 / 4, 2, record_bytes=256, workers=8, budget=4096, slots=8,
        cols=16, groups=9)
    bw = kernel_cost.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert got == need / bw / (2 * 5e5 * 1e-9) * 100.0
    # the whole-table roofline divides every chip's rows by one chip's
    # bandwidth, so it reads above the per-chip share here
    assert extract_hbm_roofline.read(four) > 3.9 * got
    assert extract_kernel_ms.read(four) == 5e5 * 1e-6
    # on one chip the two readers agree
    one = _kernel_ctx(1, [5e5])
    assert extract_chip_hbm_roofline.read(one) == extract_hbm_roofline.read(one)


def test_chip_roofline_reads_nothing_without_the_kernel():
    ctx = _kernel_ctx(4, [5e5] * 4)
    ctx["trace"] = _summary([[("%fusion.1 = f32[8] fusion(%x)", 0.0, 9.0)]] * 4)
    assert extract_chip_hbm_roofline.read(ctx) is None
