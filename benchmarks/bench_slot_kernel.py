"""Round-step extraction throughput: ref vs pallas vs pallas-interpret.

Times one engine round step (gather + parse + slot eval + merge) of the
slot-table plane over the default synthetic table, sweeping the slot count S
and the per-worker tuple budget B.  Headline metrics are tuples/s and bytes/s
of raw extraction per round step — the system's scarce resource.

Backends:

* ``ref``              — the decode_ref + ``slot_evaluate`` composition
                         (materializes the (S, W, B) eval tensor);
* ``pallas``           — the fused ``kernels/slot_extract.py`` kernel,
                         compiled (TPU only — skipped off-TPU);
* ``pallas-interpret`` — the same kernel under the Pallas interpreter
                         (correctness mode; numbers reported for visibility
                         but exempt from any speedup bar).

The acceptance bar — fused pallas ≥ 2× ref round-step throughput at
S=8, B=256 — applies to the *compiled* kernel; off-TPU the result file
records ``speedup_pallas_vs_ref: null`` with ``interpret_exempt: true``.

The ``calibration`` block (measured aggregate extraction tuples/s of the
production backend plus measured raw-read bytes/s) is what
``repro.serve.ola_server.load_measured_rates`` feeds into the Eq. (4) plan
selector in place of the modeled constants.  It also records the linear fit
of the S sweep — ``round_us(S) = round_base_us + round_slot_us · S`` — from
which the workload scheduler derives its *measured* per-round slot capacity
(``repro.sched.fairness.measured_slot_capacity``): the base term is the
scan-side cost of one round, the slope the marginal cost of one
fully-counted slot evaluation.

Results land in ``BENCH_slot_kernel.json`` at the repo root.

Standalone:  PYTHONPATH=src python -m benchmarks.bench_slot_kernel [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core.engine import EngineConfig, SlotOLAEngine, _Collectives
from repro.core.queries import (
    Linear,
    Query,
    Range,
    empty_slot_table,
    encode_slot,
    slot_table_set,
)
from repro.data.generator import make_synthetic_zipf, store_dataset

NUM_COLS = 8
WORKERS = 8


def _slot_table(s: int, seed: int = 1):
    """S active linear+range slots with varied selectivities."""
    rng = np.random.default_rng(seed)
    coeffs = tuple(1.0 / (k + 1) for k in range(NUM_COLS))
    table = empty_slot_table(s, NUM_COLS)
    for i in range(s):
        q = Query(agg=("sum", "count", "avg")[i % 3], expr=Linear(coeffs),
                  pred=Range(i % NUM_COLS, 0.0,
                             float(rng.uniform(0.3, 1.0)) * 1e8),
                  epsilon=0.05, name=f"s{i}")
        table = slot_table_set(table, i, encode_slot(q, NUM_COLS))
    return table


def _make_step(engine: SlotOLAEngine, b: int):
    """Non-donating jitted round step (state is reused across timing reps)."""
    coll = _Collectives()

    def step(state, table, packed, speeds):
        return engine.program.round_body(state, packed, speeds, b, coll,
                                         slots=table)

    return jax.jit(step)


def _time_round_step(store, backend: str, s: int, b: int, iters: int):
    # backend is a valid EngineConfig.extract_backend value; in particular
    # "pallas-interpret" forces the Pallas interpreter even on TPU, keeping
    # the three lanes distinct there
    cfg = EngineConfig(num_workers=WORKERS, budget_init=b, budget_min=b,
                       budget_max=b, seed=7, extract_backend=backend)
    engine = SlotOLAEngine(store, s, cfg)
    table = _slot_table(s)
    state0 = engine.init_state()
    step = _make_step(engine, b)
    # one round advances claims so every worker holds a chunk; time from there
    state, rep = step(state0, table, engine.packed, engine.speeds)
    jax.block_until_ready(rep)
    tuples_round = float(rep.tuples_round)
    t0 = time.perf_counter()
    for _ in range(iters):
        _, rep = step(state, table, engine.packed, engine.speeds)
    jax.block_until_ready(rep)
    dt = (time.perf_counter() - t0) / iters
    tuples_round = max(float(rep.tuples_round), tuples_round)
    return {
        "backend": backend, "S": s, "B": b,
        "us_per_round": round(dt * 1e6, 1),
        "tuples_per_round": int(tuples_round),
        "tuples_per_sec": round(tuples_round / dt, 1),
        "bytes_per_sec": round(
            tuples_round * store.codec.record_bytes / dt, 1),
    }


def _round_cost_fit(entries, backend: str, b: int) -> tuple:
    """Least-squares fit ``round_us(S) = base + slot_us·S`` over the S sweep
    of one ``(backend, B)`` lane — the scheduler's measured-capacity input.
    Returns ``(base_us, slot_us)``, or ``(0.0, 0.0)`` when the sweep has
    fewer than two S points or the fit is degenerate (non-positive base or
    slope: timing noise measured extra slots as free)."""
    pts = sorted({(e["S"], e["us_per_round"]) for e in entries
                  if e["backend"] == backend and e["B"] == b})
    if len(pts) < 2:
        return 0.0, 0.0
    s = np.asarray([p[0] for p in pts], float)
    us = np.asarray([p[1] for p in pts], float)
    slot_us, base_us = np.polyfit(s, us, 1)
    if not (np.isfinite(base_us) and np.isfinite(slot_us)
            and base_us > 0.0 and slot_us > 0.0):
        return 0.0, 0.0
    return float(base_us), float(slot_us)


def _measure_read_bw(store, iters: int = 5) -> float:
    """Raw READ bandwidth proxy: a full reduction over the packed device
    buffer (the chunks are memory-resident — the NoDB cache — so READ is
    memory traffic, not disk)."""
    packed, _ = store.packed_device_view()
    import jax.numpy as jnp

    buf = jnp.asarray(packed)
    red = jax.jit(lambda x: jnp.sum(x.astype(jnp.uint32)))
    jax.block_until_ready(red(buf))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = red(buf)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    return buf.size / dt


def run(fast: bool = False, smoke: bool = False) -> str:
    if smoke:
        t, chunks, iters = 2048, 8, 2
        s_sweep, b_sweep = [4, 8], [64, 256]
    elif fast:
        t, chunks, iters = 8192, 16, 3
        s_sweep, b_sweep = [1, 8], [64, 256]
    else:
        t, chunks, iters = 32768, 32, 5
        s_sweep, b_sweep = [1, 8, 32], [64, 256, 1024]
    store = store_dataset(make_synthetic_zipf(t, NUM_COLS, seed=0), chunks,
                          "ascii")
    on_tpu = jax.default_backend() == "tpu"
    backends = ["ref", "pallas-interpret"] + (["pallas"] if on_tpu else [])

    entries = []
    for s in s_sweep:
        for b in b_sweep:
            for be in backends:
                e = _time_round_step(store, be, s, b, iters)
                entries.append(e)
                print(f"[bench_slot_kernel] {be:16s} S={s:3d} B={b:5d}  "
                      f"{e['us_per_round']:10.1f} us/round  "
                      f"{e['tuples_per_sec']:12.0f} tuples/s")

    def _at(be, s, b):
        for e in entries:
            if (e["backend"], e["S"], e["B"]) == (be, s, b):
                return e
        return None

    s_bar = 8 if 8 in s_sweep else s_sweep[-1]
    b_bar = 256 if 256 in b_sweep else b_sweep[-1]
    ref_bar = _at("ref", s_bar, b_bar)
    pallas_bar = _at("pallas", s_bar, b_bar)
    interp_bar = _at("pallas-interpret", s_bar, b_bar)
    speedup = (round(pallas_bar["tuples_per_sec"] / ref_bar["tuples_per_sec"],
                     3) if pallas_bar else None)

    from benchmarks.common import memory_report, runner_fingerprint

    io_bps = _measure_read_bw(store)
    # calibration uses the production backend for this platform: the compiled
    # kernel on TPU, the XLA ref path elsewhere (interpret is a debug mode)
    cal_entry = pallas_bar if on_tpu and pallas_bar else ref_bar
    base_us, slot_us = _round_cost_fit(entries, cal_entry["backend"], b_bar)
    out = {
        "platform": jax.default_backend(),
        "workers": WORKERS,
        "table_tuples": t,
        "record_bytes": store.codec.record_bytes,
        "S_sweep": s_sweep,
        "B_sweep": b_sweep,
        "entries": entries,
        "speedup_pallas_vs_ref": speedup,
        "speedup_interpret_vs_ref": round(
            interp_bar["tuples_per_sec"] / ref_bar["tuples_per_sec"], 3),
        "interpret_exempt": not on_tpu,
        "memory": memory_report(),
        "fingerprint": runner_fingerprint(),
        "calibration": {
            "backend": cal_entry["backend"],
            "S": cal_entry["S"], "B": cal_entry["B"],
            "workers": WORKERS,
            "cpu_tuples_per_sec": cal_entry["tuples_per_sec"],
            "io_bytes_per_sec": round(io_bps, 1),
            # extraction cost of the calibration codec: lets select_plan
            # rescale the tuple rate when serving a different codec
            "cost_per_tuple": float(store.codec.extract_cost_per_tuple()),
            # S-sweep round-cost fit: round_us(S) = base + slot_us·S.  Feeds
            # the scheduler's measured slot capacity; 0.0 = fit unavailable
            "round_base_us": round(base_us, 1),
            "round_slot_us": round(slot_us, 2),
        },
    }
    from benchmarks.common import bench_output_paths

    for path in bench_output_paths("slot_kernel"):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    print(f"[bench_slot_kernel] calibration: "
          f"{out['calibration']['cpu_tuples_per_sec']:.0f} tuples/s "
          f"({out['calibration']['backend']}), "
          f"read {io_bps / 1e9:.2f} GB/s")
    return json.dumps({
        "speedup_pallas_vs_ref": speedup,
        "interpret_exempt": out["interpret_exempt"],
        "ref_tuples_per_sec": ref_bar["tuples_per_sec"],
        "cal_tuples_per_sec": out["calibration"]["cpu_tuples_per_sec"],
    })


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for the CI bench-smoke step")
    args = ap.parse_args()
    run(fast=args.fast, smoke=args.smoke)


if __name__ == "__main__":
    use_compile_cache()
    main()
