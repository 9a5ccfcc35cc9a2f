"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Finds the cell in ``BENCHMARK.json``, its configuration and traffic mix in
files of their own, and runs it on the accelerator this process holds (see
``perfbench/harness.py``).  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window.  The last line of standard output is
the result as one JSON object; the numbers compared for ``correct`` are the
last lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits with 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
for p in (CHECKOUT, CHECKOUT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def fail(msg: str) -> int:
    print(f"perfbench: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from perfbench import harness

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chips, JAX sees "
                    f"{len(devices)}")
    return run(cell, args, devices)


def run(cell, args, devices, backend=None, drain_s=60.0, cache=True,
        fault=None) -> int:
    """One run.  The tests steer it: ``backend`` overrides the
    configuration's EXTRACT backend (the kernel in interpret mode),
    ``drain_s`` bounds the wait for open queries after the window,
    ``cache=False`` leaves JAX's compile cache alone, and ``fault`` plants
    one of :mod:`perfbench.faults` under the window."""
    from perfbench import harness, tracing

    say = harness.say
    cache_dir = harness.use_compile_cache() if cache else None
    clock = harness.CompileClock()
    dev = devices[0]
    say(f"device kind={dev.device_kind!r} count={len(devices)} "
        f"compile_cache={cache_dir}")
    setup = harness.setup_run(cell, args.seed, clock, backend=backend)
    setup_s = time.perf_counter() - T_START
    parts = " ".join(f"{k}={v['s']!r}s(compile {v['compile_s']!r}s)"
                     for k, v in setup.parts.items())
    parts += " warm_up: " + " ".join(
        f"{k}={v['s']!r}s({v['compiles']} compiles)"
        for k, v in setup.warm_parts.items())
    say(f"setup_s={setup_s!r} parts: {parts} compiles={clock.compiles} "
        f"persistent_cache={clock.cache}")
    if fault is not None:
        from perfbench import faults

        faults.install(setup.engine, fault)
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        jax = sys.modules["jax"]
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=harness.profile_options())

        def stop():
            jax.profiler.stop_trace()
    else:
        stop = None
    window = harness.serve_window(setup, args.seconds, clock,
                                  drain_s=drain_s, on_window_end=stop)
    used = devices[:cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    e2e = harness.end_to_end(window)
    say(f"window: rounds={window.rounds} passes={window.passes} "
        f"rebuild_s={window.rebuild_s!r} "
        f"compiles_in_window={len(window.compiles)} {window.compiles}"
        f" tuples_scanned={window.tuples_scanned} "
        f"answers={e2e['answers']} latency_samples={e2e['samples']} "
        f"drain_s={window.drain_s!r} sampled_rounds={len(window.records)}")
    setup.engine = None
    gc.collect()
    numbers, counts = harness.checks(setup, window)
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    say(f"checked: extract_sums={counts['extract_sums']} "
        f"failed={counts['failed']} verdicts={counts['verdicts']}"
        f"; worst sum: {counts['extract_worst']}; "
        f"worst answer: {counts['answer_worst']}")
    metrics, out_dev = {}, {"platform": dev.platform, "kind": dev.device_kind,
                            "count": len(devices),
                            "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        summary = tracing.reduce(tracing.find_xplane(trace_dir),
                                 harness.ANNOTATIONS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": summary, "rounds": window.rounds,
               "answers": e2e["answers"],
               "tuples_scanned": window.tuples_scanned,
               "record_bytes": setup.spec.record_bytes,
               "config": cell.config, "device_kind": dev.device_kind}
        for m in cell.per_layer:
            v = harness.load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out_dev["busy_s"] = summary.busy_s
        out_dev["window_s"] = summary.window_s
        breakdown = {"device_ops": summary.top_ops(),
                     "idle_gaps": summary.idle_gaps()}
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result = {"correct": bool(correct),
              "attempted": len(window.requests),
              "failed": counts["failed"], "metrics": metrics,
              "device": out_dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = numbers
    sys.stdout.flush()
    for k, v in numbers.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
