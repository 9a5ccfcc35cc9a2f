"""Readings that set the limits of ``correct``: sound runs, the control
and the planted faults, several seeds in one process.

    python3 perfbench/control.py --workload NAME --seeds 1,2,3 \\
        --seconds S [--fault-seconds F] [--faults verdict_inverted]

It builds the cell once (the table, the engine and the pool are the same
for every seed) and, for each seed, serves one window of the program as it
is (the sound reading), reads the control on the same answers and rounds,
and serves one short window under each planted fault.  The control is the
reference put in the program's place one precision lower: values parsed and
the expression evaluated with bfloat16 operands (float32 accumulation),
the step that dropping ``precision=HIGHEST`` on the TPU would take.  Its
EXTRACT reading is given over all sums and over the slot sums alone (the
group cells aside).  One JSON line per seed goes to standard output.  The
benchmark's own runs do not run this.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent
for p in (CHECKOUT, CHECKOUT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def control_answers(setup, window, exact_bf16) -> float:
    """The answers' number as the control reads it: each answered query's
    exact answer computed in the control's precision, against float64."""
    from perfbench.harness import _err_eps

    worst = 0.0
    for req in window.requests:
        t = req.template
        if req.result is None:
            continue
        if t.grouped:
            keys, want = setup.exact.groups(t)
            _, got = exact_bf16.groups(t)
            for i in range(len(keys)):
                worst = max(worst, _err_eps(got[i], want[i], t.epsilon))
        else:
            worst = max(worst, _err_eps(exact_bf16.answer(t),
                                        setup.exact.answer(t), t.epsilon))
    return worst


SLOT_STATS = ("ysum", "ysq", "psum")


def readings(setup, window, bf16: bool = False) -> dict:
    from perfbench import harness

    a = harness.check_answers(setup, window)
    ext, n, at = harness.check_extract(setup, window, bf16=bf16)
    slot, n_slot, _ = harness.check_extract(setup, window, bf16=bf16,
                                            stats=SLOT_STATS)
    return {**{k: a[k] for k in ("answer_err_eps", "topk_wrong", "unanswered",
                                 "verdict_self", "verdict_wrong",
                                 "verdicts")},
            "extract_sum_err": ext, "extract_sums": n,
            "extract_sum_err_slots": slot, "slot_sums": n_slot,
            "answers": sum(r.result is not None for r in window.requests),
            "worst_answer": a["worst"], "worst_sum": at}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault-seconds", type=float, default=10.0)
    ap.add_argument("--faults", default="verdict_inverted")
    args = ap.parse_args(argv)

    import jax

    from perfbench import faults, harness, reference

    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    clock = harness.CompileClock()
    seeds = [int(s) for s in args.seeds.split(",")]
    setup = harness.setup_run(cell, seeds[0], clock)
    exact_bf16 = reference.Exact(setup.spec, setup.table.ranks, bf16=True)
    for seed in seeds:
        t0 = time.perf_counter()
        setup.reseed(seed)
        win = harness.serve_window(setup, args.seconds, clock)
        sound = readings(setup, win)
        control = readings(setup, win, bf16=True)
        control = {k: control[k] for k in ("extract_sum_err",
                                           "extract_sum_err_slots",
                                           "worst_sum")}
        control["answer_err_eps"] = control_answers(setup, win, exact_bf16)
        planted = {}
        for name in [f for f in args.faults.split(",") if f]:
            undo = faults.install(setup.engine, name)
            fw = harness.serve_window(setup, args.fault_seconds, clock,
                                      drain_s=10.0)
            undo()
            planted[name] = readings(setup, fw)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "sound": sound, "control": control,
                          "faults": planted, "compiles_in_window":
                          win.compiles,
                          "seconds": time.perf_counter() - t0}), flush=True)
        del win
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
