"""EXTRACT hot-spot microbenchmarks.

Times the production CPU path (pure-jnp oracle compiled by XLA — what the
engine executes on this host) for the three kernels, and reports the
interpret-mode Pallas checksum agreement.  TPU wall-times come from the
target hardware; on CPU the value of the Pallas kernels is validated
semantics + the VMEM-tiled structure the dry-run lowers.
"""

from __future__ import annotations

import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.queries import Linear, Query, Range, TRUE, linear_plan
from repro.data.formats import AsciiFixedFormat
from repro.kernels import chunk_agg, extract_parse, round_stats


def _time(fn, *args, reps=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run(fast: bool = False) -> str:
    c = 8
    t = 4096 if fast else 16384
    fmt = AsciiFixedFormat(c)
    rng = np.random.default_rng(0)
    vals = rng.uniform(-1e6, 1e6, (t, c))
    raw = jnp.asarray(fmt.encode(vals))
    qs = [Query(agg="sum", expr=Linear((1.0,) * c), pred=Range(0, -1e5, 1e5)),
          Query(agg="count", pred=TRUE)]
    plan = linear_plan(qs, c)

    out = {}
    t_parse = _time(lambda r: extract_parse(r, c, backend="ref"), raw)
    out["extract_parse"] = {
        "us_per_call": round(t_parse * 1e6, 1),
        "mtuples_per_s": round(t / t_parse / 1e6, 2),
    }

    n = 8
    m = t // n
    raw3 = jnp.asarray(np.stack([fmt.encode(vals[i * m:(i + 1) * m])
                                 for i in range(n)]))
    sizes = jnp.full((n,), m, jnp.int32)
    t_agg = _time(lambda r: chunk_agg(r, sizes, plan.coeffs, plan.lo, plan.hi,
                                      backend="ref"), raw3)
    out["chunk_agg"] = {"us_per_call": round(t_agg * 1e6, 1),
                        "mtuples_per_s": round(t / t_agg / 1e6, 2)}

    w, b = 8, 256
    slab = jnp.asarray(np.stack([fmt.encode(vals[i * b:(i + 1) * b])
                                 for i in range(w)]))
    beff = jnp.full((w,), b, jnp.int32)
    t_rs = _time(lambda s: round_stats(s, beff, plan.coeffs, plan.lo, plan.hi,
                                       backend="ref"), slab)
    out["round_stats"] = {"us_per_call": round(t_rs * 1e6, 1),
                          "mtuples_per_s": round(w * b / t_rs / 1e6, 2)}

    # pallas interpret-mode agreement (semantics checksum)
    a = extract_parse(raw[:256], c, backend="pallas-interpret")
    r = extract_parse(raw[:256], c, backend="ref")
    out["pallas_interpret_max_err"] = float(jnp.max(jnp.abs(a - r)))

    with open("results/bench_kernels.json", "w") as f:
        json.dump(out, f, indent=1)
    return json.dumps(out)
