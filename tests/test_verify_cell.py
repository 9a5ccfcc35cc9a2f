"""OLA-verify production cell: sharded-store round soundness (subprocess)."""

import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.launch.verify_cell as vc

# shrink the production program for a functional run
def small_program(budget, queues=1):
    from repro.core.engine import EngineConfig, EngineProgram
    from repro.core.queries import Column, Query, Range, TRUE
    from repro.data.formats import AsciiFixedFormat
    codec = AsciiFixedFormat(6)
    # no HAVING: a decided query stops, and once every query has stopped
    # the round closes chunks early, so the last estimate is no census
    queries = [
        Query(agg="avg", expr=Column(1), pred=TRUE, epsilon=1e-9,
              name="avg_quality"),
        Query(agg="avg", expr=Column(3), pred=TRUE, epsilon=1e-9,
              name="avg_dup"),
        Query(agg="count", pred=Range(0, 0.0, 16.0), epsilon=1e-9,
              name="short_docs"),
    ]
    cfg = EngineConfig(num_workers=8, strategy="resource_aware",
                       budget_init=budget, seed=0,
                       residency="sharded" if queues > 1 else "packed")
    sizes = np.full(16, 64, np.int64)
    return EngineProgram(codec=codec, queries=queries, config=cfg,
                         n_chunks=16, m_max=64, chunk_sizes=sizes,
                         queues=queues), cfg, codec

vc.production_verify_program = lambda **kw: small_program(
    kw.get("budget", 16), kw.get("queues", 1))

mesh = jax.make_mesh((8,), ("data",))
fn, args, program = vc.build_verify_cell(mesh, layout="sharded", budget=16)
step = jax.jit(fn, donate_argnums=(0,))

# real data: 16 chunks x 64 tuples x 6 cols
rng = np.random.default_rng(0)
vals = np.stack([rng.uniform(0, 100, (64, 6)) for _ in range(16)])
raw = np.stack([program.codec.encode(v) for v in vals])
packed = jax.device_put(jnp.asarray(raw), NamedSharding(mesh, P("data")))
speeds = jax.device_put(jnp.ones(8, jnp.float32), NamedSharding(mesh, P("data")))
state = jax.device_put(program.init_state(),
                       jax.tree.map(lambda s: NamedSharding(mesh, s),
                                    __import__("repro.core.engine_spmd",
                                               fromlist=["engine_state_specs"]).engine_state_specs(),
                                    is_leaf=lambda x: isinstance(x, P)))
rep = None
for _ in range(100):
    state, rep = step(state, packed, speeds)
    if bool(rep.exhausted):
        break
flat = vals.reshape(-1, 6)
truth_q = flat[:, 1].mean()
truth_d = flat[:, 3].mean()
truth_c = float(((flat[:, 0] >= 0) & (flat[:, 0] < 16)).sum())
est = np.asarray(rep.estimate, np.float64)
print(json.dumps({
    "exhausted": bool(rep.exhausted),
    "census": bool((np.asarray(state.scan_m) == 64).all()),
    "est": est.tolist(),
    "truth": [truth_q, truth_d, truth_c],
    "rel_err": [abs(est[0]-truth_q)/truth_q, abs(est[1]-truth_d)/truth_d,
                abs(est[2]-truth_c)/max(truth_c,1)],
}))
"""


@pytest.mark.slow
def test_sharded_verify_round_exact_at_exhaustion():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["exhausted"] and res["census"], res
    assert all(e < 5e-3 for e in res["rel_err"]), res
