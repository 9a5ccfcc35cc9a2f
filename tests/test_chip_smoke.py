"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The smoke itself needs a TPU.  Here its phases run on the Pallas interpreter
(``"pallas-interpret"``) over a small table, so the suite covers the smoke's
control flow and its answer checks; the script's refusal to run without a
TPU, or without the rest of the repository, is checked too.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]

# a table small enough for the interpreter, scanned 16 tuples per worker per
# round so that the grouped query folds enough rounds to promote its cells.
# The scan ends before every answer meets its ε, so the checks run with
# strict=False: each answer lies within 3·max(ε, its own CI half-width) of
# the exact one.
TUPLES, CHUNKS, BUDGET = 2048, 16, 16


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def table(smoke):
    return smoke.make_table(0, TUPLES, CHUNKS)


def test_serve_phase_answers_within_bound(smoke, table):
    values, store = table
    out = smoke.serve_phase(store, values, backend="pallas-interpret",
                            budget=BUDGET, strict=False)
    names = [r.name for r in out["results"]]
    assert names == ["sum_range", "count_range", "having_sum", "avg_all",
                     "topk_groups"]
    having = out["results"][2]
    assert having.decision == 1          # the exact answer is below it
    groups = out["results"][-1].groups
    assert sum(not g.is_other for g in groups) >= smoke.TOP_K
    assert out["handout"].shape[1] == smoke.WORKERS


def test_parity_phase_interpreter_vs_ref(smoke, table):
    _, store = table
    runs = smoke.parity_phase(store, backend="pallas-interpret", rounds=4,
                              budget=64)
    assert set(runs) == {"pallas-interpret", "ref"}
    assert runs["ref"]["m"].sum() > 0


def test_checks_reject_a_wrong_answer(smoke, table):
    """The bound is not vacuous: an estimate 4ε off, an answer that did not
    meet its ε, or a wrong HAVING verdict fails the smoke."""
    values, _ = table
    q = smoke.base_queries()[0]
    exact = smoke.exact_answer(values, q)

    def result(est, decision=-1, err=0.01):
        return types.SimpleNamespace(estimate=est, decision=decision, err=err,
                                     rounds_resident=1, tuples_seen=1)

    smoke.check_answer(values, q, result(exact * (1 + 2 * smoke.EPS)))
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_answer(values, q, result(exact * (1 + 4 * smoke.EPS)))
    # strict: an answer that never met its ε fails however close it is
    with pytest.raises(smoke.SmokeFailure, match="without meeting"):
        smoke.check_answer(values, q, result(exact, err=0.2))
    smoke.check_answer(values, q, result(exact * 1.5, err=0.2), strict=False)
    having = smoke.workload(values)[2][0]
    exact = smoke.exact_answer(values, having)
    with pytest.raises(smoke.SmokeFailure, match="HAVING"):
        smoke.check_answer(values, having, result(exact, decision=0))


def test_main_fails_without_tpu(smoke, capsys):
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is present")
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""


def test_script_fails_alone(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repository, the script exits non-zero and prints no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


_SPMD_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import jax
import chip_smoke as smoke
values, store = smoke.make_table(0, int(sys.argv[2]), int(sys.argv[3]))
mesh = jax.make_mesh((4,), ("data",))
smoke.spmd_phase(store, values, mesh, backend="pallas-interpret",
                 budget=int(sys.argv[4]), strict=False)
print("SPMD_OK")
"""


def test_spmd_phase_on_four_cpu_devices():
    """The ``--chips 4`` phase on four forced CPU devices: the server on a
    4-device mesh hands out chunks as one device does."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SPMD_SCRIPT, str(ROOT), str(TUPLES),
         str(CHUNKS), str(BUDGET)], env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SPMD_OK" in out.stdout


def test_exact_groups_match_a_python_loop(smoke):
    """The numpy GROUP BY reference against a plain loop."""
    rng = np.random.default_rng(1)
    values = np.zeros((200, smoke.COLS))
    values[:, smoke.GROUP_COL] = rng.integers(0, 5, 200) * 1000.0
    values[:, 1] = rng.uniform(0, 1e6, 200)
    q = smoke.workload(np.ones((4, smoke.COLS)))[-1][0]
    keys, sums = smoke.exact_groups(values, q)
    for k, s in zip(keys, sums):
        rows = values[values[:, smoke.GROUP_COL] == k]
        assert s == pytest.approx(float(sum(rows[:, 1])), rel=1e-12)


_CACHE_SCRIPT = r"""
import json, jax, jax.numpy as jnp
from repro.compile_cache import use_compile_cache
path = use_compile_cache()
if jax.config.jax_compilation_cache_dir != path:
    raise SystemExit("cache dir not applied")
if path.endswith(".jax_cache"):
    print(json.dumps(path))          # placed, nothing compiled into the repo
else:
    jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
    print(json.dumps(path))
"""


def test_compile_cache_placement(tmp_path):
    """Entry points cache in $JAX_COMPILATION_CACHE_DIR when it is set (and
    entries land there), else in <checkout>/.jax_cache."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    run = [sys.executable, "-c", _CACHE_SCRIPT]
    out = subprocess.run(run, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == f'"{ROOT / ".jax_cache"}"'

    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    out = subprocess.run(run, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == f'"{tmp_path}"'
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())
