"""CPU tests of the benchmark harness at a tiny table size.

The sizes and the EXTRACT backend are steered from here (``tiny.py``), not
through the command line.  Run with::

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q -p xdist -n 6 \\
        --dist loadfile tests/bench
"""

from __future__ import annotations

import json
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from perfbench import harness, reference, tablegen, traffic
from perfbench import run as bench_run
import bench_tiny as tiny

CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
                 "checks"]


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, workload, capsys, seconds=4.0, trace=0, seed=11, **kw):
    cell = tiny.tiny_cell(root, workload)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
    rc = bench_run.run(cell, args, jax.devices(), drain_s=20.0, cache=False,
                       **kw)
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out


@pytest.mark.parametrize("workload,backend", [
    ("tiny-binary", None), ("tiny-ascii", "pallas-interpret")])
def test_last_line_has_the_contract_keys(root, capsys, workload, backend):
    rc, res, out = _run(root, workload, capsys, backend=backend)
    assert rc == 0
    assert list(res) == CONTRACT_KEYS
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = {"answers_per_s", "answer_p50_s", "answer_p95_s", "setup_s"}
    assert set(res["metrics"]) == names
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    # the numbers compared are the last lines of standard error
    tail = out.err.strip().splitlines()[-len(res["checks"]):]
    assert [ln.split(":")[0] for ln in tail] == [
        f"check {k}" for k in res["checks"]]
    # nothing compiled inside the window
    assert "compiles_in_window=0 " in out.out


def test_traced_run_reports_per_layer_metrics_it_can_read(root, capsys):
    rc, res, _ = _run(root, "tiny-binary", capsys, trace=1)
    assert rc == 0 and res["correct"] is True
    assert list(res) == CONTRACT_KEYS[:5] + ["breakdown", "checks"]
    # a CPU trace has no device plane: only the program counter is read
    assert set(res["metrics"]) == {"tuples_per_answer"}
    assert res["metrics"]["tuples_per_answer"]["value"] > 0


def _tiny_spec(fmt="ascii"):
    return tablegen.TableSpec(num_tuples=4096, num_cols=16, num_chunks=16,
                              zipf_support=100_000, zipf_step=0.25,
                              value_max=1e8 - 1, format=fmt)


def test_every_seed_asks_for_the_same_queries_in_another_order(root):
    cell = tiny.tiny_cell(root, "tiny-ascii")
    clock = harness.CompileClock()

    def draw(seed):
        setup = harness.setup_run(cell, seed, clock, backend="ref")
        subs = traffic.Sessions(setup.templates, cell.mix,
                                setup.traffic_word)
        n = int(cell.mix["sessions"])
        streams = [[subs.next(s).tid for _ in range(20)] for s in range(n)]
        return setup, streams

    (a, sa), (b, sb), (c, sc) = draw(2**33 + 1), draw(2**33 + 1), draw(5)
    # the data, the engine's sampling order and the pool are the same for
    # every seed; the seed deals the streams to the sessions
    for x in (b, c):
        assert all(np.array_equal(u, v) for u, v in
                   zip(a.table.chunks, x.table.chunks))
        assert a.templates == x.templates and a.engine_seed == x.engine_seed
    assert sa == sb and sa != sc
    assert sorted(sa) == sorted(sc)
    assert (a.traffic_word, a.sample_word) != (c.traffic_word, c.sample_word)
    # another data seed makes another table
    spec = _tiny_spec()
    t1, t2 = (tablegen.generate(spec, s, block_chunks=4) for s in (1, 2))
    assert not np.array_equal(np.concatenate(t1.chunks),
                              np.concatenate(t2.chunks))


def test_a_mix_and_a_cell_are_added_by_files_alone(root, capsys):
    """A new mix, its cell's limits and its entry, written into a copy of
    the benchmark's files; no harness file changes, and the cell runs."""
    import hashlib

    def code_digest():
        src = Path(harness.__file__).parent
        return {f.name: hashlib.sha256(f.read_bytes()).hexdigest()
                for f in sorted(src.rglob("*.py"))}

    before = code_digest()
    mix = dict(tiny.TINY_MIX, sessions=3, pool_seed=77,
               epsilon=[0.1], having=None)
    new_root = tiny.make_root(
        root.parent / "added", mix=mix, cells=[
            {"name": "tiny-added", "config": "synth16-binary",
             "traffic": "tiny", "chips": 1, "why": "added by files"}])
    rc, res, _ = _run(new_root, "tiny-added", capsys)
    assert rc == 0 and res["correct"] is True, res["checks"]
    assert res["attempted"] >= 3
    assert code_digest() == before


def test_device_encoding_matches_the_program_codecs():
    from repro.data.formats import AsciiFixedFormat, BinaryBigEndianFormat

    for fmt, codec in (("ascii", AsciiFixedFormat(16)),
                       ("binary", BinaryBigEndianFormat(16))):
        spec = _tiny_spec(fmt)
        tab = tablegen.generate(spec, 3, block_chunks=4)
        vals = tablegen.rank_values(spec)[tab.ranks]
        assert np.array_equal(codec.encode(vals), np.concatenate(tab.chunks))
        assert np.array_equal(
            tablegen.parse(spec, np.concatenate(tab.chunks)),
            tablegen.stored_rank_values(spec)[tab.ranks])


def test_permutation_copy_names_the_program_rows():
    import jax.numpy as jnp

    from repro.sampling.permutation import chunk_seed, permutation_window_dyn

    for master in (0, 2**31 - 7, 4_000_000_000):
        seeds = np.asarray(chunk_seed(jnp.uint32(master),
                                      jnp.arange(9, dtype=jnp.uint32)))
        assert np.array_equal(seeds, reference.chunk_seeds(master, 9))
        for start, count, m in ((0, 300, 16384), (16000, 600, 16384),
                                (5, 100, 1000)):
            got = reference.window_rows(seeds[4], start, count, m, 16384)
            want = permutation_window_dyn(jnp.uint32(seeds[4]), start, count,
                                          m, 16384)
            assert np.array_equal(got, np.asarray(want))


def test_exhausted_pass_carries_open_queries_with_their_clock(root):
    cell = tiny.tiny_cell(root, "tiny-binary", tuples=4096, chunks=64,
                          budget=16)
    mix = dict(cell.mix)
    mix["selectivity"] = {"dist": "uniform", "lo": 0.05, "hi": 0.1}
    mix["epsilon"] = [0.02]
    cell.mix = mix
    clock = harness.CompileClock()
    setup = harness.setup_run(cell, 3, clock)
    win = harness.serve_window(setup, 4.0, clock, drain_s=30.0)
    assert win.passes >= 2
    carried = [r for r in win.requests if r.passes > 1]
    assert carried, "no query outlived its pass"
    assert all(r.t_answer is not None for r in win.requests)
    first_rebuild = win.rebuilds[0]
    assert any(r.t_first < first_rebuild < r.t_answer for r in carried)


def test_mix_needs_fit_one_pass_and_counts(capsys):
    """Tuples each template needs (simple random sampling at its ε), from a
    2**20-tuple sample of the full-size distribution, against one pass of
    the 2**23-tuple table; printed for PERF.md."""
    spec = tablegen.TableSpec.from_dict(
        json.loads((tiny.CHECKOUT / "perfbench/configs/synth16-ascii.json")
                   .read_text())["table"])
    full = spec.num_tuples
    spec = tablegen.TableSpec(**{**spec.__dict__, "num_tuples": 2**20,
                                 "num_chunks": 64})
    tab = tablegen.generate(spec, 17, block_chunks=16)
    ex = reference.Exact(spec, tab.ranks)
    round_rows = 8 * 4096
    with capsys.disabled():
        for name in ("scan", "short"):
            mix = traffic.load_mix(name)
            pool = traffic.build_templates(mix, ex)
            need = np.asarray([traffic.tuples_needed(ex, t) for t in pool])
            print(f"\nmix {name}: tuples needed per template: median "
                  f"{np.median(need):.0f}, p90 {np.percentile(need, 90):.0f}"
                  f", max {need.max():.0f} = {need.max() / full:.3f} of a "
                  f"pass; rounds of {round_rows} tuples: median "
                  f"{np.median(need) / round_rows:.2f}, max "
                  f"{need.max() / round_rows:.1f}; share within the 4096-"
                  f"tuple synopsis {np.mean(need <= 4096):.3f}")
            assert need.max() < full, name


def test_no_topology_is_described_at_import():
    src = Path(harness.__file__).parent
    for f in src.rglob("*.py"):
        if f.parent.name != "tests":
            assert "topolog" not in f.read_text(), f
