"""The control, at a size a test run can hold: the reference put in the
program's place one precision lower (bfloat16 operands, float32
accumulation) must fail ``correct`` where the program passes."""

from __future__ import annotations

import pytest

from perfbench import control, harness, reference
import bench_tiny as tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", ["tiny-ascii", "tiny-binary"])
def test_control_fails_where_the_program_passes(root, workload):
    cell = tiny.tiny_cell(root, workload)
    clock = harness.CompileClock()
    setup = harness.setup_run(cell, 31, clock, backend="ref")
    win = harness.serve_window(setup, 3.0, clock, drain_s=20.0)
    sound = control.readings(setup, win)
    limit = cell.limits["extract_sum_err"]
    assert sound["extract_sums"] > 0
    assert sound["extract_sum_err"] <= limit
    ctrl, n, _ = harness.check_extract(setup, win, bf16=True)
    assert n == sound["extract_sums"]
    assert ctrl > 3 * limit, (ctrl, limit)
    exact_bf16 = reference.Exact(setup.spec, setup.table.ranks, bf16=True)
    assert control.control_answers(setup, win, exact_bf16) >= 0.0
