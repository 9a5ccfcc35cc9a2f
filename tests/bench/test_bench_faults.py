"""A run with its timed path broken underneath must come out not correct.

Each fault of :mod:`perfbench.faults` that a one-chip cell can have is
planted under the window of a tiny CPU run, past the harness's look for a
chip; ``correct`` must read false, and the number that catches it is
named.
"""

from __future__ import annotations

import json
import types

import jax
import pytest

from perfbench import run as bench_run
import bench_tiny as tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("fault,caught_by", [
    ("unchanged", "unanswered"),
    ("half_batch", "extract_sum_err"),
    ("answer_altered", "answer_err_eps"),
    ("verdict_inverted", "verdict_self"),
])
def test_planted_fault_is_not_correct(root, capsys, fault, caught_by):
    cell = tiny.tiny_cell(root, "tiny-binary")
    args = types.SimpleNamespace(seed=23, seconds=3.0, trace=0)
    rc = bench_run.run(cell, args, jax.devices(), drain_s=3.0, cache=False,
                       fault=fault)
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert res["correct"] is False
    c = res["checks"][caught_by]
    assert c["value"] > c["limit"], res["checks"]
