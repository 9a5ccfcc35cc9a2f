"""A tiny copy of the benchmark's cells for CPU tests.

``make_root`` lays out a directory that holds what the harness reads: a
``BENCHMARK.json`` with the repository's entries plus the tiny cells, and
``perfbench/{configs,mixes,limits}`` with the repository's files plus the
tiny mix.  New cells and mixes are added as files only.  ``tiny_cell``
then cuts a configuration's table and rounds to CPU size in memory.
"""

from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

from perfbench import harness

CHECKOUT = Path(__file__).resolve().parents[2]

TINY_MIX = {
    "why": "CPU rehearsal: wide ranges at loose epsilon, every query kind",
    "source": "the scan mix's generator at a size a CPU test can hold",
    "assumed": {"sessions": "six, fewer than a pass's rounds"},
    "pool_seed": 5,
    "sessions": 6,
    "templates": 64,
    "aggregates": {"sum": 0.5, "count": 0.3, "avg": 0.2},
    "expression": "inverse_column",
    "predicate_cols": [0, 8],
    "selectivity": {"dist": "uniform", "lo": 0.4, "hi": 1.0},
    "epsilon": [0.05, 0.1],
    "having": {"share_of_sum": 0.25, "gap_eps": 2.0},
    "grouped": None,
}
TINY_CELLS = [
    {"name": "tiny-ascii", "config": "synth16-ascii", "traffic": "tiny",
     "chips": 1, "why": "CPU rehearsal"},
    {"name": "tiny-binary", "config": "synth16-binary", "traffic": "tiny",
     "chips": 1, "why": "CPU rehearsal"},
]
TINY_LIMITS = {"answer_err_eps": 3.0, "topk_wrong": 0, "verdict_self": 0,
               "verdict_wrong": 0.5, "unanswered": 0,
               "extract_sum_err": 1e-5}


def make_root(tmp: Path, mix: dict = TINY_MIX, cells=TINY_CELLS) -> Path:
    with open(CHECKOUT / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["workloads"] += cells
    (tmp / "perfbench").mkdir(parents=True, exist_ok=True)
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    for sub in ("configs", "mixes", "limits"):
        shutil.copytree(CHECKOUT / "perfbench" / sub, tmp / "perfbench" / sub,
                        dirs_exist_ok=True)
    with open(tmp / "perfbench" / "mixes" / "tiny.json", "w") as f:
        json.dump(mix, f)
    for c in cells:
        with open(tmp / "perfbench" / "limits" / f"{c['name']}.json",
                  "w") as f:
            json.dump(TINY_LIMITS, f)
    return tmp


def tiny_cell(root: Path, workload: str, tuples: int = 16384,
              chunks: int = 64, budget: int = 64, workers: int = 4,
              synopsis: int = 1024) -> harness.Cell:
    """The cell as the repository defines it, with its table and rounds cut
    to CPU size (the synopsis budget keeps the extraction cache at 64 rows
    a chunk, as at full size)."""
    cell = harness.load_cell(workload, root)
    cfg = copy.deepcopy(cell.config)
    cfg["table"].update(num_tuples=tuples, num_chunks=chunks)
    cfg["engine"].update(budget=budget, num_workers=workers)
    cfg["server"].update(synopsis_budget_tuples=synopsis)
    cell.config = cfg
    return cell
