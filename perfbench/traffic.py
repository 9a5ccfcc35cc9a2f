"""One general generator for every traffic mix.

A mix is a JSON file of parameters under ``perfbench/mixes/``.  From it and
the seed this module builds a pool of query templates and the order in
which the closed loop submits them.  The query mix (SUM/COUNT/AVG in
proportions 0.5/0.3/0.2, a selectivity and an ε drawn per query) is the one
of ``benchmarks/bench_workload.py`` (``build_queries``).

Every seed asks for the same work in another order.  The pool and the
streams the sessions walk are drawn from the mix's own ``pool_seed``; the
run's seed only deals the streams to the sessions, which changes the order
in which they are first submitted and queue for slots.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from perfbench.reference import Exact, Template

MIX_DIR = Path(__file__).resolve().parent / "mixes"


def load_mix(name: str, directory: Path = MIX_DIR) -> dict:
    with open(directory / f"{name}.json") as f:
        mix = json.load(f)
    mix["name"] = name
    return mix


def _expression(mix: dict, num_cols: int) -> tuple:
    if mix["expression"] != "inverse_column":
        raise ValueError(f"unknown expression {mix['expression']!r}")
    return tuple(1.0 / (k + 1) for k in range(num_cols))


def _selectivities(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified selectivities: the mid-quantiles of the mix's
    distribution."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(dist["lo"]), float(dist["hi"])
    if dist["dist"] == "loguniform":
        return np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    if dist["dist"] == "uniform":
        return lo + u * (hi - lo)
    raise ValueError(f"unknown selectivity distribution {dist['dist']!r}")


def _counts(total: int, shares: dict) -> dict:
    keys = list(shares)
    out = {k: int(round(total * float(shares[k]))) for k in keys[:-1]}
    out[keys[-1]] = total - sum(out.values())
    return out


def build_templates(mix: dict, exact: Exact) -> list[Template]:
    """The mix's template pool, the same for every seed."""
    spec = exact.spec
    n = int(mix["templates"])
    grouped = mix.get("grouped")
    n_group = int(round(n * float(grouped["share"]))) if grouped else 0
    rest = n - n_group
    fixed = np.random.default_rng([int(mix["pool_seed"]), 0])
    aggs = []
    for agg, k in _counts(rest, mix["aggregates"]).items():
        aggs += [agg] * k
    aggs = np.asarray(aggs)[fixed.permutation(rest)]
    eps = np.resize(np.asarray(mix["epsilon"], float), rest)[
        fixed.permutation(rest)]
    sel = _selectivities(mix["selectivity"], rest)[fixed.permutation(rest)]
    rng = np.random.default_rng([int(mix["pool_seed"]), 1])
    c0, c1 = mix["predicate_cols"]
    cols = rng.integers(c0, c1, rest)
    starts = rng.random(rest)
    hv = mix.get("having")
    sums = np.flatnonzero(aggs == "sum")
    n_having = int(round(len(sums) * float(hv["share_of_sum"]))) if hv else 0
    having_at = set(fixed.permutation(sums)[:n_having].tolist())
    coeffs = _expression(mix, spec.num_cols)
    out = []
    for i in range(rest):
        a = starts[i] * (1.0 - sel[i])
        r_lo = exact.quantile_rank(int(cols[i]), a)
        r_hi = max(exact.quantile_rank(int(cols[i]), a + sel[i]), r_lo)
        t = Template(tid=len(out), agg=str(aggs[i]), coeffs=coeffs,
                     pred_col=int(cols[i]), lo=(r_lo - 0.5) * spec.step,
                     hi=(r_hi + 0.5) * spec.step, epsilon=float(eps[i]),
                     selectivity=float(sel[i]))
        if i in having_at:
            gap = float(hv["gap_eps"]) * t.epsilon
            sign = 1.0 if rng.random() < 0.5 else -1.0
            op = "<" if rng.random() < 0.5 else ">"
            thr = exact.answer(t) * (1.0 + sign * gap)
            t = Template(**{**t.__dict__, "having": (op, thr)})
        out.append(t)
    for _ in range(n_group):
        unit = tuple(1.0 if k == grouped["expr_col"] else 0.0
                     for k in range(spec.num_cols))
        out.append(Template(
            tid=len(out), agg=grouped["agg"], coeffs=unit,
            epsilon=float(grouped["epsilon"]),
            group_col=int(grouped["group_col"]),
            max_groups=int(grouped["max_groups"]),
            top_k=int(grouped["top_k"])))
    return out


class Sessions:
    """The closed loop's queries.  The pool, in an order drawn from the
    mix's ``pool_seed``, is dealt into one stream per session, and a session
    walks its stream round and round.  The seed deals the streams to the
    sessions: the queries a window asks for are the same on every seed."""

    def __init__(self, templates: list, mix: dict, seed_word: int):
        n = int(mix["sessions"])
        order = np.random.default_rng([int(mix["pool_seed"]), 2]).permutation(
            len(templates))
        self.templates = templates
        self.streams = [order[k::n] for k in range(n)]
        self.deal = np.random.default_rng([int(seed_word), 2]).permutation(n)
        self.pos = [0] * n

    def next(self, session: int) -> Template:
        stream = self.streams[self.deal[session]]
        k = self.pos[session]
        self.pos[session] += 1
        return self.templates[stream[k % len(stream)]]


# ------------------------------------------------- what a mix needs ----

def tuples_needed(exact: Exact, t: Template, z: float = 1.959964,
                  ) -> float:
    """Tuples a simple random sample needs before the query retires (its
    error ratio 2·z·σ/|estimate| at ε, or a HAVING verdict), ignoring the
    finite-population correction.  Grouped: the largest of its top-K
    cells."""
    vals = exact.vals
    r = exact.ranks
    n_all = r.shape[0]
    if t.pred_col >= 0:
        c = vals[r[:, t.pred_col]]
        p = (c >= t.lo) & (c < t.hi)
    else:
        p = np.ones(n_all, bool)
    if t.agg == "count":
        x = np.ones(n_all)
    else:
        x = np.zeros(n_all)
        for k, cf in enumerate(t.coeffs):
            if cf:
                x += cf * vals[r[:, k]]
    half = t.epsilon / 2.0
    if t.having is not None:
        half = abs(t.having[1] / exact.answer(t) - 1.0)

    def need(y: np.ndarray, mean: float) -> float:
        if mean == 0:
            return float("inf")
        return float((z * y.std() / (half * abs(mean))) ** 2)

    if t.grouped:
        g = r[:, t.group_col]
        keys, answers = exact.groups(t)
        top = np.argsort(-np.abs(answers), kind="stable")[:t.top_k]
        out = 0.0
        for i in top:
            ind = g == int(round(keys[i] / exact.spec.step))
            y = x * p * ind
            out = max(out, need(y, y.mean()))
        return out
    if t.agg == "avg":
        ratio = (x * p).sum() / max(p.sum(), 1)
        d = p * (x - ratio)
        return need(d, p.mean() * ratio)
    y = x * p
    return need(y, y.mean())
