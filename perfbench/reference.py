"""The plain reference: exact answers and the EXTRACT layer's sums.

Nothing here imports the system under test.  Exact answers are float64
full-scan aggregates over the generated values, computed from per-rank
histograms (a value depends on its rank alone).  The EXTRACT check parses
the raw rows a round sampled with the benchmark's own reader
(:mod:`perfbench.tablegen`) and sums them in float64.

Which rows a round sampled follows from the per-chunk random order: the
engine extracts ``perm_j[offset_j : offset_j + b]`` of chunk j, where
``perm_j`` is a keyed 4-round Feistel bijection over the chunk's tuple
ordinals.  :func:`window_rows` is a copy of that arithmetic
(``repro/sampling/permutation.py``: ``chunk_seed``, ``feistel_permute_dyn``,
``permutation_window_dyn``), so the reference names the rows without
asking the program.

The arithmetic of the exact answers follows ``chip_smoke.py`` (``_terms``,
``_aggregate``, ``exact_groups``): expression times 0/1 predicate, summed in
float64; AVG is the ratio of the two sums.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from perfbench import tablegen

# ------------------------------------------------------------- queries ----


@dataclasses.dataclass(frozen=True)
class Template:
    """One query of a traffic mix, in the benchmark's own terms."""

    tid: int
    agg: str                         # sum | count | avg
    coeffs: tuple                    # linear expression (ignored for count)
    pred_col: int = -1               # -1: no predicate
    lo: float = -np.inf
    hi: float = np.inf
    epsilon: float = 0.05
    having: Optional[tuple] = None   # (op, threshold)
    group_col: int = -1
    max_groups: int = 0
    top_k: int = 0
    selectivity: float = 1.0         # the share of rows the mix aimed at

    @property
    def grouped(self) -> bool:
        return self.group_col >= 0


def terms(values: np.ndarray, t: Template, bf16: bool = False):
    """-> (x, p) float64 per row: expression times predicate, and the 0/1
    predicate.  ``bf16`` computes the expression with bfloat16 operands and
    float32 accumulation (the control)."""
    n = values.shape[0]
    p = np.ones(n)
    if t.pred_col >= 0:
        c = values[:, t.pred_col]
        p = ((c >= t.lo) & (c < t.hi)).astype(np.float64)
    if t.agg == "count":
        return p, p
    coef = np.asarray(t.coeffs, np.float64)
    v = values[:, :len(coef)]
    if bf16:
        import ml_dtypes

        b = ml_dtypes.bfloat16
        v = v.astype(b).astype(np.float32)
        coef = coef.astype(b).astype(np.float32)
        x = (v * coef).astype(np.float32).sum(axis=1, dtype=np.float32)
        x = x.astype(np.float64)
    else:
        x = v @ coef
    return x * p, p


def bf16_values(spec: tablegen.TableSpec, values: np.ndarray) -> np.ndarray:
    """The control's reading of the stored values: an ASCII field parsed as
    digits times powers of ten with bfloat16 operands (float32 accumulate);
    a binary field is an exact float32 either way."""
    if spec.format != "ascii":
        return values
    import ml_dtypes

    b = ml_dtypes.bfloat16
    ip = np.floor(values)
    fr = np.rint((values - ip) * 1e6)
    out = np.zeros(values.shape, np.float32)
    for d in range(tablegen.INT_DIGITS):
        k = 10.0 ** (tablegen.INT_DIGITS - 1 - d)
        dig = np.floor(ip / k) % 10
        out += (dig.astype(np.float32)
                * np.float32(np.asarray(k).astype(b).astype(np.float32)))
    for d in range(tablegen.FRAC_DIGITS):
        k = 10.0 ** (tablegen.FRAC_DIGITS - 1 - d)
        dig = np.floor(fr / k) % 10
        w = np.float32(np.asarray(k * 1e-6).astype(b).astype(np.float32))
        out += dig.astype(np.float32) * w
    return out.astype(np.float64)


# ------------------------------------------------------- exact answers ----


class Exact:
    """Exact float64 full-scan answers over the generated table.  With
    ``bf16`` the same answers as the control computes them: values parsed
    and the expression evaluated with bfloat16 operands."""

    def __init__(self, spec: tablegen.TableSpec, ranks: np.ndarray,
                 bf16: bool = False):
        self.spec = spec
        self.ranks = ranks
        self.bf16 = bf16
        self.vals = tablegen.stored_rank_values(spec)    # (support,)
        if bf16:
            self.vals = bf16_values(spec, self.vals)
        self._x: dict = {}
        self._hist: dict = {}
        self._groups: dict = {}

    def _expr(self, t: Template) -> np.ndarray:
        key = ("count",) if t.agg == "count" else tuple(t.coeffs)
        if key not in self._x:
            if t.agg == "count":
                self._x[key] = None
            elif self.bf16:
                import ml_dtypes

                b = ml_dtypes.bfloat16
                v = self.vals.astype(b).astype(np.float32)
                x = np.zeros(self.ranks.shape[0], np.float32)
                for k, c in enumerate(t.coeffs):
                    if c:
                        x += np.float32(np.asarray(c).astype(b)) * v[
                            self.ranks[:, k]]
                self._x[key] = x.astype(np.float64)
            else:
                x = np.zeros(self.ranks.shape[0])
                for k, c in enumerate(t.coeffs):
                    if c:
                        x += c * self.vals[self.ranks[:, k]]
                self._x[key] = x
        return key, self._x[key]

    def _prefix(self, key, x, col: int):
        """Prefix sums over ranks of column ``col``: (Σx, Σ1)."""
        hk = (key, col)
        if hk not in self._hist:
            r = self.ranks[:, col]
            sup = self.spec.zipf_support
            n = np.bincount(r, minlength=sup).astype(np.float64)
            s = n if x is None else np.bincount(r, weights=x, minlength=sup)
            self._hist[hk] = (np.concatenate([[0.0], np.cumsum(s)]),
                              np.concatenate([[0.0], np.cumsum(n)]))
        return self._hist[hk]

    def sums(self, t: Template) -> tuple[float, float]:
        """-> (Σ x·p, Σ p) over the whole table."""
        key, x = self._expr(t)
        col = t.pred_col if t.pred_col >= 0 else 0
        ps, pn = self._prefix(key, x, col)
        if t.pred_col < 0:
            return float(ps[-1]), float(pn[-1])
        a = int(np.searchsorted(self.vals, t.lo, "left"))
        b = int(np.searchsorted(self.vals, t.hi, "left"))
        b = max(a, b)
        return float(ps[b] - ps[a]), float(pn[b] - pn[a])

    def answer(self, t: Template) -> float:
        s, n = self.sums(t)
        if t.agg == "count":
            return n
        if t.agg == "avg":
            return s / n if n else float("nan")
        return s

    def groups(self, t: Template) -> tuple[np.ndarray, np.ndarray]:
        """-> (group values (float64, as stored), exact answers)."""
        gk = (t.agg, t.coeffs, t.pred_col, t.lo, t.hi, t.group_col)
        if gk not in self._groups:
            self._groups[gk] = self._group_answers(t)
        return self._groups[gk]

    def _group_answers(self, t: Template) -> tuple[np.ndarray, np.ndarray]:
        key, x = self._expr(t)
        r = self.ranks[:, t.group_col]
        sup = self.spec.zipf_support
        if t.pred_col >= 0:
            c = self.vals[self.ranks[:, t.pred_col]]
            p = ((c >= t.lo) & (c < t.hi)).astype(np.float64)
        else:
            p = np.ones(len(r))
        n = np.bincount(r, weights=p, minlength=sup)
        s = n if x is None else np.bincount(r, weights=x * p, minlength=sup)
        live = np.flatnonzero(n > 0)
        if t.agg == "avg":
            s = s / np.maximum(n, 1)
        return self.vals[live], s[live]

    def quantile_rank(self, col: int, q: float) -> int:
        """Smallest rank whose CDF over column ``col`` reaches ``q``."""
        _, pn = self._prefix(("count",), None, col)
        return int(np.searchsorted(pn[1:] / pn[-1], q, "left"))


# ------------------------------------------------ the sampled rows ----

_C1 = np.uint32(0x9E3779B9)
_C2 = np.uint32(0x85EBCA6B)
_C3 = np.uint32(0xC2B2AE35)


def _mix32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.uint32)
    x = (x ^ (x >> np.uint32(16))) * _C1
    x = (x ^ (x >> np.uint32(13))) * _C2
    return x ^ (x >> np.uint32(16))


def chunk_seeds(master: int, n: int) -> np.ndarray:
    j = np.arange(n, dtype=np.uint32)
    return _mix32(np.uint32(master) ^ (_mix32(j) + _C3))


def _half_bits(m: int) -> int:
    total = max(2, int(np.ceil(np.log2(max(int(m), 2)))))
    return (total + total % 2) // 2


def _round_trip(x: np.ndarray, keys: np.ndarray, hb: int) -> np.ndarray:
    mask = np.uint32((1 << hb) - 1)
    left = (x >> np.uint32(hb)) & mask
    right = x & mask
    for k in keys:
        f = _mix32(right ^ k) & mask
        left, right = right, left ^ f
    return (left << np.uint32(hb)) | right


def window_rows(seed: np.uint32, start: int, count: int, m: int,
                width: int) -> np.ndarray:
    """Rows ``perm[start : start + count] mod m`` of one chunk's order."""
    with np.errstate(over="ignore"):
        keys = _mix32(np.uint32(seed)
                      + (np.arange(4, dtype=np.uint32) + np.uint32(1)) * _C2)
        hb = _half_bits(width)
        offs = ((start + np.arange(count, dtype=np.int64)) % max(m, 1)
                ).astype(np.uint32)
        y = _round_trip(offs, keys, hb)
        bad = y >= m
        while bad.any():
            y[bad] = _round_trip(y[bad], keys, hb)
            bad = y >= m
    return y.astype(np.int64)


# ------------------------------------------------- EXTRACT-layer check ----

STATS = ("ysum", "ysq", "psum")


def _ref_sums(x: np.ndarray, p: np.ndarray) -> tuple:
    """(sums, scales) for (Σx, Σx², Σp): the value and the sum of absolute
    terms it is compared against."""
    return ((x.sum(), (x * x).sum(), p.sum()),
            (np.abs(x).sum(), (x * x).sum(), p.sum()))


def _rel(pre: float, post: float, ref: float, scale: float) -> float:
    """Relative error of the round's sum ``post - pre`` against ``ref``,
    over the larger of the two (a sum where the other is 0 reads 1).  The
    statistics are cumulative float32, so the difference of the two
    readings is known only to their spacing; that much is forgiven."""
    if not (np.isfinite(pre) and np.isfinite(post)):
        return float("inf")
    slack = float(np.spacing(np.float32(abs(pre)))
                  + np.spacing(np.float32(abs(post))))
    gap = max(abs(post - pre - ref) - slack, 0.0)
    return gap / max(scale, abs(post - pre), 1e-30)


def extract_errors(spec, chunks: list, round_rec: dict, engine_seed: int,
                   m_width: int, bf16: bool = False) -> list[tuple]:
    """Relative errors of one sampled round's per-(slot, chunk) sums — the
    slot statistics and, for grouped slots, each live group cell's — against
    the reference over the same raw rows.

    ``round_rec`` holds host copies of the state before and after the round
    (``pre``/``post``), the slot table's group cells and the templates
    resident in each slot.  Returns ``[(error, what)]``.  A slot whose
    sample-size delta disagrees with the scan's is an error of ``inf``.
    """
    pre, post = round_rec["pre"], round_rec["post"]
    slots = round_rec["slots"]
    sizes = np.asarray([c.shape[0] for c in chunks])
    d_scan = post["scan_m"] - pre["scan_m"]
    seeds = chunk_seeds(engine_seed, len(chunks))
    errs: list = []
    for j in np.flatnonzero(d_scan > 0):
        rows = window_rows(seeds[j], int(pre["offset"][j]), int(d_scan[j]),
                           int(sizes[j]), m_width)
        vals = tablegen.parse(spec, chunks[j][rows])
        if bf16:
            vals = bf16_values(spec, vals)
        for s, t in enumerate(slots):
            dm = int(post["m"][s, j] - pre["m"][s, j])
            if t is None or dm == 0:
                continue
            if dm != int(d_scan[j]):
                errs.append((float("inf"), f"m slot {s} chunk {j}"))
                continue
            x, p = terms(vals, t, bf16=bf16)
            refs, scales = _ref_sums(x, p)
            for k, name in enumerate(STATS):
                a, b = float(pre[name][s, j]), float(post[name][s, j])
                errs.append((_rel(a, b, refs[k], scales[k]),
                             f"{name} slot {s} chunk {j} t{t.tid} "
                             f"prog {b - a!r} ref {refs[k]!r}"))
            if not t.grouped or post["gys"].shape[1] == 0:
                continue
            gval, gact = round_rec["gval"][s], round_rec["gact"][s]
            gcol = np.float32(vals[:, t.group_col])
            # tracked cells first; the last cell, __other__, is the rest
            tracked = np.zeros(len(rows))
            g_last = len(gval) - 1
            for g in range(g_last + 1):
                if gact[g] <= 0:
                    continue
                if g < g_last:
                    ind = (gcol == np.float32(gval[g])).astype(np.float64)
                    tracked += ind
                else:
                    ind = 1.0 - tracked
                refs, scales = _ref_sums(x * ind, p * ind)
                for k, name in enumerate(("gys", "gyq", "gps")):
                    a = float(pre[name][s, g, j])
                    b = float(post[name][s, g, j])
                    errs.append((_rel(a, b, refs[k], scales[k]),
                                 f"{name} slot {s} cell {g} chunk {j} "
                                 f"t{t.tid} prog {b - a!r} ref {refs[k]!r}"))
    return errs
