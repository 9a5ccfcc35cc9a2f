"""Query plane: the aggregate-query AST and its compiled tile evaluator.

Queries follow the paper's Section 2.2 form::

    SELECT AGGREGATE(expression) FROM T WHERE predicate [HAVING agg <op> thr]

with AGGREGATE in {SUM, COUNT, AVERAGE}, ``expression`` a numeric expression
over columns, and ``predicate`` a conjunction of range/comparison terms.
GROUP BY is expressed as ``Query(group_by=GroupBy(col, max_groups, top_k))``:
one slot owns a bounded vector of per-group cells whose values are discovered
online during the scan (a SpaceSaving-style heavy-hitter sketch promotes hot
values into cells; rare values spill into an ``__other__`` cell so memory
stays fixed).  The paper's original prescription — each group a separate
query with a group-membership predicate — survives as :func:`group_fanout`
and is the bit-exactness oracle for the grouped plane.

``compile_queries`` lowers a list of queries to a single jitted *tile
evaluator*  ``cols (t, C) -> (x (Q, t), p (Q, t))``  where ``x_i`` is the
expression value predicate-masked per Table 1 (``x_i = 0`` if the tuple fails
the predicate) and ``p_i`` is the 0/1 predicate indicator.  Both the pure-JAX
engine and the Pallas ``chunk_agg`` / ``sampled_stats`` kernels consume this
evaluator's coefficient form.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtri

# ---------------------------------------------------------------------------
# Expressions
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Linear:
    """``Σ_k coeffs[k] · col_k`` — the paper's evaluation expression
    (``SUM(Σ_i c_i · A_i)`` in Section 7)."""

    coeffs: tuple[float, ...]

    def __call__(self, cols: jnp.ndarray) -> jnp.ndarray:
        c = jnp.asarray(self.coeffs, dtype=cols.dtype)
        return jnp.matmul(cols[..., : len(self.coeffs)], c,
                          precision=jax.lax.Precision.HIGHEST)


@dataclasses.dataclass(frozen=True)
class Column:
    """A single column reference, e.g. ``T.a``."""

    index: int

    def __call__(self, cols: jnp.ndarray) -> jnp.ndarray:
        return cols[..., self.index]


@dataclasses.dataclass(frozen=True)
class SquaredDiff:
    """``(T.a - T.b)^2`` — the paper's example of a non-linear expression."""

    a: int
    b: int

    def __call__(self, cols: jnp.ndarray) -> jnp.ndarray:
        d = cols[..., self.a] - cols[..., self.b]
        return d * d


@dataclasses.dataclass(frozen=True)
class Custom:
    """Arbitrary jnp-traceable expression ``f(cols (..., C)) -> (...)``."""

    fn: Callable[[jnp.ndarray], jnp.ndarray]

    def __call__(self, cols: jnp.ndarray) -> jnp.ndarray:
        return self.fn(cols)


ONE = Custom(fn=lambda cols: jnp.ones(cols.shape[:-1], cols.dtype))
"""Expression ``1`` — COUNT is SUM with expression = 1 (Section 4.3)."""


# ---------------------------------------------------------------------------
# Predicates (conjunctive normal form over simple terms)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Range:
    """``lo <= col < hi`` — the paper's selectivity-controlling predicate."""

    col: int
    lo: float = -np.inf
    hi: float = np.inf

    def __call__(self, cols: jnp.ndarray) -> jnp.ndarray:
        c = cols[..., self.col]
        return (c >= self.lo) & (c < self.hi)


@dataclasses.dataclass(frozen=True)
class Cmp:
    col: int
    op: str  # one of < <= > >= == !=
    value: float

    def __call__(self, cols: jnp.ndarray) -> jnp.ndarray:
        c = cols[..., self.col]
        v = jnp.asarray(self.value, cols.dtype)
        return {
            "<": c < v, "<=": c <= v, ">": c > v, ">=": c >= v,
            "==": c == v, "!=": c != v,
        }[self.op]


@dataclasses.dataclass(frozen=True)
class GroupEq:
    """Group-membership predicate used by the GROUP BY expansion."""

    col: int
    value: float

    def __call__(self, cols: jnp.ndarray) -> jnp.ndarray:
        return cols[..., self.col] == jnp.asarray(self.value, cols.dtype)


@dataclasses.dataclass(frozen=True)
class And:
    terms: tuple

    def __call__(self, cols: jnp.ndarray) -> jnp.ndarray:
        out = jnp.ones(cols.shape[:-1], dtype=bool)
        for t in self.terms:
            out = out & t(cols)
        return out


TRUE = And(terms=())


# ---------------------------------------------------------------------------
# Queries
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Having:
    op: str  # < <= > >=
    threshold: float


@dataclasses.dataclass(frozen=True)
class GroupBy:
    """Online GROUP BY over one column, bounded at ``max_groups`` cells.

    Up to ``max_groups`` distinct values get dedicated group cells with their
    own sufficient stats and CIs; values are discovered online by a bounded
    heavy-hitter sketch fed from per-round group tallies, and everything not
    tracked spills into an ``__other__`` cell so memory stays fixed.  The
    query retires when its ``top_k`` largest cells (by |estimate|) meet the
    query's epsilon.  ``values`` pins known group values into cells at
    admission — pinned cells accumulate from round 0 and are bit-exact
    against the :func:`group_fanout` expansion on the ref backend.
    """

    col: int
    max_groups: int = 8
    top_k: int = 0  # 0 -> all max_groups cells must converge
    values: Optional[tuple[float, ...]] = None

    def __post_init__(self):
        if self.max_groups < 1:
            raise ValueError("GroupBy.max_groups must be >= 1")
        if not (0 <= self.top_k <= self.max_groups):
            raise ValueError("GroupBy.top_k must be in [0, max_groups]")
        if self.values is not None:
            vals = tuple(float(v) for v in self.values)
            if len(vals) > self.max_groups:
                raise ValueError(
                    f"GroupBy: {len(vals)} pinned values exceed "
                    f"max_groups={self.max_groups}")
            if len(set(vals)) != len(vals):
                raise ValueError("GroupBy: pinned values must be distinct")
            object.__setattr__(self, "values", vals)

    @property
    def effective_top_k(self) -> int:
        return self.top_k if self.top_k > 0 else self.max_groups


@dataclasses.dataclass(frozen=True)
class GroupResult:
    """One cell of a grouped answer (``WorkloadResult.groups``).

    ``value`` is the group's column value (``nan`` for the ``__other__``
    spill cell, flagged by ``is_other``); ``n`` is the number of tuples
    sampled while the cell was live; ``decision`` is the HAVING decision
    code for the cell (1 pass / 0 fail / -1 undecided or no clause).
    """

    value: float
    estimate: float
    lo: float
    hi: float
    err: float
    n: int
    decision: int = -1
    is_other: bool = False


@dataclasses.dataclass(frozen=True)
class Query:
    """One OLA query.  ``epsilon`` is the target error ratio (stop condition),
    ``confidence`` the CI level, both per Section 2.2's user parameters.
    ``group_by`` turns the scalar aggregate into an online GROUP BY (the
    scalar ``estimate/lo/hi`` then describe the *base predicate* population
    and the per-group answers arrive as ``WorkloadResult.groups``)."""

    agg: str  # 'sum' | 'count' | 'avg'
    expr: object = ONE
    pred: object = TRUE
    having: Optional[Having] = None
    epsilon: float = 0.05
    confidence: float = 0.95
    name: str = "q"
    group_by: Optional[GroupBy] = None

    def __post_init__(self):
        if self.agg not in ("sum", "count", "avg"):
            raise ValueError(f"unsupported aggregate: {self.agg}")
        if self.group_by is not None and not isinstance(self.group_by, GroupBy):
            raise TypeError("Query.group_by must be a GroupBy (or None)")

    @property
    def columns_used(self) -> frozenset[int]:
        """Columns the query touches — drives synopsis reuse (Section 6)."""
        cols: set[int] = set()

        def walk(node):
            if isinstance(node, Linear):
                cols.update(range(len(node.coeffs)))
            elif isinstance(node, (Column,)):
                cols.add(node.index)
            elif isinstance(node, SquaredDiff):
                cols.update((node.a, node.b))
            elif isinstance(node, Custom):
                cols.add(-1)  # unknown support: requires all columns
            elif isinstance(node, (Range, Cmp, GroupEq)):
                cols.add(node.col)
            elif isinstance(node, And):
                for t in node.terms:
                    walk(t)

        walk(self.expr)
        walk(self.pred)
        if self.group_by is not None:
            cols.add(self.group_by.col)
        return frozenset(cols)


def group_fanout(base: Query, group_col: int, group_values: Sequence[float],
                 ) -> list[Query]:
    """GROUP BY per Section 2.2's original prescription: one scalar query per
    *pre-known* group value, identical except for an extra group-membership
    conjunct, all run simultaneously.  This expansion is the correctness
    oracle for the grouped slot plane — a ``Query(group_by=...)`` over the
    same known values must be bit-exact against it on the ref backend."""
    out = []
    for v in group_values:
        pred = And(terms=(base.pred, GroupEq(group_col, float(v))))
        out.append(dataclasses.replace(base, pred=pred, group_by=None,
                                       name=f"{base.name}[g={v}]"))
    return out


def expand_group_by(base: Query, group_col: int, group_values: Sequence[float],
                    ) -> list[Query]:
    """Deprecated: express GROUP BY as
    ``Query(group_by=GroupBy(col, max_groups, top_k))`` and read the answer
    from ``WorkloadResult.groups``.

    This wrapper is the pre-grouped-plane workaround — one slot per
    *pre-known* group value, no online discovery, no ``__other__`` spill.
    Behavior is unchanged (it delegates to :func:`group_fanout`); it emits a
    ``DeprecationWarning`` and will be removed once no caller needs the
    explicit fan-out."""
    warnings.warn(
        "expand_group_by is deprecated; use "
        "Query(group_by=GroupBy(col, max_groups, top_k)) and read "
        "WorkloadResult.groups",
        DeprecationWarning, stacklevel=2)
    return group_fanout(base, group_col, group_values)


# ---------------------------------------------------------------------------
# Compilation to a tile evaluator
# ---------------------------------------------------------------------------


def compile_queries(queries: Sequence[Query]) -> Callable[[jnp.ndarray], tuple]:
    """Lower queries to ``cols (t, C) -> (x (Q, t), p (Q, t))`` (see module doc).

    The returned function is pure jnp (trace-safe) and is consumed by the
    engine inside jit; the kernels use :func:`linear_plan` instead when every
    query is linear+range (the common fast path).
    """
    qs = tuple(queries)

    def evaluate(cols: jnp.ndarray):
        xs, ps = [], []
        for q in qs:
            p = q.pred(cols)
            e = jnp.ones(cols.shape[:-1], cols.dtype) if q.agg == "count" else q.expr(cols)
            pf = p.astype(cols.dtype)
            xs.append(jnp.asarray(e, cols.dtype) * pf)
            ps.append(pf)
        return jnp.stack(xs, axis=0), jnp.stack(ps, axis=0)

    return evaluate


@dataclasses.dataclass(frozen=True)
class LinearPlan:
    """Coefficient form for the Pallas kernels: every query is a linear
    expression with conjunctive range predicates.

    ``coeffs (Q, C)``; predicate as per-column bounds ``lo/hi (Q, C)`` with
    ±inf for unconstrained columns.
    """

    coeffs: np.ndarray
    lo: np.ndarray
    hi: np.ndarray

    @property
    def num_queries(self) -> int:
        return self.coeffs.shape[0]


# ---------------------------------------------------------------------------
# Dynamic query slot table (workload serving)
# ---------------------------------------------------------------------------
#
# The slot table is the *data-driven* counterpart of ``compile_queries``: a
# fixed-size (max_slots) array pytree describing up to S concurrently-running
# linear+range queries.  Because the table is a plain pytree of arrays, the
# engine round step can take it as a dynamic argument — admitting or retiring
# a query is a host-side row write, with no recompilation.  Only queries whose
# expression/predicate fit :class:`LinearPlan` coefficient form are encodable
# (the same restriction as the Pallas kernels); arbitrary ``Custom`` queries
# still go through the frozen ``compile_queries`` path.

AGG_SUM, AGG_COUNT, AGG_AVG = 0, 1, 2
_AGG_CODES = {"sum": AGG_SUM, "count": AGG_COUNT, "avg": AGG_AVG}

PLAN_CHUNK_LEVEL, PLAN_HOLISTIC, PLAN_SINGLE_PASS, PLAN_RESOURCE_AWARE = 0, 1, 2, 3
PLAN_CODES = {"chunk_level": PLAN_CHUNK_LEVEL, "holistic": PLAN_HOLISTIC,
              "single_pass": PLAN_SINGLE_PASS,
              "resource_aware": PLAN_RESOURCE_AWARE}

# op codes live with the decision rule (single source of truth)
from repro.core.estimators import HAVING_NONE, HAVING_OP_CODES as _HAVING_CODES


class SlotTable(NamedTuple):
    """Dynamic per-slot query descriptors, all arrays of leading dim S.

    ``coeffs/lo/hi`` are the :class:`LinearPlan` coefficient form; ``agg``
    and ``plan`` are code columns (``AGG_*`` / ``PLAN_*``); ``having_op`` is
    ``HAVING_NONE`` for slots without a HAVING clause.  ``active`` gates a
    slot's participation in extraction, chunk-close voting, and stopping.
    """

    coeffs: jnp.ndarray      # (S, C) f32
    lo: jnp.ndarray          # (S, C) f32
    hi: jnp.ndarray          # (S, C) f32
    agg: jnp.ndarray         # (S,) int32  AGG_* code
    plan: jnp.ndarray        # (S,) int32  PLAN_* code
    eps: jnp.ndarray         # (S,) f32 target error ratio
    z: jnp.ndarray           # (S,) f32 z-score of the slot's confidence level
    having_op: jnp.ndarray   # (S,) int32  _HAVING_CODES or HAVING_NONE
    having_thr: jnp.ndarray  # (S,) f32
    active: jnp.ndarray      # (S,) bool
    weight: jnp.ndarray      # (S,) f32 fairness share in (0, 1]: the slot
                             # counts only the first ceil(weight·b_eff)
                             # tuples of each worker window per round
                             # (repro.sched.fairness; 1 = unweighted round)
    gcol: jnp.ndarray        # (S,) int32 group-by column; -1 = ungrouped
    gval: jnp.ndarray        # (S, G) f32 tracked group values
    gact: jnp.ndarray        # (S, G) f32 0/1 cell-live flags; cell G-1 is
                             # the __other__ spill cell.  G = max_groups+1
                             # (0 when the engine has no grouped support —
                             # the grouped code then compiles away entirely)
    gtopk: jnp.ndarray       # (S,) int32 cells that must meet eps to stop

    @property
    def max_slots(self) -> int:
        return int(self.agg.shape[0])

    @property
    def group_cells(self) -> int:
        """G — per-slot group cells incl. ``__other__`` (0 = ungrouped table)."""
        return int(self.gval.shape[1])


def empty_slot_table(max_slots: int, num_cols: int,
                     max_groups: int = 0) -> SlotTable:
    """All-inactive table; inactive slots have an always-false predicate.

    ``max_groups > 0`` sizes every slot for grouped queries: ``max_groups``
    tracked-value cells plus one ``__other__`` spill cell.  The default 0
    keeps the group arrays zero-width so ungrouped engines are statically
    unchanged."""
    s, c = int(max_slots), int(num_cols)
    g = int(max_groups) + 1 if int(max_groups) > 0 else 0
    return SlotTable(
        coeffs=jnp.zeros((s, c), jnp.float32),
        lo=jnp.full((s, c), jnp.inf, jnp.float32),   # empty range: pred False
        hi=jnp.full((s, c), -jnp.inf, jnp.float32),
        agg=jnp.zeros((s,), jnp.int32),
        plan=jnp.full((s,), PLAN_RESOURCE_AWARE, jnp.int32),
        eps=jnp.ones((s,), jnp.float32),
        z=jnp.full((s,), 1.959964, jnp.float32),   # 95% placeholder
        having_op=jnp.full((s,), HAVING_NONE, jnp.int32),
        having_thr=jnp.zeros((s,), jnp.float32),
        active=jnp.zeros((s,), bool),
        weight=jnp.ones((s,), jnp.float32),
        gcol=jnp.full((s,), -1, jnp.int32),
        gval=jnp.zeros((s, g), jnp.float32),
        gact=jnp.zeros((s, g), jnp.float32),
        gtopk=jnp.zeros((s,), jnp.int32),
    )


def encode_slot(query: Query, num_cols: int, plan: str = "resource_aware",
                max_groups: int = 0) -> dict:
    """Encode one linear+range query as a slot-table row (numpy scalars/rows).

    ``max_groups`` is the *table's* group capacity (``empty_slot_table``'s
    parameter); a grouped query raises if it asks for more cells than the
    table carries.  Pinned ``GroupBy.values`` go live in cells ``0..k-1``
    at admission; the ``__other__`` cell (last) is always live for grouped
    slots so undiscovered groups accumulate from round 0.

    Raises ``ValueError`` (via :func:`linear_plan`) for queries outside the
    coefficient form.
    """
    lp = linear_plan([query], num_cols)
    hop = HAVING_NONE if query.having is None else _HAVING_CODES[query.having.op]
    thr = 0.0 if query.having is None else float(query.having.threshold)
    g = int(max_groups) + 1 if int(max_groups) > 0 else 0
    gcol, gtopk = -1, 0
    gval = np.zeros((g,), np.float32)
    gact = np.zeros((g,), np.float32)
    gb = query.group_by
    if gb is not None:
        if gb.max_groups > int(max_groups):
            raise ValueError(
                f"query {query.name}: group_by.max_groups={gb.max_groups} "
                f"exceeds the slot table's max_groups={int(max_groups)}")
        if not (0 <= gb.col < num_cols):
            raise ValueError(
                f"query {query.name}: group_by column {gb.col} out of range")
        gcol, gtopk = gb.col, gb.effective_top_k
        gact[g - 1] = 1.0  # __other__ live from admission
        for i, v in enumerate(gb.values or ()):
            gval[i] = np.float32(v)
            gact[i] = 1.0
    return dict(
        coeffs=lp.coeffs[0], lo=lp.lo[0], hi=lp.hi[0],
        agg=np.int32(_AGG_CODES[query.agg]),
        plan=np.int32(PLAN_CODES[plan]),
        eps=np.float32(query.epsilon),
        z=np.float32(ndtri((1.0 + query.confidence) / 2.0)),
        having_op=np.int32(hop), having_thr=np.float32(thr),
        active=True, weight=np.float32(1.0),
        gcol=np.int32(gcol), gval=gval, gact=gact, gtopk=np.int32(gtopk),
    )


def slot_row_values(table: SlotTable, row: dict) -> dict:
    """The values of one table row, field by field (numpy, in each field's
    dtype), from an :func:`encode_slot` row.

    Group columns default to the ungrouped row (``gcol=-1``, all cells dead)
    when absent or sized for a different table capacity, so rows encoded
    without ``max_groups`` slot into a grouped table cleanly."""
    g = int(table.gval.shape[1])
    gval_row = np.asarray(row.get("gval", ()), np.float32).reshape(-1)
    gact_row = np.asarray(row.get("gact", ()), np.float32).reshape(-1)
    if gval_row.shape != (g,) or gact_row.shape != (g,):
        gval_row = np.zeros((g,), np.float32)
        gact_row = np.zeros((g,), np.float32)
    return dict(
        coeffs=np.asarray(row["coeffs"], np.float32),
        lo=np.asarray(row["lo"], np.float32),
        hi=np.asarray(row["hi"], np.float32),
        agg=np.int32(row["agg"]), plan=np.int32(row["plan"]),
        eps=np.float32(row["eps"]), z=np.float32(row["z"]),
        having_op=np.int32(row["having_op"]),
        having_thr=np.float32(row["having_thr"]),
        active=np.bool_(row["active"]),
        weight=np.float32(row.get("weight", 1.0)),
        gcol=np.int32(row.get("gcol", -1)), gval=gval_row, gact=gact_row,
        gtopk=np.int32(row.get("gtopk", 0)))


def slot_table_set(table: SlotTable, s: int, row: dict) -> SlotTable:
    """Functional row write of a device table: one indexed update a field
    (the workload server stages its rows on the host instead)."""
    vals = slot_row_values(table, row)
    return SlotTable(**{f: getattr(table, f).at[s].set(vals[f])
                        for f in SlotTable._fields})


def slot_evaluate(table: SlotTable, cols: jnp.ndarray,
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Data-driven tile evaluator: ``cols (..., C) -> (x, p) (S, ...)``.

    Mirrors ``compile_queries`` semantics: ``p`` is the 0/1 conjunctive-range
    predicate indicator, ``x`` the predicate-masked expression value (1 for
    COUNT slots).  Inactive slots produce all-zero rows (their range is
    empty), so they never contaminate merged statistics.
    """
    dtype = cols.dtype
    c = cols[..., None, :]                                      # (..., 1, C)
    # unconstrained columns carry lo=-inf / hi=+inf, which satisfy both
    # comparisons for any finite value — no special-casing needed
    inb = (c >= table.lo.astype(dtype)) & (c < table.hi.astype(dtype))
    p = jnp.all(inb, axis=-1)                                   # (..., S)
    # full f32 precision: XLA:TPU's default rounds matmul operands to bf16
    lin = jnp.einsum("...c,sc->...s", cols, table.coeffs.astype(dtype),
                     precision=jax.lax.Precision.HIGHEST)
    is_count = table.agg == AGG_COUNT
    expr = jnp.where(is_count, jnp.ones_like(lin), lin)
    pf = p.astype(dtype)
    x = expr * pf
    # move the slot axis to the front: (..., S) -> (S, ...)
    return jnp.moveaxis(x, -1, 0), jnp.moveaxis(pf, -1, 0)


def linear_plan(queries: Sequence[Query], num_cols: int) -> LinearPlan:
    """Extract the coefficient form, or raise if a query is not linear+range."""
    q_n = len(queries)
    coeffs = np.zeros((q_n, num_cols), np.float32)
    lo = np.full((q_n, num_cols), -np.inf, np.float32)
    hi = np.full((q_n, num_cols), np.inf, np.float32)
    for qi, q in enumerate(queries):
        if q.agg == "count":
            pass  # coeffs stay zero; kernels compute count from the predicate
        elif isinstance(q.expr, Linear):
            coeffs[qi, : len(q.expr.coeffs)] = q.expr.coeffs
        elif isinstance(q.expr, Column):
            coeffs[qi, q.expr.index] = 1.0
        else:
            raise ValueError(f"query {q.name}: expression not linear, "
                             "use the pure-JAX evaluator path")

        def add_pred(node):
            # Lowering must be *exact* in f32 (the engine compares decoded
            # f32 values against these bounds with `lo <= c < hi`): closed
            # upper bounds and strict lower bounds shift by one f32 ulp via
            # nextafter, equality becomes the degenerate range [v, v⁺), and
            # '!=' has no conjunctive-range form — it must raise, never be
            # silently approximated (the ref evaluator computes it exactly,
            # so a lossy encoding would make the backends disagree).
            if isinstance(node, And):
                for t in node.terms:
                    add_pred(t)
            elif isinstance(node, Range):
                lo[qi, node.col] = max(lo[qi, node.col], node.lo)
                hi[qi, node.col] = min(hi[qi, node.col], node.hi)
            elif isinstance(node, (GroupEq, Cmp)):
                op = "==" if isinstance(node, GroupEq) else node.op
                v = np.float32(node.value)
                up = np.nextafter(v, np.float32(np.inf))
                if up != 0 and abs(up) < np.finfo(np.float32).tiny:
                    # XLA flushes denormals to zero, so a denormal bound
                    # (only reachable near v == 0) would compare as 0 and
                    # make the range empty; the smallest *normal* float is
                    # the nearest bound that survives FTZ, and it is exact
                    # for decoded data (nonzero magnitudes are >= 1e-6)
                    up = np.float32(np.copysign(np.finfo(np.float32).tiny, up))
                if op == "<":
                    hi[qi, node.col] = min(hi[qi, node.col], v)
                elif op == "<=":    # c <= v  ≡  c < nextafter(v)
                    hi[qi, node.col] = min(hi[qi, node.col], up)
                elif op == ">":     # c > v   ≡  c >= nextafter(v)
                    lo[qi, node.col] = max(lo[qi, node.col], up)
                elif op == ">=":
                    lo[qi, node.col] = max(lo[qi, node.col], v)
                elif op == "==":
                    lo[qi, node.col] = max(lo[qi, node.col], v)
                    hi[qi, node.col] = min(hi[qi, node.col], up)
                else:
                    raise ValueError(
                        f"query {q.name}: {op!r} is not range-encodable, "
                        "use the pure-JAX evaluator path")
            else:
                raise ValueError(f"query {q.name}: predicate not range-conjunctive")

        add_pred(q.pred)
    return LinearPlan(coeffs=coeffs, lo=lo, hi=hi)
