"""Bi-level sampling estimators — paper Section 4.3, Eq. (1), (2), (3).

Everything is computed from the per-chunk sufficient statistics of Table 1:

    M_j   tuples on chunk j           (file metadata)
    m_j   tuples sampled from chunk j
    y'_j  sum of x_i over the sample     (x_i = expr(tuple_i) * pred(tuple_i))
    y''_j sum of x_i^2 over the sample
    p_j   sum of pred(tuple_i) over the sample   (for COUNT / AVERAGE)

so the estimator state is a fixed-size array pytree over chunk *slots* and
merges trivially across workers (a ``psum``) and across rounds (an add).
All functions broadcast over leading query/group dimensions: arrays are
``(..., N)`` where N is the number of chunk slots; slots with ``m == 0`` are
outside the sample (U') and are masked out.

``m`` itself may carry leading dimensions too: under the workload server each
query slot joined the scan at a different point, so slot s has its own sample
size ``m[s, j]`` for chunk j.  Every estimator treats ``m`` as ``(..., N)``
broadcasting against ``ysum``; the classic single-scan case is the ``(N,)``
special case and is numerically unchanged.

Numerical conventions: the library computes in the dtype of its inputs
(float32 inside the engine, float64 under ``jax.experimental.enable_x64`` in
the statistical tests).  Degenerate cases follow the paper's semantics:

* ``m_j == M_j``  -> within-chunk term vanishes (the ``M_j - m_j`` factor).
* ``m_j == 1 < M_j`` -> within-chunk variance is not estimable; we take the
  conservative route of flagging the estimate (``valid=False``) rather than
  silently dropping the term, and the engine's budget rules never produce a
  1-tuple sample from a multi-tuple chunk except transiently in round 0.
* ``n == 1 < N`` -> between-chunk term not estimable -> variance = +inf
  (bounds stay open until two chunks are in the sample, matching Figure 2's
  "error infinite until estimable").
"""

from __future__ import annotations

import statistics
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtri


class BiLevelStats(NamedTuple):
    """Pytree of per-chunk-slot sufficient statistics.

    Shapes: ``M, m`` are ``(N,)``; ``ysum, ysq, psum`` are ``(..., N)`` with
    optional leading per-query dims.  ``n_total`` is the total number of
    chunks N in the table (static), ``m_total`` the total number of tuples M.
    """

    M: jnp.ndarray
    m: jnp.ndarray
    ysum: jnp.ndarray
    ysq: jnp.ndarray
    psum: jnp.ndarray
    n_total: int
    m_total: int

    @property
    def in_sample(self) -> jnp.ndarray:
        return self.m > 0

    @property
    def n(self) -> jnp.ndarray:
        """|U'| — number of chunks currently in the sample (per leading dim
        when ``m`` carries per-slot dimensions)."""
        return jnp.sum(self.in_sample.astype(jnp.int32), axis=-1)

    def merge(self, other: "BiLevelStats") -> "BiLevelStats":
        """Combine disjoint samples of the same table (cross-worker psum/add)."""
        return BiLevelStats(
            M=self.M,
            m=self.m + other.m,
            ysum=self.ysum + other.ysum,
            ysq=self.ysq + other.ysq,
            psum=self.psum + other.psum,
            n_total=self.n_total,
            m_total=self.m_total,
        )


def init_stats(chunk_sizes: jnp.ndarray, query_shape: tuple = (), dtype=jnp.float32,
               m_total: int | None = None) -> BiLevelStats:
    """Fresh all-zero statistics for a table with the given per-chunk sizes."""
    n = chunk_sizes.shape[0]

    def zeros():
        # fresh buffer per field: aliased buffers break jit donation
        return jnp.zeros(query_shape + (n,), dtype=dtype)
    if m_total is not None:
        total = int(m_total)
    else:
        try:
            total = int(jnp.sum(chunk_sizes))
        except jax.errors.ConcretizationTypeError:
            total = -1  # traced sizes: callers must pass m_total for reporting
    return BiLevelStats(
        M=jnp.asarray(chunk_sizes),
        m=jnp.zeros((n,), dtype=jnp.int32),
        ysum=zeros(),
        ysq=zeros(),
        psum=zeros(),
        n_total=n,
        m_total=total,
    )


def _f(x, dtype):
    return jnp.asarray(x).astype(dtype)


def chunk_estimates(stats: BiLevelStats) -> jnp.ndarray:
    """Per-chunk unbiased estimator  ŷ_j = (M_j / m_j) · y'_j  (zero off-sample)."""
    dtype = stats.ysum.dtype
    m_safe = jnp.maximum(stats.m, 1)
    yhat = _f(stats.M, dtype) / _f(m_safe, dtype) * stats.ysum
    return jnp.where(stats.in_sample, yhat, jnp.zeros_like(yhat))


def tau_hat(stats: BiLevelStats) -> jnp.ndarray:
    """Eq. (1):  τ̂ = (N / n) Σ_{j∈U'} ŷ_j  — unbiased for τ = Σ_i x_i."""
    dtype = stats.ysum.dtype
    n = jnp.maximum(stats.n, 1).astype(dtype)          # (...,) per-slot |U'|
    big_n = _f(stats.n_total, dtype)
    return big_n / n * jnp.sum(chunk_estimates(stats), axis=-1)


def _within_chunk_ss(sum_a, sum_b, cross, m, dtype):
    """Σ_i (a_i - ā)(b_i - b̄) over the sample of one chunk = cross − Σa·Σb/m."""
    m_safe = jnp.maximum(m, 1).astype(dtype)
    return cross - sum_a * sum_b / m_safe


def _cov_hat(stats: BiLevelStats, sum_a, sum_b, cross) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Generic Eq. (3)-shaped unbiased (co)variance estimator.

    With ``sum_a == sum_b == ysum`` and ``cross == ysq`` this is exactly
    Theorem 2; with mixed sums it is the two-stage covariance used by the
    AVERAGE ratio estimator.  Returns ``(cov, valid)``.
    """
    dtype = sum_a.dtype
    mask = stats.in_sample
    maskf = mask.astype(dtype)
    big_n = _f(stats.n_total, dtype)
    n = jnp.maximum(stats.n, 1).astype(dtype)          # (...,) per-slot |U'|
    m = stats.m
    m_safe = jnp.maximum(m, 1).astype(dtype)
    big_m = _f(stats.M, dtype)

    scale = big_m / m_safe  # M_j / m_j
    ahat = jnp.where(mask, scale * sum_a, 0.0)
    bhat = jnp.where(mask, scale * sum_b, 0.0)

    # ---- between-chunk term:  N/n · (N-n)/(n-1) · Σ_j (âⱼ - ā)(b̂ⱼ - b̄)
    abar = jnp.sum(ahat, axis=-1, keepdims=True) / n[..., None]
    bbar = jnp.sum(bhat, axis=-1, keepdims=True) / n[..., None]
    between_ss = jnp.sum(maskf * (ahat - abar) * (bhat - bbar), axis=-1)
    n_gt1 = stats.n > 1
    between = jnp.where(
        n_gt1,
        big_n / n * (big_n - n) / jnp.maximum(n - 1.0, 1.0) * between_ss,
        jnp.inf,
    )
    # A census of the chunk space (n == N) has no between-chunk variance even
    # when N == 1: the first `where` above already yields 0 via (N - n) = 0,
    # but n == N == 1 falls into the n==1 branch, so fix it up explicitly.
    between = jnp.where(stats.n == stats.n_total, jnp.nan_to_num(between, posinf=0.0), between)

    # ---- within-chunk term:  N/n · Σ_j (M_j/m_j) (M_j-m_j)/(m_j-1) · SS_j
    ss_within = _within_chunk_ss(sum_a, sum_b, cross, m, dtype)
    fpc = (big_m - m_safe) / jnp.maximum(m_safe - 1.0, 1.0)  # (M_j - m_j)/(m_j - 1)
    within_j = jnp.where(mask, scale * fpc * ss_within, 0.0)
    # m_j == 1 on a multi-tuple chunk: term not estimable; contribute 0 but
    # mark invalid so callers can widen the report.
    singleton = mask & (m == 1) & (stats.M > 1)
    within_j = jnp.where(singleton, 0.0, within_j)
    within = big_n / n * jnp.sum(within_j, axis=-1)

    valid = jnp.logical_not(jnp.any(singleton, axis=-1)) & (
        n_gt1 | (stats.n == stats.n_total))
    return between + within, valid


def var_hat(stats: BiLevelStats) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Eq. (3): unbiased estimator of Var(τ̂).  Returns ``(variance, valid)``."""
    return _cov_hat(stats, stats.ysum, stats.ysum, cross=stats.ysq)


def count_tau_hat(stats: BiLevelStats) -> jnp.ndarray:
    """COUNT is SUM with expression = 1 (Section 4.3): estimate from psum."""
    return tau_hat(stats._replace(ysum=stats.psum))


def count_var_hat(stats: BiLevelStats) -> tuple[jnp.ndarray, jnp.ndarray]:
    # pred is 0/1 so Σ p_i^2 = Σ p_i.
    return _cov_hat(stats, stats.psum, stats.psum, cross=stats.psum)


def avg_estimate(stats: BiLevelStats) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """AVERAGE = SUM/COUNT ratio estimator with delta-method variance.

    Following the paper ("only minor modifications ... for complex aggregates"
    via [Haas & König 2004]):  R̂ = τ̂_x / τ̂_p and

        Var(R̂) ≈ (Var_x + R̂² Var_p − 2 R̂ Cov_xp) / τ̂_p²

    where the covariance uses the same two-stage structure.  The cross moment
    Σ x_i·p_i equals Σ x_i because x_i is already predicate-masked.
    Returns ``(estimate, variance, valid)``.
    """
    tx = tau_hat(stats)
    tp = count_tau_hat(stats)
    var_x, vx_ok = var_hat(stats)
    var_p, vp_ok = count_var_hat(stats)
    cov_xp, cv_ok = _cov_hat(stats, stats.ysum, stats.psum, cross=stats.ysum)
    r, var_r = _ratio(tx, tp, var_x, var_p, cov_xp)
    return r, var_r, vx_ok & vp_ok & cv_ok


def _ratio(tx, tp, var_x, var_p, cov_xp):
    """R̂ = τ̂_x / τ̂_p and its delta-method variance (see avg_estimate)."""
    dtype = tx.dtype
    tp_safe = jnp.where(jnp.abs(tp) > 0, tp, jnp.ones_like(tp))
    r = tx / tp_safe
    var_r = (var_x + r * r * var_p - 2.0 * r * cov_xp) / (tp_safe * tp_safe)
    # Delta-method variances can go slightly negative near m_j == M_j; clamp.
    var_r = jnp.maximum(var_r, jnp.zeros_like(var_r))
    var_r = jnp.where(jnp.abs(tp) > 0, var_r, jnp.asarray(jnp.inf, dtype))
    return r, var_r


# ---------------------------------------------------------------------------
# Stratified bi-level sampling: the chunk axis split into contiguous strata
# (the per-shard claim queues of a sharded table), each sampled in its own
# committed random order.
# ---------------------------------------------------------------------------

def stratum_totals(chunk_sizes, dropped, strata: int):
    """Per-stratum population ``(N_h, M_h)``, each ``(strata,)``: the chunk
    count and tuple total of each contiguous chunk range, less the chunks
    ``dropped`` (quarantined) from it."""
    sizes = jnp.asarray(chunk_sizes).reshape(strata, -1)
    gone = jnp.asarray(dropped).reshape(strata, -1)
    n_h = sizes.shape[1] - jnp.sum(gone.astype(jnp.int32), axis=-1)
    m_h = jnp.sum(jnp.where(gone, 0, sizes), axis=-1)
    return n_h, m_h


def split_strata(stats: BiLevelStats, strata: int) -> BiLevelStats:
    """View of ``stats`` with the chunk axis ``(..., N)`` split into
    ``(..., strata, N / strata)`` contiguous strata.  ``n_total`` and
    ``m_total`` must already be per stratum (:func:`stratum_totals`)."""
    def split(x):
        x = jnp.asarray(x)
        return x.reshape(x.shape[:-1] + (strata, x.shape[-1] // strata))

    return stats._replace(M=split(stats.M), m=split(stats.m),
                          ysum=split(stats.ysum), ysq=split(stats.ysq),
                          psum=split(stats.psum))


def bilevel_estimates(stats: BiLevelStats, strata: int = 1,
                      aggs=("sum", "count", "avg")) -> tuple:
    """``(sum_t, sum_v, cnt_t, cnt_v, avg_t, avg_v)``: SUM, COUNT and
    AVERAGE estimates with their variances, each pair ``None`` unless
    ``aggs`` names it.

    With one stratum these are Eq. (1) and (3) over the whole chunk space.
    With ``strata > 1`` the chunk axis is split as in :func:`split_strata`:
    Eq. (1) and (3) apply within each stratum, and the stratum estimates and
    variances are summed (stratified sampling); AVERAGE is the combined
    ratio of the summed SUM and COUNT estimates, with the summed
    covariance.  A stratum with fewer than two sampled chunks leaves every
    variance infinite.
    """
    sum_t = sum_v = cnt_t = cnt_v = avg_t = avg_v = None
    if strata == 1:
        if "sum" in aggs:
            sum_t, (sum_v, _) = tau_hat(stats), var_hat(stats)
        if "count" in aggs:
            cnt_t, (cnt_v, _) = count_tau_hat(stats), count_var_hat(stats)
        if "avg" in aggs:
            avg_t, avg_v, _ = avg_estimate(stats)
        return sum_t, sum_v, cnt_t, cnt_v, avg_t, avg_v
    st = split_strata(stats, strata)
    if aggs:
        sum_t = jnp.sum(tau_hat(st), axis=-1)
        sum_v = jnp.sum(var_hat(st)[0], axis=-1)
        cnt_t = jnp.sum(count_tau_hat(st), axis=-1)
        cnt_v = jnp.sum(count_var_hat(st)[0], axis=-1)
    if "avg" in aggs:
        cov = jnp.sum(_cov_hat(st, st.ysum, st.psum, cross=st.ysum)[0],
                      axis=-1)
        avg_t, avg_v = _ratio(sum_t, cnt_t, sum_v, cnt_v, cov)
        # inf - inf in the delta method would read as a NaN variance
        known = jnp.isfinite(sum_v) & jnp.isfinite(cnt_v) & jnp.isfinite(cov)
        avg_v = jnp.where(known, avg_v, jnp.asarray(jnp.inf, avg_v.dtype))
    return sum_t, sum_v, cnt_t, cnt_v, avg_t, avg_v


def stratified_row_answer(agg: str, sizes, n_h, m, ysum, ysq, psum,
                          confidence: float = 0.95) -> tuple:
    """``(estimate, lo, hi, err)`` of one query's statistics row,
    stratified over ``len(n_h)`` contiguous chunk ranges, computed on the
    host in float64 numpy: :func:`bilevel_estimates` (``strata > 1``) and
    :func:`confidence_bounds` for a single row, without a device op.
    ``sizes`` are the chunk sizes M_j, ``n_h`` each stratum's chunk
    population (:func:`stratum_totals`), ``m``/``ysum``/``ysq``/``psum``
    the ``(N,)`` row."""
    q = len(n_h)

    def f(x):
        return np.asarray(x, np.float64).reshape(q, -1)

    big_m, m = f(sizes), f(m)
    big_n = np.asarray(n_h, np.float64)
    ins = m > 0
    n = ins.sum(axis=1).astype(np.float64)
    n1 = np.maximum(n, 1.0)
    m1 = np.maximum(m, 1.0)
    scale = np.where(ins, big_m / m1, 0.0)                   # M_j / m_j
    fpc = (big_m - m1) / np.maximum(m1 - 1.0, 1.0)
    # m_j == 1 on a multi-tuple chunk: the within term is not estimable
    within_ok = ins & ~((m == 1) & (big_m > 1))

    def tau(a):
        return float(np.sum(big_n / n1 * np.sum(scale * f(a), axis=1)))

    def cov(a, b, cross):
        a, b, cross = f(a), f(b), f(cross)
        ahat, bhat = scale * a, scale * b
        abar = ahat.sum(axis=1, keepdims=True) / n1[:, None]
        bbar = bhat.sum(axis=1, keepdims=True) / n1[:, None]
        ss = np.sum(ins * (ahat - abar) * (bhat - bbar), axis=1)
        between = np.where(
            n > 1, big_n / n1 * (big_n - n) / np.maximum(n - 1.0, 1.0) * ss,
            np.inf)
        # a census of the stratum has no between-chunk variance
        between = np.where(n == big_n, np.nan_to_num(between, posinf=0.0),
                           between)
        within = np.where(within_ok, scale * fpc * (cross - a * b / m1), 0.0)
        return float(np.sum(between + big_n / n1 * within.sum(axis=1)))

    with np.errstate(all="ignore"):
        if agg == "sum":
            e, v = tau(ysum), cov(ysum, ysum, ysq)
        elif agg == "count":
            e, v = tau(psum), cov(psum, psum, psum)
        else:
            tx, tp = tau(ysum), tau(psum)
            vx, vp = cov(ysum, ysum, ysq), cov(psum, psum, psum)
            cxp = cov(ysum, psum, ysum)
            e = tx / tp if tp != 0 else tx
            if tp == 0 or not np.isfinite([vx, vp, cxp]).all():
                v = np.inf
            else:
                v = max((vx + e * e * vp - 2.0 * e * cxp) / (tp * tp), 0.0)
        half = (statistics.NormalDist().inv_cdf((1.0 + confidence) / 2.0)
                * np.sqrt(max(v, 0.0)))
        lo, hi = e - half, e + half
        err = (hi - lo) / max(abs(e), 1e-30)
    return e, lo, hi, err


def confidence_bounds(estimate, variance, confidence: float = 0.95):
    """CLT bounds (Section 4.3): ``estimate ± z_{(1+c)/2} · sqrt(variance)``."""
    dtype = jnp.asarray(estimate).dtype
    z = ndtri(jnp.asarray((1.0 + confidence) / 2.0, dtype=dtype))
    half = z * jnp.sqrt(jnp.maximum(variance, 0.0))
    return estimate - half, estimate + half


def error_ratio(estimate, lo, hi) -> jnp.ndarray:
    """The paper's reported metric: relative CI width (high-low)/|estimate|."""
    denom = jnp.maximum(jnp.abs(estimate), jnp.asarray(1e-30, jnp.asarray(estimate).dtype))
    return (hi - lo) / denom


# HAVING op codes shared by the frozen path (string ops) and the slot-table
# path (per-slot code columns); -1 marks "no HAVING clause".
HAVING_NONE = -1
HAVING_OP_CODES = {"<": 0, "<=": 1, ">": 2, ">=": 3}


def having_decision_coded(lo, hi, op, threshold) -> jnp.ndarray:
    """Decide ``HAVING agg <op> threshold`` from the confidence interval,
    with ``op`` given as (arrays of) ``HAVING_OP_CODES`` values.

    Returns int8: 1 = decidedly true, 0 = decidedly false, -1 = undecided
    (also -1 wherever ``op == HAVING_NONE``).  The PTF early-out (Section
    1): a verification query stops as soon as the whole interval is on one
    side of the threshold.
    """
    t = jnp.asarray(threshold, jnp.asarray(lo).dtype)
    op = jnp.asarray(op, jnp.int32)
    true_ = jnp.select([op == 0, op == 1, op == 2, op == 3],
                       [hi < t, hi <= t, lo > t, lo >= t], False)
    false_ = jnp.where(op <= 1, lo > t, hi < t)
    return jnp.where(
        op == HAVING_NONE, jnp.int8(-1),
        jnp.where(true_, jnp.int8(1),
                  jnp.where(false_, jnp.int8(0), jnp.int8(-1))))


def having_decision(lo, hi, op: str, threshold) -> jnp.ndarray:
    """String-op convenience wrapper over :func:`having_decision_coded`."""
    if op not in HAVING_OP_CODES:
        raise ValueError(f"unsupported HAVING op: {op}")
    return having_decision_coded(lo, hi, HAVING_OP_CODES[op], threshold)


def having_verdict(lo, hi, op: str, threshold) -> int:
    """:func:`having_decision` of one interval on the host, in float32
    numpy: the serving host decides seed-only and per-cell verdicts without
    running (or compiling) a device op."""
    if op not in HAVING_OP_CODES:
        raise ValueError(f"unsupported HAVING op: {op}")
    lo, hi, t = np.float32(lo), np.float32(hi), np.float32(threshold)
    true_ = {"<": hi < t, "<=": hi <= t, ">": lo > t, ">=": lo >= t}[op]
    false_ = lo > t if op in ("<", "<=") else hi < t
    return 1 if true_ else (0 if false_ else -1)


# ---------------------------------------------------------------------------
# Design-time (true) variance, Eq. (2) — used by tests and by the Monte-Carlo
# coverage benchmark to compare the estimator against ground truth.
# ---------------------------------------------------------------------------

def variance_true(chunk_sums: jnp.ndarray, within_ss: jnp.ndarray,
                  chunk_sizes: jnp.ndarray, n: int, m: jnp.ndarray) -> jnp.ndarray:
    """Eq. (2) for a fixed design (n chunks, m_j tuples from chunk j).

    ``chunk_sums`` are the true y_j, ``within_ss[j] = Σ_{i∈C_j}(x_i − y_j/M_j)²``.
    """
    dtype = chunk_sums.dtype
    big_n = _f(chunk_sums.shape[-1], dtype)
    n = _f(n, dtype)
    big_m = chunk_sizes.astype(dtype)
    m = jnp.maximum(m.astype(dtype), 1.0)
    ybar = jnp.mean(chunk_sums, axis=-1, keepdims=True)
    between = big_n / (big_n - 1.0) * (big_n - n) / n * jnp.sum(
        (chunk_sums - ybar) ** 2, axis=-1)
    fpc = big_m / jnp.maximum(big_m - 1.0, 1.0) * (big_m - m) / m
    within = big_n / n * jnp.sum(fpc * within_ss, axis=-1)
    return between + within


def sample_size_for_accuracy(estimate, variance, m_used, epsilon, confidence=0.95):
    """Rough inverse-CLT planning helper: how many more tuples (at the current
    per-tuple variance rate) until ``error_ratio <= epsilon``.  Used by the
    resource-aware policy's calibration (Section 5.4) to set round budgets."""
    dtype = jnp.asarray(estimate).dtype
    z = ndtri(jnp.asarray((1.0 + confidence) / 2.0, dtype=dtype))
    target_half = jnp.abs(estimate) * epsilon / 2.0
    target_var = (target_half / z) ** 2
    ratio = jnp.where(target_var > 0, variance / jnp.maximum(target_var, 1e-30), jnp.inf)
    return jnp.ceil(jnp.maximum(ratio - 1.0, 0.0) * jnp.maximum(m_used, 1))
