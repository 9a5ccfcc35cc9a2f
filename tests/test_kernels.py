"""Pallas kernels: shape/dtype sweeps, interpret-mode vs pure-jnp oracle."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.queries import Linear, Query, Range, TRUE, linear_plan
from repro.data.formats import AsciiFixedFormat
from repro.kernels import chunk_agg, extract_parse, round_stats
from repro.kernels import ref as R

RTOL = 2e-5


def _plan(num_cols, nq=2):
    qs = [Query(agg="sum", expr=Linear((1.0,) * num_cols),
                pred=Range(0, -500.0, 500.0)),
          Query(agg="count", pred=TRUE)][:nq]
    return linear_plan(qs, num_cols)


@pytest.mark.parametrize("t", [1, 7, 255, 256, 300])
@pytest.mark.parametrize("c", [1, 3, 8, 16])
def test_extract_parse_sweep(t, c):
    rng = np.random.default_rng(t * 31 + c)
    fmt = AsciiFixedFormat(c)
    vals = rng.uniform(-1e6, 1e6, (t, c))
    raw = jnp.asarray(fmt.encode(vals))
    a = np.asarray(extract_parse(raw, c, backend="pallas-interpret"))
    b = np.asarray(extract_parse(raw, c, backend="ref"))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(a, vals, rtol=1e-5, atol=1e-2)


@pytest.mark.parametrize("n,m", [(1, 50), (3, 256), (5, 300)])
@pytest.mark.parametrize("c", [4, 8])
def test_chunk_agg_sweep(n, m, c):
    rng = np.random.default_rng(n * 100 + m + c)
    fmt = AsciiFixedFormat(c)
    raw = np.stack([fmt.encode(rng.uniform(-1000, 1000, (m, c)))
                    for _ in range(n)])
    sizes = rng.integers(1, m + 1, n).astype(np.int32)
    plan = _plan(c)
    a = np.asarray(chunk_agg(jnp.asarray(raw), sizes, plan.coeffs, plan.lo,
                             plan.hi, backend="pallas-interpret"))
    b = np.asarray(chunk_agg(jnp.asarray(raw), sizes, plan.coeffs, plan.lo,
                             plan.hi, backend="ref"))
    np.testing.assert_allclose(a, b, rtol=RTOL, atol=1e-2)
    # count column == sizes
    np.testing.assert_allclose(a[:, 0, 0], sizes, rtol=1e-6)


@pytest.mark.parametrize("w,b", [(1, 8), (4, 32), (7, 64)])
def test_round_stats_sweep(w, b):
    c = 6
    rng = np.random.default_rng(w * 10 + b)
    fmt = AsciiFixedFormat(c)
    slab = np.stack([fmt.encode(rng.uniform(-1000, 1000, (b, c)))
                     for _ in range(w)])
    beff = rng.integers(0, b + 1, w).astype(np.int32)
    plan = _plan(c)
    a = np.asarray(round_stats(jnp.asarray(slab), beff, plan.coeffs, plan.lo,
                               plan.hi, backend="pallas-interpret"))
    rr = np.asarray(round_stats(jnp.asarray(slab), beff, plan.coeffs, plan.lo,
                                plan.hi, backend="ref"))
    np.testing.assert_allclose(a, rr, rtol=RTOL, atol=1e-2)
    np.testing.assert_allclose(a[:, 0, 0], beff, rtol=1e-6)


def test_chunk_agg_matches_brute_force():
    """End-to-end semantic check against a numpy recompute."""
    c, n, m = 4, 3, 128
    rng = np.random.default_rng(0)
    fmt = AsciiFixedFormat(c)
    data = [rng.uniform(-1000, 1000, (m, c)) for _ in range(n)]
    raw = np.stack([fmt.encode(d) for d in data])
    sizes = np.asarray([m, 77, 5], np.int32)
    plan = _plan(c, nq=1)
    out = np.asarray(chunk_agg(jnp.asarray(raw), sizes, plan.coeffs, plan.lo,
                               plan.hi, backend="pallas-interpret"))
    for j in range(n):
        d = data[j][: sizes[j]]
        sel = (d[:, 0] >= -500) & (d[:, 0] < 500)
        x = d.sum(1) * sel
        np.testing.assert_allclose(out[j, 0, 1], x.sum(), rtol=1e-4)
        np.testing.assert_allclose(out[j, 0, 2], (x ** 2).sum(), rtol=1e-4)
        np.testing.assert_allclose(out[j, 0, 3], sel.sum(), rtol=1e-6)
