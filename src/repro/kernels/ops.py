"""Jitted public wrappers with platform dispatch for the kernels package.

``backend`` semantics:

* ``"auto"``    — the compiled kernel on TPU, the pure-jnp oracle elsewhere
                  (the oracle compiles to decent XLA:CPU code, while the
                  Pallas interpreter is a debugging tool).
* ``"pallas"``  — the compiled kernel.  Needs a TPU: anywhere else it
                  raises rather than quietly running the interpreter (a
                  libtpu that failed to start leaves JAX on the CPU).
* ``"pallas-interpret"`` — the kernel under the Pallas interpreter, on any
                  platform (the CPU parity tests and the benchmarks'
                  correctness-mode lane).
* ``"ref"``     — the oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.chunk_agg import chunk_agg_pallas
from repro.kernels.extract_parse import extract_parse_pallas
from repro.kernels.round_stats import round_stats_pallas
from repro.kernels.slot_extract import (
    slot_eval_decoded_pallas,
    slot_extract_grouped_pallas,
    slot_extract_pallas,
    slot_extract_stream_pallas,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def require_tpu(backend: str) -> None:
    """Raise unless JAX runs on a TPU: ``backend`` names the compiled kernel."""
    if not _on_tpu():
        raise RuntimeError(
            f"backend {backend!r} runs the compiled Pallas kernel, which needs "
            f"a TPU, but JAX's default backend is {jax.default_backend()!r}; "
            "use 'pallas-interpret' for the interpreter or 'ref'")


def _resolve(backend: str) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    if backend == "auto":
        return (_on_tpu(), False)
    if backend == "pallas":
        require_tpu(backend)
        return (True, False)
    if backend == "pallas-interpret":
        return (True, True)
    if backend == "ref":
        return (False, False)
    raise ValueError(backend)


def extract_parse(raw: jnp.ndarray, num_cols: int,
                  backend: str = "auto") -> jnp.ndarray:
    """(T, rec_bytes) uint8 fixed-width ASCII -> (T, C) f32."""
    use_pallas, interpret = _resolve(backend)
    if use_pallas:
        return extract_parse_pallas(raw, num_cols, interpret=interpret)
    return _ref.parse_ascii_ref(raw, num_cols)


def chunk_agg(raw: jnp.ndarray, sizes: jnp.ndarray, coeffs, lo, hi,
              backend: str = "auto") -> jnp.ndarray:
    """(N, M, rec) uint8 + plan -> (N, Q, 4) per-chunk (count, Σx, Σx², Σp)."""
    num_cols = int(coeffs.shape[1])
    use_pallas, interpret = _resolve(backend)
    if use_pallas:
        return chunk_agg_pallas(raw, jnp.asarray(sizes, jnp.int32),
                                jnp.asarray(coeffs, jnp.float32),
                                jnp.asarray(lo, jnp.float32),
                                jnp.asarray(hi, jnp.float32),
                                num_cols=num_cols, interpret=interpret)
    return _ref.chunk_agg_ref(raw, num_cols, jnp.asarray(coeffs, jnp.float32),
                              jnp.asarray(lo, jnp.float32),
                              jnp.asarray(hi, jnp.float32),
                              jnp.asarray(sizes, jnp.int32))


def slot_extract(packed: jnp.ndarray, jw: jnp.ndarray, idx: jnp.ndarray,
                 b_eff: jnp.ndarray, coeffs, lo, hi, is_count, gate,
                 return_cols: bool = False, backend: str = "auto",
                 weights=None, gcol=None, gval=None, gact=None, salt=None,
                 tally_buckets: int = _ref.TALLY_BUCKETS):
    """Fused round extraction: gather + parse + slot eval + partial stats.

    packed (N, M, rec) uint8, jw (W,) chunk ids, idx (W, B) window rows ->
    (stats (W, S, 4), cols (W, B, C) | None).  This is the engine round's
    ``extract_backend="pallas"`` path (see core/engine.py).

    Passing the grouped-plane descriptors (``gcol (S,)`` int32, ``gval``/
    ``gact (S, G)`` f32, ``salt`` uint32 round number) switches to the
    grouped variant, which additionally returns per-cell partial stats
    ``(W, S, G, 4)`` and salted group tallies ``(W, S, 3, H)``:
    ``(stats, cols|None, gstats, tal)``.
    """
    num_cols = int(coeffs.shape[1])
    use_pallas, interpret = _resolve(backend)
    jw, idx, b_eff = (jnp.asarray(jw, jnp.int32), jnp.asarray(idx, jnp.int32),
                      jnp.asarray(b_eff, jnp.int32))
    coeffs, lo, hi, is_count, gate = (
        jnp.asarray(a, jnp.float32) for a in (coeffs, lo, hi, is_count, gate))
    if weights is None:
        weights = jnp.ones((coeffs.shape[0],), jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    grouped = gval is not None and int(gval.shape[1]) > 0
    if grouped:
        gcol = jnp.asarray(gcol, jnp.int32)
        gval = jnp.asarray(gval, jnp.float32)
        gact = jnp.asarray(gact, jnp.float32)
        salt = (jnp.asarray(0, jnp.uint32) if salt is None
                else jnp.asarray(salt, jnp.uint32))
        if use_pallas:
            return slot_extract_grouped_pallas(
                packed, jw, idx, b_eff, coeffs, lo, hi, is_count, gate,
                weights, gcol, gval, gact, salt, num_cols=num_cols,
                tally_buckets=tally_buckets, return_cols=return_cols,
                interpret=interpret)
        return _ref.slot_extract_grouped_ref(
            packed, jw, idx, b_eff, coeffs, lo, hi, is_count, gate,
            gcol, gval, gact, salt, num_cols=num_cols,
            tally_buckets=tally_buckets, return_cols=return_cols,
            weights=weights)
    if use_pallas:
        return slot_extract_pallas(packed, jw, idx, b_eff, coeffs, lo, hi,
                                   is_count, gate, weights,
                                   num_cols=num_cols,
                                   return_cols=return_cols,
                                   interpret=interpret)
    return _ref.slot_extract_ref(packed, jw, idx, b_eff, coeffs, lo, hi,
                                 is_count, gate, num_cols=num_cols,
                                 return_cols=return_cols, weights=weights)


def slot_extract_stream(slab: jnp.ndarray, idx: jnp.ndarray,
                        b_eff: jnp.ndarray, coeffs, lo, hi, is_count, gate,
                        row_tile: int = 256, backend: str = "auto",
                        weights=None, cache_cap: int = 0, m_before=None):
    """Slab-streaming fused round extraction (``residency="stream"``).

    slab (W, R, rec) uint8 — worker w's chunk rows at slab[w] (assembled by
    ``data/pipeline.SlabPrefetcher``), idx (W, B) window rows, b_eff (W,) ->
    stats (W, S, 4).  Unlike :func:`slot_extract` the kernel grids over row
    *tiles* of the slab, so chunks larger than VMEM stream tile-by-tile.

    ``cache_cap > 0`` additionally returns the synopsis-cache delta rows
    ``(W, cache_cap, C)`` at scan positions ``m_before`` — the streaming
    path's replacement for re-decoding the whole window just to feed the
    cache: the call then returns ``(stats, cache_rows)``.
    """
    num_cols = int(coeffs.shape[1])
    use_pallas, interpret = _resolve(backend)
    idx, b_eff = jnp.asarray(idx, jnp.int32), jnp.asarray(b_eff, jnp.int32)
    coeffs, lo, hi, is_count, gate = (
        jnp.asarray(a, jnp.float32) for a in (coeffs, lo, hi, is_count, gate))
    if weights is None:
        weights = jnp.ones((coeffs.shape[0],), jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    if m_before is not None:
        m_before = jnp.asarray(m_before, jnp.int32)
    if use_pallas:
        return slot_extract_stream_pallas(slab, idx, b_eff, coeffs, lo, hi,
                                          is_count, gate, weights,
                                          num_cols=num_cols,
                                          row_tile=row_tile,
                                          cache_cap=cache_cap,
                                          m_before=m_before,
                                          interpret=interpret)
    stats = _ref.slot_extract_stream_ref(slab, idx, b_eff, coeffs, lo, hi,
                                         is_count, gate, num_cols=num_cols,
                                         weights=weights)
    if cache_cap > 0:
        if m_before is None:
            m_before = jnp.zeros((idx.shape[0],), jnp.int32)
        return stats, _ref.stream_cache_rows_ref(slab, idx, b_eff, m_before,
                                                 cache_cap, num_cols)
    return stats


def slot_eval_decoded(dec: jnp.ndarray, idx: jnp.ndarray, b_eff: jnp.ndarray,
                      coeffs, lo, hi, is_count, gate, row_tile: int = 256,
                      backend: str = "auto", weights=None, cache_cap: int = 0,
                      m_before=None):
    """Decoded-input slot eval (the parse-once fast path).

    dec (W, R, C) f32 — worker w's already-decoded chunk rows at dec[w]
    (served by the decoded-chunk cache), idx (W, B) window rows, b_eff (W,)
    -> stats (W, S, 4), skipping tokenize/parse entirely.  Same
    ``cache_cap``/``m_before`` synopsis-cache emission contract as
    :func:`slot_extract_stream`.
    """
    use_pallas, interpret = _resolve(backend)
    num_cols = int(coeffs.shape[1])
    idx, b_eff = jnp.asarray(idx, jnp.int32), jnp.asarray(b_eff, jnp.int32)
    coeffs, lo, hi, is_count, gate = (
        jnp.asarray(a, jnp.float32) for a in (coeffs, lo, hi, is_count, gate))
    if weights is None:
        weights = jnp.ones((coeffs.shape[0],), jnp.float32)
    weights = jnp.asarray(weights, jnp.float32)
    if m_before is not None:
        m_before = jnp.asarray(m_before, jnp.int32)
    if use_pallas:
        return slot_eval_decoded_pallas(dec, idx, b_eff, coeffs, lo, hi,
                                        is_count, gate, weights,
                                        num_cols=num_cols, row_tile=row_tile,
                                        cache_cap=cache_cap,
                                        m_before=m_before,
                                        interpret=interpret)
    stats = _ref.slot_eval_decoded_ref(dec, idx, b_eff, coeffs, lo, hi,
                                       is_count, gate, weights=weights)
    if cache_cap > 0:
        if m_before is None:
            m_before = jnp.zeros((idx.shape[0],), jnp.int32)
        w = idx.shape[0]
        cols = dec[jnp.arange(w, dtype=jnp.int32)[:, None], idx]
        return stats, _ref.window_cache_rows_ref(cols, b_eff, m_before,
                                                 cache_cap)
    return stats


def round_stats(slab: jnp.ndarray, b_eff: jnp.ndarray, coeffs, lo, hi,
                backend: str = "auto") -> jnp.ndarray:
    """(W, B, rec) uint8 slab + budgets -> (W, Q, 4) partial stats."""
    num_cols = int(coeffs.shape[1])
    use_pallas, interpret = _resolve(backend)
    if use_pallas:
        return round_stats_pallas(slab, jnp.asarray(b_eff, jnp.int32),
                                  jnp.asarray(coeffs, jnp.float32),
                                  jnp.asarray(lo, jnp.float32),
                                  jnp.asarray(hi, jnp.float32),
                                  num_cols=num_cols, interpret=interpret)
    return _ref.round_stats_ref(slab, num_cols,
                                jnp.asarray(coeffs, jnp.float32),
                                jnp.asarray(lo, jnp.float32),
                                jnp.asarray(hi, jnp.float32),
                                jnp.asarray(b_eff, jnp.int32))
