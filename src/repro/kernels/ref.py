"""Pure-jnp oracles for every kernel in this package.

These are the semantics contract: each Pallas kernel must ``allclose`` these
functions across the shape/dtype sweeps in tests/test_kernels.py.  They are
also the CPU execution path used by the engine when no TPU is present.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.data.formats import AsciiFixedFormat

# f32 contractions here run at full precision: XLA:TPU's default rounds f32
# matmul operands to bf16, far outside the kernels' fp32 parity tolerance.
HIGHEST = jax.lax.Precision.HIGHEST

# Group-discovery tally table width (power of two; shared by the engine's
# jnp path, the Pallas kernel, and the host-side sketch fold).
TALLY_BUCKETS = 128


def tally_hash(vals: jnp.ndarray, salt: jnp.ndarray,
               buckets: int) -> jnp.ndarray:
    """Salted multiplicative hash of f32 group values into [0, buckets).

    ``salt`` (uint32 — the engine passes the round number) re-buckets every
    round, so two values colliding this round almost surely separate next
    round: collisions are *transient*, and the host-side SpaceSaving fold
    only trusts buckets whose moments prove a single occupant
    (Σv² · count == (Σv)² within fp tolerance).
    """
    lg = int(buckets).bit_length() - 1
    assert (1 << lg) == int(buckets), "tally buckets must be a power of two"
    u = jax.lax.bitcast_convert_type(vals.astype(jnp.float32), jnp.uint32)
    h = (u ^ (salt * jnp.uint32(2654435761))) * jnp.uint32(2246822519)
    return (h >> jnp.uint32(32 - lg)).astype(jnp.int32)


def parse_ascii_ref(raw: jnp.ndarray, num_cols: int) -> jnp.ndarray:
    """(T, rec_bytes) uint8 fixed-width ASCII -> (T, C) f32: the codec's
    ``decode_ref``."""
    return AsciiFixedFormat(num_cols).decode_ref(raw)


def eval_plan_ref(vals: jnp.ndarray, coeffs: jnp.ndarray, lo: jnp.ndarray,
                  hi: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Linear-plan evaluation: vals (..., C) -> x (Q, ...), p (Q, ...).

    ``x`` is predicate-masked (Table 1 convention), ``p`` the 0/1 indicator.
    COUNT queries carry zero coefficients; callers use ``p`` for them.
    """
    qshape = (lo.shape[0],) + (1,) * (vals.ndim - 1) + (lo.shape[-1],)
    lo_b = lo.reshape(qshape)
    hi_b = hi.reshape(qshape)
    pred = jnp.all((vals[None] >= lo_b) & (vals[None] < hi_b), axis=-1)  # (Q, ...)
    expr = jnp.einsum("...c,qc->q...", vals, coeffs, precision=HIGHEST)
    pf = pred.astype(vals.dtype)
    return expr * pf, pf


def chunk_agg_ref(raw: jnp.ndarray, num_cols: int, coeffs, lo, hi,
                  sizes: jnp.ndarray) -> jnp.ndarray:
    """Full-chunk fused parse+eval+aggregate.

    raw (N, M, rec) uint8, sizes (N,) -> out (N, Q, 4) with
    out[j, q] = (m_valid, Σx, Σx², Σp) over the first ``sizes[j]`` rows.
    """
    n, m, _ = raw.shape
    vals = parse_ascii_ref(raw.reshape(n * m, -1), num_cols).reshape(n, m, num_cols)
    x, p = eval_plan_ref(vals, coeffs, lo, hi)    # (Q, N, M)
    row_ok = (jnp.arange(m)[None, :] < sizes[:, None]).astype(vals.dtype)  # (N, M)
    x = x * row_ok[None]
    p = p * row_ok[None]
    cnt = jnp.broadcast_to(jnp.sum(row_ok, -1)[None], x.shape[:2])  # (Q, N)
    out = jnp.stack([cnt, jnp.sum(x, -1), jnp.sum(x * x, -1), jnp.sum(p, -1)],
                    axis=-1)                      # (Q, N, 4)
    return jnp.transpose(out, (1, 0, 2))          # (N, Q, 4)


def _slot_stats_from_cols(cols: jnp.ndarray, b_eff: jnp.ndarray, coeffs, lo,
                          hi, is_count, gate, weights=None) -> jnp.ndarray:
    """Decoded window (W, B, C) f32 -> per-(worker, slot) stats (W, S, 4).

    The shared back half of :func:`slot_extract_ref` and the decoded-input
    fast path: slot eval + fairness-capped budget masking + stat sums.  Op
    order is the historic one, so the raw path stays bit-identical.
    """
    b = cols.shape[1]
    x, p = eval_plan_ref(cols, coeffs, lo, hi)    # (S, W, B)
    x = jnp.where(jnp.asarray(is_count)[:, None, None] > 0.0, p, x)
    if weights is None:
        weights = jnp.ones((x.shape[0],), jnp.float32)
    bs = jnp.minimum(jnp.ceil(jnp.asarray(weights, jnp.float32)[:, None]
                              * b_eff[None, :].astype(jnp.float32)
                              ).astype(b_eff.dtype), b_eff[None, :])  # (S, W)
    ok_s = (jnp.arange(b)[None, None, :]
            < bs[:, :, None]).astype(cols.dtype)  # (S, W, B)
    mask = ok_s * jnp.asarray(gate, cols.dtype)[:, None, None]
    x = x * mask
    p = p * mask
    cnt = jnp.sum(ok_s, -1)                       # (S, W)
    out = jnp.stack([cnt, jnp.sum(x, -1), jnp.sum(x * x, -1), jnp.sum(p, -1)],
                    axis=-1)                      # (S, W, 4)
    return jnp.transpose(out, (1, 0, 2))


def _group_stats_from_cols(cols: jnp.ndarray, b_eff: jnp.ndarray, coeffs, lo,
                           hi, is_count, gate, gcol, gval, gact, salt,
                           tally_buckets: int, weights=None,
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Grouped back half: decoded window (W, B, C) -> per-(worker, slot,
    cell) stats (W, S, G, 4) plus salted group tallies (W, S, 3, H).

    Stats lanes are ``(rows matched, Σx, Σx², Σp)`` with the same masking as
    :func:`_slot_stats_from_cols`; every mask factor is an exact 0/1 float,
    so tracked-cell sums are bit-exact against a dedicated fan-out slot
    whose predicate carries the group-membership conjunct.  Cell G-1 is the
    ``__other__`` spill: its indicator is the complement of the tracked-cell
    sum (a row matches at most one tracked value).
    """
    w, b, c = cols.shape
    x, p = eval_plan_ref(cols, coeffs, lo, hi)    # (S, W, B)
    x = jnp.where(jnp.asarray(is_count)[:, None, None] > 0.0, p, x)
    if weights is None:
        weights = jnp.ones((x.shape[0],), jnp.float32)
    bs = jnp.minimum(jnp.ceil(jnp.asarray(weights, jnp.float32)[:, None]
                              * b_eff[None, :].astype(jnp.float32)
                              ).astype(b_eff.dtype), b_eff[None, :])  # (S, W)
    ok_s = (jnp.arange(b)[None, None, :]
            < bs[:, :, None]).astype(cols.dtype)  # (S, W, B)
    mask = ok_s * jnp.asarray(gate, cols.dtype)[:, None, None]
    x = x * mask
    p = p * mask
    colv = jnp.moveaxis(cols, -1, 0)[jnp.clip(jnp.asarray(gcol), 0, c - 1)]
    gvalf = jnp.asarray(gval, cols.dtype)         # (S, G)
    gactf = jnp.asarray(gact, cols.dtype)
    eq = (colv[:, None] == gvalf[:, :, None, None]).astype(cols.dtype)
    trk = eq * gactf[:, :, None, None]            # (S, G, W, B)
    other = ((1.0 - jnp.sum(trk[:, :-1], axis=1))
             * gactf[:, -1][:, None, None])       # (S, W, B)
    ind = jnp.concatenate([trk[:, :-1], other[:, None]], axis=1)  # (S, G, W, B)
    gx = ind * x[:, None]
    gp = ind * p[:, None]
    cnt = jnp.sum(ind * mask[:, None], -1)        # (S, G, W)
    out = jnp.stack([cnt, jnp.sum(gx, -1), jnp.sum(gx * gx, -1),
                     jnp.sum(gp, -1)], axis=-1)   # (S, G, W, 4)
    gstats = jnp.transpose(out, (2, 0, 1, 3))     # (W, S, G, 4)

    h = tally_hash(colv, jnp.asarray(salt, jnp.uint32), tally_buckets)
    oh = (h[..., None] == jnp.arange(tally_buckets, dtype=jnp.int32)
          ).astype(cols.dtype)                    # (S, W, B, H)
    # tallies only exist while the slot discovers groups (__other__ cell
    # live); ungrouped slots would otherwise tally their clipped column
    moments = jnp.stack([p, p * colv, p * colv * colv], axis=2)  # (S, W, 3, B)
    moments = moments * gactf[:, -1][:, None, None, None]
    tal = jnp.einsum("swmb,swbh->wsmh", moments, oh,
                     precision=HIGHEST)                          # (W, S, 3, H)
    return gstats, tal


def slot_extract_grouped_ref(packed: jnp.ndarray, jw: jnp.ndarray,
                             idx: jnp.ndarray, b_eff: jnp.ndarray, coeffs,
                             lo, hi, is_count, gate, gcol, gval, gact, salt,
                             num_cols: int, tally_buckets: int = TALLY_BUCKETS,
                             return_cols: bool = False, weights=None):
    """Grouped fused-extraction oracle (packed residency).

    :func:`slot_extract_ref`'s contract plus per-cell stats and group
    tallies: returns ``(stats (W, S, 4), cols|None, gstats (W, S, G, 4),
    tal (W, S, 3, H))``.
    """
    w, b = idx.shape
    raw = packed[jw[:, None], idx]
    cols = parse_ascii_ref(raw.reshape(w * b, -1), num_cols).reshape(
        w, b, num_cols)
    stats = _slot_stats_from_cols(cols, b_eff, coeffs, lo, hi, is_count, gate,
                                  weights)
    gstats, tal = _group_stats_from_cols(cols, b_eff, coeffs, lo, hi,
                                         is_count, gate, gcol, gval, gact,
                                         salt, tally_buckets, weights)
    return stats, (cols if return_cols else None), gstats, tal


def slot_extract_ref(packed: jnp.ndarray, jw: jnp.ndarray, idx: jnp.ndarray,
                     b_eff: jnp.ndarray, coeffs, lo, hi, is_count, gate,
                     num_cols: int, return_cols: bool = False, weights=None):
    """Fused round extraction oracle (see kernels/slot_extract.py).

    packed (N, M, rec) uint8, jw (W,) chunk ids, idx (W, B) permutation-window
    rows, b_eff (W,), coeffs/lo/hi (S, C), is_count/gate (S,) ->
    (stats (W, S, 4) = (m, Σx, Σx², Σp), cols (W, B, C) | None).
    ``weights`` (S,) are the scheduler's per-slot fairness shares: slot s
    counts only the first ``ceil(weight_s·b_eff)`` window rows (``None`` or
    all-ones = the unweighted round, bit-identical to the historic path).
    """
    w, b = idx.shape
    raw = packed[jw[:, None], idx]                # (W, B, rec) gathered rows
    cols = parse_ascii_ref(raw.reshape(w * b, -1), num_cols).reshape(
        w, b, num_cols)
    stats = _slot_stats_from_cols(cols, b_eff, coeffs, lo, hi, is_count, gate,
                                  weights)
    return stats, (cols if return_cols else None)


def slot_eval_decoded_ref(dec: jnp.ndarray, idx: jnp.ndarray,
                          b_eff: jnp.ndarray, coeffs, lo, hi, is_count, gate,
                          weights=None) -> jnp.ndarray:
    """Decoded-input round extraction oracle: skip tokenize/parse entirely.

    ``dec (W, R, C)`` f32 — worker w's *already decoded* chunk rows at
    ``dec[w]`` (the parse-once decoded-chunk cache) — idx (W, B) window rows,
    b_eff (W,) -> stats (W, S, 4).  Identical contract to
    :func:`slot_extract_stream_ref` minus the EXTRACT: the gathered rows go
    straight to slot eval, which is what makes re-scans of cached chunks
    cheap.
    """
    w = idx.shape[0]
    cols = dec[jnp.arange(w, dtype=jnp.int32)[:, None], idx]  # (W, B, C)
    return _slot_stats_from_cols(cols, b_eff, coeffs, lo, hi, is_count, gate,
                                 weights)


def window_cache_rows_ref(cols: jnp.ndarray, b_eff: jnp.ndarray,
                          m_before: jnp.ndarray,
                          cache_cap: int) -> jnp.ndarray:
    """Synopsis-cache delta rows from a decoded window.

    cols (W, B, C) f32, b_eff (W,), m_before (W,) scan positions ->
    (W, cache_cap, C) where row ``r`` holds ``cols[w, r - m_before[w]]`` when
    that window position exists (``0 <= r - m_before < b_eff``) and zeros
    otherwise — exactly the rows the round scatters into the per-chunk
    synopsis cache, without materializing anything per window row.
    """
    w, b, _ = cols.shape
    k = (jnp.arange(cache_cap, dtype=jnp.int32)[None, :]
         - jnp.asarray(m_before, jnp.int32)[:, None])          # (W, cap)
    valid = (k >= 0) & (k < b_eff[:, None])
    rows = jnp.take_along_axis(cols, jnp.clip(k, 0, b - 1)[..., None], axis=1)
    return rows * valid[..., None].astype(cols.dtype)


def stream_cache_rows_ref(slab: jnp.ndarray, idx: jnp.ndarray,
                          b_eff: jnp.ndarray, m_before: jnp.ndarray,
                          cache_cap: int, num_cols: int) -> jnp.ndarray:
    """Raw-slab oracle for the in-kernel synopsis-cache emission: gather +
    parse the window, then select the cache rows (see
    :func:`window_cache_rows_ref`)."""
    w, b = idx.shape
    raw = slab[jnp.arange(w, dtype=jnp.int32)[:, None], idx]
    cols = parse_ascii_ref(raw.reshape(w * b, -1), num_cols).reshape(
        w, b, num_cols)
    return window_cache_rows_ref(cols, b_eff, m_before, cache_cap)


def slot_extract_stream_ref(slab: jnp.ndarray, idx: jnp.ndarray,
                            b_eff: jnp.ndarray, coeffs, lo, hi, is_count,
                            gate, num_cols: int, weights=None) -> jnp.ndarray:
    """Slab-streaming round extraction oracle (see kernels/slot_extract.py).

    Identical contract to :func:`slot_extract_ref` except the raw source is
    the round's per-worker slab ``(W, R, rec)`` — worker w's rows live at
    ``slab[w]`` — instead of the whole packed store, so there is no chunk-id
    indirection.  Returns stats ``(W, S, 4)`` only (the streaming path
    decodes the synopsis slab separately when it needs it).
    """
    w = idx.shape[0]
    stats, _ = slot_extract_ref(slab, jnp.arange(w, dtype=jnp.int32), idx,
                                b_eff, coeffs, lo, hi, is_count, gate,
                                num_cols=num_cols, return_cols=False,
                                weights=weights)
    return stats


def round_stats_ref(slab: jnp.ndarray, num_cols: int, coeffs, lo, hi,
                    b_eff: jnp.ndarray) -> jnp.ndarray:
    """Bi-level round slab: fused parse+eval+budget-masked stats.

    slab (W, B, rec) uint8 (rows already gathered in the chunk's permutation
    order), b_eff (W,) -> out (W, Q, 4) = (m, y', y'', p') over rows < b_eff.
    """
    w, b, _ = slab.shape
    vals = parse_ascii_ref(slab.reshape(w * b, -1), num_cols).reshape(w, b, num_cols)
    x, p = eval_plan_ref(vals, coeffs, lo, hi)    # (Q, W, B)
    ok = (jnp.arange(b)[None, :] < b_eff[:, None]).astype(vals.dtype)  # (W, B)
    x = x * ok[None]
    p = p * ok[None]
    cnt = jnp.broadcast_to(jnp.sum(ok, -1)[None], x.shape[:2])  # (Q, W)
    out = jnp.stack([cnt, jnp.sum(x, -1), jnp.sum(x * x, -1), jnp.sum(p, -1)],
                    axis=-1)                      # (Q, W, 4)
    return jnp.transpose(out, (1, 0, 2))          # (W, Q, 4)
