"""Fused engine-round extraction as a Pallas kernel: gather + parse + slot eval.

This is the bi-level round's hot loop for the *dynamic* query plane (and the
frozen plane lowered to coefficient form): for each worker, gather its
permutation-window rows from the packed chunk buffer, parse the raw bytes in
VMEM, evaluate the slot table — per-slot ``coeffs/lo/hi`` with the active
mask as a multiplicative gate — and accumulate the per-(worker, slot)
sufficient statistics ``(m, Σx, Σx², Σp)`` in one pass.  Neither the
``(S, W, B)`` evaluation tensor nor a decoded ``(W, B, C)`` copy is ever
materialized in HBM (the decoded slab is emitted *only* when the caller needs
it for the synopsis extraction cache).

Geometry (grid ``(W,)`` — one step per worker):

* ``packed (N, M_pad, rec)`` uint8 stays in HBM; the worker's chunk id is a
  **scalar-prefetch** argument, so the BlockSpec index map selects block
  ``(1, M_pad, rec)`` — the worker's whole chunk — for the VMEM window.
  This is the paper's in-memory chunk: M_pad·rec bytes (double-buffered)
  must fit VMEM, 8 MiB at 16384 rows of 256 bytes; beyond that,
  :func:`slot_extract_stream_pallas` below streams the round's slab through
  VMEM in row tiles.  ``M_pad`` is the chunk row count padded to
  GATHER_ROWS (the engine's resident view is built padded).
* ``idx (W, B)`` int32 permutation-window rows and ``b_eff (W,)`` budgets are
  scalar-prefetch too (SMEM).  The kernel walks the window ROW_BLOCK rows at
  a time: gather the block's rows into an int32 VMEM scratch, parse, evaluate
  and fold into the output.  Mosaic lowers a uint8 load only at a sublane
  offset it can prove is a multiple of the (32, 128) int8 tile, so each row
  is selected out of the aligned 32-row tile that holds it.
* plan blocks ``coeffs/lo/hi (S, C)`` f32, ``is_count/gate (S,)`` f32 are
  whole-array VMEM blocks shared by every step.
* out ``(1, S, 4)`` f32 per step; optional ``(1, B, C)`` decoded block.

B is a power of two from the engine's t_eval ladder, so block shapes are
stable across rounds and recompiles are bounded, and ROW_BLOCK divides it.
VMEM per step at B=4096, C=16: the chunk block, a (256, 256) int32 scratch,
O(S·256) temporaries and the small plan/out blocks (tests/test_tpu_compile.py
compiles it for a v5e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.data.formats import FIELD_BYTES
from repro.kernels.chunk_agg import _eval_plan_block
from repro.kernels.extract_parse import _parse_block
from repro.kernels.ref import HIGHEST, TALLY_BUCKETS

# int32 twins of the uint32 hash constants in repro.kernels.ref.tally_hash —
# two's-complement multiply/xor wrap to the same bits, so the in-kernel hash
# stays bit-identical to the oracle without uint arithmetic.
_HASH_SALT_MUL = -1640531535      # uint32 2654435761
_HASH_MIX_MUL = -2048144777       # uint32 2246822519


# Window rows are gathered, parsed and folded ROW_BLOCK at a time, so the
# int32 byte scratch and every (S, ..., rows) temporary stay O(ROW_BLOCK)
# whatever the budget rung.
ROW_BLOCK = 256
# Mosaic loads uint8 only at sublane offsets it can prove are multiples of
# the (32, 128) int8 tile; the chunk block's rows are padded to this multiple.
GATHER_ROWS = 32


def _gather_rows(packed_ref, idx_ref, w, start, scratch):
    """``scratch[i] <- packed[chunk, idx[w, start + i]]`` widened to int32.

    Each row is selected out of the aligned GATHER_ROWS-row tile that holds
    it: a single-row uint8 load at a data-dependent offset does not lower.
    """
    m_rows = packed_ref.shape[1]

    def body(i, carry):
        row = jnp.clip(idx_ref[w, start + i], 0, m_rows - 1)
        base = pl.multiple_of(row // GATHER_ROWS * GATHER_ROWS, GATHER_ROWS)
        tile = packed_ref[0, pl.ds(base, GATHER_ROWS), :].astype(jnp.int32)
        hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == row - base
        scratch[pl.ds(i, 1), :] = jnp.sum(jnp.where(hit, tile, 0), axis=0,
                                          keepdims=True)
        return carry

    jax.lax.fori_loop(0, scratch.shape[0], body, 0)


def _fold_block(vals, start, beff, coeffs_ref, lo_ref, hi_ref, isc_ref,
                gate_ref, wts_ref):
    """Slot eval of one row block at window positions ``start + [0, rows)``
    -> masked ``x, p`` (S, rows), the 0/1 budget mask ``ok_s`` and
    ``mask = ok_s · gate``."""
    x, p = _eval_plan_block(vals, coeffs_ref[...],
                            lo_ref[...], hi_ref[...])        # (S, rows)
    # COUNT slots carry zero coefficients; their x is the indicator itself
    x = jnp.where(isc_ref[...][:, None] > 0.0, p, x)
    # per-slot budget: fairness weight w_s caps slot s at the first
    # ceil(w_s·b_eff) window rows (w_s = 1 → the full b_eff, bit-identical
    # to the unweighted round)
    bs = jnp.minimum(jnp.ceil(wts_ref[...] * beff.astype(jnp.float32)
                              ).astype(jnp.int32), beff)     # (S,)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, vals.shape[0]), 1) + start
    ok_s = (pos < bs[:, None]).astype(jnp.float32)           # (S, rows)
    mask = ok_s * gate_ref[...][:, None]                     # (S, rows)
    return x * mask, p * mask, ok_s, mask


def _moments(ok_s, x, p):
    """(S, rows) block -> (S, 4) partial ``(m, Σx, Σx², Σp)``."""
    return jnp.stack([jnp.sum(ok_s, -1), jnp.sum(x, -1),
                      jnp.sum(x * x, -1), jnp.sum(p, -1)], axis=-1)


def _slot_extract_kernel(jw_ref, beff_ref, idx_ref, packed_ref, coeffs_ref,
                         lo_ref, hi_ref, isc_ref, gate_ref, wts_ref, *refs,
                         num_cols: int, budget: int, return_cols: bool):
    if return_cols:
        stats_ref, cols_ref, scratch = refs
    else:
        (stats_ref, scratch), cols_ref = refs, None
    w = pl.program_id(0)
    rows = scratch.shape[0]
    stats_ref[...] = jnp.zeros_like(stats_ref)

    def block(k, carry):
        start = pl.multiple_of(k * rows, rows)
        _gather_rows(packed_ref, idx_ref, w, start, scratch)
        vals = _parse_block(scratch[...], num_cols)          # (rows, C) f32
        if cols_ref is not None:
            cols_ref[0, pl.ds(start, rows), :] = vals
        x, p, ok_s, _ = _fold_block(vals, start, beff_ref[w], coeffs_ref,
                                    lo_ref, hi_ref, isc_ref, gate_ref,
                                    wts_ref)
        stats_ref[0] += _moments(ok_s, x, p)
        return carry

    jax.lax.fori_loop(0, budget // rows, block, 0)


def _packed_block_spec(m_rows: int, rec: int):
    """The worker's whole chunk, selected by the prefetched chunk id."""
    return pl.BlockSpec((1, m_rows, rec),
                        lambda i, jw_ref, *refs: (jw_ref[i], 0, 0))


def _pad_rows(packed):
    """Pad the chunk axis to a GATHER_ROWS multiple (no-op for the engine's
    resident view, which is built padded)."""
    m = packed.shape[1]
    pad = -m % GATHER_ROWS
    return jnp.pad(packed, ((0, 0), (0, pad), (0, 0))) if pad else packed


@functools.partial(jax.jit, static_argnames=("num_cols", "return_cols",
                                             "interpret"))
def slot_extract_pallas(packed: jnp.ndarray, jw: jnp.ndarray,
                        idx: jnp.ndarray, b_eff: jnp.ndarray,
                        coeffs, lo, hi, is_count, gate, weights,
                        num_cols: int,
                        return_cols: bool = False, interpret: bool = False):
    """Fused round extraction.

    packed (N, M_max, rec) uint8, jw (W,) chunk ids, idx (W, B) window rows,
    b_eff (W,) budgets, coeffs/lo/hi (S, C) f32, is_count/gate/weights (S,)
    f32 -> stats (W, S, 4) f32 ``(m, Σx, Σx², Σp)`` [, cols (W, B, C) f32].
    ``weights`` are the scheduler's per-slot fairness shares (1 = full
    budget, see ``repro.sched.fairness``).
    """
    packed = _pad_rows(packed)
    n, m_rows, rec = packed.shape
    assert rec == num_cols * FIELD_BYTES, (rec, num_cols)
    w, b = idx.shape
    s = coeffs.shape[0]
    out_shape = [jax.ShapeDtypeStruct((w, s, 4), jnp.float32)]
    out_specs = [pl.BlockSpec((1, s, 4), lambda i, *refs: (i, 0, 0))]
    if return_cols:
        out_shape.append(jax.ShapeDtypeStruct((w, b, num_cols), jnp.float32))
        out_specs.append(pl.BlockSpec((1, b, num_cols),
                                      lambda i, *refs: (i, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # jw, b_eff, idx
        grid=(w,),
        in_specs=[
            _packed_block_spec(m_rows, rec),
            pl.BlockSpec((s, num_cols), lambda i, *refs: (0, 0)),
            pl.BlockSpec((s, num_cols), lambda i, *refs: (0, 0)),
            pl.BlockSpec((s, num_cols), lambda i, *refs: (0, 0)),
            pl.BlockSpec((s,), lambda i, *refs: (0,)),
            pl.BlockSpec((s,), lambda i, *refs: (0,)),
            pl.BlockSpec((s,), lambda i, *refs: (0,)),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((min(b, ROW_BLOCK), rec), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_slot_extract_kernel, num_cols=num_cols,
                          budget=b, return_cols=return_cols),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(jw, jnp.int32), jnp.asarray(b_eff, jnp.int32),
      jnp.asarray(idx, jnp.int32), packed,
      jnp.asarray(coeffs, jnp.float32), jnp.asarray(lo, jnp.float32),
      jnp.asarray(hi, jnp.float32), jnp.asarray(is_count, jnp.float32),
      jnp.asarray(gate, jnp.float32), jnp.asarray(weights, jnp.float32))
    return tuple(out) if return_cols else (out[0], None)


# ---------------------------------------------------------------------------
# Grouped variant: per-(worker, slot, group-cell) partials + discovery tallies.
#
# Same geometry as _slot_extract_kernel (grid (W,), whole chunk in VMEM via
# scalar-prefetch chunk id), plus three static-G/H additions, all VMEM-only:
# the slot's group column is selected with an exact one-hot matmul
# (goh (S, C) @ vals.T), tracked-cell indicators are 0/1 equality masks
# against gval with the __other__ cell as the tracked-sum complement, and the
# salted discovery tallies are per-slot (3, B) @ (B, H) one-hot matmuls.
# Only the (S, G, 4) sufficient stats and the (S, 3, H) tallies reach HBM.
# ---------------------------------------------------------------------------


def _slot_extract_grouped_kernel(jw_ref, beff_ref, idx_ref, salt_ref,
                                 packed_ref, coeffs_ref, lo_ref, hi_ref,
                                 isc_ref, gate_ref, wts_ref, goh_ref,
                                 gval_ref, gact_ref, *refs, num_cols: int,
                                 budget: int, tally_buckets: int,
                                 return_cols: bool):
    if return_cols:
        stats_ref, cols_ref, gstats_ref, tal_ref, scratch = refs
    else:
        (stats_ref, gstats_ref, tal_ref, scratch), cols_ref = refs, None
    w = pl.program_id(0)
    rows = scratch.shape[0]
    stats_ref[...] = jnp.zeros_like(stats_ref)
    gstats_ref[...] = jnp.zeros_like(gstats_ref)
    tal_ref[...] = jnp.zeros_like(tal_ref)
    gvals = gval_ref[...]                                    # (S, G)
    gacts = gact_ref[...]
    n_slots, g = gvals.shape
    lg = tally_buckets.bit_length() - 1
    salt = salt_ref[0]

    def block(k, carry):
        start = pl.multiple_of(k * rows, rows)
        _gather_rows(packed_ref, idx_ref, w, start, scratch)
        vals = _parse_block(scratch[...], num_cols)          # (rows, C) f32
        if cols_ref is not None:
            cols_ref[0, pl.ds(start, rows), :] = vals
        x, p, ok_s, mask = _fold_block(vals, start, beff_ref[w], coeffs_ref,
                                       lo_ref, hi_ref, isc_ref, gate_ref,
                                       wts_ref)
        stats_ref[0] += _moments(ok_s, x, p)

        # per-slot group-column values via exact one-hot contraction over C
        colv = jax.lax.dot_general(goh_ref[...], vals,
                                   (((1,), (1,)), ((), ())),
                                   precision=HIGHEST,
                                   preferred_element_type=jnp.float32)
        eq = (colv[:, None, :] == gvals[:, :, None]).astype(jnp.float32)
        trk = eq * gacts[:, :, None]                         # (S, G, rows)
        # __other__ (cell G-1): complement of the tracked-cell sum — a row
        # matches at most one tracked value, so this is an exact 0/1 indicator
        tracked = trk * (jax.lax.broadcasted_iota(jnp.int32, (1, g, 1), 1)
                         < g - 1).astype(jnp.float32)
        other = ((1.0 - jnp.sum(tracked, axis=1))
                 * gacts[:, g - 1][:, None])                 # (S, rows)
        is_last = jax.lax.broadcasted_iota(jnp.int32, (1, g, 1), 1) == g - 1
        ind = jnp.where(is_last, other[:, None, :], trk)     # (S, G, rows)
        gstats_ref[0] += _moments(ind * mask[:, None], ind * x[:, None],
                                  ind * p[:, None])

        # salted discovery tallies: hash bits match ref.tally_hash exactly
        # (int32 wraparound == uint32), low-bit mask recovers the logical
        # shift
        u = jax.lax.bitcast_convert_type(colv, jnp.int32)    # (S, rows)
        h = ((u ^ (salt * jnp.int32(_HASH_SALT_MUL)))
             * jnp.int32(_HASH_MIX_MUL))
        h = (jnp.right_shift(h, jnp.int32(32 - lg))
             & jnp.int32(tally_buckets - 1))
        hcol = jax.lax.broadcasted_iota(jnp.int32, (rows, tally_buckets), 1)
        tal = []
        for s_i in range(n_slots):
            oh = (h[s_i][:, None] == hcol).astype(jnp.float32)   # (rows, H)
            # tallies only while the slot discovers groups (__other__ live)
            pt = p[s_i] * gacts[s_i, g - 1]
            mom = jnp.stack([pt, pt * colv[s_i],
                             pt * colv[s_i] * colv[s_i]], axis=0)  # (3, rows)
            tal.append(jnp.dot(mom, oh, precision=HIGHEST,
                               preferred_element_type=jnp.float32))
        tal_ref[0] += jnp.stack(tal, axis=0)                 # (S, 3, H)
        return carry

    jax.lax.fori_loop(0, budget // rows, block, 0)


@functools.partial(jax.jit, static_argnames=("num_cols", "tally_buckets",
                                             "return_cols", "interpret"))
def slot_extract_grouped_pallas(packed: jnp.ndarray, jw: jnp.ndarray,
                                idx: jnp.ndarray, b_eff: jnp.ndarray,
                                coeffs, lo, hi, is_count, gate, weights,
                                gcol, gval, gact, salt, num_cols: int,
                                tally_buckets: int = TALLY_BUCKETS,
                                return_cols: bool = False,
                                interpret: bool = False):
    """Grouped fused round extraction (packed residency).

    :func:`slot_extract_pallas`'s contract plus the grouped plane: gcol (S,)
    int32 group columns (-1 = ungrouped slot), gval/gact (S, G) f32 tracked
    values / live-cell mask (cell G-1 = ``__other__``), salt uint32 round
    number -> ``(stats (W, S, 4), cols|None, gstats (W, S, G, 4),
    tal (W, S, 3, H))``.  Must allclose ``ref.slot_extract_grouped_ref``.
    """
    packed = _pad_rows(packed)
    n, m_rows, rec = packed.shape
    assert rec == num_cols * FIELD_BYTES, (rec, num_cols)
    w, b = idx.shape
    s = coeffs.shape[0]
    g = gval.shape[1]
    gcol_c = jnp.clip(jnp.asarray(gcol, jnp.int32), 0, num_cols - 1)
    goh = (jnp.arange(num_cols, dtype=jnp.int32)[None, :]
           == gcol_c[:, None]).astype(jnp.float32)           # (S, C)
    salt1 = jnp.asarray(salt, jnp.uint32).astype(jnp.int32).reshape(1)
    out_shape = [jax.ShapeDtypeStruct((w, s, 4), jnp.float32)]
    out_specs = [pl.BlockSpec((1, s, 4), lambda i, *refs: (i, 0, 0))]
    if return_cols:
        out_shape.append(jax.ShapeDtypeStruct((w, b, num_cols), jnp.float32))
        out_specs.append(pl.BlockSpec((1, b, num_cols),
                                      lambda i, *refs: (i, 0, 0)))
    out_shape += [
        jax.ShapeDtypeStruct((w, s, g, 4), jnp.float32),
        jax.ShapeDtypeStruct((w, s, 3, tally_buckets), jnp.float32)]
    out_specs += [
        pl.BlockSpec((1, s, g, 4), lambda i, *refs: (i, 0, 0, 0)),
        pl.BlockSpec((1, s, 3, tally_buckets),
                     lambda i, *refs: (i, 0, 0, 0))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,   # jw, b_eff, idx, salt
        grid=(w,),
        in_specs=[
            _packed_block_spec(m_rows, rec),
            pl.BlockSpec((s, num_cols), lambda i, *refs: (0, 0)),
            pl.BlockSpec((s, num_cols), lambda i, *refs: (0, 0)),
            pl.BlockSpec((s, num_cols), lambda i, *refs: (0, 0)),
            pl.BlockSpec((s,), lambda i, *refs: (0,)),
            pl.BlockSpec((s,), lambda i, *refs: (0,)),
            pl.BlockSpec((s,), lambda i, *refs: (0,)),
            pl.BlockSpec((s, num_cols), lambda i, *refs: (0, 0)),
            pl.BlockSpec((s, g), lambda i, *refs: (0, 0)),
            pl.BlockSpec((s, g), lambda i, *refs: (0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((min(b, ROW_BLOCK), rec), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(_slot_extract_grouped_kernel, num_cols=num_cols,
                          budget=b, tally_buckets=tally_buckets,
                          return_cols=return_cols),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(jw, jnp.int32), jnp.asarray(b_eff, jnp.int32),
      jnp.asarray(idx, jnp.int32), salt1, packed,
      jnp.asarray(coeffs, jnp.float32), jnp.asarray(lo, jnp.float32),
      jnp.asarray(hi, jnp.float32), jnp.asarray(is_count, jnp.float32),
      jnp.asarray(gate, jnp.float32), jnp.asarray(weights, jnp.float32),
      goh, jnp.asarray(gval, jnp.float32), jnp.asarray(gact, jnp.float32))
    if return_cols:
        return tuple(out)
    return out[0], None, out[1], out[2]


# ---------------------------------------------------------------------------
# Slab-streaming variant (ROADMAP PR-2 follow-on): chunks larger than VMEM.
#
# The kernel above brings a worker's *whole* chunk into one VMEM window via
# scalar-prefetch indexing — fine while M_max·rec fits VMEM, impossible
# beyond.  The streaming variant takes the round's bounded (W, R, rec) slab
# (worker w's chunk at slab[w], assembled by data/pipeline.SlabPrefetcher)
# and grids over (W, R/T) *row tiles*: each step parses one (T, rec) tile,
# evaluates the plan on all T rows, and folds in only the rows the worker's
# permutation window selected — a per-tile membership weight built from the
# prefetched idx row — accumulating the same per-(worker, slot) (m, Σx, Σx²,
# Σp) contract into a VMEM-resident (1, S, 4) output block.  VMEM per step
# is O(T·rec + S·T), independent of chunk size.
# ---------------------------------------------------------------------------

# window positions are compared against a tile in sub-blocks of this many
# indices, bounding the (IDX_TILE, T) membership temp in VMEM
IDX_TILE = 512


def _slot_extract_stream_kernel(beff_ref, mb_ref, slab_ref, idx_ref,
                                coeffs_ref, lo_ref, hi_ref, isc_ref, gate_ref,
                                wts_ref, *out_refs, num_cols: int, budget: int,
                                row_tile: int, decoded_input: bool,
                                cache_cap: int):
    if cache_cap > 0:
        stats_ref, cache_ref = out_refs
    else:
        (stats_ref,), cache_ref = out_refs, None
    w = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        stats_ref[...] = jnp.zeros_like(stats_ref)
        if cache_ref is not None:
            cache_ref[...] = jnp.zeros_like(cache_ref)

    if decoded_input:
        vals = slab_ref[0]                                    # (T, C) f32
    else:
        raw = slab_ref[0].astype(jnp.int32)                   # (T, rec)
        vals = _parse_block(raw, num_cols)                    # (T, C)
    x, p = _eval_plan_block(vals, coeffs_ref[...],
                            lo_ref[...], hi_ref[...])         # (S, T)
    x = jnp.where(isc_ref[...][:, None] > 0.0, p, x)

    # per-slot membership weight: how many of *slot s's* valid window
    # positions (the first ceil(weight_s·b_eff), fairness-capped) land on
    # each tile row.  Position validity (S, bt) × membership (bt, T) is a
    # small matmul per idx sub-block; every operand is 0/1 so the f32
    # accumulation is exact (weights of 1 reproduce the unweighted round
    # bit-for-bit).
    base = t * row_tile
    beff = beff_ref[w]
    bs = jnp.minimum(jnp.ceil(wts_ref[...] * beff.astype(jnp.float32)
                              ).astype(jnp.int32), beff)      # (S,)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (1, row_tile), 1) + base

    bt = min(budget, IDX_TILE)
    n_slots = bs.shape[0]
    cap_ids = jax.lax.broadcasted_iota(jnp.int32, (max(cache_cap, 1), 1), 0)
    mb = mb_ref[w]

    def fold(i, carry):
        acc, cacc = carry
        # idx_ref is (1, B//bt, bt): sub-block i on the sublane dim
        sl = idx_ref[0, pl.ds(i, 1), :]
        k = jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1) + i * bt
        valid_s = (k < bs[:, None]).astype(jnp.float32)       # (S, bt)
        mem = (sl.reshape(bt, 1) == row_ids).astype(jnp.float32)  # (bt, T)
        acc = acc + jnp.dot(valid_s, mem,
                            preferred_element_type=jnp.float32)   # (S, T)
        if cache_cap > 0:
            # synopsis-cache rows: window position k's decoded value lands at
            # cache row m_before + k.  mem @ vals picks each position's tile
            # row (0 if it lives in another tile); sel scatters positions
            # into their cache rows — only O(cap·C) ever reaches HBM.
            in_win = (k < beff).astype(jnp.float32)               # (1, bt)
            sel = ((mb + k) == cap_ids).astype(jnp.float32) * in_win
            wv = jnp.dot(mem, vals, precision=HIGHEST,
                         preferred_element_type=jnp.float32)      # (bt, C)
            cacc = cacc + jnp.dot(sel, wv, precision=HIGHEST,
                                  preferred_element_type=jnp.float32)
        return acc, cacc

    weight, cache_acc = jax.lax.fori_loop(
        0, budget // bt, fold,
        (jnp.zeros((n_slots, row_tile), jnp.float32),
         jnp.zeros((max(cache_cap, 1), num_cols), jnp.float32)))

    gate = gate_ref[...]
    xw = x * (weight * gate[:, None])                         # (S, T)
    pw = p * (weight * gate[:, None])
    stats_ref[0] += jnp.stack([
        jnp.sum(weight, -1),
        jnp.sum(xw, -1), jnp.sum(x * xw, -1), jnp.sum(pw, -1)], axis=-1)
    if cache_ref is not None:
        cache_ref[0] += cache_acc


@functools.partial(jax.jit, static_argnames=("num_cols", "row_tile",
                                             "cache_cap", "decoded_input",
                                             "interpret"))
def _stream_pallas_impl(slab, idx, b_eff, m_before, coeffs, lo, hi, is_count,
                        gate, weights, num_cols: int, row_tile: int,
                        cache_cap: int, decoded_input: bool, interpret: bool):
    w, r, width = slab.shape
    if decoded_input:
        assert width == num_cols and slab.dtype == jnp.float32, (
            slab.shape, slab.dtype)
    else:
        assert width == num_cols * FIELD_BYTES, (width, num_cols)
    b = idx.shape[1]
    s = coeffs.shape[0]
    bt = min(b, IDX_TILE)
    idx3 = jnp.asarray(idx, jnp.int32).reshape(w, b // bt, bt)
    r_pad = (r + row_tile - 1) // row_tile * row_tile
    if r_pad != r:
        slab = jnp.pad(slab, ((0, 0), (0, r_pad - r), (0, 0)))
    out_shape = [jax.ShapeDtypeStruct((w, s, 4), jnp.float32)]
    out_specs = [pl.BlockSpec((1, s, 4), lambda i, t, *refs: (i, 0, 0))]
    if cache_cap > 0:
        out_shape.append(
            jax.ShapeDtypeStruct((w, cache_cap, num_cols), jnp.float32))
        out_specs.append(pl.BlockSpec((1, cache_cap, num_cols),
                                      lambda i, t, *refs: (i, 0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,   # b_eff, m_before
        grid=(w, r_pad // row_tile),
        in_specs=[
            pl.BlockSpec((1, row_tile, width),
                         lambda i, t, *refs: (i, t, 0)),
            pl.BlockSpec((1, b // bt, bt), lambda i, t, *refs: (i, 0, 0)),
            pl.BlockSpec((s, num_cols), lambda i, t, *refs: (0, 0)),
            pl.BlockSpec((s, num_cols), lambda i, t, *refs: (0, 0)),
            pl.BlockSpec((s, num_cols), lambda i, t, *refs: (0, 0)),
            pl.BlockSpec((s,), lambda i, t, *refs: (0,)),
            pl.BlockSpec((s,), lambda i, t, *refs: (0,)),
            pl.BlockSpec((s,), lambda i, t, *refs: (0,)),
        ],
        out_specs=out_specs,
    )
    out = pl.pallas_call(
        functools.partial(_slot_extract_stream_kernel, num_cols=num_cols,
                          budget=b, row_tile=row_tile,
                          decoded_input=decoded_input, cache_cap=cache_cap),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(jnp.asarray(b_eff, jnp.int32), jnp.asarray(m_before, jnp.int32),
      slab, idx3,
      jnp.asarray(coeffs, jnp.float32), jnp.asarray(lo, jnp.float32),
      jnp.asarray(hi, jnp.float32), jnp.asarray(is_count, jnp.float32),
      jnp.asarray(gate, jnp.float32), jnp.asarray(weights, jnp.float32))
    return tuple(out) if cache_cap > 0 else out[0]


def slot_extract_stream_pallas(slab: jnp.ndarray, idx: jnp.ndarray,
                               b_eff: jnp.ndarray, coeffs, lo, hi, is_count,
                               gate, weights, num_cols: int,
                               row_tile: int = 256, cache_cap: int = 0,
                               m_before=None, interpret: bool = False):
    """Slab-streaming fused round extraction.

    slab (W, R, rec) uint8 (worker w's chunk rows at slab[w], zero-padded),
    idx (W, B) window rows, b_eff (W,) budgets, coeffs/lo/hi (S, C) f32,
    is_count/gate/weights (S,) f32 -> stats (W, S, 4) f32
    ``(m, Σx, Σx², Σp)``; ``weights`` are the per-slot fairness shares.

    With ``cache_cap > 0`` the kernel *also* emits the synopsis-cache delta
    rows ``(W, cache_cap, C)`` (window position k's decoded value at cache
    row ``m_before[w] + k``, rows ≥ cap dropped in-kernel) and returns
    ``(stats, cache_rows)`` — the whole decoded ``(W, B, C)`` slab never
    reaches HBM.

    Rows ``>= b_eff[w]`` of the window and slab rows outside the window
    contribute nothing; padded slab rows are never selected because window
    indices are drawn below the chunk's true tuple count.
    """
    if m_before is None:
        m_before = jnp.zeros((idx.shape[0],), jnp.int32)
    return _stream_pallas_impl(slab, idx, b_eff, m_before, coeffs, lo, hi,
                               is_count, gate, weights, num_cols=num_cols,
                               row_tile=row_tile, cache_cap=cache_cap,
                               decoded_input=False, interpret=interpret)


def slot_eval_decoded_pallas(dec: jnp.ndarray, idx: jnp.ndarray,
                             b_eff: jnp.ndarray, coeffs, lo, hi, is_count,
                             gate, weights, num_cols: int,
                             row_tile: int = 256, cache_cap: int = 0,
                             m_before=None, interpret: bool = False):
    """Decoded-input slot eval: the parse-once fast path.

    Same grid and stats contract as :func:`slot_extract_stream_pallas`, but
    the slab is the *already decoded* ``(W, R, C)`` f32 block from the
    decoded-chunk cache, so the tokenize/parse stage disappears from the
    round entirely — only the membership-weight fold and slot eval remain.
    """
    if m_before is None:
        m_before = jnp.zeros((idx.shape[0],), jnp.int32)
    return _stream_pallas_impl(dec, idx, b_eff, m_before, coeffs, lo, hi,
                               is_count, gate, weights, num_cols=num_cols,
                               row_tile=row_tile, cache_cap=cache_cap,
                               decoded_input=True, interpret=interpret)
