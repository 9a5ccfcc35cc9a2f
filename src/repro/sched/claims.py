"""Variance-guided chunk claim ordering.

The engine claims chunks in a committed random schedule.  For correctness
only the *first-touch* order matters: the inspection-paradox guarantee (§4.2)
needs the set of started chunks to always be a prefix of the committed random
order, so sample inclusion never depends on content.  The order in which
already-started chunks are *revisited* (top-up passes re-opening early-closed
chunks, schedules rewound behind re-opened work) is statistically free — and
that freedom is worth using: claiming the chunks with the highest within-chunk
variance across the live slots first shrinks the dominant CI terms soonest,
so high-uncertainty queries converge and release their slots earlier (Neyman
allocation, applied to claim order).

:func:`variance_claim_order` therefore permutes only the unclaimed tail of
``state.schedule`` (positions ≥ head), in three bands:

1. never-started chunks, in their original committed order (unknown variance
   — the paper's ``plan_schedule`` treats them as infinite);
2. started-and-open chunks, by measured aggregate variance, descending;
3. closed/exhausted chunks last (claiming them burns a round for nothing).

The result is written back into the engine state by the server *before* the
round's claim prediction runs, so the streaming prefetcher and the in-jit
CLAIM follow the same order (host-predictability is preserved by
construction — the ordering is itself a host-side computation).
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def slot_chunk_variances(state, active: Optional[np.ndarray] = None,
                         slot_need: Optional[np.ndarray] = None,
                         ) -> np.ndarray:
    """Aggregate per-chunk within-variance across slots — ``(N,)``.

    Same s²/m proxy as ``BiLevelSynopsis.within_variances``, but masked to
    the live slots: the claim order should chase uncertainty that some
    *resident* query still cares about.  Chunks a slot has fewer than two
    tuples from contribute zero (no variance estimate yet).

    ``slot_need`` (optional, ``(S,)`` non-negative) weights each slot's
    variance plane by its remaining **distance to its ε target** before
    aggregating — ``need_s = max(err_s/ε_s − 1, 0)`` as computed by the
    server from the last round report.  A slot at 3× its target then pulls
    claim order twice as hard as one at 2×, and a slot that already met ε
    (need 0) stops steering entirely; the aggregate becomes the
    need-weighted **sum** over slots (total outstanding uncertainty — the
    Neyman-allocation reading) instead of the unweighted max PR 4 used,
    which let one nearly-converged slot's noisy chunk outrank a chunk every
    far-from-target slot needs.  Without ``slot_need`` the PR-4 max key is
    kept (the policy-unit tests pin both forms).
    """
    m = np.asarray(state.stats.m, np.float64)          # (S, N)
    ys = np.asarray(state.stats.ysum, np.float64)
    yq = np.asarray(state.stats.ysq, np.float64)
    if m.ndim == 1:
        # frozen plane: the (N,) sample size is shared by every query row —
        # broadcast it so the max below aggregates over ALL queries, not
        # just the first
        m = np.broadcast_to(m[None], ys.shape)
    ss = yq - np.where(m > 0, ys * ys / np.maximum(m, 1.0), 0.0)
    v = np.where(m >= 2, np.maximum(ss / np.maximum(m - 1.0, 1.0), 0.0), 0.0)
    if active is not None:
        active = np.asarray(active, bool)
        if active.shape[0] != v.shape[0]:
            raise ValueError(
                f"active mask length {active.shape[0]} does not match the "
                f"stats plane's leading dim {v.shape[0]}")
        v = v * active[:, None]
    if slot_need is not None:
        need = np.asarray(slot_need, np.float64)
        if need.shape[0] != v.shape[0]:
            raise ValueError(
                f"slot_need length {need.shape[0]} does not match the "
                f"stats plane's leading dim {v.shape[0]}")
        return (v * need[:, None]).sum(axis=0)
    return v.max(axis=0)


def variance_claim_order(state, chunk_sizes: np.ndarray,
                         active: Optional[np.ndarray] = None,
                         slot_need: Optional[np.ndarray] = None,
                         ) -> Optional[np.ndarray]:
    """New ``(N,)`` schedule with the unclaimed tail variance-ordered, or
    ``None`` when the order is already optimal / there is nothing to
    reorder.  Positions ``< state.head`` (claimed or done — every worker's
    held position is below the head) are never moved; with claim queues
    (``state.qhead``), each queue's segment keeps its claimed prefix and
    reorders its own tail.  ``slot_need``
    switches the per-chunk key to the ε-distance-weighted aggregate (see
    :func:`slot_chunk_variances`)."""
    schedule = np.asarray(state.schedule)
    n = len(schedule)
    qhead = np.asarray(getattr(state, "qhead", ()), np.int64)
    if qhead.size:
        # claim queues: each queue's segment reorders its own tail, so a
        # chunk never moves into another queue
        per = n // qhead.size
        tails = [(q * per + int(h), (q + 1) * per)
                 for q, h in enumerate(qhead)]
    else:
        tails = [(int(state.head), n)]
    if all(lo >= hi - 1 for lo, hi in tails):
        return None
    scan_m = np.asarray(state.scan_m)
    closed = np.asarray(state.closed)
    sizes = np.asarray(chunk_sizes)
    v = slot_chunk_variances(state, active, slot_need)
    out, moved = schedule.copy(), False
    for lo, hi in tails:
        new_tail = _tail_order(schedule[lo:hi], v, closed, scan_m, sizes)
        if new_tail is not None:
            out[lo:hi] = new_tail
            moved = True
    return out.astype(np.int32) if moved else None


def _tail_order(tail, v, closed, scan_m, sizes) -> Optional[np.ndarray]:
    """One unclaimed tail in the three bands, or ``None`` if unchanged."""
    if len(tail) <= 1:
        return None
    dead = closed[tail] | (scan_m[tail] >= sizes[tail])
    started = scan_m[tail] > 0
    band = np.where(dead, 2, np.where(started, 1, 0))
    if not (band == 1).any():
        # nothing measured in the tail: variance ordering is the committed
        # order (never-started chunks must keep it), modulo dead chunks
        if not dead.any() or (band == 2).all():
            return None
    # lexsort: most-significant key last; stability keeps band-0 chunks in
    # committed order and makes band-1 variance ties deterministic
    order = np.lexsort((np.arange(len(tail)), -v[tail], band))
    new_tail = tail[order]
    if np.array_equal(new_tail, tail):
        return None
    return new_tail
