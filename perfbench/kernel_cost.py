"""Bytes the EXTRACT step needs per round, whatever implements it.

The fused kernel (``kernels/slot_extract.py``) reads the rows a round
samples and writes per-(worker, slot) partial statistics.  What the
algorithm needs is counted here from the round's shapes: the sampled raw
rows, the row indices, the slot plan and the statistics written.  The
whole-chunk block that today's kernel copies into fast memory is not
counted, so a kernel that reads less keeps the count, and a share above
100% of the roofline can only mean skipped work.
"""

from __future__ import annotations

import json
from pathlib import Path

F32 = 4
I32 = 4
# per-slot hash buckets of the grouped plane's value tallies (the program's
# kernels/ref.py TALLY_BUCKETS)
TALLY_BUCKETS = 128
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def extract_bytes(rows: int, calls: int, *, record_bytes: int, workers: int,
                  budget: int, slots: int, cols: int, groups: int) -> float:
    """Bytes read and written by ``calls`` EXTRACT calls that sample
    ``rows`` raw rows in all.  ``groups`` is the grouped plane's cell count
    (``max_groups + 1``), 0 for the ungrouped kernel."""
    per_call = (
        workers * (budget + 2) * I32                  # idx, chunk ids, b_eff
        + 3 * slots * cols * F32 + 3 * slots * F32    # coeffs, lo, hi; flags
        + workers * slots * 4 * F32)                  # (W, S, 4) partials
    if groups:
        per_call += (slots * cols * F32 + 2 * slots * groups * F32
                     + workers * slots * groups * 4 * F32
                     + workers * slots * 3 * TALLY_BUCKETS * F32)
    return float(rows) * record_bytes + calls * per_call


def peaks(device_kind: str) -> dict:
    """The peak row of ``device_kind``; an unknown device is an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]
