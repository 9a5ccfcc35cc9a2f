"""Pallas TPU kernels for the paper's measured hot spots.

The paper's bottleneck is the EXTRACT stage (tokenize/parse) fused with
per-tuple aggregation — Section 3 calls out CPU-bound extraction as the very
reason bi-level sampling beats chunk-level sampling.  Three kernels cover the
three access patterns the engine uses:

* :mod:`extract_parse` — fixed-width ASCII-decimal records → f32 columns
  (the EXTRACT stage itself, VPU-vectorized digit arithmetic).
* :mod:`chunk_agg`     — fused parse + predicate + (count, Σx, Σx², Σp) per
  chunk over *full* chunks (chunk-level / holistic strategies; the analogue
  of Instant Loading's SIMD tokenizer feeding an aggregator).
* :mod:`round_stats`   — fused parse + multi-query eval + budget-masked
  partial statistics over a gathered ``(workers, budget)`` slab — the
  bi-level engine's per-round hot loop (frozen query plans, HBM-side gather).
* :mod:`slot_extract`  — the fully fused round: in-kernel permutation-window
  gather (scalar-prefetch chunk/window indexing) + parse + *slot table*
  evaluation + per-(worker, slot) sufficient statistics.  This is the
  ``EngineConfig.extract_backend="pallas"`` path of the engine round for
  both query planes.

``ref.py`` holds the pure-jnp oracles; ``ops.py`` the jitted wrappers that
dispatch to the compiled kernel (``"pallas"``, TPU only), the Pallas
interpreter (``"pallas-interpret"``) or the oracle (``"ref"``).
"""

from repro.kernels.ops import (
    chunk_agg,
    extract_parse,
    round_stats,
    slot_extract,
)

__all__ = ["chunk_agg", "extract_parse", "round_stats", "slot_extract"]
