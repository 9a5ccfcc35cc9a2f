"""The OLA-verify dry-run cell: the paper's engine round at production scale.

This is the hillclimb cell "most representative of the paper's technique":
one SPMD engine round (claim → extract → merge → decide → estimate) lowered
on the production mesh for a production-sized raw metadata table
(4096 chunks × 65536 tuples × 6 ASCII columns ≈ 25.8 GB raw).

Two store layouts are measured:

* ``replicated``  — the paper's shared-memory model verbatim: every device
  sees the whole raw buffer (baseline; the dry-run's memory analysis shows
  this cannot scale — ~26 GB of raw bytes per chip, over v5e HBM).
* ``sharded``     — the engine's ``residency="sharded"``: chunks sharded
  over the data axis with a claim queue per shard.  Each shard owns a
  contiguous chunk range and processes it in its own committed random
  order.  Chunk inclusion is still decided before execution
  (content-independent), so the no-inspection-paradox argument survives;
  the single global prefix becomes a union of per-shard prefixes, and
  Eq. (1)/(3) apply per shard, summed (stratified SRSWOR over the committed
  orders).  Raw bytes per chip drop by the data-axis factor (16x), and the
  claim step's all-gather disappears (claims are shard-local).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import EngineConfig, EngineProgram, _Collectives
from repro.core.engine_spmd import engine_state_specs, report_specs
from repro.core.queries import Column, Having, Query, Range, TRUE
from repro.data.formats import AsciiFixedFormat


def production_verify_program(n_chunks: int = 4096, m_per_chunk: int = 65536,
                              num_cols: int = 6, workers: int = 256,
                              budget: int = 256, queues: int = 1):
    codec = AsciiFixedFormat(num_cols)
    queries = [
        Query(agg="avg", expr=Column(1), pred=TRUE, having=Having(">", 75.0),
              epsilon=0.05, name="avg_quality"),
        Query(agg="avg", expr=Column(3), pred=TRUE, having=Having("<", 10.0),
              epsilon=0.05, name="avg_dup"),
        Query(agg="count", pred=Range(0, 0.0, 16.0), having=Having("<", 1e6),
              epsilon=0.05, name="short_docs"),
    ]
    cfg = EngineConfig(num_workers=workers, strategy="resource_aware",
                       budget_init=budget, seed=0,
                       residency="sharded" if queues > 1 else "packed")
    sizes = np.full(n_chunks, m_per_chunk, np.int64)
    program = EngineProgram(codec=codec, queries=queries, config=cfg,
                            n_chunks=n_chunks, m_max=m_per_chunk,
                            chunk_sizes=sizes, queues=queues)
    return program, cfg, codec


def build_verify_cell(mesh: Mesh, layout: str = "replicated",
                      budget: int = 256):
    """-> (fn_shardmapped, abstract_args, program)."""
    n_dev = mesh.shape["data"]
    sharded = layout == "sharded"
    program, cfg, codec = production_verify_program(
        budget=budget, workers=n_dev, queues=n_dev if sharded else 1)
    specs = engine_state_specs()
    n, m, rb = program.n_chunks, program.m_max, codec.record_bytes
    packed_spec = P("data") if sharded else P()
    coll = _Collectives(axis_name="data", workers_per_device=1,
                        chunks_per_device=n // n_dev if sharded else None)

    def step(state, packed, speeds):
        return program.round_body(state, packed, speeds, budget, coll)

    sm = jax.shard_map(step, mesh=mesh,
                       in_specs=(specs, packed_spec, P("data")),
                       out_specs=(specs, report_specs()),
                       check_vma=False)

    state_abs = jax.eval_shape(program.init_state)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    state_in = jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
        state_abs, shardings)
    packed_in = jax.ShapeDtypeStruct((n, m, rb), jnp.uint8,
                                     sharding=NamedSharding(mesh, packed_spec))
    speeds_in = jax.ShapeDtypeStruct((cfg.num_workers,), jnp.float32,
                                     sharding=NamedSharding(mesh, P("data")))
    return sm, (state_in, packed_in, speeds_in), program
