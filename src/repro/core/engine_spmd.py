"""SPMD (multi-device) execution of the OLA-RAW engine via shard_map.

The worker axis is sharded over the mesh ``data`` axis (DESIGN.md §3:
EXTRACT threads → devices); every other piece of engine state is replicated
and advanced by psum-merged deltas, so all devices hold identical state —
the SPMD analogue of the paper's shared memory.  Under packed residency the
raw chunk buffer is replicated too, mirroring the paper's "all threads see
the file" model.  Under ``residency="sharded"`` each device holds only its
contiguous range of chunks, placed range by range, and its workers claim
from that range's own queue (one queue per device); state stays indexed by
global chunk id and estimates are stratified over the ranges
(``EngineConfig.residency``).

Semantics are *identical* to the single-device engine with
``num_workers = devices × workers_per_device`` (and, sharded, with
``queues = devices``) — property-tested in tests/test_engine_spmd.py and
tests/test_engine_sharded.py.  The claim step's prefix-sum sees the
all-gathered idle flags in global worker order (a queue's own workers,
sharded), so chunk hand-out order is deterministic and independent of
device count.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import (
    EngineConfig,
    EngineProgram,
    EngineState,
    RoundReport,
    _Collectives,
    _ResidencyMixin,
    budget_ladder,
)
from repro.core.estimators import BiLevelStats
from repro.core.queries import Query, SlotTable
from repro.kernels.slot_extract import GATHER_ROWS
from repro.obs.trace import NULL_TRACER


def engine_state_specs() -> EngineState:
    """PartitionSpecs for EngineState: `cur` sharded over data, rest replicated.

    The static ints inside BiLevelStats become replicated scalars under
    shard_map — harmless, they are only used arithmetically.
    """
    rep = P()
    stats_spec = BiLevelStats(M=rep, m=rep, ysum=rep, ysq=rep, psum=rep,
                              n_total=rep, m_total=rep)
    return EngineState(
        stats=stats_spec, scan_m=rep, offset=rep, closed=rep, acc_met=rep,
        head=rep, cur=P("data"), budget=rep, decay=rep, calib_sum=rep,
        calib_cnt=rep, first_est=rep, stopped=rep, round=rep, t_io=rep,
        t_cpu=rep, cpu_bound=rep, cached_m=rep, raw_touched=rep, cache=rep,
        schedule=rep, quarantined=rep, gm=rep, gys=rep, gyq=rep, gps=rep,
        qhead=rep)


def report_specs() -> RoundReport:
    return RoundReport(*([P()] * len(RoundReport._fields)))


def slot_table_specs() -> SlotTable:
    """The slot table is replicated: every device evaluates every slot (the
    query plane is tiny next to the data plane)."""
    return SlotTable(*([P()] * len(SlotTable._fields)))


class _SPMDEngineBase(_ResidencyMixin):
    """Shared mesh plumbing for the SPMD engines: worker split over the
    ``data`` axis, replicated chunk buffer (packed residency), chunk ranges
    split over the axis (sharded residency) or a worker-sharded per-round
    slab (stream residency), sharded per-worker speeds, state sharding, the
    per-budget compile cache, and the t_eval ladder.  ``tracer`` receives
    the ``ola.shard_place`` span around a sharded table's placement."""

    def __init__(self, store, config: EngineConfig, mesh: Mesh,
                 tracer=NULL_TRACER):
        self.store = store
        self.tracer = tracer
        # The engine runs on an Auto-typed view of the caller's mesh: the
        # serving host writes single rows of the sharded state eagerly
        # (admission seeds, claim reorders, quarantine), and under the
        # Explicit axes that jax.make_mesh gives by default such an indexed
        # update outside jax.set_mesh raises.
        mesh = self.mesh = Mesh(mesh.devices, mesh.axis_names,
                                axis_types=(AxisType.Auto,) * mesh.devices.ndim)
        self.n_dev = mesh.shape["data"]
        assert config.num_workers % self.n_dev == 0, (
            f"num_workers={config.num_workers} must divide over "
            f"data axis size {self.n_dev}")
        self.wpd = config.num_workers // self.n_dev
        self.config = config
        self.sharded = config.residency == "sharded"
        self.queues = self.n_dev if self.sharded else 1
        # slab rows are per-worker, so under stream residency the slab shards
        # over the mesh's worker axis — each device receives only its
        # workers' chunks; the packed view stays replicated
        self.chunk_sizes = self._init_residency(
            store, config,
            slab_put=lambda a: jax.device_put(
                a, NamedSharding(mesh, P("data"))),
            packed_put=lambda a: jax.device_put(
                a, NamedSharding(mesh, P())),
            shards_put=self._place_shards)
        self.m_max = int(store.max_chunk_tuples)
        speeds = config.worker_speed or (1.0,) * config.num_workers
        assert len(speeds) == config.num_workers
        self.speeds = jax.device_put(np.asarray(speeds, np.float32),
                                     NamedSharding(mesh, P("data")))
        self._round_fns: dict[tuple, callable] = {}

    def _place_shards(self, store) -> jax.Array:
        """The packed ``(N, M_pad, rec)`` view split over the ``data`` axis,
        built and copied one device's chunk range at a time, so the host
        never holds a second copy of the whole table."""
        n = store.num_chunks
        assert n % self.n_dev == 0, (
            f"{n} chunks do not split over {self.n_dev} devices")
        rows = -(-store.max_chunk_tuples // GATHER_ROWS) * GATHER_ROWS
        shape = (n, rows, store.codec.record_bytes)
        sharding = NamedSharding(self.mesh, P("data"))
        parts = []
        for dev, index in sharding.addressable_devices_indices_map(
                shape).items():
            lo, hi, _ = index[0].indices(n)
            block, _ = store.packed_device_view(row_multiple=GATHER_ROWS,
                                                chunks=range(lo, hi))
            parts.append(jax.device_put(block, dev).block_until_ready())
            del block
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        parts)

    def _collectives(self) -> _Collectives:
        return _Collectives(
            axis_name="data", workers_per_device=self.wpd,
            chunks_per_device=(self.store.num_chunks // self.n_dev
                               if self.sharded else None))

    def _put_state(self, state: EngineState) -> EngineState:
        shardings = jax.tree.map(lambda spec: NamedSharding(self.mesh, spec),
                                 engine_state_specs(),
                                 is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(state, shardings)

    def _compile_round(self, step, extra_in_specs: tuple,
                       decoded_mode: str = "none"):
        """shard_map + jit one round step; ``step`` takes
        ``(state, *extras, data, speeds)``.  The raw-data argument is
        replicated in packed residency and worker-sharded in stream
        residency (slab rows follow their workers); a decoded round's data
        is the ``(raw, dec, is_decoded)`` triple — every leaf is per-worker,
        so all three shard over the mesh worker axis.  A sharded table
        splits over the axis by chunk range."""
        specs = engine_state_specs()
        if self.config.residency == "stream":
            data_spec = ((P("data"), P("data"), P("data"))
                         if decoded_mode != "none" else P("data"))
        elif self.sharded:
            data_spec = P("data")
        else:
            data_spec = P()
        sm = jax.shard_map(step, mesh=self.mesh,
                           in_specs=(specs, *extra_in_specs, data_spec,
                                     P("data")),
                           out_specs=(specs, report_specs()),
                           check_vma=False)
        return jax.jit(sm, donate_argnums=(0,))

    def budget_ladder(self, b: float) -> int:
        return budget_ladder(self.config, self.m_max, b)


class SPMDEngine(_SPMDEngineBase):
    """Multi-device OLA engine over a mesh with a ``data`` axis."""

    def __init__(self, store, queries: Sequence[Query], config: EngineConfig,
                 mesh: Mesh, schedule: Optional[np.ndarray] = None,
                 tracer=NULL_TRACER):
        super().__init__(store, config, mesh, tracer=tracer)
        self.program = EngineProgram(
            codec=store.codec, queries=queries, config=config,
            n_chunks=store.num_chunks, m_max=store.max_chunk_tuples,
            chunk_sizes=self.chunk_sizes, schedule=schedule,
            queues=self.queues)

    @property
    def queries(self):
        return self.program.queries

    def init_state(self, synopsis_seed: Optional[dict] = None) -> EngineState:
        return self._put_state(self.program.init_state(synopsis_seed))

    def round_fn(self, b_static: int, decoded_mode: str = "none"):
        key = (b_static, decoded_mode)
        if key not in self._round_fns:
            coll = self._collectives()

            def step(state, packed, speeds):
                return self.program.round_body(state, packed, speeds,
                                               b_static, coll,
                                               decoded_mode=decoded_mode)

            self._round_fns[key] = self._compile_round(
                step, (), decoded_mode=decoded_mode)
        return self._round_fns[key]

    def run(self, max_rounds: int = 100_000, wall_timeout_s: float = 600.0,
            synopsis_seed: Optional[dict] = None, collect_history: bool = True):
        state = self.init_state(synopsis_seed)
        history = []
        t0 = time.perf_counter()
        for _ in range(max_rounds):
            b = self.budget_ladder(float(state.budget))
            state, data = self.round_data(state)
            mode, data = self.data_mode(data)
            state, rep = self.round_fn(b, mode)(state, data, self.speeds)
            if collect_history:
                history.append(jax.tree.map(np.asarray, rep))
            if bool(rep.all_stopped) or bool(rep.exhausted):
                break
            if time.perf_counter() - t0 > wall_timeout_s:
                break
        return state, history


class SlotSPMDEngine(_SPMDEngineBase):
    """Multi-device slot-table engine: :class:`~repro.core.engine.SlotOLAEngine`
    with the worker axis sharded over the mesh ``data`` axis.

    Drop-in round-step compatible with the single-device slot engine (the
    workload server drives either through the same
    ``round_fn(b)(state, table, packed, speeds)`` signature): the slot table
    is replicated, ``cur`` is sharded, and chunk-slot deltas are psum-merged,
    so chunk hand-out order — and therefore every slot's sample — is
    deterministic and independent of device count (the claim step's
    prefix-sum runs over all-gathered idle flags in global worker order).
    Parity is property-tested in tests/test_engine_spmd.py.
    """

    def __init__(self, store, max_slots: int, config: EngineConfig,
                 mesh: Mesh, schedule: Optional[np.ndarray] = None,
                 confidence: float = 0.95, tracer=NULL_TRACER):
        super().__init__(store, config, mesh, tracer=tracer)
        self.program = EngineProgram(
            codec=store.codec, config=config, n_chunks=store.num_chunks,
            m_max=store.max_chunk_tuples, chunk_sizes=self.chunk_sizes,
            schedule=schedule, max_slots=max_slots, confidence=confidence,
            queues=self.queues)

    @property
    def max_slots(self) -> int:
        return self.program.max_slots

    def init_state(self) -> EngineState:
        return self._put_state(self.program.init_state())

    def round_fn(self, b_static: int, decoded_mode: str = "none"):
        key = (b_static, decoded_mode)
        if key not in self._round_fns:
            coll = self._collectives()

            def step(state, table, packed, speeds):
                return self.program.round_body(state, packed, speeds,
                                               b_static, coll, slots=table,
                                               decoded_mode=decoded_mode)

            self._round_fns[key] = self._compile_round(
                step, (slot_table_specs(),), decoded_mode=decoded_mode)
        return self._round_fns[key]
