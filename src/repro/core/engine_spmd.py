"""SPMD (multi-device) execution of the OLA-RAW engine via shard_map.

The worker axis is sharded over the mesh ``data`` axis (DESIGN.md §3:
EXTRACT threads → devices); every other piece of engine state is replicated
and advanced by psum-merged deltas, so all devices hold identical state —
the SPMD analogue of the paper's shared memory.  The raw chunk buffer is
replicated too, mirroring the paper's "all threads see the file" model; a
host-sharded store with a per-host queue is the scale-out extension
(distributed/fault.py handles chunk reassignment on host loss).

Semantics are *identical* to the single-device engine with
``num_workers = devices × workers_per_device`` — property-tested in
tests/test_engine_spmd.py.  The claim step's prefix-sum sees the all-gathered
idle flags in global worker order, so chunk hand-out order is deterministic
and independent of device count.
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
        schedule=rep, quarantined=rep, gm=rep, gys=rep, gyq=rep, gps=rep)


def report_specs() -> RoundReport:
    return RoundReport(*([P()] * len(RoundReport._fields)))


def slot_table_specs() -> SlotTable:
    """The slot table is replicated: every device evaluates every slot (the
    query plane is tiny next to the data plane)."""
    return SlotTable(*([P()] * len(SlotTable._fields)))


class _SPMDEngineBase(_ResidencyMixin):
    """Shared mesh plumbing for the SPMD engines: worker split over the
    ``data`` axis, replicated chunk buffer (packed residency) or a
    worker-sharded per-round slab (stream residency), sharded per-worker
    speeds, state sharding, the per-budget compile cache, and the t_eval
    ladder."""

    def __init__(self, store, config: EngineConfig, mesh: Mesh):
        self.store = store
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
        # slab rows are per-worker, so under stream residency the slab shards
        # over the mesh's worker axis — each device receives only its
        # workers' chunks; the packed view stays replicated
        self.chunk_sizes = self._init_residency(
            store, config,
            slab_put=lambda a: jax.device_put(
                a, NamedSharding(mesh, P("data"))),
            packed_put=lambda a: jax.device_put(
                a, NamedSharding(mesh, P())))
        self.m_max = int(store.max_chunk_tuples)
        speeds = config.worker_speed or (1.0,) * config.num_workers
        assert len(speeds) == config.num_workers
        self.speeds = jax.device_put(np.asarray(speeds, np.float32),
                                     NamedSharding(mesh, P("data")))
        self._round_fns: dict[tuple, callable] = {}

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
        so all three shard over the mesh worker axis."""
        specs = engine_state_specs()
        if self.config.residency == "stream":
            data_spec = ((P("data"), P("data"), P("data"))
                         if decoded_mode != "none" else P("data"))
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
                 mesh: Mesh, schedule: Optional[np.ndarray] = None):
        super().__init__(store, config, mesh)
        self.program = EngineProgram(
            codec=store.codec, queries=queries, config=config,
            n_chunks=store.num_chunks, m_max=store.max_chunk_tuples,
            chunk_sizes=self.chunk_sizes, schedule=schedule)

    @property
    def queries(self):
        return self.program.queries

    def init_state(self, synopsis_seed: Optional[dict] = None) -> EngineState:
        return self._put_state(self.program.init_state(synopsis_seed))

    def round_fn(self, b_static: int, decoded_mode: str = "none"):
        key = (b_static, decoded_mode)
        if key not in self._round_fns:
            coll = _Collectives(axis_name="data", workers_per_device=self.wpd)

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
                 confidence: float = 0.95):
        super().__init__(store, config, mesh)
        self.program = EngineProgram(
            codec=store.codec, config=config, n_chunks=store.num_chunks,
            m_max=store.max_chunk_tuples, chunk_sizes=self.chunk_sizes,
            schedule=schedule, max_slots=max_slots, confidence=confidence)

    @property
    def max_slots(self) -> int:
        return self.program.max_slots

    def init_state(self) -> EngineState:
        return self._put_state(self.program.init_state())

    def round_fn(self, b_static: int, decoded_mode: str = "none"):
        key = (b_static, decoded_mode)
        if key not in self._round_fns:
            coll = _Collectives(axis_name="data", workers_per_device=self.wpd)

            def step(state, table, packed, speeds):
                return self.program.round_body(state, packed, speeds,
                                               b_static, coll, slots=table,
                                               decoded_mode=decoded_mode)

            self._round_fns[key] = self._compile_round(
                step, (slot_table_specs(),), decoded_mode=decoded_mode)
        return self._round_fns[key]
