"""The OLA-verify dry-run cell: the paper's engine round at production scale.

This is the hillclimb cell "most representative of the paper's technique":
one SPMD engine round (claim → extract → merge → decide → estimate) lowered
on the production mesh for a production-sized raw metadata table
(4096 chunks × 65536 tuples × 6 ASCII columns ≈ 25.8 GB raw).

Two store layouts are measured:

* ``replicated``  — the paper's shared-memory model verbatim: every device
  sees the whole raw buffer (baseline; the dry-run's memory analysis shows
  this cannot scale — ~26 GB of raw bytes per chip, over v5e HBM).
* ``sharded``     — chunks sharded over the data axis with per-shard queues:
  each shard owns a contiguous chunk range and processes it in its own
  committed random order.  Chunk inclusion is still decided before execution
  (content-independent), so the no-inspection-paradox argument survives; the
  single global prefix becomes a union of per-shard prefixes (stratified
  SRSWOR over the committed orders — Eq. (1)/(3) apply unchanged).  Raw
  bytes per chip drop by the data-axis factor (16x), and the claim step's
  all-gather disappears (claims are shard-local).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import estimators as est
from repro.core.engine import EngineConfig, EngineProgram, _Collectives
from repro.core.engine_spmd import engine_state_specs, report_specs
from repro.core.queries import Column, Having, Query, Range, TRUE
from repro.data.formats import AsciiFixedFormat
from repro.sampling.permutation import permutation_window_dyn, random_chunk_order


def production_verify_program(n_chunks: int = 4096, m_per_chunk: int = 65536,
                              num_cols: int = 6, workers: int = 256,
                              budget: int = 256):
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
                       budget_init=budget, seed=0)
    sizes = np.full(n_chunks, m_per_chunk, np.int64)
    program = EngineProgram(codec=codec, queries=queries, config=cfg,
                            n_chunks=n_chunks, m_max=m_per_chunk,
                            chunk_sizes=sizes)
    return program, cfg, codec


def _sharded_round(program: EngineProgram, n_dev: int, budget: int):
    """Per-shard-queue engine round (one worker per device, local chunks).

    The device's current/next chunk is *derived* from the replicated state
    (open chunk in my range, else my local schedule at my closed-count), so
    no new engine state is needed and checkpointing is unchanged.
    """
    n = program.n_chunks
    nl = n // n_dev
    # committed per-shard schedules: row d permutes shard d's chunk range
    rng_rows = [random_chunk_order(program.config.seed + 17 * d, nl) + d * nl
                for d in range(n_dev)]
    sched2d = jnp.asarray(np.stack(rng_rows), jnp.int32)      # (D, nl)
    z = float(jax.scipy.special.ndtri((1.0 + program.conf) / 2.0))

    def round_step(state, packed_local, speeds_local):
        dtype = state.stats.ysum.dtype
        cfg = program.config
        d = jax.lax.axis_index("data")
        sizes = state.stats.M
        mine = (jnp.arange(n, dtype=jnp.int32) // nl) == d

        open_mine = (state.stats.m > 0) & ~state.closed & mine
        has_open = jnp.any(open_mine)
        local_head = jnp.sum((state.closed & mine).astype(jnp.int32))
        nxt = sched2d[d, jnp.clip(local_head, 0, nl - 1)]
        j = jnp.where(has_open, jnp.argmax(open_mine), nxt)
        active = has_open | (local_head < nl)

        mj = sizes[j]
        off = state.offset[j]
        m_before = state.stats.m[j]
        b_eff = jnp.minimum(jnp.floor(budget * speeds_local[0]).astype(jnp.int32),
                            jnp.maximum(mj - m_before, 0))
        b_eff = jnp.where(active, b_eff, 0)

        idx = permutation_window_dyn(program.seeds[j], off, budget, mj,
                                     program.m_max)
        raw = packed_local[j - d * nl][idx]                     # local slab
        cols = program.codec.decode_ref(raw)
        x, pr = program.evaluate(cols)                          # (Q, B)
        valid = (jnp.arange(budget) < b_eff).astype(dtype)
        x = x.astype(dtype) * valid
        pr = pr.astype(dtype) * valid

        q = len(program.queries)
        af = active.astype(jnp.int32)
        deltas = jax.lax.psum(dict(
            dm=jnp.zeros((n,), jnp.int32).at[j].add(b_eff * af),
            dys=jnp.zeros((q, n), dtype).at[:, j].add(jnp.sum(x, -1) * af),
            dyq=jnp.zeros((q, n), dtype).at[:, j].add(jnp.sum(x * x, -1) * af),
            dps=jnp.zeros((q, n), dtype).at[:, j].add(jnp.sum(pr, -1) * af),
            doff=jnp.zeros((n,), jnp.int32).at[j].add(b_eff * af),
        ), "data")
        stats = state.stats._replace(
            m=state.stats.m + deltas["dm"], ysum=state.stats.ysum + deltas["dys"],
            ysq=state.stats.ysq + deltas["dyq"], psum=state.stats.psum + deltas["dps"])
        offset = state.offset + deltas["doff"]

        # local accuracy (Theorem 3) on my chunk; close + io accounting
        mj_new = stats.m[j].astype(dtype)
        big_m = sizes[j].astype(dtype)
        scale = big_m / jnp.maximum(mj_new, 1.0)
        ys_j = stats.ysum[:, j]
        ss = stats.ysq[:, j] - ys_j * ys_j / jnp.maximum(mj_new, 1.0)
        fpc = (big_m - mj_new) / jnp.maximum(mj_new - 1.0, 1.0)
        v_local = scale * fpc * jnp.maximum(ss, 0.0)
        yhat = scale * ys_j
        local_ok = jnp.all(2.0 * z * jnp.sqrt(jnp.maximum(v_local, 0.0))
                           <= program.eps.astype(dtype)
                           * jnp.maximum(jnp.abs(yhat), 1e-12))
        local_ok &= mj_new >= 2.0
        exhausted = stats.m[j] >= sizes[j]
        close = active & (exhausted | (local_ok & state.cpu_bound))
        closed = state.closed | (jax.lax.psum(
            jnp.zeros((n,), jnp.int32).at[j].add(close.astype(jnp.int32)),
            "data") > 0)
        newly_raw = active & (b_eff > 0) & ~state.raw_touched[j]
        raw_touched = state.raw_touched | (jax.lax.psum(
            jnp.zeros((n,), jnp.int32).at[j].add(newly_raw.astype(jnp.int32)),
            "data") > 0)
        bytes_round = jax.lax.psum(
            jnp.where(newly_raw, program.chunk_bytes[j], 0.0), "data")
        tuples = jax.lax.psum(b_eff, "data")
        round_cpu = (tuples.astype(jnp.float32) * program.cost_per_tuple
                     / cfg.cpu_tuple_ops_per_sec / cfg.num_workers)
        round_io = bytes_round.astype(jnp.float32) / cfg.io_bytes_per_sec

        # global estimate over the union of per-shard prefixes
        mask = stats.m > 0
        stats_est = stats._replace(
            m=jnp.where(mask, stats.m, 0),
            ysum=jnp.where(mask[None], stats.ysum, 0),
            ysq=jnp.where(mask[None], stats.ysq, 0),
            psum=jnp.where(mask[None], stats.psum, 0))
        avg_t, avg_v, _ = est.avg_estimate(stats_est)
        cnt_t = est.count_tau_hat(stats_est)
        cnt_v, _ = est.count_var_hat(stats_est)
        estimate = jnp.stack([avg_t[0], avg_t[1], cnt_t[2]])
        variance = jnp.stack([avg_v[0], avg_v[1], cnt_v[2]])
        lo, hi = est.confidence_bounds(estimate, variance, program.conf)
        err = est.error_ratio(estimate, lo, hi)
        decided = jnp.stack([
            est.having_decision(lo[0], hi[0], ">", 75.0),
            est.having_decision(lo[1], hi[1], "<", 10.0),
            est.having_decision(lo[2], hi[2], "<", 1e6)])
        stopped = state.stopped | (err <= program.eps.astype(dtype)) | (
            decided != -1)

        from repro.core.engine import EngineState, RoundReport

        new_state = EngineState(
            stats=stats, scan_m=state.scan_m + deltas["dm"],
            offset=offset, closed=closed, acc_met=state.acc_met,
            head=state.head + 1, cur=state.cur, budget=state.budget,
            decay=state.decay, calib_sum=state.calib_sum,
            calib_cnt=state.calib_cnt, first_est=jnp.asarray(True),
            stopped=stopped, round=state.round + 1,
            t_io=state.t_io + round_io, t_cpu=state.t_cpu + round_cpu,
            cpu_bound=round_cpu > round_io, cached_m=state.cached_m,
            raw_touched=raw_touched, cache=state.cache,
            schedule=state.schedule, quarantined=state.quarantined,
            gm=state.gm, gys=state.gys, gyq=state.gyq, gps=state.gps)
        # grouped plane is zero-width here (cfg.max_groups == 0)
        gz = jnp.zeros((q, program.group_cells), dtype)
        report = RoundReport(
            estimate=estimate, lo=lo, hi=hi, err=err, decided=decided,
            n_chunks=stats_est.n, m_tuples=jnp.sum(stats_est.m),
            round_io_s=round_io, round_cpu_s=round_cpu, tuples_round=tuples,
            bytes_round=bytes_round, all_stopped=jnp.all(stopped),
            exhausted=jnp.all(closed),
            g_est=gz, g_lo=gz, g_hi=gz, g_err=gz,
            g_n=jnp.zeros((q, program.group_cells), jnp.int32),
            g_tal=jnp.zeros((q, 3, program.tally_buckets), dtype))
        return new_state, report

    return round_step


def build_verify_cell(mesh: Mesh, layout: str = "replicated",
                      budget: int = 256):
    """-> (fn_shardmapped, abstract_args, program)."""
    n_dev = mesh.shape["data"]
    program, cfg, codec = production_verify_program(budget=budget,
                                                    workers=n_dev)
    wpd = 1
    specs = engine_state_specs()
    n, m, rb = program.n_chunks, program.m_max, codec.record_bytes

    if layout == "replicated":
        packed_spec = P()
        coll = _Collectives(axis_name="data", workers_per_device=wpd)

        def step(state, packed, speeds):
            return program.round_body(state, packed, speeds, budget, coll)
    else:
        packed_spec = P("data")
        step = _sharded_round(program, n_dev, budget)

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
