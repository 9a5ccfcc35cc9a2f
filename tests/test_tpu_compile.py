"""The fused kernels compile for a TPU v5e at the chip smoke's shapes.

Interpret mode never enforces Mosaic's tiling rules (aligned sublane
offsets, scoped VMEM), so the parity tests cannot catch a kernel the chip's
compiler refuses.  These tests compile each kernel for a *described* v5e
(no chip needed) at the shapes ``chip_smoke.py`` runs: 16 ASCII columns
(256-byte records), 16384-row chunks, 8 workers, 8 slots and the largest
budget rung.  The whole round of the four-chip cell compiles too, sharded
over a described v5e:2x2.  The topology is described inside a fixture,
never at import.
"""

import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core.engine import EngineConfig
from repro.kernels.ref import TALLY_BUCKETS

# repro.kernels re-exports a function named slot_extract over the module
se = importlib.import_module("repro.kernels.slot_extract")

COLS, CHUNK_ROWS, CHUNKS = 16, 16384, 512
WORKERS, SLOTS, GROUPS = 8, 8, 8 + 1          # max_groups=8 plus __other__
BUDGET = EngineConfig().budget_max              # the largest budget rung
REC = COLS * 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shapes(one_chip):
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    plan = sd((SLOTS, COLS), jnp.float32)
    per_slot = sd((SLOTS,), jnp.float32)
    return {
        "packed": sd((CHUNKS, CHUNK_ROWS, REC), jnp.uint8),
        "slab": sd((WORKERS, CHUNK_ROWS, REC), jnp.uint8),
        "dec": sd((WORKERS, CHUNK_ROWS, COLS), jnp.float32),
        "jw": sd((WORKERS,), jnp.int32),
        "idx": sd((WORKERS, BUDGET), jnp.int32),
        "b_eff": sd((WORKERS,), jnp.int32),
        "plan": (plan, plan, plan, per_slot, per_slot, per_slot),
        "gcol": sd((SLOTS,), jnp.int32),
        "gcell": sd((SLOTS, GROUPS), jnp.float32),
        "salt": sd((), jnp.uint32),
    }


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("return_cols", [False, True])
def test_packed_kernel_compiles(shapes, no_compile_cache, return_cols):
    s = shapes
    _compile(lambda *a: se.slot_extract_pallas(
        *a, num_cols=COLS, return_cols=return_cols),
        s["packed"], s["jw"], s["idx"], s["b_eff"], *s["plan"])


def test_grouped_kernel_compiles(shapes, no_compile_cache):
    s = shapes
    _compile(lambda *a: se.slot_extract_grouped_pallas(
        *a, num_cols=COLS, tally_buckets=TALLY_BUCKETS, return_cols=True),
        s["packed"], s["jw"], s["idx"], s["b_eff"], *s["plan"], s["gcol"],
        s["gcell"], s["gcell"], s["salt"])


def test_stream_kernel_compiles(shapes, no_compile_cache):
    s = shapes
    m_before = jax.ShapeDtypeStruct((WORKERS,), jnp.int32,
                                    sharding=s["jw"].sharding)
    _compile(lambda slab, idx, b_eff, mb, *plan: se.slot_extract_stream_pallas(
        slab, idx, b_eff, *plan, num_cols=COLS, cache_cap=64, m_before=mb),
        s["slab"], s["idx"], s["b_eff"], m_before, *s["plan"])


def test_decoded_kernel_compiles(shapes, no_compile_cache):
    s = shapes
    _compile(lambda *a: se.slot_eval_decoded_pallas(*a, num_cols=COLS),
             s["dec"], s["idx"], s["b_eff"], *s["plan"])


def test_sharded_round_compiles_for_four_chips(topo, no_compile_cache,
                                               monkeypatch):
    """The whole round of ``ascii-scan-4chip`` (4,096 chunks of 16,384
    records sharded over a v5e:2x2 ``data`` axis, 32 workers, the grouped
    kernel) compiles, and each chip holds a quarter of the table."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.engine import EngineProgram, _Collectives
    from repro.core.engine_spmd import (engine_state_specs, report_specs,
                                        slot_table_specs)
    from repro.core.queries import empty_slot_table
    from repro.data.formats import AsciiFixedFormat
    from repro.kernels import ops as kernel_ops

    monkeypatch.setattr(kernel_ops, "_on_tpu", lambda: True)
    chunks, chips, workers = 4096, 4, 32
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("data",),
                axis_types=(AxisType.Auto,))
    cfg = EngineConfig(num_workers=workers, budget_init=BUDGET,
                       extract_backend="pallas", residency="sharded",
                       max_groups=GROUPS - 1, cache_cap=64)
    program = EngineProgram(codec=AsciiFixedFormat(COLS), config=cfg,
                            n_chunks=chunks, m_max=CHUNK_ROWS,
                            chunk_sizes=np.full(chunks, CHUNK_ROWS),
                            max_slots=SLOTS, queues=chips)
    coll = _Collectives(axis_name="data", workers_per_device=workers // chips,
                        chunks_per_device=chunks // chips)

    def step(state, table, data, speeds):
        return program.round_body(state, data, speeds, BUDGET, coll,
                                  slots=table)

    specs = engine_state_specs()
    fn = jax.shard_map(step, mesh=mesh,
                       in_specs=(specs, slot_table_specs(), P("data"),
                                 P("data")),
                       out_specs=(specs, report_specs()), check_vma=False)

    def shaped(tree, tree_specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, s)),
            tree, tree_specs, is_leaf=lambda x: isinstance(x, P))

    state = shaped(jax.eval_shape(program.init_state), specs)
    table = shaped(jax.eval_shape(
        lambda: empty_slot_table(SLOTS, COLS, GROUPS - 1)),
        slot_table_specs())
    data = jax.ShapeDtypeStruct((chunks, CHUNK_ROWS, REC), jnp.uint8,
                                sharding=NamedSharding(mesh, P("data")))
    speeds = jax.ShapeDtypeStruct((workers,), jnp.float32,
                                  sharding=NamedSharding(mesh, P("data")))
    compiled = jax.jit(fn).lower(state, table, data, speeds).compile()
    assert "tpu_custom_call" in compiled.as_text()
    quarter = chunks // chips * CHUNK_ROWS * REC
    mem = compiled.memory_analysis()
    assert quarter < mem.argument_size_in_bytes < quarter + 2**28
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
